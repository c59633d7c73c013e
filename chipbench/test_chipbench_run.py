"""The run path off the chip: the device check refuses a CPU, a checkout
without the program gives no result, and the whole run past the device
check serves each mix at smoke widths (Pallas in interpret mode) and
assembles the result line."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import cell as C
from chipbench.run import Compiles, main, run_cell
from chipbench.peaks import PEAKS, UnknownDevice, peaks_for
from chipbench.smoke import END_TO_END, smoke_cell

# (cell, arrival law): the offline law runs on the chat mix's lengths; the
# sessions mix fills its prefixes before the window
CELLS = {"qwen2-7b.chat": ("qwen2-7b.chat", None),
         "qwen2-7b.sessions": ("qwen2-7b.sessions", None),
         "qwen2-7b.offline": ("qwen2-7b.chat", "offline")}
KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def json_lines(text):
    out = []
    for line in text.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        if isinstance(obj, dict):
            out.append(obj)
    return out


def test_refuses_without_a_tpu(capsys, monkeypatch, tmp_path):
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "tune.json"))
    rc = main(["--workload", "qwen2-7b.chat", "--seed", "1",
               "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert "needs 1 TPU chip" in out.err
    assert not json_lines(out.out)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(UnknownDevice):
        peaks_for("TPU v99")


def test_no_result_without_the_program(tmp_path):
    shutil.copy(os.path.join(C.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(C.HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload",
                        "qwen2-7b.chat", "--seed", "3", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert not json_lines(p.stdout)


def smoke_run(name, trace_on=False, arrivals=None, **kw):
    return run_cell(smoke_cell(name, arrivals=arrivals), 2**31 + 11, 1.5,
                    trace_on, peak=PEAKS["TPU v5 lite"],
                    device={"platform": "cpu", "kind": "cpu", "count": 1,
                            "compiles": Compiles()},
                    smoke=True, interpret=True, **kw)


@pytest.mark.parametrize("name", CELLS)
def test_serves_each_mix_and_assembles_the_result(name, capsys):
    res = smoke_run(CELLS[name][0], arrivals=CELLS[name][1])
    assert list(res)[:5] == KEYS
    assert list(res)[-1] == "compared"
    assert set(res) == set(KEYS) | {"compared"}
    assert res["correct"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {m["name"] for m in END_TO_END}
    for m in res["metrics"].values():
        assert m["value"] > 0 and set(m) == {"value", "unit"}
    assert set(res["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    gap = res["compared"]["widest_gap"]
    assert 0 <= gap["value"] <= gap["limit"]
    out = capsys.readouterr().out
    assert '"compiles": 0' in out.split("compiles_in_window:")[1].split("\n")[0]
    json.dumps(res)


def test_traced_run_reports_per_layer_metrics_it_can_read():
    res = smoke_run("qwen2-7b.sessions", trace_on=True)
    assert set(res) == set(KEYS) | {"breakdown", "compared"}
    # no TPU plane off the chip: a reader with nothing to read is left out
    names = {m["name"] for m in C.benchmark()["per_layer"]}
    assert set(res["metrics"]) <= names
    for m in res["metrics"].values():
        assert m["value"] > 0
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
