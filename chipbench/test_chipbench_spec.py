"""BENCHMARK.json holds together: every name it gives is found as a file,
each per-layer metric has a reader, and the shapes and limits of the file
are kept."""

import json
import math
import os
import re

import pytest

from chipbench import cell as C

B = C.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
E2E = {m["name"]: m for m in B["end_to_end"]}
CELLS = {w["name"]: w for w in B["workloads"]}


def test_top_level_keys_and_command():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert B["command"] == ["python3", "chipbench/run.py"]
    assert B["paths"] == ["chipbench"]
    assert isinstance(B["run_seconds"], int) and 1 <= B["run_seconds"] <= 51
    assert len(json.dumps(B)) < 64 * 1024


def test_a_full_check_fits_with_24_cells():
    runs = 2 + 14 * 24
    total = runs * (B["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert total <= 43200


@pytest.mark.parametrize("conf", B["configs"], ids=lambda c: c["name"])
def test_configs(conf):
    assert set(conf) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(conf["name"]) and conf["source"].startswith("https://")
    assert conf["file"].startswith("chipbench/configs/")
    f = C.load_json(os.path.join(C.ROOT, conf["file"]))
    assert f["name"] == conf["name"]
    assert sorted(conf["reduced"]) == sorted(f["reduced"])
    assert f["source"] == conf["source"]
    assert f["correct"]["max_gap"] > 0
    assert any(w["config"] == conf["name"] for w in B["workloads"])


@pytest.mark.parametrize("w", B["workloads"], ids=lambda w: w["name"])
def test_workloads(w):
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(w["name"]) and w["chips"] == 1
    assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    assert os.path.exists(os.path.join(C.HERE, "traffic",
                                       w["traffic"] + ".json"))
    cell = C.find_cell(w["name"])
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer


def test_metrics_entries():
    for m in B["end_to_end"] + B["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= set(CELLS)
    for m in B["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert E2E["setup_s"]["bound"] <= 0.25


@pytest.mark.parametrize("m", B["per_layer"], ids=lambda m: m["name"])
def test_per_layer_reader_agrees(m):
    assert callable(C.metric_reader(m["name"]).read)
    assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
    assert m["moves"] in E2E
    moved = E2E[m["moves"]]
    for w in m["workloads"]:
        assert w in moved.get("workloads", CELLS)
    if m["name"].endswith("_roofline") or "mfu" in m["name"]:
        assert m["unit"] == "%"


def test_every_reader_is_a_metric_of_the_benchmark():
    readers = {f[:-3] for f in os.listdir(os.path.join(C.HERE, "metrics"))
               if f.endswith(".py")}
    names = {m["name"] for m in B["per_layer"]}
    assert readers <= names | {n.split(".")[0] for n in names}


def test_a_split_metric_is_read_by_its_base_reader():
    # decode_mfu.offline would move output_tok_per_s; no file of its own
    assert C.metric_reader("decode_mfu.offline").read is not None
    assert C.metric_reader("decode_mfu.offline").PROGRAM == \
        C.metric_reader("decode_mfu").PROGRAM


def test_each_kernel_roofline_has_a_whole_step_share_beside_it():
    for m in B["per_layer"]:
        if m["name"].endswith("_roofline"):
            assert any("mfu" in o["name"] and o["moves"] == m["moves"]
                       for o in B["per_layer"])


def test_no_cell_waits_on_an_unbounded_percentile():
    # a tail needs ten requests past it: the serve mixes' windows hold them
    for w in B["workloads"]:
        mix = C.find_cell(w["name"]).traffic
        for m in C.find_cell(w["name"]).end_to_end:
            q = re.match(r"^\w+_p(\d+)_ms$", m["name"])
            if q and mix["arrivals"]["kind"] == "poisson":
                n = round(mix["arrivals"]["rate_per_s"] * B["run_seconds"])
                assert n - math.ceil(int(q[1]) / 100 * n) >= 10
