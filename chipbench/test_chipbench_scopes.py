"""The reduction of the program's own marks (``scopes.py``) and the readers
built on it: on made-up traces with program spans and scope paths, on a
slice recorded on the chip with this program's marks (values read
independently), on a real profiler file written on the CPU (spans with
args, the HLO ``op_name`` table), and on the earlier chip slice without
marks, where every reading must stay as the harness's own reduction gives
it."""

import gzip
import json
import os
import types

import jax
import jax.numpy as jnp
import pytest

from chipbench import cell as C, scopes, trace

SLICE = os.path.join(os.path.dirname(__file__), "testdata",
                     "chat_trace_slice.json.gz")
SCOPED = os.path.join(os.path.dirname(__file__), "testdata",
                      "chat_scoped_slice.json.gz")
SEG, ADM = "jit__segment_impl(1)", "jit__admit_batch_impl(2)"
BODY = "jit(_segment_impl)/jit(main)/while/body/"


def made_up():
    """One admission run (0-100) and one segment run (200-400) of two
    steps, the host's spans around them, and an idle gap in each of
    ``serving.admit.inputs``, ``serving.checkpoint`` and the harness's
    wait."""
    ops = [("fusion.1", 10, 60), ("fusion.2", 70, 20),       # admission
           ("while.3", 200, 190),                            # segment loop
           ("scatter.4", 210, 10), ("fusion.5", 220, 100),
           ("fusion.6", 320, 40), ("copy.7", 360, 30)]
    scope = {"fusion.1": "jit(_admit_batch_impl)/attention/dot_general",
             "fusion.2": "jit(_admit_batch_impl)/lm_head/dot_general",
             "while.3": BODY[:-6],
             "scatter.4": BODY + "attention/kv_write/scatter",
             "fusion.5": BODY + "mlp/dot_general",
             "fusion.6": BODY + "sample/argmax",
             "copy.7": ""}
    modules = [(ADM, 10, 80), (SEG, 200, 190)]
    harness = [("chipbench.window", 0, 1000), ("chipbench.boundary", 0, 420),
               ("chipbench.wait", 600, 300)]
    serving = [
        ("serving.step", 0, 410, {"boundary": 1}),
        ("serving.admit", 0, 100, {"rows": 2, "bucket": 512}),
        ("serving.admit.inputs", 0, 10, {}),
        ("serving.admit.wait", 10, 90, {}),
        ("serving.checkpoint", 100, 100, {}),
        ("serving.segment.wait", 200, 195, {}),
        ("serving.commit", 395, 15, {"tokens": 3, "steps": 2, "rows": 4})]
    base = trace.from_rows(ops, modules, harness, (0, 1000))
    return scopes.from_rows(base, [scope[n] for n in base.ops.names],
                            serving)


def test_scope_self_time_inside_a_program():
    m = made_up()
    assert m.scope_s("jit__segment_impl", ["kv_write"]) == \
        pytest.approx(10e-9)
    # the loop's own time (190 less its body's 180) is under no scope
    assert m.scope_s("jit__segment_impl", ["attention"]) == \
        pytest.approx(10e-9)
    assert m.scope_s("jit__segment_impl", ["lm_head", "sample"]) == \
        pytest.approx(40e-9)
    assert m.scope_s("jit__admit_batch_impl", ["lm_head", "sample"]) == \
        pytest.approx(20e-9)
    assert m.scope_s("jit__admit_batch_impl", ["kv_write"]) == 0.0
    # a scope is a whole component of the path, not a substring
    assert m.scope_s("jit__segment_impl", ["body"]) == pytest.approx(150e-9)
    assert m.scope_s("jit__segment_impl", ["bod"]) == 0.0


def test_gaps_named_by_the_innermost_program_span():
    m = made_up()
    # gaps 0-10, 90-200 and 390-1000, named at their midpoints
    assert m.idle_gaps() == [["wait", pytest.approx(610e-9)],
                             ["serving.checkpoint", pytest.approx(110e-9)],
                             ["serving.admit.inputs", pytest.approx(10e-9)]]
    # cut at the spans' edges, each piece to its innermost span
    assert m.idle_by_span() == {
        "wait": pytest.approx(300e-9),                     # 600-900
        "other": pytest.approx(280e-9),                    # 420-600, 900-
        "serving.checkpoint": pytest.approx(100e-9),       # 100-200
        "serving.commit": pytest.approx(15e-9),            # 395-410
        "serving.admit.inputs": pytest.approx(10e-9),      # 0-10
        "serving.admit.wait": pytest.approx(10e-9),        # 90-100
        "boundary": pytest.approx(10e-9),                  # 410-420
        "serving.segment.wait": pytest.approx(5e-9)}       # 390-395
    by = m.idle_by_span()
    idle = m.base.window_s - m.base.busy_s()
    assert sum(by.values()) == pytest.approx(idle)
    assert m.where(205) == "serving.segment.wait"
    assert m.where(500) == "other"


def record(m, tracks=(), end_s=0.0):
    return types.SimpleNamespace(
        trace=m.base, marks=m,
        window=types.SimpleNamespace(tracks=list(tracks), end_s=end_s))


def read(name, rec):
    return C.metric_reader(name).read(rec)


def test_readers_on_made_up_marks():
    rec = record(made_up())
    assert read("prefill_head_ms", rec) == pytest.approx(20e-9 / 2 * 1e3)
    assert read("decode_kv_write_ms", rec) == pytest.approx(10e-9 / 2 * 1e3)
    assert read("decode_head_ms", rec) == pytest.approx(40e-9 / 2 * 1e3)
    assert read("decode_occupancy", rec) == pytest.approx(100 * 3 / 8)


def test_queue_wait_is_the_nearest_rank_p80_of_admission_stamps():
    def track(due, admitted):
        return types.SimpleNamespace(
            item=types.SimpleNamespace(due=due),
            req=types.SimpleNamespace(t_admitted=admitted))
    waits = [0.01 * k for k in range(1, 10)]          # 10 ... 90 ms
    tracks = [track(1.0, 1.0 + w) for w in waits]
    tracks.append(track(4.0, None))                   # never admitted
    rec = record(made_up(), tracks, end_s=5.0)
    # ten waits: 10..90 ms and 1000 ms; rank ceil(0.8 * 10) = 8 -> 80 ms
    assert read("queue_wait_p80_ms", rec) == pytest.approx(80.0)
    assert read("queue_wait_p80_ms", record(made_up())) is None


@pytest.fixture(scope="module")
def scoped():
    """``testdata/chat_scoped_slice.json.gz``: 0.4 s of a qwen2-7b.chat
    traced run of this program on one TPU v5 lite (two admissions, two
    segments, the program's spans with their args, each operation's scope
    path)."""
    with gzip.open(SCOPED) as f:
        d = json.load(f)
    base = trace.from_rows(d["ops"], d["modules"], d["spans"], d["window"])
    paths = [d["scope_paths"][i] for i in d["op_scopes"]]
    # Events.of sorts the rows; the scope of each row goes with it
    order = sorted(range(len(paths)),
                   key=lambda i: (d["ops"][i][1], -d["ops"][i][2]))
    return scopes.from_rows(base, [paths[i] for i in order], d["serving"])


def test_scoped_slice_scope_times(scoped):
    """Plain sums of the durations of the operations under each scope in
    each program's runs (every such operation is a leaf: only loops
    enclose operations on the slice, and no loop is under these
    scopes)."""
    assert scoped.base.busy_s() == pytest.approx(0.3826843, rel=1e-3)
    for program, names, want in [
            ("jit__admit_batch_impl", ("lm_head", "sample"), 0.005876241),
            ("jit__segment_impl", ("kv_write",), 0.013238608),
            ("jit__segment_impl", ("lm_head", "sample"), 0.01302535),
            ("jit__segment_impl", ("attention",), 0.087817848),
            ("jit__segment_impl", ("mlp",), 0.097090776)]:
        assert scoped.scope_s(program, names) == pytest.approx(want, rel=1e-6)


def test_scoped_slice_readers(scoped):
    rec = record(scoped)
    # two admissions of one request each; one commit of 8 steps x 8 rows
    assert read("prefill_head_ms", rec) == \
        pytest.approx(0.005876241 / 2 * 1e3, rel=1e-6)
    assert read("decode_kv_write_ms", rec) == \
        pytest.approx(0.013238608 / 8 * 1e3, rel=1e-6)
    assert read("decode_head_ms", rec) == \
        pytest.approx(0.01302535 / 8 * 1e3, rel=1e-6)
    assert read("decode_occupancy", rec) == pytest.approx(100 * 40 / 64)


def test_scoped_slice_idle_by_span(scoped):
    """Against a 100 ns timeline: each idle bin goes to the latest-starting
    program span over its centre, else the harness's span, else
    ``other`` (to 0.1%, or two bins for the smallest)."""
    want = {"serving.admit.wait": 0.0048325,
            "serving.segment.inputs": 0.003297,
            "serving.commit": 0.0027107, "serving.segment.wait": 0.0024332,
            "serving.admit.inputs": 0.0015779,
            "serving.admit.plan": 0.0014476, "serving.step": 0.000893,
            "serving.growth": 6.02e-05, "other": 3.87e-05,
            "serving.checkpoint": 7.4e-06, "serving.preflight": 6.4e-06,
            "boundary": 5.1e-06, "serving.admit": 3.9e-06,
            "serving.restore": 2.1e-06}
    got = scoped.idle_by_span()
    assert set(got) == set(want)
    for name, v in want.items():
        assert got[name] == pytest.approx(v, rel=1e-3, abs=2e-7), name
    # the longest gap, from the first segment run's end (55.268432 ms +
    # 234.332537 ms) to the second admission run's start (296.607876 ms),
    # has its midpoint in the first segment's commit
    assert scoped.idle_gaps(1) == [["serving.commit",
                                    pytest.approx(0.007006907, rel=1e-3)]]


@pytest.fixture(scope="module")
def chip():
    with gzip.open(SLICE) as f:
        d = json.load(f)
    base = trace.from_rows(d["ops"], d["modules"], d["spans"], d["window"])
    return scopes.from_rows(base, [""] * len(base.ops.names), [])


def test_slice_without_marks_reads_as_before(chip):
    # gaps named exactly as the harness's reduction names them
    assert chip.idle_gaps() == chip.base.idle_gaps()
    by = chip.idle_by_span()
    assert set(by) <= {"boundary", "wait", "other"}
    assert sum(by.values()) == pytest.approx(
        chip.base.window_s - chip.base.busy_s())
    assert by["boundary"] == pytest.approx(
        chip.base.idle_in("chipbench.boundary")[0])
    rec = record(chip)
    for name in ("prefill_head_ms", "decode_kv_write_ms", "decode_head_ms",
                 "decode_occupancy"):
        assert read(name, rec) is None


def test_existing_readers_unchanged_on_the_slice(chip):
    """The five metrics the benchmark had read the values the slice's
    pinned times give (``test_chipbench_trace.py``)."""
    from chipbench import work
    w = work.Work(flops=1e15, attn_flops=1e13, attn_bytes=1e10)
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    rec = types.SimpleNamespace(trace=chip.base, prefill=w, decode=w,
                                peak=peak)
    assert read("boundary_idle_ms", rec) == \
        pytest.approx(0.0233556 / 3 * 1e3, rel=1e-3)
    assert read("prefill_mfu", rec) == \
        pytest.approx(100 * 1e15 / 0.100959266 / 197e12, rel=1e-6)
    assert read("decode_mfu", rec) == \
        pytest.approx(100 * 1e15 / 0.275581422 / 197e12, rel=1e-6)
    least = max(1e13 / 197e12, 1e10 / 819e9)
    assert read("flash_prefill_ragged_roofline", rec) == \
        pytest.approx(100 * least / 0.02209274, rel=1e-6)
    assert read("flash_decode_paged_roofline", rec) == \
        pytest.approx(100 * least / 0.05127321, rel=1e-6)


def test_a_profiler_file_gives_spans_args_and_op_names(tmp_path):
    """On the CPU: the program spans and their args come back from the
    file, and the HLO table maps each instruction to its scope path."""
    from repro.serving.observe import span

    @jax.jit
    def f(x):
        with jax.named_scope("kv_write"):
            x = x.at[0].set(1.0)
        with jax.named_scope("lm_head"):
            return jnp.cos(x) * 2

    x = jnp.ones((8, 8))
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("chipbench.window"):
            with span("serving.step", boundary=7):
                with span("serving.commit", tokens=3, steps=8, rows=4):
                    f(x).block_until_ready()
    path = trace.xplane_file(str(tmp_path))
    with open(path, "rb") as fh:
        names = scopes.op_names(fh.read())
    (prog, table), = [(k, v) for k, v in names.items()
                      if k.startswith("jit_f(")]
    paths = set(table.values())
    assert any(p.endswith("/kv_write/scatter") for p in paths)
    assert any("/lm_head/" in p for p in paths)
    m = scopes.load(path, trace.load(path))
    assert m.serving.names == ["serving.step", "serving.commit"]
    assert m.args == [{"boundary": 7}, {"tokens": 3, "steps": 8, "rows": 4}]
    assert m.serving_args("serving.commit") == [m.args[1]]
    assert scopes.op_names(open(path, "rb").read(), {prog}) == {prog: table}


def test_an_unreadable_file_reads_as_no_marks(tmp_path, monkeypatch):
    """A reader reports nothing, and does not end the run, where the trace
    file's metadata cannot be parsed."""
    import chipbench.run as R
    monkeypatch.setattr(R, "OUT", str(tmp_path))
    (tmp_path / "trace").mkdir()
    (tmp_path / "trace" / "h.xplane.pb").write_bytes(b"")

    def unparseable(path, base):
        list(scopes._fields(bytes([0x0B]), 0, 1))        # wire type 3

    monkeypatch.setattr(scopes, "load", unparseable)
    base = trace.from_rows([], [], [("chipbench.window", 0, 10)], (0, 10))
    rec = types.SimpleNamespace(trace=base)
    assert read("decode_occupancy", rec) is None
    assert rec.marks is None
