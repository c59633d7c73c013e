"""The trace reduction, on a 0.4 s slice of a trace recorded on one TPU v5
lite (``testdata/chat_trace_slice.json.gz``: a qwen2-7b.chat traced run,
the operations, program runs and host spans that meet the slice) and on
small made-up traces.

The expected values were read from the slice independently: a 100 ns
timeline marked busy wherever an operation ran, and plain sums of the
events' durations by name."""

import gzip
import json
import os

import pytest

from chipbench import trace

SLICE = os.path.join(os.path.dirname(__file__), "testdata",
                     "chat_trace_slice.json.gz")


@pytest.fixture(scope="module")
def chip():
    with gzip.open(SLICE) as f:
        d = json.load(f)
    return trace.from_rows(d["ops"], d["modules"], d["spans"], d["window"])


def test_busy_and_idle(chip):
    assert chip.window_s == pytest.approx(0.4)
    # timeline reading: 0.3765388 s busy (100 ns bins)
    assert chip.busy_s() == pytest.approx(0.3765388, rel=1e-3)


def test_program_and_kernel_times(chip):
    assert chip.module_s("jit__admit_batch_impl") == \
        pytest.approx(0.100959266, rel=1e-6)
    assert chip.module_s("jit__segment_impl") == \
        pytest.approx(0.275581422, rel=1e-6)
    assert chip.op_s("flash_decode_paged") == \
        pytest.approx(0.05127321, rel=1e-6)
    assert chip.op_s("flash_prefill_ragged") == \
        pytest.approx(0.02209274, rel=1e-6)


def test_idle_inside_boundaries(chip):
    idle, n = chip.idle_in("chipbench.boundary")
    assert n == 3
    assert idle == pytest.approx(0.0233556, rel=1e-3)


def test_self_time_of_nested_operations():
    ops = [("while.1", 0, 100), ("fusion.2", 10, 20), ("copy.3", 40, 10),
           ("fusion.2", 120, 30)]
    t = trace.from_rows(ops, [], [], (0, 200))
    s = t.self_s()
    assert s["while.1"] == pytest.approx(70e-9)
    assert s["fusion.2"] == pytest.approx(50e-9)
    assert t.busy_s() == pytest.approx(130e-9)
    assert t.top_ops(1) == [["while.1", pytest.approx(70e-9)]]


def test_idle_gaps_named_by_host_span():
    ops = [("a.1", 0, 10), ("a.1", 50, 10), ("a.1", 65, 5)]
    spans = [("chipbench.window", 0, 100), ("chipbench.boundary", 8, 50),
             ("chipbench.wait", 70, 30)]
    t = trace.from_rows(ops, [], spans, (0, 100))
    gaps = t.idle_gaps()
    assert gaps[0] == ["boundary", pytest.approx(40e-9)]
    assert gaps[1] == ["wait", pytest.approx(30e-9)]
    assert gaps[2] == ["other", pytest.approx(5e-9)]
    idle, n = t.idle_in("chipbench.boundary")
    assert (n, idle) == (1, pytest.approx(40e-9))


def test_instruction_names():
    assert trace.instruction(
        "%flash_decode_paged.9 = bf16[8,4,7,128]{3,2,1,0} custom-call(x)") \
        == "flash_decode_paged.9"
    assert trace.instruction("fusion.3") == "fusion.3"
