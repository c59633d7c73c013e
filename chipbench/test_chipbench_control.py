"""The control of ``correct`` at a size a test run holds: a whole run with
the program's own ``fp8`` precision path switched on in the served engine
(the step below the configuration's bfloat16) comes out not correct, by the
harness's own comparison, where the program's run on the same seed passes.
On the chip the same runs, at the cell's own size, set each
configuration's limit (``control.py``, ``PERF.md``)."""

import pytest

from chipbench.control import precision_path
from chipbench.peaks import PEAKS
from chipbench.run import Compiles, run_cell
from chipbench.smoke import smoke_cell


def run(seed, level):
    return run_cell(smoke_cell("qwen2-7b.chat"), seed, 2.0, False,
                    peak=PEAKS["TPU v5 lite"],
                    device={"platform": "cpu", "kind": "cpu", "count": 1,
                            "compiles": Compiles()},
                    smoke=True, interpret=True,
                    break_engine=precision_path(level) if level else None)


@pytest.mark.parametrize("seed", [1, 2])
def test_fp8_control_fails_where_the_program_passes(seed):
    prog, ctrl = run(seed, None), run(seed, "fp8")
    assert prog["correct"] is True
    assert ctrl["correct"] is False
    gap = ctrl["compared"]["widest_gap"]
    assert gap["value"] > gap["limit"]
