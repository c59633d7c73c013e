"""``chip_smoke.py`` off the chip: its phases at smoke width in Pallas
interpret mode, so a change that would break the chip smoke fails here
first, and its refusal to run without a TPU."""

import importlib.util
import os

import pytest

from repro.configs.registry import get_config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_to_run_without_a_tpu(chip_smoke, capsys):
    with pytest.raises(SystemExit, match="no TPU found"):
        chip_smoke.main([])
    assert '"ok"' not in capsys.readouterr().out


def test_phases_pass_at_smoke_width(chip_smoke, capsys):
    """Every phase and check of the smoke, on qwen2's smoke config: the
    kernels against their oracle, 8 requests served by both engines with
    prefix sharing and a copy-on-write fork, and the logit comparison."""
    chip_smoke.run(0, interpret=True,
                   cfg=get_config("qwen2_7b", smoke=True))
    out = capsys.readouterr().out
    assert "'new_tokens': [32, 32, 32, 32, 32, 32, 32, 32]" in out
    assert out.count("'late_cow_fork': True") == 2
    assert "logits_kernel_vs_jnp_rel_err" in out
