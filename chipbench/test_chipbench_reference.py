"""``reference.py`` against the program's own jnp forward at smoke widths,
for both decoder variants, on the benchmark's weight draw (every bias and
norm scale non-trivial).

The program runs here in float32 (its ``fp32`` precision level, kernels
off), so the two differ only by float32 reassociation: the tolerance is
1e-4 of the largest logit.  A wrong RoPE pairing, a dropped bias or a
wrong norm moves logits by a good share of their scale, which the
second test shows for the pairing."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import reference, weights
from chipbench.cell import arch, program_config
from chipbench.smoke import smoke_cell

TOL = 1e-4
SEED = 2**31 + 5


def program_params(a):
    """A draw of its own: laying weights out for the program donates them."""
    return weights.to_program(a, weights.draw(a, weights.seed_key(SEED)))


def program_logits(cell, params, tokens):
    from repro.models import transformer as T
    from repro.models.api import build_model
    from repro.quant.policy import PrecisionPolicy
    cfg = program_config(cell.config, smoke=True).replace(
        act_dtype="float32")
    model = build_model(cfg, policy=PrecisionPolicy(default="fp32"))
    logits, _ = T.lm_apply(model.ctx(), cfg, params, jnp.asarray(tokens)[None])
    return np.asarray(logits[0], np.float32)


@pytest.fixture(scope="module", params=["qwen2-7b.chat", "starcoder2-3b.repo"])
def setup(request):
    cell = smoke_cell(request.param)
    a = arch(cell.config)
    w = weights.draw(a, weights.seed_key(SEED))
    tokens = np.random.default_rng(0).integers(0, a.vocab, 40).astype(
        np.int32)
    return cell, a, w, tokens


def test_biases_and_norms_are_drawn_non_trivial(setup):
    _, a, w, _ = setup
    L = w["layers"]
    assert float(jnp.std(L["q_b"].astype(jnp.float32))) > 0.05
    assert float(jnp.std(L["ln1_w"].astype(jnp.float32))) > 0.05
    if a.norm == "layer":
        assert float(jnp.std(L["ln1_b"].astype(jnp.float32))) > 0.05
    if a.mlp_bias:
        assert float(jnp.std(L["fc_b"].astype(jnp.float32))) > 0.05


def test_reference_matches_the_program(setup):
    cell, a, w, tokens = setup
    rows = np.arange(len(tokens))
    served = list(tokens[1:]) + [0]
    _, ref = reference.served_gaps(a, w, tokens[:1], served)
    ref = np.asarray(ref)
    prog = program_logits(cell, program_params(a), tokens)
    scale = np.abs(ref).max()
    assert np.abs(prog[rows] - ref).max() <= TOL * scale


def test_reference_sees_a_wrong_rope_pairing(setup):
    cell, a, w, tokens = setup
    _, ref = reference.served_gaps(a, w, tokens[:1],
                                   list(tokens[1:]) + [0])
    ref = np.asarray(ref)
    params = program_params(a)
    # undo the pair permutation: the program then pairs the wrong dims
    inv = np.argsort(weights.rope_pair_order(a.hd))
    for k in ("wq", "wk", "bq", "bk"):
        x = params["blocks"]["attn"][k]
        lead = x.shape[:-1]
        x = x.reshape(*lead, -1, a.hd)[..., inv].reshape(*lead, -1)
        params["blocks"]["attn"][k] = x
    prog = program_logits(cell, params, tokens)
    assert np.abs(prog - ref).max() > 100 * TOL * np.abs(ref).max()
