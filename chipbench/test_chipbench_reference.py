"""``reference.py`` against the program's own jnp forward at smoke widths,
on the benchmark's weight draw (every bias and norm scale non-trivial), and
the draw and the reference pinned on a fixed sequence.

The program runs here in float32 (its ``fp32`` precision level, kernels
off), so the two differ only by float32 reassociation: the tolerance is
1e-4 of the largest logit.  A wrong RoPE pairing, a dropped bias or a
wrong norm moves logits by a good share of their scale, which the
second test shows for the pairing."""

import hashlib

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


@pytest.fixture(scope="module", params=["qwen2-7b.chat"])
def setup(request):
    cell = smoke_cell(request.param)
    a = arch(cell.config)
    w = weights.draw(a, weights.seed_key(SEED))
    tokens = np.random.default_rng(0).integers(0, a.vocab, 40).astype(
        np.int32)
    return cell, a, w, tokens


def test_biases_and_norms_are_drawn_non_trivial(setup):
    _, _, w, _ = setup
    L = w["layers"]
    assert float(jnp.std(L["q_b"].astype(jnp.float32))) > 0.05
    assert float(jnp.std(L["ln1_w"].astype(jnp.float32))) > 0.05


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


# The smoke-width draw from SEED, in both layouts, and the reference on a
# fixed sequence: pinned, so that the weights, gaps and logits a cell reads
# move only when its equations are meant to.  The reference is compared
# within a millionth of its largest logit (about ten float32 ulps), since the
# CPU backend's matmul may split its sums by the host's core count.
DRAW_SHA256 = {
    "published": "b9da24ec20facefc276f1a775bade25eb288276970c50ff34f2a7a766b842eb8",
    "program": "a7f00babbb8759ab23dbd42a684fdef5f82eb015463bc3c51bd8a5c9b4d7c97f"}
PIN_TOL = 1e-6
GAPS = [
    2.9453742504119873, 2.556762218475342, 3.170417547225952,
    3.5841338634490967, 3.1291680335998535, 3.3311684131622314,
    3.9973061084747314, 5.367462635040283, 2.5962741374969482,
    2.468160629272461, 3.580662727355957, 2.821465253829956]
# logits at every served row, every 32nd vocabulary column
LOGITS = [
    [0.34836477041244507, -0.11242634057998657, -0.7714111804962158,
     -1.2125707864761353, 2.1569716930389404, 0.08935568481683731,
     0.39124903082847595, 0.5254853963851929],
    [2.3132503032684326, 1.0454955101013184, -1.15702223777771,
     -0.6925382018089294, 1.6455165147781372, -0.2623988389968872,
     -1.11106276512146, 0.6128883957862854],
    [2.282827377319336, 0.9734722971916199, -1.4153037071228027,
     -0.8194869756698608, 1.7012662887573242, -0.7551267743110657,
     -0.24926821887493134, -0.9228373169898987],
    [1.9087213277816772, 0.19592928886413574, -0.9580761790275574,
     -0.4242633879184723, 1.5723240375518799, -0.7552792429924011,
     -0.4111413061618805, 1.3550843000411987],
    [1.8583110570907593, 0.8919768929481506, -1.1263319253921509,
     -1.6272380352020264, 1.9836422204971313, -0.8453865051269531,
     -0.7667563557624817, 0.6469365954399109],
    [2.3521735668182373, 0.7795045375823975, -0.9757515788078308,
     -0.34128206968307495, 1.6502665281295776, 0.20269230008125305,
     -0.7371068596839905, 0.47719717025756836],
    [2.337367534637451, -0.11229139566421509, -1.1887532472610474,
     -1.4652243852615356, 1.2948459386825562, -0.12579882144927979,
     -0.6282737255096436, 0.10569091141223907],
    [2.282548189163208, 0.6282231211662292, -2.268514633178711,
     -0.874549150466919, 1.2329530715942383, -0.4218130111694336,
     -0.5351121425628662, 0.5413155555725098],
    [1.970949411392212, 0.7170911431312561, -1.0146150588989258,
     -0.7036101222038269, 1.7297699451446533, -0.9618543982505798,
     -0.36414188146591187, 0.8465352058410645],
    [1.5047540664672852, 1.783632755279541, -1.3447362184524536,
     -0.7148665189743042, 1.6048165559768677, -0.595642626285553,
     0.04269386827945709, -0.2581509053707123],
    [1.663844108581543, 0.9281108379364014, -0.6801636219024658,
     -1.293602705001831, 1.2839106321334839, -0.5199398398399353,
     -0.6348170638084412, 0.4438956677913666],
    [2.225452423095703, 1.3298436403274536, -1.8841218948364258,
     0.09145805239677429, 1.4428116083145142, -0.34660792350769043,
     -0.1552436500787735, 0.9831133484840393],
]


def digest(tree) -> str:
    h = hashlib.sha256()
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    for path, leaf in sorted(leaves, key=lambda t: jax.tree_util.keystr(t[0])):
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).view(np.uint16).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("layout", DRAW_SHA256)
def test_weight_draw_is_pinned(setup, layout):
    _, a, w, _ = setup
    tree = w if layout == "published" else program_params(a)
    assert digest(tree) == DRAW_SHA256[layout]


def test_reference_is_pinned(setup):
    _, a, w, _ = setup
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, a.vocab, 20).astype(np.int32)
    served = [int(t) for t in rng.integers(0, a.vocab, 12)]
    gaps, lg = reference.served_gaps(a, w, prompt, served)
    lg = np.asarray(lg)
    tol = PIN_TOL * np.abs(lg).max()
    np.testing.assert_allclose(gaps, GAPS, rtol=0, atol=tol)
    np.testing.assert_allclose(lg[:, ::32], LOGITS, rtol=0, atol=tol)
