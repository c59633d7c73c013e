"""Per-arch smoke tests: reduced same-family config, one forward/train
step on CPU, asserting output shapes + no NaNs; decode-vs-full
consistency in fp32 (deliverable f)."""

import jax
import jax.numpy as jnp
import pytest

from repro.configs.registry import ARCH_IDS, get_config
from repro.models import transformer as T
from repro.models.api import build_model
from repro.optim.optimizers import adamw
from repro.runtime.train_loop import init_train_state, make_train_step

KEY = jax.random.PRNGKey(0)


def _batch(cfg, b=2, s=32):
    toks = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, axis=1)}
    if cfg.enc_dec:
        batch["frames"] = jax.random.normal(
            jax.random.PRNGKey(2), (b, cfg.n_frames, cfg.d_model)) * 0.1
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_shapes_and_finite(arch):
    cfg = get_config(arch, smoke=True)
    model = build_model(cfg)
    params = model.init(KEY)
    batch = _batch(cfg)
    loss, metrics = model.loss(params, batch)
    assert jnp.isfinite(loss), f"{arch}: non-finite loss"
    assert float(loss) > 0


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_one_train_step(arch):
    cfg = get_config(arch, smoke=True)
    model = build_model(cfg)
    optimizer = adamw(1e-3)
    state = init_train_state(model, optimizer, KEY)
    step = jax.jit(make_train_step(model, optimizer))
    batch = _batch(cfg)
    new_state, metrics = step(state, batch)
    assert jnp.isfinite(metrics["loss"])
    # params changed and stayed finite
    leaves_old = jax.tree.leaves(state["params"])
    leaves_new = jax.tree.leaves(new_state["params"])
    assert any(float(jnp.max(jnp.abs(a - b))) > 0
               for a, b in zip(leaves_old, leaves_new))
    assert all(bool(jnp.all(jnp.isfinite(b))) for b in leaves_new
               if b.dtype.kind == "f")


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_decode_matches_full_forward(arch):
    cfg = get_config(arch, smoke=True).replace(act_dtype="float32")
    model = build_model(cfg)
    params = model.init(KEY)
    b, s = 2, 16
    toks = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks}
    if cfg.enc_dec:
        frames = jax.random.normal(jax.random.PRNGKey(2),
                                   (b, cfg.n_frames, cfg.d_model)) * 0.1
        batch["frames"] = frames
        enc = T.encdec_encode(model.ctx(), cfg, params, frames)
        full, _ = T.encdec_decode(model.ctx(), cfg, params, toks,
                                  enc_out=enc)
    else:
        full, _ = T.lm_apply(model.ctx(), cfg, params, toks)
    cache, _ = model.init_cache(b, 32, dtype=jnp.float32)
    _, cache = model.prefill(params, {**batch, "tokens": toks[:, :s - 1]},
                             cache=cache)
    dl, cache = model.decode_step(params, cache, toks[:, s - 1:s])
    err = float(jnp.max(jnp.abs(dl[:, 0] - full[:, s - 1])))
    scale = float(jnp.max(jnp.abs(full))) + 1e-6
    assert err / scale < 2e-4, f"{arch}: decode mismatch {err} vs {scale}"


@pytest.mark.parametrize("arch", ["xlstm_125m", "zamba2_2p7b",
                                  "h2o_danube_3_4b"])
def test_multistep_decode_consistency(arch):
    """Sub-quadratic archs (the long_500k set): 4 decode steps == full."""
    cfg = get_config(arch, smoke=True).replace(act_dtype="float32")
    model = build_model(cfg)
    params = model.init(KEY)
    b, s, tail = 2, 16, 4
    toks = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0,
                              cfg.vocab_size)
    full, _ = T.lm_apply(model.ctx(), cfg, params, toks)
    cache, _ = model.init_cache(b, 32, dtype=jnp.float32)
    _, cache = model.prefill(params, {"tokens": toks[:, :s - tail]},
                             cache=cache)
    for t in range(s - tail, s):
        dl, cache = model.decode_step(params, cache, toks[:, t:t + 1])
        err = float(jnp.max(jnp.abs(dl[:, 0] - full[:, t])))
        assert err < 2e-3, f"{arch} step {t}: {err}"


def test_sliding_window_ring_buffer_decode():
    """Danube SWA: decode beyond the window uses the ring buffer."""
    cfg = get_config("h2o_danube_3_4b", smoke=True).replace(
        act_dtype="float32", sliding_window=8)
    model = build_model(cfg)
    params = model.init(KEY)
    b, s = 1, 24
    toks = jax.random.randint(jax.random.PRNGKey(3), (b, s), 0,
                              cfg.vocab_size)
    full, _ = T.lm_apply(model.ctx(), cfg, params, toks)
    # cache capped at window size: (layers, batch, window, kv, hd)
    cache, _ = model.init_cache(b, s, dtype=jnp.float32)
    assert cache["k"].shape[2] == 8  # cache_len == window
    _, cache = model.prefill(params, {"tokens": toks[:, :8]}, cache=cache)
    for t in range(8, s):
        dl, cache = model.decode_step(params, cache, toks[:, t:t + 1])
        err = float(jnp.max(jnp.abs(dl[:, 0] - full[:, t])))
        assert err < 2e-3, f"SWA decode step {t}: err={err}"


def test_vlm_chameleon_accepts_fused_tokens():
    cfg = get_config("chameleon_34b", smoke=True)
    model = build_model(cfg)
    params = model.init(KEY)
    toks = jax.random.randint(KEY, (2, 16), 0, cfg.vocab_size)
    logits, _ = T.lm_apply(model.ctx(), cfg, params, toks)
    assert logits.shape == (2, 16, cfg.vocab_size)


def test_input_specs_cover_shapes():
    from repro.configs.base import SHAPES
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        model = build_model(cfg)
        for shape in SHAPES.values():
            specs = model.input_specs(shape)
            assert "tokens" in specs
            if shape.kind == "train":
                assert specs["tokens"].shape == (shape.global_batch,
                                                 shape.seq_len)
            if shape.is_decode:
                assert specs["tokens"].shape == (shape.global_batch, 1)


def _eager_init(cfg, key):
    """The eager recipe ``LMModel.init`` replaced: per-layer inits vmapped
    over layer keys, each weight a float32 ``jax.random.normal`` times its
    scale, then cast — every op dispatched on its own."""
    from repro.models import common, ssm

    def stack_vmap(key, n, init_fn):
        keys = jax.random.split(key, n)
        params = jax.vmap(lambda k: init_fn(k)[0])(keys)
        return params, None

    def plain_normal(key, shape):
        return jax.random.normal(key, shape, jnp.float32)

    mp = pytest.MonkeyPatch()
    try:
        mp.setattr(T, "_stack_init", stack_vmap)
        mp.setattr(common, "normal_draw", plain_normal)
        mp.setattr(ssm, "normal_draw", plain_normal)
        init = T.init_encdec if cfg.enc_dec else T.init_lm
        return init(key, cfg)[0]
    finally:
        mp.undo()


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_jitted_init_bit_identical_to_eager(arch):
    """One compiled init program (so full-width weights materialise in
    param_dtype with no stacked float32 transient) gives exactly the
    parameters the eager init gave."""
    import numpy as np
    cfg = get_config(arch, smoke=True)
    got = build_model(cfg).init(KEY)
    ref = _eager_init(cfg, KEY)
    assert jax.tree.structure(got) == jax.tree.structure(ref)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert np.array_equal(np.asarray(a).view(np.uint8),
                              np.asarray(b).view(np.uint8))
