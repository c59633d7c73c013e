"""Model protocol: one uniform handle over the whole zoo.

``build_model(cfg, mesh=...)`` returns a :class:`LMModel` (decoder-only
families + enc-dec audio) exposing:

- ``init(key)`` / ``abstract_params()`` (eval_shape — no allocation)
- ``param_axes()``: logical-axis pytree parallel to params
- ``loss(params, batch, ctx)``: LM cross-entropy (+ MoE aux loss)
- ``prefill(params, batch, ctx)`` / ``decode_step(params, cache, tokens)``
- ``init_cache`` / ``abstract_cache`` + cache axes
- ``input_specs(shape)``: ShapeDtypeStruct stand-ins for the dry-run

The paper's CNN benchmarks (jet_dnn / vgg7 / resnet9) use the lighter
functional interface in models/cnn.py — the O-tasks accept either through
``repro.tasks.model_gen``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ShapeSpec
from repro.models import transformer as T
from repro.models.common import Ctx
from repro.quant.policy import PrecisionPolicy

# MoE routers and SSM gate/Δ projections exempt from quant/prune by default
DEFAULT_EXEMPT = ["*router*", "*w_if*", "*dt_*", "*A_log*", "*gate_logit*"]


def _xent(cfg, logits, labels):
    """Cross-entropy with vocab-padding masking and optional seq chunking
    (cfg.loss_chunk tokens at a time — bounds the fp32 softmax live set)."""
    v_real = cfg.vocab_size
    v = logits.shape[-1]

    def chunk_nll(lg, lb):
        lf = lg.astype(jnp.float32)
        if v > v_real:  # mask padded vocab columns exactly
            col = jnp.arange(v)
            lf = jnp.where(col[None, None] < v_real, lf, -1e30)
        logz = jax.scipy.special.logsumexp(lf, axis=-1)
        gold = jnp.take_along_axis(lf, lb[..., None], axis=-1)[..., 0]
        return logz - gold, logz

    c = cfg.loss_chunk
    if not c or logits.shape[1] <= c or logits.shape[1] % c:
        return chunk_nll(logits, labels)
    n = logits.shape[1] // c
    lg = logits.reshape(logits.shape[0], n, c, v).transpose(1, 0, 2, 3)
    lb = labels.reshape(labels.shape[0], n, c).transpose(1, 0, 2)
    (nll, logz) = jax.lax.map(lambda t: chunk_nll(*t), (lg, lb))
    return (nll.transpose(1, 0, 2).reshape(labels.shape),
            logz.transpose(1, 0, 2).reshape(labels.shape))


@functools.partial(jax.jit, static_argnums=(1,))
def _init_params(key, cfg: ArchConfig):
    """One compiled init program: each weight is drawn and cast inside it,
    so only the ``param_dtype`` result is materialised.  Run eagerly, every
    stacked float32 draw would be held before its cast — 4.3 GB for one
    MLP matrix of qwen2-7b at 16 layers, on top of the weights already
    resident."""
    init = T.init_encdec if cfg.enc_dec else T.init_lm
    return init(key, cfg)[0]


@dataclasses.dataclass
class LMModel:
    cfg: ArchConfig
    mesh: Any = None
    policy: PrecisionPolicy | None = None
    use_kernels: bool = False
    interpret: bool = False
    fsdp_params: bool = False
    moe_fsdp_mode: str = "gather"

    # ----------------------------------------------------------- context
    def ctx(self, decode: bool = False) -> Ctx:
        return Ctx(policy=self.policy, mesh=self.mesh,
                   use_kernels=self.use_kernels, interpret=self.interpret,
                   remat=self.cfg.remat, decode=decode,
                   fsdp_params=self.fsdp_params,
                   moe_fsdp_mode=self.moe_fsdp_mode)

    # -------------------------------------------------------------- init
    def init(self, key):
        """Seeded random parameters, materialised in ``param_dtype``."""
        return _init_params(key, self.cfg)

    @functools.cached_property
    def _abstract(self):
        """(abstract params, axes) with zero device allocation.

        The init runs under eval_shape; the (static, python-built) axes
        tree is captured through a side channel during tracing.
        """
        init = T.init_encdec if self.cfg.enc_dec else T.init_lm
        box = {}

        def f(k):
            p, a = init(k, self.cfg)
            box["axes"] = a
            return p

        shapes = jax.eval_shape(f, jax.random.PRNGKey(0))
        return shapes, box["axes"]

    @property
    def _axes(self):
        return self._abstract[1]

    def param_axes(self):
        return self._axes

    def abstract_params(self):
        return self._abstract[0]

    # -------------------------------------------------------------- loss
    def loss(self, params, batch, ctx: Ctx | None = None):
        """Mean LM cross-entropy over the batch (+ 0.01 * MoE aux loss)."""
        ctx = ctx or self.ctx()
        cfg = self.cfg
        if cfg.enc_dec:
            enc_out = T.encdec_encode(ctx, cfg, params, batch["frames"])
            logits, _ = T.encdec_decode(ctx, cfg, params, batch["tokens"],
                                        enc_out=enc_out)
        else:
            inp = batch.get("embeds", batch["tokens"])
            logits, _ = T.lm_apply(ctx, cfg, params, inp)
        labels = batch["labels"]
        nll, logz = _xent(cfg, logits, labels)
        loss = jnp.mean(nll)
        # z-loss for stability at scale
        loss = loss + 1e-4 * jnp.mean(logz ** 2)
        return loss, {"nll": jnp.mean(nll),
                      "ppl_proxy": jnp.exp(jnp.minimum(jnp.mean(nll), 20.0))}

    # ----------------------------------------------------------- serving
    def prefill(self, params, batch, cache=None, ctx: Ctx | None = None):
        ctx = ctx or self.ctx(decode=False)
        cfg = self.cfg
        if cfg.enc_dec:
            b = batch["tokens"].shape[0]
            if cache is None:
                seq = self._cache_len()
                dtype = jnp.bfloat16
            else:
                seq = cache["self"]["k"].shape[2]
                dtype = cache["cross_k"].dtype
            cache, _ = T.init_encdec_cache(ctx, cfg, params, b, seq,
                                           frames=batch["frames"],
                                           dtype=dtype)
            logits, cache = T.encdec_decode(ctx, cfg, params,
                                            batch["tokens"], cache=cache)
            return logits, cache
        if cache is None:
            cache, _ = self.init_cache(batch["tokens"].shape[0],
                                       batch["tokens"].shape[1])
        inp = batch.get("embeds", batch["tokens"])
        return T.lm_apply(ctx, cfg, params, inp, cache=cache)

    def decode_step(self, params, cache, tokens, ctx: Ctx | None = None):
        """One-token decode.  tokens: (B,1) int32."""
        ctx = ctx or self.ctx(decode=True)
        cfg = self.cfg
        if cfg.enc_dec:
            return T.encdec_decode(ctx, cfg, params, tokens, cache=cache)
        return T.lm_apply(ctx, cfg, params, tokens, cache=cache)

    # -------------------------------------------------------------- cache
    def _cache_len(self, seq_len: int | None = None) -> int:
        return seq_len if seq_len is not None else 4096

    def init_cache(self, batch: int, seq_len: int, dtype=jnp.bfloat16):
        cfg = self.cfg
        if cfg.enc_dec:
            return self._encdec_empty_cache(batch, seq_len, dtype)
        return T.init_lm_cache(cfg, batch, seq_len, dtype)

    def _encdec_empty_cache(self, batch, seq_len, dtype):
        cfg = self.cfg
        from repro.models import layers as Lay
        enc_cfg = cfg.replace(use_rope=False, sliding_window=0)
        sc, sa = Lay.init_attention_cache(enc_cfg, batch, seq_len, dtype)
        n = cfg.n_layers
        scs = jax.tree.map(lambda t: jnp.broadcast_to(t, (n,) + t.shape),
                           sc)
        sas = jax.tree.map(lambda ax: ("layers",) + tuple(ax), sa,
                           is_leaf=lambda x: isinstance(x, tuple))
        kvshape = (n, batch, cfg.n_frames, cfg.n_kv_heads, cfg.hd)
        cache = {"self": scs, "cross_k": jnp.zeros(kvshape, dtype),
                 "cross_v": jnp.zeros(kvshape, dtype)}
        axes = {"self": sas,
                "cross_k": ("layers", "batch", "frames", "kv_heads",
                            "head_dim"),
                "cross_v": ("layers", "batch", "frames", "kv_heads",
                            "head_dim")}
        return cache, axes

    def abstract_cache(self, batch: int, seq_len: int, dtype=jnp.bfloat16):
        box = {}

        def f():
            c, a = self.init_cache(batch, seq_len, dtype)
            box["axes"] = a
            return c

        shapes = jax.eval_shape(f)
        return shapes, box["axes"]

    def cache_axes(self, batch: int, seq_len: int):
        return self.abstract_cache(batch, seq_len)[1]

    # -------------------------------------------------------- input specs
    def input_specs(self, shape: ShapeSpec) -> dict[str, Any]:
        """ShapeDtypeStruct stand-ins for every model input of this shape
        (the dry-run contract; no device allocation)."""
        cfg = self.cfg
        b = shape.global_batch
        if shape.is_decode:
            toks = jax.ShapeDtypeStruct((b, 1), jnp.int32)
            return {"tokens": toks}
        s = shape.seq_len
        specs = {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32),
                 "labels": jax.ShapeDtypeStruct((b, s), jnp.int32)}
        if cfg.enc_dec:
            specs["frames"] = jax.ShapeDtypeStruct(
                (b, cfg.n_frames, cfg.d_model), jnp.bfloat16)
        if cfg.family == "vlm":
            pass  # early fusion: VQ image tokens share the token stream
        if shape.kind == "prefill":
            specs.pop("labels")
        return specs


def build_model(cfg: ArchConfig, mesh=None, policy: PrecisionPolicy = None,
                **kw) -> LMModel:
    if policy is None:
        policy = PrecisionPolicy(default="bf16", exempt=DEFAULT_EXEMPT)
    return LMModel(cfg=cfg, mesh=mesh, policy=policy, **kw)
