"""Shared model-substrate utilities: apply context, init helpers, norms.

Conventions used across the whole model zoo:

- Parameters are nested dicts of jnp arrays.  Every ``init_*`` returns
  ``(params, axes)`` where ``axes`` is a parallel nested dict whose leaves
  are tuples of *logical axis names* (see parallel/sharding.py).
- Weights are stored ``(in_features, out_features)``; contraction is always
  on axis 0 of the weight.
- ``Ctx`` carries cross-cutting state through apply functions: the
  quantization policy (the QUANTIZATION O-task's output), the mesh (for
  shard_map-based expert parallelism), and kernel dispatch flags.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.quant.policy import (BF16, FP32, FP8, INT8, PrecisionPolicy,
                                quantize_int8)


@dataclasses.dataclass(frozen=True)
class Ctx:
    """Apply-time context threaded through every layer."""
    policy: PrecisionPolicy | None = None
    mesh: Any = None                 # jax.sharding.Mesh or None
    use_kernels: bool = False        # Pallas kernels (TPU target)
    interpret: bool = False          # Pallas interpret mode (CPU tests)
    remat: str = "none"              # none | dots | full
    decode: bool = False
    fsdp_params: bool = False        # FSDP-shard MoE expert weights
    moe_fsdp_mode: str = "gather"    # gather weights | "partial": compute
    # on f-sharded weights and psum activations (12x less volume when
    # C*d << weight bytes — §Perf pair B)

    def level_for(self, name: str) -> str:
        if self.policy is None:
            return BF16
        return self.policy.level_for(name)


DEFAULT_CTX = Ctx()


# ------------------------------------------------------------------ init
def normal_draw(key, shape) -> jnp.ndarray:
    """Standard-normal float32 draw, kept a value of its own under jit.

    ``jax.random.normal`` ends in a multiply by sqrt(2).  Inside one
    compiled init program XLA would fold a caller's constant scale into
    that factor — one rounding instead of two — and move weights by an
    ulp against an eager init; the barrier keeps both roundings."""
    return jax.lax.optimization_barrier(
        jax.random.normal(key, shape, jnp.float32))


def dense_init(key, d_in: int, d_out: int, dtype=jnp.float32,
               scale: float | None = None) -> jnp.ndarray:
    scale = scale if scale is not None else 1.0 / np.sqrt(d_in)
    return (normal_draw(key, (d_in, d_out)) * scale).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype=jnp.float32) -> jnp.ndarray:
    return (normal_draw(key, (vocab, d)) * 0.02).astype(dtype)


# ------------------------------------------------------------- linear op
def linear(ctx: Ctx, name: str, x: jnp.ndarray, w: jnp.ndarray,
           b: jnp.ndarray | None = None) -> jnp.ndarray:
    """Policy-dispatched linear layer: ``x @ w + b``.

    The precision level for ``name`` decides the executed kernel — this is
    the cross-stage hook where the QUANTIZATION O-task's per-layer policy is
    "instrumented into the kernel" (paper §V-B, DESIGN.md §2).
    """
    level = ctx.level_for(name)
    out_dtype = x.dtype
    if level == INT8:
        y = _int8_matmul(ctx, x, w)
    elif level == FP8:
        # weight-only fp8 (e4m3) storage; bf16 MACs.
        w8 = w.astype(jnp.dtype("float8_e4m3fn")).astype(jnp.bfloat16)
        y = jnp.matmul(x.astype(jnp.bfloat16), w8,
                       preferred_element_type=jnp.float32)
    elif level == FP32:
        y = jnp.matmul(x.astype(jnp.float32), w.astype(jnp.float32))
    else:  # BF16
        y = jnp.matmul(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
    y = y.astype(out_dtype)
    if b is not None:
        y = y + b.astype(out_dtype)
    return y


@jax.custom_vjp
def _int8_mm_ste(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """Dynamic-activation int8 x int8 matmul, int32 accumulation.

    custom_vjp: the FORWARD runs real int8 dots (MXU int8 path — this is
    what the dry-run/roofline sees); the BACKWARD is the straight-through
    estimator (grads as if the matmul were full-precision), so int8
    policies train correctly (QAT semantics).  Without this, jnp.round's
    zero derivative silently kills the backward pass — found the hard way
    in §Perf pair A.
    """
    wq, wscale = quantize_int8(w, axis=0)           # (in,out), (1,out)
    absmax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    xscale = jnp.maximum(absmax, 1e-8) / 127.0
    xq = jnp.clip(jnp.round(x.astype(jnp.float32) / xscale), -127, 127
                  ).astype(jnp.int8)
    acc = jax.lax.dot_general(
        xq, wq, (((xq.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * xscale * wscale.reshape(
        (1,) * (acc.ndim - 1) + (-1,))


def _int8_mm_fwd(x, w):
    return _int8_mm_ste(x, w), (x, w)


def _int8_mm_bwd(res, g):
    x, w = res
    gf = g.astype(jnp.float32)
    gx = jnp.matmul(gf, w.astype(jnp.float32).T).astype(x.dtype)
    x2 = x.astype(jnp.float32).reshape(-1, x.shape[-1])
    g2 = gf.reshape(-1, gf.shape[-1])
    gw = jnp.matmul(x2.T, g2).astype(w.dtype)
    return gx, gw


_int8_mm_ste.defvjp(_int8_mm_fwd, _int8_mm_bwd)


def _int8_matmul(ctx: Ctx, x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    if ctx.use_kernels and x.ndim >= 2 and w.ndim == 2:
        from repro.kernels import ops as kops
        lead = x.shape[:-1]
        x2 = x.reshape(-1, x.shape[-1])
        y2 = kops.quant_matmul(x2, w, interpret=ctx.interpret)
        return y2.reshape(*lead, w.shape[-1]).astype(x.dtype)
    return _int8_mm_ste(x, w)


# ------------------------------------------------------------------ norms
def init_rmsnorm(d: int, dtype=jnp.float32):
    return {"scale": jnp.ones((d,), dtype)}, {"scale": ("embed",)}


def rmsnorm(params, x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(x.dtype)


def init_layernorm(d: int, dtype=jnp.float32):
    return ({"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)},
            {"scale": ("embed",), "bias": ("embed",)})


def layernorm(params, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)
            + params["bias"].astype(jnp.float32)).astype(x.dtype)


def norm_apply(kind: str, params, x: jnp.ndarray) -> jnp.ndarray:
    return rmsnorm(params, x) if kind == "rmsnorm" else layernorm(params, x)


def init_norm(kind: str, d: int, dtype=jnp.float32):
    return init_rmsnorm(d, dtype) if kind == "rmsnorm" \
        else init_layernorm(d, dtype)


# ------------------------------------------------------------------- RoPE
def rope_freqs(head_dim: int, theta: float = 10000.0) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray,
               theta: float = 10000.0) -> jnp.ndarray:
    """x: (..., seq, heads, head_dim); positions: (..., seq)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                      # (hd/2,)
    ang = positions[..., :, None, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    xr1 = x1 * cos - x2 * sin
    xr2 = x2 * cos + x1 * sin
    out = jnp.stack([xr1, xr2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


def sinusoid_positions(n: int, d: int) -> jnp.ndarray:
    pos = np.arange(n)[:, None]
    i = np.arange(d // 2)[None, :]
    ang = pos / np.power(10000.0, 2 * i / d)
    emb = np.concatenate([np.sin(ang), np.cos(ang)], axis=-1)
    return jnp.asarray(emb, jnp.float32)


# ------------------------------------------------------------- activations
def act_fn(kind: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu,
            "relu": jax.nn.relu}[kind]


def shard_hidden(ctx: Ctx, x: jnp.ndarray) -> jnp.ndarray:
    """Constraint: activations sharded on batch over (pod,data)."""
    if ctx.mesh is None:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P
    axes = tuple(a for a in ("pod", "data") if a in ctx.mesh.axis_names)
    if not axes:
        return x
    spec = P(axes if len(axes) > 1 else axes[0],
             *([None] * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(ctx.mesh, spec))
