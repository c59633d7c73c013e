"""Plain float32 reference of the two dense decoders, as published.

Qwen2: RMSNorm, q/k/v with bias, rotate-half RoPE, grouped-query causal
attention, SwiGLU MLP, untied lm_head.  Starcoder2: LayerNorm with bias,
q/k/v/o with bias, rotate-half RoPE, grouped-query causal attention, GELU
(tanh) MLP with biases, lm_head tied to the embedding.  Nothing here
imports the program; the weights are the benchmark's own draw
(``weights.draw``) in the published layout, held in bfloat16 as served and
widened to float32 at each use, and every product runs at ``highest``
precision.

It runs after the window, one sequence at a time and layer by layer, so
that it fits on the chip beside nothing else: a sequence is padded at its
end to a multiple of ``PAD`` tokens (causal attention never lets a valid
position see the padding), and logits are formed only at the positions
that produced a served token.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.cell import Arch

HI = jax.lax.Precision.HIGHEST
PAD = 512
F32 = jnp.float32


def _mm(x, w):
    return jnp.matmul(x, w.astype(F32), precision=HI)


def _norm(a: Arch, x, w, b=None):
    if a.norm == "rms":
        y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + a.eps)
        return y * w.astype(F32)
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + a.eps) * w.astype(F32) \
        + b.astype(F32)


def _rope(a: Arch, x, pos):
    """Rotate-half RoPE: dims (i, i + hd/2) rotate by pos * theta^(-2i/hd).
    x: (S, heads, hd)."""
    half = a.hd // 2
    inv = 1.0 / (a.rope_theta ** (jnp.arange(half, dtype=F32) * 2 / a.hd))
    ang = pos[:, None, None].astype(F32) * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _bias(lw, name):
    return lw[name].astype(F32) if name in lw else 0.0


@functools.partial(jax.jit, static_argnums=0)
def _layer(a: Arch, layers, i, x):
    """Decoder layer ``i`` of the stacked ``layers`` on x: (S, d) float32."""
    lw = jax.tree.map(lambda t: jax.lax.dynamic_index_in_dim(t, i, 0, False),
                      layers)
    s = x.shape[0]
    pos = jnp.arange(s)
    h = _norm(a, x, lw["ln1_w"], lw.get("ln1_b"))
    q = (_mm(h, lw["q_w"]) + _bias(lw, "q_b")).reshape(s, a.n_heads, a.hd)
    k = (_mm(h, lw["k_w"]) + _bias(lw, "k_b")).reshape(s, a.n_kv, a.hd)
    v = (_mm(h, lw["v_w"]) + _bias(lw, "v_b")).reshape(s, a.n_kv, a.hd)
    q, k = _rope(a, q, pos), _rope(a, k, pos)
    g = a.n_heads // a.n_kv
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    sc = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / math.sqrt(a.hd)
    sc = jnp.where(pos[None, :, None] >= pos[None, None, :], sc, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, axis=-1), v,
                   precision=HI).reshape(s, a.n_heads * a.hd)
    x = x + _mm(o, lw["o_w"])          # the program has no o_proj bias
    h = _norm(a, x, lw["ln2_w"], lw.get("ln2_b"))
    if a.glu:
        f = jax.nn.silu(_mm(h, lw["gate_w"])) * _mm(h, lw["up_w"])
        return x + _mm(f, lw["down_w"])
    f = jax.nn.gelu(_mm(h, lw["fc_w"]) + _bias(lw, "fc_b"), approximate=True)
    return x + _mm(f, lw["proj_w"]) + _bias(lw, "proj_b")


@functools.partial(jax.jit, static_argnums=0)
def _embed(a: Arch, embed, tokens):
    return embed[tokens].astype(F32)


@functools.partial(jax.jit, static_argnums=0)
def _logits(a: Arch, w, x, rows, tokens):
    """Final norm and lm_head at ``rows`` of x: (n, V) logits, and how far
    the logit of ``tokens[j]`` lies below the best of row ``rows[j]``."""
    h = _norm(a, x[rows], w["norm_w"], w.get("norm_b"))
    head = w["embed"].T if a.tied else w["head"]
    lg = _mm(h, head)
    got = jnp.take_along_axis(lg, tokens[:, None], axis=1)[:, 0]
    return lg, jnp.max(lg, axis=-1) - got


def padded(x: np.ndarray, to: int) -> np.ndarray:
    out = np.zeros((-(-len(x) // to) * to,), np.int32)
    out[:len(x)] = x
    return out


def served_gaps(a: Arch, w, prompt: np.ndarray, served: list[int]):
    """Per served token, how far its reference logit lies below the
    reference's best at the position that produced it, and the reference
    logits there.  The reference runs once over the prompt followed by the
    served tokens (teacher forcing)."""
    seq = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
    n = len(served)
    rows = np.arange(len(prompt) - 1, len(seq))
    with jax.default_matmul_precision("highest"):
        x = _embed(a, w["embed"], jnp.asarray(padded(seq, PAD)))
        for i in range(a.layers):
            x = _layer(a, w["layers"], jnp.int32(i), x)
        lg, gap = _logits(a, w, x, jnp.asarray(padded(rows, 128)),
                          jnp.asarray(padded(np.asarray(served), 128)))
    return np.asarray(gap)[:n], lg[:n]
