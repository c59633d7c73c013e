"""Seeded weights, drawn on the device in the published layout, and their
layout for the program.

``draw(arch, key)`` is one jitted program that returns every parameter of
the published architecture in bfloat16: matrices ``N(0, 1/fan_in)``, the
embedding ``N(0, 0.02^2)``, biases ``N(0, 0.1^2)``, norm scales
``1 + N(0, 0.1^2)`` and norm biases ``N(0, 0.1^2)``, so that every bias and
norm path moves the logits.  Layers are drawn one at a time
(``lax.map``) and the vocabulary in row blocks, so only one slice's float32
draw is live at once.  Names and shapes follow the published checkpoints
(``q_w`` is ``(hidden, heads * head_dim)`` with the heads' dims in the
rotate-half order).

``to_program(arch, published)`` lays them out as the program's parameter
tree; its only arithmetic-free change is the RoPE pair permutation of the
q and k columns (see the configuration files' ``departures``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.cell import Arch

DT = jnp.bfloat16
BIAS_STD = 0.1
NORM_STD = 0.1
EMBED_STD = 0.02


def seed_key(seed: int):
    """A JAX key from any whole-number seed (some exceed 32 bits)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def _normal(key, shape, std):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(DT)


def _layer(a: Arch, key):
    d, h, kv, hd, ff = a.d, a.n_heads, a.n_kv, a.hd, a.ff
    k = iter(jax.random.split(key, 16))
    p = {"ln1_w": (1.0 + jax.random.normal(next(k), (d,)) * NORM_STD
                   ).astype(DT),
         "ln2_w": (1.0 + jax.random.normal(next(k), (d,)) * NORM_STD
                   ).astype(DT),
         "q_w": _normal(next(k), (d, h * hd), d ** -0.5),
         "k_w": _normal(next(k), (d, kv * hd), d ** -0.5),
         "v_w": _normal(next(k), (d, kv * hd), d ** -0.5),
         "o_w": _normal(next(k), (h * hd, d), (h * hd) ** -0.5)}
    if a.norm == "layer":
        p["ln1_b"] = _normal(next(k), (d,), NORM_STD)
        p["ln2_b"] = _normal(next(k), (d,), NORM_STD)
    if a.qkv_bias:
        p["q_b"] = _normal(next(k), (h * hd,), BIAS_STD)
        p["k_b"] = _normal(next(k), (kv * hd,), BIAS_STD)
        p["v_b"] = _normal(next(k), (kv * hd,), BIAS_STD)
    if a.glu:
        p["gate_w"] = _normal(next(k), (d, ff), d ** -0.5)
        p["up_w"] = _normal(next(k), (d, ff), d ** -0.5)
        p["down_w"] = _normal(next(k), (ff, d), ff ** -0.5)
    else:
        p["fc_w"] = _normal(next(k), (d, ff), d ** -0.5)
        p["proj_w"] = _normal(next(k), (ff, d), ff ** -0.5)
        if a.mlp_bias:
            p["fc_b"] = _normal(next(k), (ff,), BIAS_STD)
            p["proj_b"] = _normal(next(k), (d,), BIAS_STD)
    return p


def _blocks(n: int) -> int:
    """Row blocks the vocabulary is drawn in."""
    return next(b for b in (32, 16, 8, 4, 2, 1) if n % b == 0)


def _by_rows(key, rows: int, cols: int, std: float):
    nb = _blocks(rows)
    out = jax.lax.map(lambda k: _normal(k, (rows // nb, cols), std),
                      jax.random.split(key, nb))
    return out.reshape(rows, cols)


@functools.partial(jax.jit, static_argnums=0)
def draw(a: Arch, key):
    k_emb, k_layers, k_norm, k_head = jax.random.split(key, 4)
    out = {"embed": _by_rows(k_emb, a.vocab, a.d, EMBED_STD),
           "layers": jax.lax.map(functools.partial(_layer, a),
                                 jax.random.split(k_layers, a.layers))}
    kn = jax.random.split(k_norm, 2)
    out["norm_w"] = (1.0 + jax.random.normal(kn[0], (a.d,)) * NORM_STD
                     ).astype(DT)
    if a.norm == "layer":
        out["norm_b"] = _normal(kn[1], (a.d,), NORM_STD)
    if not a.tied:
        out["head"] = _by_rows(k_head, a.d, a.vocab, a.d ** -0.5)
    return out


def rope_pair_order(hd: int) -> np.ndarray:
    """Column ``j`` of a program head holds published column ``src[j]``:
    the program pairs (2i, 2i+1) where the published model pairs
    (i, i + hd/2)."""
    half = np.arange(hd // 2)
    return np.stack([half, half + hd // 2], axis=-1).reshape(-1)


def _heads_permuted(x, hd: int):
    lead, cols = x.shape[:-1], x.shape[-1]
    x = x.reshape(*lead, cols // hd, hd)
    return x[..., rope_pair_order(hd)].reshape(*lead, cols)


@functools.partial(jax.jit, static_argnums=0, donate_argnums=1)
def to_program(a: Arch, w):
    """The program's parameter tree for the published weights ``w``."""
    L = w["layers"]

    def norm(scale, bias):
        return {"scale": scale} if bias is None else {"scale": scale,
                                                      "bias": bias}

    attn = {"wq": _heads_permuted(L["q_w"], a.hd),
            "wk": _heads_permuted(L["k_w"], a.hd),
            "wv": L["v_w"], "wo": L["o_w"]}
    if a.qkv_bias:
        attn.update(bq=_heads_permuted(L["q_b"], a.hd),
                    bk=_heads_permuted(L["k_b"], a.hd), bv=L["v_b"])
    if a.glu:
        mlp = {"w_gate": L["gate_w"], "w_up": L["up_w"],
               "w_down": L["down_w"]}
    else:
        mlp = {"w_up": L["fc_w"], "w_down": L["proj_w"]}
        if a.mlp_bias:
            mlp.update(b_up=L["fc_b"], b_down=L["proj_b"])
    out = {"embed": w["embed"],
           "blocks": {"attn_norm": norm(L["ln1_w"], L.get("ln1_b")),
                      "attn": attn,
                      "mlp_norm": norm(L["ln2_w"], L.get("ln2_b")),
                      "mlp": mlp},
           "final_norm": norm(w["norm_w"], w.get("norm_b"))}
    if not a.tied:
        out["lm_head"] = w["head"]
    return out


def program_params(a: Arch, model, key):
    """Draw and lay out the weights; the tree must match the program's own
    abstract parameters leaf for leaf (structure, shape and dtype)."""
    params = to_program(a, draw(a, key))
    want = model.abstract_params()
    got_s, want_s = jax.tree.structure(params), jax.tree.structure(want)
    if got_s != want_s:
        raise ValueError(f"weight tree differs from the program's:\n"
                         f"{got_s}\n{want_s}")
    for g, w_ in zip(jax.tree.leaves(params), jax.tree.leaves(want)):
        if g.shape != w_.shape or g.dtype != w_.dtype:
            raise ValueError(f"leaf {g.shape} {g.dtype} != program's "
                             f"{w_.shape} {w_.dtype}")
    return params
