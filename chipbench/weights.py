"""Seeded weights, drawn on the device in the published layout, and their
layout for the program.

``draw(arch, key)`` is one jitted program that returns every parameter of
the configuration's architecture (its plug-in's ``Arch.draw``, see
``cell.arch_plugin``) in bfloat16, with the laws kept here: matrices
``N(0, 1/fan_in)``, the embedding ``N(0, 0.02^2)``, biases ``N(0, 0.1^2)``
and norm scales ``1 + N(0, 0.1^2)``, so that every bias and norm path moves
the logits.  A plug-in draws its layers one at a time (``lax.map``) and
the vocabulary in row blocks (``by_rows``), so that only one slice's
float32 draw is live at once.  Names and shapes follow the published
checkpoints (``q_w`` is ``(hidden, heads * head_dim)`` with the heads'
dims in the rotate-half order).

``to_program(arch, published)`` lays them out as the program's parameter
tree (``Arch.to_program``); for a rotate-half model its only
arithmetic-free change is the RoPE pair permutation of the q and k
columns, ``heads_permuted`` (see the configuration files' ``departures``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

DT = jnp.bfloat16
BIAS_STD = 0.1
NORM_STD = 0.1
EMBED_STD = 0.02


def seed_key(seed: int):
    """A JAX key from any whole-number seed (some exceed 32 bits)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def normal(key, shape, std):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(DT)


def norm_scale(key, shape):
    return (1.0 + jax.random.normal(key, shape) * NORM_STD).astype(DT)


def _blocks(n: int) -> int:
    """Row blocks the vocabulary is drawn in."""
    return next(b for b in (32, 16, 8, 4, 2, 1) if n % b == 0)


def by_rows(key, rows: int, cols: int, std: float):
    """``normal`` of shape (rows, cols), drawn in row blocks."""
    nb = _blocks(rows)
    out = jax.lax.map(lambda k: normal(k, (rows // nb, cols), std),
                      jax.random.split(key, nb))
    return out.reshape(rows, cols)


@functools.partial(jax.jit, static_argnums=0)
def draw(a, key):
    return a.draw(key)


def rope_pair_order(hd: int) -> np.ndarray:
    """Column ``j`` of a program head holds published column ``src[j]``:
    the program pairs (2i, 2i+1) where the published model pairs
    (i, i + hd/2)."""
    half = np.arange(hd // 2)
    return np.stack([half, half + hd // 2], axis=-1).reshape(-1)


def heads_permuted(x, hd: int):
    lead, cols = x.shape[:-1], x.shape[-1]
    x = x.reshape(*lead, cols // hd, hd)
    return x[..., rope_pair_order(hd)].reshape(*lead, cols)


@functools.partial(jax.jit, static_argnums=0, donate_argnums=1)
def to_program(a, w):
    """The program's parameter tree for the published weights ``w``."""
    return a.to_program(w)


def program_params(a, model, key):
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
