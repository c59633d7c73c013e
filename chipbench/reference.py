"""Plain float32 reference of a served decoder, as published.

The architecture's own equations (its layer and its head) are its
plug-in's, ``archs/<model_type>.py`` (``Arch.layer``, ``Arch.logits``);
what every decoder shares is here: the padding, the embedding lookup, the
layer-by-layer loop and the gaps at the served rows.  Nothing here or in a
plug-in imports the program; the weights are the benchmark's own draw
(``weights.draw``) in the published layout, held in bfloat16 as served and
widened to float32 at each use, and every product runs at ``highest``
precision (``mm``).

It runs after the window, one sequence at a time and layer by layer, so
that it fits on the chip beside nothing else: a sequence is padded at its
end to a multiple of ``PAD`` tokens (causal attention never lets a valid
position see the padding), and logits are formed only at the positions
that produced a served token.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
PAD = 512
F32 = jnp.float32


def mm(x, w):
    """x @ w in float32 at ``highest`` precision, ``w`` widened."""
    return jnp.matmul(x, w.astype(F32), precision=HI)


@functools.partial(jax.jit, static_argnums=0)
def _layer(a, layers, i, x):
    """Decoder layer ``i`` of the stacked ``layers`` on x: (S, d) float32."""
    lw = jax.tree.map(lambda t: jax.lax.dynamic_index_in_dim(t, i, 0, False),
                      layers)
    return a.layer(lw, x, jnp.arange(x.shape[0]))


@jax.jit
def _embed(embed, tokens):
    return embed[tokens].astype(F32)


@functools.partial(jax.jit, static_argnums=0)
def _logits(a, w, x, rows, tokens):
    """The head at ``rows`` of x: (n, V) logits, and how far the logit of
    ``tokens[j]`` lies below the best of row ``rows[j]``."""
    lg = a.logits(w, x[rows])
    got = jnp.take_along_axis(lg, tokens[:, None], axis=1)[:, 0]
    return lg, jnp.max(lg, axis=-1) - got


def padded(x: np.ndarray, to: int) -> np.ndarray:
    out = np.zeros((-(-len(x) // to) * to,), np.int32)
    out[:len(x)] = x
    return out


def served_gaps(a, w, prompt: np.ndarray, served: list[int]):
    """Per served token, how far its reference logit lies below the
    reference's best at the position that produced it, and the reference
    logits there.  The reference runs once over the prompt followed by the
    served tokens (teacher forcing)."""
    seq = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
    n = len(served)
    rows = np.arange(len(prompt) - 1, len(seq))
    with jax.default_matmul_precision("highest"):
        x = _embed(w["embed"], jnp.asarray(padded(seq, PAD)))
        for i in range(a.layers):
            x = _layer(a, w["layers"], jnp.int32(i), x)
        lg, gap = _logits(a, w, x, jnp.asarray(padded(rows, 128)),
                          jnp.asarray(padded(np.asarray(served), 128)))
    return np.asarray(gap)[:n], lg[:n]
