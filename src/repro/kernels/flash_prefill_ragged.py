"""Pallas TPU kernel: batched ragged prefill attention over a block-table
paged KV cache.

The admission-side mirror of kernels/flash_decode_paged.py: a segment
boundary can admit several requests at once, each with a different prompt
length and a different *shared-prefix offset* (pages already resident
from the prefix cache — serving/paged_cache.py::PrefixCache).  Instead of
one batch-1 prefill dispatch per admission, this kernel computes causal
attention for every admission's *suffix* tokens (the tokens after its
shared prefix) in one dispatch, reading K/V — shared prefix and freshly
scattered suffix alike — straight out of the page pool through the block
table.

The pool is the whole model's, head-major, ``(L, P, KV, page_size, D)``
(serving/paged_cache.py), so each grid step DMAs one head's page of one
layer as a contiguous ``(page_size, D)`` tile; the layer rides in as a
scalar-prefetch operand the index maps read.

Grid: ``(slots, kv_heads, q_tiles, blocks)``.  The innermost dimension
walks the request's block table exactly like the paged decode kernel,
reducing pages into the partial-softmax ``(m, l, acc)`` carry held in
VMEM scratch; the block table rides in as a scalar-prefetch operand so
the K/V index maps DMA page ``bt[r, j]`` directly.  Two more
scalar-prefetch operands carry the per-sequence ragged geometry:
``offsets[r]`` (absolute position of the request's first suffix token =
its shared-prefix length) and ``lens[r]`` (valid suffix tokens).  The
kernel derives its causal/validity mask from them with iotas — the same
predicate ``models/layers.py::ragged_prefill_attention_mask`` builds for
the jnp oracle (pinned against each other in tests/test_paged.py), so
the two paths cannot disagree about which (query, slot) pairs interact.
Tiles with no live pair — a q tile past the request's suffix, a page
beyond the causal frontier, an idle batch slot (``lens[r] == 0``) — skip
their MXU work entirely (``pl.when`` on a scalar test of the same
predicate), which is what makes one padded dispatch serve a ragged
admission batch.

GQA uses the grouped-q fold of the decode kernels, extended to multiple
query positions: q is laid out ``(R, KV, S * g, D)`` so the ``g`` query
heads sharing a kv head occupy adjacent rows of one tile and score
against a single K/V page read.

``block_q`` is the tunable tile (kernels/autotune.py
``flash_prefill_ragged``); the page size is fixed by the pool layout and
arrives through the K/V shapes.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_decode import (online_softmax_finish,
                                        online_softmax_init)
from repro.kernels.flash_decode_paged import online_softmax_page

BQ = 32


def _ragged_prefill_kernel(bt_ref, off_ref, len_ref, layer_ref, q_ref,
                           k_ref, v_ref, out_ref, m_ref, l_ref, acc_ref, *,
                           blocks: int, bq: int, ps: int, g: int,
                           scale: float):
    del bt_ref, layer_ref  # consumed by the BlockSpec index maps
    ri = pl.program_id(0)
    qi = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        online_softmax_init(m_ref, l_ref, acc_ref)

    off = off_ref[ri]
    ln = len_ref[ri]
    # the tile holds a live pair iff its first query is valid and page j
    # starts at or before the causal frontier of its last valid query
    q_last = jnp.minimum(qi * bq + bq, ln) - 1

    @pl.when((qi * bq < ln) & (j * ps <= off + q_last))
    def _step():
        # rows of the (bq * g, ps) score panel: row -> suffix-local q
        # index (g adjacent rows share one query position), col -> slot
        # in page j.  Mirrors models/layers.py::
        # ragged_prefill_attention_mask: a slot participates when its
        # logical position <= the query's absolute position (causal over
        # prefix + own suffix) and the query is live.
        qrel = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq * g, ps),
                                                  0) // g
        kv_pos = j * ps + jax.lax.broadcasted_iota(jnp.int32, (bq * g, ps),
                                                   1)
        live = (kv_pos <= off + qrel) & (qrel < ln)
        online_softmax_page(q_ref[0, 0].astype(jnp.float32) * scale,
                            k_ref[0, 0, 0], v_ref[0, 0, 0], live,
                            m_ref, l_ref, acc_ref)

    @pl.when(j == blocks - 1)
    def _finish():
        online_softmax_finish(out_ref, m_ref, l_ref, acc_ref)


@functools.partial(jax.jit, static_argnames=("interpret", "block_q"))
def flash_prefill_ragged(q: jnp.ndarray, k_pages: jnp.ndarray,
                         v_pages: jnp.ndarray, block_tables: jnp.ndarray,
                         offsets: jnp.ndarray, lens: jnp.ndarray, layer,
                         *, interpret: bool = False,
                         block_q: int | None = None) -> jnp.ndarray:
    """q: (R,S,H,D) suffix queries; k/v_pages: (L, P, KV, page_size, D),
    the whole model's pools, with H % KV == 0; block_tables:
    (R, max_blocks) int32 (entries past a request's pages parked on the
    serving layer's scratch page); offsets/lens: (R,) int32 — absolute
    position of q[:, 0] (the shared prefix length) and valid suffix
    tokens per request (0 = idle slot); layer: the layer whose pages are
    attended, an int or an int32 scalar (the layer scan's traced index).
    Suffix K/V must already be scattered into the pages (the layer does
    this before attending, exactly like the decode path).  Returns
    (R,S,H,D); rows at or past ``lens`` are zero.
    """
    r, s, h, d = q.shape
    _, _, kvh, ps, _ = k_pages.shape
    rt, blocks = block_tables.shape
    assert h % kvh == 0, (h, kvh)
    assert rt == r, (rt, r)
    assert offsets.shape == (r,) and lens.shape == (r,)
    g = h // kvh
    bq = min(block_q or BQ, s)
    pad = (-s) % bq
    # grouped-q fold with a seq axis: g query heads sharing one kv head
    # sit in adjacent rows, so one tile is (bq * g, d) rows vs one page
    qf = q.reshape(r, s, kvh, g, d).transpose(0, 2, 1, 3, 4)
    if pad:
        qf = jnp.pad(qf, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    s_p = s + pad
    qf = qf.reshape(r, kvh, s_p * g, d)
    grid = (r, kvh, s_p // bq, blocks)

    def kv_map(ri, kv, qi, j, bt, off, ln, lyr):
        return (lyr[0], bt[ri, j], kv, 0, 0)

    out = pl.pallas_call(
        functools.partial(_ragged_prefill_kernel, blocks=blocks, bq=bq,
                          ps=ps, g=g, scale=1.0 / math.sqrt(d)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, bq * g, d),
                             lambda ri, kv, qi, j, *_: (ri, kv, qi, 0)),
                pl.BlockSpec((1, 1, 1, ps, d), kv_map),
                pl.BlockSpec((1, 1, 1, ps, d), kv_map),
            ],
            out_specs=pl.BlockSpec((1, 1, bq * g, d),
                                   lambda ri, kv, qi, j, *_:
                                   (ri, kv, qi, 0)),
            scratch_shapes=[
                pltpu.VMEM((bq * g, 1), jnp.float32),
                pltpu.VMEM((bq * g, 1), jnp.float32),
                pltpu.VMEM((bq * g, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((r, kvh, s_p * g, d), q.dtype),
        interpret=interpret,
    )(block_tables, offsets.astype(jnp.int32), lens.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), qf, k_pages, v_pages)
    out = out.reshape(r, kvh, s_p, g, d).transpose(0, 2, 1, 3, 4)
    return out.reshape(r, s_p, h, d)[:, :s]
