"""Pallas TPU kernel: paged flash-decoding attention over a block-table KV
cache.

The paged mirror of kernels/flash_decode.py: one generated token per
request attends over that request's KV history, but the cache is no longer
one contiguous ``(B, L, KV, D)`` buffer — it is a shared pool of
fixed-size pages plus a per-request *block table* mapping logical block
``j`` of request ``r`` to a physical page.  That indirection is what lets
the serving engine admit/evict requests without ever copying or
compacting KV state (src/repro/serving/).

The pool is the whole model's, head-major, ``(L, P, KV, page_size, D)``
(the layout serving/paged_cache.py owns), so one grid step DMAs one
head's page of one layer as a contiguous ``(page_size, D)`` tile — a
block the TPU compiler accepts, where a ``(page_size, 1, D)`` slice of a
token-major pool is refused.  The layer is a scalar-prefetch operand the
index maps read, so the layer scan hands the kernel the pool it carries
and no layer's pages are ever sliced out of it.

Mechanically the kv-split of flash_decode becomes the page: the grid is
``(slots, kv_heads, blocks_per_req)`` and the innermost dimension walks
the request's block table, reducing pages with the partial-softmax
``(m, l, acc)`` carry in VMEM scratch.  The block table rides in as a
*scalar-prefetch* operand (``pltpu.PrefetchScalarGridSpec``) so the K/V
BlockSpec index maps can dereference it — the DMA for page ``bt[r, j]``
is issued directly from the table, no gather of the pool ever
materializes.

GQA uses the same grouped-q fold as flash_decode: q is reshaped
``(R, H, D) -> (R, KV, g, D)`` so the ``g`` query heads sharing a kv head
score against one K/V page read.

Masking: ``seq_lens`` is a second scalar-prefetch operand and the kernel
builds its validity predicate from an iota — logical slot ``j * ps + i``
is live iff it is ``<= seq_lens[r]``, exactly
``models/layers.py::paged_decode_attention_mask`` (pinned against each
other in tests/test_paged.py).  Ragged per-request lengths are therefore
free; a page wholly past a request's length skips its MXU work
(``pl.when``) and its index map re-points at the request's last live page,
so the pipeline issues no DMA for it either.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_decode import (NEG_INF, online_softmax_finish,
                                        online_softmax_init)

DEFAULT_PAGE_SIZE = 16


def online_softmax_page(q, k, v, live, m_ref, l_ref, acc_ref) -> None:
    """Accumulate one K/V page into the (m, l, acc) carry.

    q: (rows, d) f32, already scaled; k/v: (ps, d); live: (rows, ps)
    bool.  Rows with no live slot in any page so far keep m == NEG_INF,
    where exp(s - m) would be exp(0) = 1 and poison them with a false
    uniform weighting; their terms are zeroed so dead rows finish at
    l = 0 (zero output).  Live rows are untouched: their masked slots
    underflow to exactly 0 anyway.  Shared by both paged kernels.
    """
    s = jax.lax.dot_general(q, k.astype(jnp.float32),
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = jnp.where(live, s, NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.where(live, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def _paged_decode_kernel(bt_ref, sl_ref, layer_ref, q_ref, k_ref, v_ref,
                         out_ref, m_ref, l_ref, acc_ref, *, blocks: int,
                         ps: int, g: int, scale: float):
    del bt_ref, layer_ref  # consumed by the BlockSpec index maps
    ri = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        online_softmax_init(m_ref, l_ref, acc_ref)

    sl = sl_ref[ri]

    @pl.when(j * ps <= sl)
    def _step():
        kv_pos = j * ps + jax.lax.broadcasted_iota(jnp.int32, (g, ps), 1)
        online_softmax_page(q_ref[0, 0].astype(jnp.float32) * scale,
                            k_ref[0, 0, 0], v_ref[0, 0, 0], kv_pos <= sl,
                            m_ref, l_ref, acc_ref)

    @pl.when(j == blocks - 1)
    def _finish():
        online_softmax_finish(out_ref, m_ref, l_ref, acc_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def flash_decode_paged(q: jnp.ndarray, k_pages: jnp.ndarray,
                       v_pages: jnp.ndarray, block_tables: jnp.ndarray,
                       seq_lens: jnp.ndarray, layer, *,
                       interpret: bool = False) -> jnp.ndarray:
    """q: (R,1,H,D); k/v_pages: (L, P, KV, page_size, D), the whole
    model's pools, with H % KV == 0; block_tables: (R, max_blocks) int32
    physical page per logical block (entries past a request's length
    must still be valid page indices — the serving layer parks them on
    its reserved scratch page); seq_lens: (R,) int32 — logical slots
    ``0..seq_lens[r]`` participate (the slot at ``seq_lens[r]`` holds the
    current token); layer: the layer whose pages are attended, an int or
    an int32 scalar (the layer scan's traced index).  Returns
    (R,1,H,D).  The page size is the kv-split: it is fixed by the pool
    layout, so it is tuned at pool-construction time
    (kernels/autotune.py ``flash_decode_paged``), not per call.
    """
    r, sq, h, d = q.shape
    _, _, kvh, ps, _ = k_pages.shape
    rt, blocks = block_tables.shape
    assert sq == 1, f"flash_decode_paged is single-token (got sq={sq})"
    assert h % kvh == 0, (h, kvh)
    assert rt == r, (rt, r)
    assert seq_lens.shape == (r,), (seq_lens.shape, r)
    g = h // kvh
    qf = q[:, 0].reshape(r, kvh, g, d)
    grid = (r, kvh, blocks)

    def kv_map(ri, kv, j, bt, sl, lyr):
        # pages past the last live one re-point at it: same block index
        # as the previous step, so the pipeline skips their copy
        return (lyr[0], bt[ri, jnp.minimum(j, sl[ri] // ps)], kv, 0, 0)

    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, blocks=blocks, ps=ps, g=g,
                          scale=1.0 / math.sqrt(d)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, g, d),
                             lambda ri, kv, j, *_: (ri, kv, 0, 0)),
                pl.BlockSpec((1, 1, 1, ps, d), kv_map),
                pl.BlockSpec((1, 1, 1, ps, d), kv_map),
            ],
            out_specs=pl.BlockSpec((1, 1, g, d),
                                   lambda ri, kv, j, *_: (ri, kv, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((r, kvh, g, d), q.dtype),
        interpret=interpret,
    )(block_tables, seq_lens.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), qf, k_pages, v_pages)
    return out.reshape(r, 1, h, d)
