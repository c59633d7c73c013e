"""Attention (GQA / MLA / SWA), MLPs, and expert-parallel MoE.

All apply functions take a :class:`repro.models.common.Ctx` so the
QUANTIZATION O-task's policy reaches every matmul, and the mesh reaches the
shard_map-based expert-parallel MoE.

Attention has two execution paths:
- prefill/train: chunked memory-efficient attention (scan over kv chunks,
  online softmax) — bounded VMEM/HBM footprint for the 32k shapes; the
  Pallas flash kernel (kernels/flash_attention.py) is the TPU-optimized
  equivalent, validated against the same math.
- decode: single-token attention against a KV cache.  Caches shard their
  *sequence* axis over the ``model`` mesh axis (flash-decoding style):
  GSPMD turns the softmax/combine reductions into tiny cross-shard
  collectives instead of all-gathering the cache.  Under
  ``ctx.use_kernels`` (and an unsharded cache sequence axis) decode runs
  in the flash_decode Pallas kernel (kernels/flash_decode.py) — kv-split
  partial softmax, GQA without K/V expansion; the jnp path stays as its
  oracle and as the path GSPMD partitions when the cache is seq-sharded.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from repro.configs.base import ArchConfig
from repro.models.common import (Ctx, act_fn, apply_rope, dense_init,
                                 init_norm, linear, norm_apply)
from repro.quant.policy import INT8, quantize_int8

NEG_INF = -1e30


# =====================================================================
# Attention
# =====================================================================
def init_attention(key, cfg: ArchConfig, cross: bool = False):
    d, hd = cfg.d_model, cfg.hd
    h, kv = cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 8)
    dt = cfg.pdt
    params: dict[str, Any] = {
        "wq": dense_init(ks[0], d, h * hd, dt),
        "wk": dense_init(ks[1], d, kv * hd, dt),
        "wv": dense_init(ks[2], d, kv * hd, dt),
        "wo": dense_init(ks[3], h * hd, d, dt, scale=1.0 / math.sqrt(h * hd)),
    }
    axes: dict[str, Any] = {
        "wq": ("embed", "heads"),
        "wk": ("embed", "kv_heads"),
        "wv": ("embed", "kv_heads"),
        "wo": ("heads", "embed"),
    }
    if cfg.qkv_bias:
        params.update(bq=jnp.zeros((h * hd,), dt),
                      bk=jnp.zeros((kv * hd,), dt),
                      bv=jnp.zeros((kv * hd,), dt))
        axes.update(bq=("heads",), bk=("kv_heads",), bv=("kv_heads",))
    if cfg.qk_norm:
        qn, qax = init_norm(cfg.norm, hd, dt)
        kn, kax = init_norm(cfg.norm, hd, dt)
        params.update(q_norm=qn, k_norm=kn)
        axes.update(q_norm={k: ("head_dim",) for k in qn},
                    k_norm={k: ("head_dim",) for k in kn})
    return params, axes


def _qkv(ctx: Ctx, cfg: ArchConfig, p, x):
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    b, s, _ = x.shape
    q = linear(ctx, "attn/wq", x, p["wq"], p.get("bq"))
    k = linear(ctx, "attn/wk", x, p["wk"], p.get("bk"))
    v = linear(ctx, "attn/wv", x, p["wv"], p.get("bv"))
    q = q.reshape(b, s, h, hd)
    k = k.reshape(b, s, kv, hd)
    v = v.reshape(b, s, kv, hd)
    if cfg.qk_norm:
        q = norm_apply(cfg.norm, p["q_norm"], q)
        k = norm_apply(cfg.norm, p["k_norm"], k)
    return q, k, v


def _expand_kv(k: jnp.ndarray, n_heads: int) -> jnp.ndarray:
    """(B,S,KV,hd) -> (B,S,H,hd) by group repetition."""
    b, s, kvh, hd = k.shape
    g = n_heads // kvh
    return jnp.repeat(k, g, axis=2) if g > 1 else k


def kv_positions_for_cache(pos, cache_len: int,
                           sliding_window: int) -> jnp.ndarray:
    """Absolute position held by each decode-cache slot (2**30 = empty).

    Linear cache: slot i holds position i for i <= pos.  Sliding-window
    ring buffer: the current token lands at ``pos % cache_len`` and older
    slots wrap, so absolute positions are recovered from the write index;
    slots that would map to negative positions were never written.

    The single source of truth for cache-slot positions — shared by the
    jnp decode oracle and the flash_decode kernel's mask construction so
    the two paths cannot drift.
    """
    slot = jnp.arange(cache_len)
    if sliding_window:
        idx = pos % cache_len
        kv_pos = jnp.where(slot <= idx, pos - idx + slot,
                           pos - idx - cache_len + slot)
        return jnp.where(kv_pos >= 0, kv_pos, 2**30)
    return jnp.where(slot <= pos, slot, 2**30)


def decode_attention_mask(kv_pos: jnp.ndarray, pos,
                          sliding_window: int) -> jnp.ndarray:
    """(cache_len,) bool: which cache slots the token at ``pos`` attends."""
    mask = (kv_pos <= pos) & (kv_pos < 2**30)
    if sliding_window:
        mask &= (pos - kv_pos) < sliding_window
    return mask


def paged_kv_positions(seq_lens: jnp.ndarray, n_slots: int) -> jnp.ndarray:
    """(R, n_slots) absolute position held by each logical paged-cache slot.

    The paged variant of :func:`kv_positions_for_cache`: pages are
    logically contiguous per request (block tables hide the physical
    scatter), so slot ``i`` of request ``r`` holds position ``i`` when
    ``i <= seq_lens[r]`` (the slot at ``seq_lens[r]`` is the current
    token, written before attention) and is empty (2**30) past it.
    ``seq_lens`` is per-request — rows are ragged by construction.  The
    jnp paged oracle masks with it; the flash_decode_paged kernel builds
    the same predicate in-kernel from ``seq_lens`` (tests/test_paged.py
    pins the two against each other).
    """
    slot = jnp.arange(n_slots)
    return jnp.where(slot[None, :] <= seq_lens[:, None], slot[None, :],
                     2**30)


def paged_decode_attention_mask(kv_pos: jnp.ndarray,
                                seq_lens: jnp.ndarray) -> jnp.ndarray:
    """(R, n_slots) bool: slots each request's current token attends."""
    return (kv_pos <= seq_lens[:, None]) & (kv_pos < 2**30)


def ragged_prefill_positions(offsets: jnp.ndarray, s: int) -> jnp.ndarray:
    """(R, s) absolute position of each suffix query token.

    Batched ragged admission prefill computes only the tokens *after*
    each request's shared prefix; ``offsets[r]`` is the prefix length, so
    suffix token ``i`` of request ``r`` sits at ``offsets[r] + i``.  The
    single source of truth for suffix positions — lm_apply feeds it to
    rope, and the mask helper below derives the causal frontier from it.
    """
    return offsets[:, None] + jnp.arange(s)[None]


def ragged_prefill_attention_mask(offsets: jnp.ndarray, lens: jnp.ndarray,
                                  s: int, n_slots: int) -> jnp.ndarray:
    """(R, s, n_slots) bool: paged-cache slots each suffix query attends.

    Logical slot ``j`` of a request holds position ``j`` (block tables
    hide the physical scatter); query ``i`` at position ``offsets[r]+i``
    attends every slot at or before it — the shared prefix written by an
    earlier admission plus its own suffix, scattered in the same dispatch
    before attention.  Rows at or past ``lens[r]`` (padding, idle slots)
    attend nothing.  The flash_prefill_ragged kernel derives the same
    predicate in-kernel from the scalar-prefetched offsets/lens
    (tests/test_paged.py pins the two against each other).
    """
    q_pos = ragged_prefill_positions(offsets, s)
    valid_q = jnp.arange(s)[None] < lens[:, None]
    slot = jnp.arange(n_slots)
    return (slot[None, None, :] <= q_pos[:, :, None]) & valid_q[:, :, None]


def _masked_decode_attention(q, k, v, mask, n_heads: int) -> jnp.ndarray:
    """jnp one-token decode attention oracle.

    q: (B, 1, H, hd) over K/V (B, L, KV, hd); mask: (L,) batch-shared
    (contiguous cache — the decode position is a scalar) or (B, L)
    per-request (paged cache — ragged batch).  One implementation shared
    by the contiguous and paged decode branches so the oracle math the
    flash kernels are validated against cannot drift between cache
    layouts.
    """
    hd = q.shape[-1]
    k_exp = _expand_kv(k, n_heads)
    v_exp = _expand_kv(v, n_heads)
    scale = 1.0 / math.sqrt(hd)
    sgl = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32) * scale,
                     k_exp.astype(jnp.float32))
    m2 = mask if mask.ndim == 2 else mask[None]
    sgl = jnp.where(m2[:, None, None, :], sgl, NEG_INF)
    w = jax.nn.softmax(sgl, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", w,
                      v_exp.astype(jnp.float32)).astype(q.dtype)


def mea_attention(q, k, v, q_positions, kv_positions, *,
                  causal: bool, window: int = 0, chunk: int = 1024,
                  bias: jnp.ndarray | None = None,
                  bf16_operands: bool = False) -> jnp.ndarray:
    """Chunked memory-efficient attention with online softmax.

    q: (B,Sq,H,hd); k/v: (B,Skv,H,hd) (kv already head-expanded).
    Scans over kv chunks carrying (m, l, acc) — the jnp oracle for the
    Pallas flash kernel.

    ``q_positions`` is (Sq,) batch-shared, or (B, Sq) per-request for the
    ragged paged-prefill oracle (each admission's suffix starts at its
    own shared-prefix offset).
    """
    b, sq, h, hd = q.shape
    hd_v = v.shape[-1]
    skv = k.shape[1]
    scale = 1.0 / math.sqrt(hd)
    chunk = min(chunk, skv)
    n_chunks = -(-skv // chunk)
    pad = n_chunks * chunk - skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_positions = jnp.pad(kv_positions, (0, pad),
                               constant_values=2**30)
    kc = k.reshape(b, n_chunks, chunk, h, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, n_chunks, chunk, h, hd_v).transpose(1, 0, 2, 3, 4)
    pc = kv_positions.reshape(n_chunks, chunk)

    # bf16 operands halve the dominant HBM traffic of the score/PV einsums;
    # accumulation stays fp32 (preferred_element_type) — §Perf knob.
    op_dt = jnp.bfloat16 if bf16_operands else jnp.float32
    qf = (q.astype(jnp.float32) * scale).astype(op_dt)

    # (Bm, Sq) query positions: Bm = B for per-request ragged rows, 1 for
    # the batch-shared case (broadcasts below exactly as mask[None] did)
    qp = q_positions if q_positions.ndim == 2 else q_positions[None]

    def body(carry, inp):
        m, l, acc = carry
        kj, vj, pj = inp
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kj.astype(op_dt),
                       preferred_element_type=jnp.float32)
        mask = jnp.ones((qp.shape[0], sq, chunk), bool)
        if causal:
            mask &= qp[:, :, None] >= pj[None, None, :]
        if window:
            mask &= (qp[:, :, None] - pj[None, None, :]) < window
        mask &= pj[None, None, :] < 2**30
        s = jnp.where(mask[:, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p.astype(op_dt), vj.astype(op_dt),
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, h, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    acc0 = jnp.zeros((b, h, sq, hd_v), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, acc0), (kc, vc, pc))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)  # (B,Sq,H,hd)


def attention(ctx: Ctx, cfg: ArchConfig, p, x, positions,
              cache: dict | None = None,
              kv_override: tuple | None = None,
              causal: bool = True):
    """Full attention layer.  Returns (y, new_cache).

    - train/prefill: ``cache is None`` → chunked MEA over the sequence; a
      supplied cache is *filled* (prefill).
    - decode (``ctx.decode`` and cache given): x is (B,1,d); k/v written at
      ``cache['pos']`` (ring-buffered under sliding-window), then one-token
      attention over the seq-sharded cache — GSPMD emits flash-decoding
      partial-softmax collectives.
    - ``kv_override``: (k, v, kv_positions) — cross-attention (never causal,
      never cached here; the caller caches encoder K/V).

    ``positions``: (S,) absolute positions of the query tokens (decode: the
    single current position).
    """
    b, s, _ = x.shape
    h, hd = cfg.n_heads, cfg.hd
    q, k, v = _qkv(ctx, cfg, p, x)
    if kv_override is not None:
        k, v, kv_pos = kv_override
        k_exp = _expand_kv(k, h)
        v_exp = _expand_kv(v, h)
        out = mea_attention(q, k_exp, v_exp, positions, kv_pos,
                            causal=False, chunk=cfg.attn_chunk,
                            bf16_operands=cfg.mea_bf16)
        y = linear(ctx, "attn/wo", out.reshape(b, s, h * hd), p["wo"])
        return y, cache

    if cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

    new_cache = cache
    if cache is not None and ctx.decode and "k_pages" in cache:
        if "prefill_lens" in cache:
            # batched ragged admission prefill: s suffix tokens per
            # request, offsets/lens injected by the serving engine
            out, new_cache = _paged_attention_prefill(ctx, cfg, q, k, v,
                                                      cache)
        else:
            out, new_cache = _paged_attention_decode(ctx, cfg, q, k, v,
                                                     cache)
    elif cache is not None and ctx.decode:
        cache_len = cache["k"].shape[1]
        pos = cache["pos"]  # scalar int32: absolute position of x[:, 0]
        idx = pos % cache_len if cfg.sliding_window else pos
        ck = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, idx, 0, 0))
        cv = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, idx, 0, 0))
        new_cache = {"k": ck, "v": cv, "pos": pos + s}
        kv_pos = kv_positions_for_cache(pos, cache_len, cfg.sliding_window)
        mask = decode_attention_mask(kv_pos, pos, cfg.sliding_window)
        # the pallas_call carries no partitioning rule, so the kernel only
        # dispatches when the cache's sequence axis is unsharded; a
        # model-axis-sharded cache keeps the jnp path, whose reductions
        # GSPMD turns into the flash-decoding cross-shard collectives
        seq_sharded = (ctx.mesh is not None
                       and "model" in ctx.mesh.axis_names
                       and _axis_size(ctx.mesh, "model") > 1)
        if ctx.use_kernels and s == 1 and not seq_sharded:
            # flash-decoding Pallas kernel: kv-split partial softmax, GQA
            # without the g x K/V copies of _expand_kv.  The kv-split
            # comes from the autotuner's persisted cache when TUNE has
            # covered this decode shape — nearest tuned cache length
            # stands in otherwise (a pure cache read — no tuning happens
            # on the trace path).
            from repro.kernels import autotune
            from repro.kernels.flash_decode import flash_decode
            tile, _ = autotune.tile_readback(
                "flash_decode",
                autotune.flash_decode_problem(q.shape, ck.shape, q.dtype))
            out = flash_decode(q, ck, cv, mask, interpret=ctx.interpret,
                               block_kv=tile["block_kv"]).astype(x.dtype)
        else:
            out = _masked_decode_attention(q, ck, cv, mask, h)
    else:
        k_exp = _expand_kv(k, h)
        v_exp = _expand_kv(v, h)
        out = mea_attention(q, k_exp, v_exp, positions, positions,
                            causal=causal, window=cfg.sliding_window,
                            chunk=cfg.attn_chunk,
                            bf16_operands=cfg.mea_bf16)
        if cache is not None:  # prefill fills the cache
            cache_len = cache["k"].shape[1]
            kk, vv = (k, v) if s <= cache_len else (k[:, -cache_len:],
                                                    v[:, -cache_len:])
            if cfg.sliding_window and s > cache_len:
                # ring layout: position p lives at slot p % cache_len.
                # The retained tail starts at position s - cache_len, so
                # rotate it into place — otherwise the first decode's
                # kv_positions_for_cache recovery reads the wrong slots
                # whenever s % cache_len != 0.
                shift = s % cache_len
                kk = jnp.roll(kk, shift, axis=1)
                vv = jnp.roll(vv, shift, axis=1)
            ck = jax.lax.dynamic_update_slice(
                cache["k"], kk.astype(cache["k"].dtype), (0, 0, 0, 0))
            cv = jax.lax.dynamic_update_slice(
                cache["v"], vv.astype(cache["v"].dtype), (0, 0, 0, 0))
            new_cache = {"k": ck, "v": cv, "pos": jnp.asarray(s, jnp.int32)}
    y = linear(ctx, "attn/wo", out.reshape(b, s, h * hd), p["wo"])
    return y, new_cache


def _paged_attention_decode(ctx: Ctx, cfg: ArchConfig, q, k, v, cache):
    """One-token attention over a paged block-table cache (ragged batch).

    q/k/v: (R, 1, ·, hd) — already roped with per-request positions.  The
    per-layer cache carries the whole model's page pools
    (``k_pages``/``v_pages`` in the head-major layout
    serving/paged_cache.py owns) and this block's ``layer`` index, plus
    the batch-shared ``block_tables`` (R, M) and ``seq_lens`` (R,), all
    injected by lm_apply.
    The current token's K/V is scattered into page
    ``block_tables[r, seq_lens[r] // ps]`` before attention, then each
    request attends its own prefix — the jnp oracle gathers pages through
    the block table and masks with paged_decode_attention_mask, the
    Pallas kernel (kernels/flash_decode_paged.py) dereferences the table
    per grid step and builds the same predicate from ``seq_lens``.
    Sliding-window ring layouts are not paged (the serving engine gates
    on ``cfg.sliding_window``).
    """
    from repro.serving.paged_cache import gather_request_kv, write_token_kv
    assert not cfg.sliding_window, \
        "paged decode supports linear caches only"
    kp, vp, layer = cache["k_pages"], cache["v_pages"], cache["layer"]
    bt, sl = cache["block_tables"], cache["seq_lens"]
    ps = kp.shape[3]
    r, _, h, _ = q.shape
    blocks = bt.shape[1]
    n_slots = blocks * ps
    # write this token's k/v at the per-request write position.  The
    # clamp only ever bites for slots the engine has parked on its
    # scratch page (capacity for live requests is sized at admission).
    pos_w = jnp.minimum(sl, n_slots - 1)
    pidx = bt[jnp.arange(r), pos_w // ps]
    slot = pos_w % ps
    with jax.named_scope("kv_write"):
        kp = write_token_kv(kp, layer, pidx, slot, k[:, 0])
        vp = write_token_kv(vp, layer, pidx, slot, v[:, 0])
    new_cache = dict(cache, k_pages=kp, v_pages=vp)
    seq_sharded = (ctx.mesh is not None
                   and "model" in ctx.mesh.axis_names
                   and _axis_size(ctx.mesh, "model") > 1)
    if ctx.use_kernels and not seq_sharded:
        from repro.kernels.flash_decode_paged import flash_decode_paged
        out = flash_decode_paged(q, kp, vp, bt, sl, layer,
                                 interpret=ctx.interpret).astype(q.dtype)
    else:
        # jnp oracle: gather each request's pages into contiguous K/V
        mask = paged_decode_attention_mask(paged_kv_positions(sl, n_slots),
                                           sl)
        out = _masked_decode_attention(q, gather_request_kv(kp[layer], bt),
                                       gather_request_kv(vp[layer], bt),
                                       mask, h)
    return out, new_cache


def _paged_attention_prefill(ctx: Ctx, cfg: ArchConfig, q, k, v, cache):
    """Batched ragged admission prefill over a paged block-table cache.

    q/k/v: (R, S, ·, hd) — each row holds one admission's *suffix* (the
    prompt tokens after its shared prefix), already roped at the absolute
    positions ``seq_lens[r] + i`` (``seq_lens`` carries the per-request
    prefix offsets during an admission dispatch; ``prefill_lens`` the
    valid suffix lengths, 0 for idle slots).  The suffix K/V is scattered
    into each request's own pages first — padding and idle rows land on
    the engine's reserved scratch page (physical page 0) — then every
    suffix query attends causally over the request's full logical prefix:
    pages mapped from the prefix cache plus the suffix written by this
    same dispatch (admissions sharing a boundary therefore read each
    other's freshly computed prefix K/V in-graph, which is what makes a
    shared-prefix burst prefill-once).

    Oracle path: gather pages through the block table and run the same
    chunked mea_attention the contiguous prefill uses (per-request 2D
    query positions) — serial batch-1 prefill and this batched path
    reduce with identical math.  Kernel path (``ctx.use_kernels``):
    kernels/flash_prefill_ragged.py, block table + offsets/lens as
    scalar prefetch, mask semantics per
    :func:`ragged_prefill_attention_mask`.
    """
    from repro.serving.paged_cache import gather_request_kv, write_token_kv
    assert not cfg.sliding_window, \
        "paged prefill supports linear caches only"
    kp, vp, layer = cache["k_pages"], cache["v_pages"], cache["layer"]
    bt, off = cache["block_tables"], cache["seq_lens"]
    lens = cache["prefill_lens"]
    _, _, kvh, ps, hd = kp.shape
    r, s, h, _ = q.shape
    blocks = bt.shape[1]
    n_slots = blocks * ps
    # scatter the suffix K/V at positions offset..offset+len-1; invalid
    # (padded / idle-slot) writes are routed to the scratch page 0
    pos = ragged_prefill_positions(off, s)
    valid = jnp.arange(s)[None] < lens[:, None]
    pos_c = jnp.minimum(pos, n_slots - 1)
    rows = jnp.arange(r)[:, None]
    pidx = jnp.where(valid, bt[rows, pos_c // ps], 0)
    slot = pos_c % ps
    with jax.named_scope("kv_write"):
        kp = write_token_kv(kp, layer, pidx, slot, k)
        vp = write_token_kv(vp, layer, pidx, slot, v)
    new_cache = dict(cache, k_pages=kp, v_pages=vp)
    seq_sharded = (ctx.mesh is not None
                   and "model" in ctx.mesh.axis_names
                   and _axis_size(ctx.mesh, "model") > 1)
    if ctx.use_kernels and not seq_sharded:
        from repro.kernels import autotune
        from repro.kernels.flash_prefill_ragged import flash_prefill_ragged
        tile, _ = autotune.tile_readback(
            "flash_prefill_ragged",
            autotune.flash_prefill_ragged_problem(r, s, h, kvh, hd,
                                                  n_slots, ps, q.dtype))
        out = flash_prefill_ragged(q, kp, vp, bt, off, lens, layer,
                                   interpret=ctx.interpret,
                                   block_q=tile["block_q"]).astype(q.dtype)
    else:
        # jnp oracle: gather each request's pages into contiguous K/V and
        # run the standard chunked-mea prefill with per-request positions
        kf = gather_request_kv(kp[layer], bt)
        vf = gather_request_kv(vp[layer], bt)
        out = mea_attention(q, _expand_kv(kf, h), _expand_kv(vf, h),
                            pos, jnp.arange(n_slots),
                            causal=True, chunk=cfg.attn_chunk,
                            bf16_operands=cfg.mea_bf16)
    return out, new_cache


def init_attention_cache(cfg: ArchConfig, batch: int, seq_len: int,
                         dtype=jnp.bfloat16):
    cache_len = seq_len if not cfg.sliding_window else min(
        seq_len, cfg.sliding_window)
    kv, hd = cfg.n_kv_heads, cfg.hd
    return (
        {"k": jnp.zeros((batch, cache_len, kv, hd), dtype),
         "v": jnp.zeros((batch, cache_len, kv, hd), dtype),
         "pos": jnp.zeros((), jnp.int32)},
        {"k": ("batch", "cache_seq", "kv_heads", "head_dim"),
         "v": ("batch", "cache_seq", "kv_heads", "head_dim"),
         "pos": ()},
    )


# =====================================================================
# MLA (DeepSeek-V2 multi-head latent attention)
# =====================================================================
def init_mla(key, cfg: ArchConfig):
    d = cfg.d_model
    h = cfg.n_heads
    qd = cfg.nope_head_dim + cfg.rope_head_dim
    ks = jax.random.split(key, 8)
    dt = cfg.pdt
    params = {}
    axes = {}
    if cfg.q_lora_rank:
        params["wq_a"] = dense_init(ks[0], d, cfg.q_lora_rank, dt)
        params["wq_b"] = dense_init(ks[1], cfg.q_lora_rank, h * qd, dt)
        qn, _ = init_norm(cfg.norm, cfg.q_lora_rank, dt)
        params["q_a_norm"] = qn
        axes.update(wq_a=("embed", "q_lora"), wq_b=("q_lora", "heads"),
                    q_a_norm={k: ("q_lora",) for k in qn})
    else:
        params["wq"] = dense_init(ks[0], d, h * qd, dt)
        axes["wq"] = ("embed", "heads")
    params["wkv_a"] = dense_init(ks[2], d,
                                 cfg.kv_lora_rank + cfg.rope_head_dim, dt)
    kn, _ = init_norm(cfg.norm, cfg.kv_lora_rank, dt)
    params["kv_a_norm"] = kn
    params["wkv_b"] = dense_init(
        ks[3], cfg.kv_lora_rank,
        h * (cfg.nope_head_dim + cfg.v_head_dim), dt)
    params["wo"] = dense_init(ks[4], h * cfg.v_head_dim, d, dt)
    axes.update(wkv_a=("embed", "kv_lora"),
                kv_a_norm={k: ("kv_lora",) for k in kn},
                wkv_b=("kv_lora", "heads"), wo=("heads", "embed"))
    return params, axes


def mla_attention(ctx: Ctx, cfg: ArchConfig, p, x, positions,
                  cache: dict | None = None):
    """MLA with the compressed-KV cache (c_kv + k_rope only)."""
    b, s, d = x.shape
    h = cfg.n_heads
    nd, rd, vd = cfg.nope_head_dim, cfg.rope_head_dim, cfg.v_head_dim
    if cfg.q_lora_rank:
        qa = linear(ctx, "attn/wq_a", x, p["wq_a"])
        qa = norm_apply(cfg.norm, p["q_a_norm"], qa)
        q = linear(ctx, "attn/wq_b", qa, p["wq_b"])
    else:
        q = linear(ctx, "attn/wq", x, p["wq"])
    q = q.reshape(b, s, h, nd + rd)
    q_nope, q_rope = q[..., :nd], q[..., nd:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    kv_a = linear(ctx, "attn/wkv_a", x, p["wkv_a"])
    ckv, k_rope = kv_a[..., :cfg.kv_lora_rank], kv_a[..., cfg.kv_lora_rank:]
    ckv = norm_apply(cfg.norm, p["kv_a_norm"], ckv)
    k_rope = apply_rope(k_rope[:, :, None, :], positions,
                        cfg.rope_theta)[:, :, 0, :]

    new_cache = cache
    if cache is not None and ctx.decode:
        pos = cache["pos"]
        ckv_all = jax.lax.dynamic_update_slice(
            cache["ckv"], ckv.astype(cache["ckv"].dtype), (0, pos, 0))
        kr_all = jax.lax.dynamic_update_slice(
            cache["krope"], k_rope.astype(cache["krope"].dtype), (0, pos, 0))
        new_cache = {"ckv": ckv_all, "krope": kr_all, "pos": pos + s}
        slot = jnp.arange(ckv_all.shape[1])
        kv_pos = jnp.where(slot <= pos, slot, 2**30)
        ckv_use, kr_use = ckv_all, kr_all
    else:
        kv_pos = positions
        ckv_use, kr_use = ckv, k_rope
        if cache is not None:
            ckv_all = jax.lax.dynamic_update_slice(
                cache["ckv"], ckv.astype(cache["ckv"].dtype), (0, 0, 0))
            kr_all = jax.lax.dynamic_update_slice(
                cache["krope"], k_rope.astype(cache["krope"].dtype),
                (0, 0, 0))
            new_cache = {"ckv": ckv_all, "krope": kr_all,
                         "pos": jnp.asarray(s, jnp.int32)}

    # absorb: k_nope = ckv @ Wk_b, v = ckv @ Wv_b.  We keep the expanded
    # form (compute k/v from the compressed cache at attention time) —
    # memory stays O(kv_lora), compute is the standard MLA recompute.
    wkv_b = p["wkv_b"].reshape(cfg.kv_lora_rank, h, nd + vd)
    k_nope = jnp.einsum("bsc,chd->bshd", ckv_use.astype(jnp.float32),
                        wkv_b[..., :nd].astype(jnp.float32))
    v = jnp.einsum("bsc,chd->bshd", ckv_use.astype(jnp.float32),
                   wkv_b[..., nd:].astype(jnp.float32)).astype(x.dtype)
    k = jnp.concatenate(
        [k_nope.astype(x.dtype),
         jnp.broadcast_to(kr_use[:, :, None, :],
                          (*kr_use.shape[:2], h, rd)).astype(x.dtype)],
        axis=-1)
    qfull = jnp.concatenate([q_nope, q_rope], axis=-1)

    if cache is not None and ctx.decode:
        scale = 1.0 / math.sqrt(nd + rd)
        sgl = jnp.einsum("bqhd,bkhd->bhqk",
                         qfull.astype(jnp.float32) * scale,
                         k.astype(jnp.float32))
        mask = kv_pos[None, None, None, :] < 2**30
        sgl = jnp.where(mask, sgl, NEG_INF)
        w = jax.nn.softmax(sgl, axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", w, v.astype(jnp.float32)
                         ).astype(x.dtype)
    else:
        out = mea_attention(qfull, k, v, positions, kv_pos, causal=True,
                            chunk=cfg.attn_chunk,
                            bf16_operands=cfg.mea_bf16)
    y = linear(ctx, "attn/wo", out.reshape(b, s, h * vd), p["wo"])
    return y, new_cache


def init_mla_cache(cfg: ArchConfig, batch: int, seq_len: int,
                   dtype=jnp.bfloat16):
    return (
        {"ckv": jnp.zeros((batch, seq_len, cfg.kv_lora_rank), dtype),
         "krope": jnp.zeros((batch, seq_len, cfg.rope_head_dim), dtype),
         "pos": jnp.zeros((), jnp.int32)},
        {"ckv": ("batch", "cache_seq", "kv_lora"),
         "krope": ("batch", "cache_seq", None),
         "pos": ()},
    )


# =====================================================================
# Dense MLPs
# =====================================================================
def init_mlp(key, cfg: ArchConfig, d_ff: int | None = None):
    d = cfg.d_model
    f = d_ff if d_ff is not None else cfg.d_ff
    ks = jax.random.split(key, 3)
    dt = cfg.pdt
    if cfg.mlp == "glu":
        params = {"w_gate": dense_init(ks[0], d, f, dt),
                  "w_up": dense_init(ks[1], d, f, dt),
                  "w_down": dense_init(ks[2], f, d, dt,
                                       scale=1.0 / math.sqrt(f))}
        axes = {"w_gate": ("embed", "ffn"), "w_up": ("embed", "ffn"),
                "w_down": ("ffn", "embed")}
    else:
        params = {"w_up": dense_init(ks[0], d, f, dt),
                  "w_down": dense_init(ks[1], f, d, dt,
                                       scale=1.0 / math.sqrt(f))}
        axes = {"w_up": ("embed", "ffn"), "w_down": ("ffn", "embed")}
        if cfg.mlp_bias:
            params.update(b_up=jnp.zeros((f,), dt),
                          b_down=jnp.zeros((d,), dt))
            axes.update(b_up=("ffn",), b_down=("embed",))
    return params, axes


def mlp(ctx: Ctx, cfg: ArchConfig, p, x):
    if cfg.mlp == "glu":
        g = linear(ctx, "mlp/w_gate", x, p["w_gate"])
        u = linear(ctx, "mlp/w_up", x, p["w_up"])
        h = act_fn("silu")(g.astype(jnp.float32)).astype(x.dtype) * u
        return linear(ctx, "mlp/w_down", h, p["w_down"])
    u = linear(ctx, "mlp/w_up", x, p["w_up"], p.get("b_up"))
    h = act_fn("gelu")(u.astype(jnp.float32)).astype(x.dtype)
    return linear(ctx, "mlp/w_down", h, p["w_down"], p.get("b_down"))


# =====================================================================
# Mixture of Experts (expert-parallel via shard_map; DESIGN.md §5)
# =====================================================================
def init_moe(key, cfg: ArchConfig):
    d, f, e = cfg.d_model, cfg.d_expert, cfg.n_experts
    ks = jax.random.split(key, 6)
    dt = cfg.pdt

    def stack(k, din, dout, scale=None):
        kk = jax.random.split(k, e)
        return jnp.stack([dense_init(kk[i], din, dout, dt, scale)
                          for i in range(e)])

    params: dict[str, Any] = {
        "router": dense_init(ks[0], d, e, jnp.float32),
        "w_gate": stack(ks[1], d, f),
        "w_up": stack(ks[2], d, f),
        "w_down": stack(ks[3], f, d, 1.0 / math.sqrt(f)),
    }
    axes: dict[str, Any] = {
        "router": ("embed", None),
        "w_gate": ("experts", "embed", "expert_ffn"),
        "w_up": ("experts", "embed", "expert_ffn"),
        "w_down": ("experts", "expert_ffn", "embed"),
    }
    if cfg.n_shared_experts:
        sh, shax = init_mlp(ks[4], cfg, cfg.d_expert * cfg.n_shared_experts)
        params["shared"] = sh
        axes["shared"] = shax
    return params, axes


def _rank_in_expert(ids: jnp.ndarray, n_experts: int) -> jnp.ndarray:
    """Position of each token copy within its expert's queue."""
    nk = ids.shape[0]
    sort_idx = jnp.argsort(ids, stable=True)
    sorted_ids = ids[sort_idx]
    counts = jax.ops.segment_sum(jnp.ones((nk,), jnp.int32), ids,
                                 num_segments=n_experts)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(counts)[:-1]])
    pos_sorted = jnp.arange(nk, dtype=jnp.int32) - starts[sorted_ids]
    return jnp.zeros((nk,), jnp.int32).at[sort_idx].set(pos_sorted)


def _expert_ffn(ctx: Ctx, recv, wg, wu, wd, psum_axes=None):
    """(E,C,d) tokens through per-expert GLU FFN (E,d,f)/(E,f,d).

    ``psum_axes``: the f dim of the weights is a SHARD (FSDP 'partial'
    mode) — silu(g)*u is computed on the local f-slice and the down-proj
    partial sums are psum'd over those axes.  NOTE: exact only because GLU
    is elementwise in f; the psum crosses only the final contraction.
    """
    level = ctx.level_for("moe/experts")
    if level == INT8:
        def q3(w):  # per-expert, per-out-channel int8
            qw, sc = quantize_int8(w, axis=1)
            deq = qw.astype(jnp.float32) * sc
            if ctx.decode:   # no grads needed: use quantized values as-is
                return deq   # (TPU path: the Pallas int8 kernel)
            # training: straight-through — quantize forward, full-precision
            # gradient (quantize_int8's round has ZERO derivative
            # otherwise; see common._int8_mm_ste)
            wf = w.astype(jnp.float32)
            return wf + jax.lax.stop_gradient(deq - wf)
        wg, wu, wd = q3(wg), q3(wu), q3(wd)
    rf = recv.astype(jnp.bfloat16)
    g = jnp.einsum("ecd,edf->ecf", rf, wg.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32)
    u = jnp.einsum("ecd,edf->ecf", rf, wu.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32)
    h = (jax.nn.silu(g) * u).astype(jnp.bfloat16)
    y = jnp.einsum("ecf,efd->ecd", h, wd.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32)
    if psum_axes is not None:
        y = jax.lax.psum(y, psum_axes)
    return y.astype(recv.dtype)


def moe_ffn(ctx: Ctx, cfg: ArchConfig, p, x):
    """Top-k routed MoE with explicit expert parallelism.

    Outside any mesh (CPU unit tests): dense reference (loop over experts).
    With a mesh: shard_map over the ``model`` axis — tokens are
    sequence-split across model ranks, routed, all-to-all'd to expert
    owners, processed, and combined back (DESIGN.md §5).
    """
    y_shared = 0.0
    if cfg.n_shared_experts:
        y_shared = mlp(ctx, cfg.replace(mlp="glu"), p["shared"], x)

    mesh = ctx.mesh
    use_ep = (mesh is not None and "model" in mesh.axis_names
              and cfg.n_experts % _axis_size(mesh, "model") == 0)
    if use_ep:
        y = _moe_ep(ctx, cfg, p, x)
    else:
        y = _moe_dense_reference(ctx, cfg, p, x)
    return y + y_shared


def _axis_size(mesh, name: str) -> int:
    return dict(zip(mesh.axis_names, mesh.devices.shape))[name]


def _router(cfg: ArchConfig, router_w, x2):
    logits = x2.astype(jnp.float32) @ router_w.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eidx = jax.lax.top_k(probs, cfg.top_k)
    gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)
    return gates, eidx


def _moe_dense_reference(ctx: Ctx, cfg: ArchConfig, p, x):
    """O(E) dense reference — smoke-test scale only."""
    b, s, d = x.shape
    x2 = x.reshape(-1, d)
    gates, eidx = _router(cfg, p["router"], x2)
    onehot = jax.nn.one_hot(eidx, cfg.n_experts, dtype=jnp.float32)
    combine = jnp.einsum("nk,nke->ne", gates, onehot)       # (N,E)
    h_g = jnp.einsum("nd,edf->nef", x2.astype(jnp.float32),
                     p["w_gate"].astype(jnp.float32))
    h_u = jnp.einsum("nd,edf->nef", x2.astype(jnp.float32),
                     p["w_up"].astype(jnp.float32))
    h = jax.nn.silu(h_g) * h_u
    y_e = jnp.einsum("nef,efd->ned", h, p["w_down"].astype(jnp.float32))
    y = jnp.einsum("ned,ne->nd", y_e, combine)
    return y.reshape(b, s, d).astype(x.dtype)


def _moe_ep(ctx: Ctx, cfg: ArchConfig, p, x):
    mesh = ctx.mesh
    tp = _axis_size(mesh, "model")
    e, k = cfg.n_experts, cfg.top_k
    e_loc = e // tp
    b, s, d = x.shape
    from jax.sharding import PartitionSpec as P
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    bd = dp_axes if len(dp_axes) > 1 else (dp_axes[0] if dp_axes else None)
    x_spec = P(bd, None, None)
    if ctx.fsdp_params and bd is not None:
        w_in_spec = P("model", None, bd)   # fsdp-shard f dim
        wd_spec = P("model", bd, None)
    else:
        w_in_spec = P("model", None, None)
        wd_spec = P("model", None, None)

    def ep_small_fn(xl, router_w, wg, wu, wd):
        """Few-token path (decode): routing is replicated across model
        ranks; each rank runs only its local experts and the outputs are
        psum-combined — no all-to-all, comm is one psum of (N, d)."""
        rank = jax.lax.axis_index("model")
        n_loc = xl.shape[0] * xl.shape[1]
        x2 = xl.reshape(n_loc, d)
        if ctx.fsdp_params and bd is not None:
            wg = jax.lax.all_gather(wg, bd, axis=2, tiled=True)
            wu = jax.lax.all_gather(wu, bd, axis=2, tiled=True)
            wd = jax.lax.all_gather(wd, bd, axis=1, tiled=True)
        gates, eidx = _router(cfg, router_w, x2)
        ids = eidx.reshape(-1)
        gflat = gates.reshape(-1)
        src = jnp.arange(n_loc * k, dtype=jnp.int32) // k
        cap = max(8, int(math.ceil(n_loc * k * cfg.capacity_factor / e)))
        pos = _rank_in_expert(ids, e)
        local = (ids >= rank * e_loc) & (ids < (rank + 1) * e_loc)
        keep = (pos < cap) & local
        slot = jnp.where(keep, (ids - rank * e_loc) * cap + pos,
                         e_loc * cap)
        disp = jnp.zeros((e_loc * cap + 1, d), x2.dtype).at[slot].set(
            x2[src] * keep[:, None].astype(x2.dtype))
        recv = disp[:e_loc * cap].reshape(e_loc, cap, d)
        y_e = _expert_ffn(ctx, recv, wg, wu, wd).reshape(e_loc * cap, d)
        y_e = jnp.concatenate([y_e, jnp.zeros((1, d), y_e.dtype)], 0)
        y_tok = y_e[slot] * (gflat * keep)[:, None].astype(y_e.dtype)
        ys = jax.ops.segment_sum(y_tok, src, num_segments=n_loc)
        ys = jax.lax.psum(ys, "model")
        return ys.reshape(xl.shape)

    def ep_fn(xl, router_w, wg, wu, wd):
        # xl: (B_loc, S, d) — replicated over model; take this rank's slice.
        rank = jax.lax.axis_index("model")
        n_loc = xl.shape[0] * xl.shape[1]
        x2 = xl.reshape(n_loc, d)
        n_slice = n_loc // tp
        xs = jax.lax.dynamic_slice(x2, (rank * n_slice, 0), (n_slice, d))
        if ctx.fsdp_params and bd is not None:
            # NOTE a "partial" variant (keep f-sharded weights, psum the
            # down-proj partials) was tried and REFUTED: with batch sharded
            # over the same (pod,data) axes, the psum mixes different data
            # ranks' tokens (EXPERIMENTS.md §Perf pair B).  Weight gather
            # it is; the gather payload is halved by int8 storage instead.
            wg = jax.lax.all_gather(wg, bd, axis=2, tiled=True)
            wu = jax.lax.all_gather(wu, bd, axis=2, tiled=True)
            wd = jax.lax.all_gather(wd, bd, axis=1, tiled=True)

        gates, eidx = _router(cfg, router_w, xs)
        ids = eidx.reshape(-1)                      # (n_slice*k,)
        gflat = gates.reshape(-1)
        src = jnp.arange(n_slice * k, dtype=jnp.int32) // k
        cap = max(8, int(math.ceil(n_slice * k * cfg.capacity_factor / e)))
        pos = _rank_in_expert(ids, e)
        keep = pos < cap
        slot = jnp.where(keep, ids * cap + pos, e * cap)
        disp = jnp.zeros((e * cap + 1, d), xs.dtype).at[slot].set(
            xs[src] * keep[:, None].astype(xs.dtype))
        disp = disp[:e * cap].reshape(tp, e_loc, cap, d)
        recv = jax.lax.all_to_all(disp, "model", split_axis=0,
                                  concat_axis=0, tiled=False)
        recv = recv.transpose(1, 0, 2, 3).reshape(e_loc, tp * cap, d)
        recv = checkpoint_name(recv, "moe_recv")
        y_e = _expert_ffn(ctx, recv, wg, wu, wd)
        y_e = y_e.reshape(e_loc, tp, cap, d).transpose(1, 0, 2, 3)
        back = jax.lax.all_to_all(y_e, "model", split_axis=0,
                                  concat_axis=0, tiled=False)
        back = back.reshape(e * cap, d)
        back = jnp.concatenate([back, jnp.zeros((1, d), back.dtype)], 0)
        y_tok = back[slot] * (gflat * keep)[:, None].astype(back.dtype)
        ys = jax.ops.segment_sum(y_tok.astype(jnp.float32), src,
                                 num_segments=n_slice)
        # cast before the cross-model gather: halves the largest per-layer
        # activation collective (f32 -> activation dtype)
        y_full = jax.lax.all_gather(ys.astype(xl.dtype), "model", axis=0,
                                    tiled=True)
        return y_full.reshape(xl.shape)

    # few tokens per data shard (decode): the token-slice/all-to-all path
    # can't split the tokens across model ranks — use the local-expert+psum
    # path instead.
    dp = 1
    for a in dp_axes:
        dp *= _axis_size(mesh, a)
    n_loc_static = (b // max(1, dp)) * s
    body = ep_fn if (n_loc_static % tp == 0 and n_loc_static >= tp) \
        else ep_small_fn
    fn = jax.shard_map(body, mesh=mesh, check_vma=False,
                       in_specs=(x_spec, P(None, None), w_in_spec,
                                 w_in_spec, wd_spec),
                       out_specs=x_spec)
    return fn(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])


def moe_aux_loss(cfg: ArchConfig, router_w, x) -> jnp.ndarray:
    """Switch-style load-balancing auxiliary loss."""
    d = x.shape[-1]
    x2 = x.reshape(-1, d)
    logits = x2.astype(jnp.float32) @ router_w.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    _, eidx = jax.lax.top_k(probs, cfg.top_k)
    onehot = jax.nn.one_hot(eidx, cfg.n_experts, dtype=jnp.float32)
    frac_tokens = jnp.mean(jnp.sum(onehot, axis=1), axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    return cfg.n_experts * jnp.sum(frac_tokens * frac_probs)
