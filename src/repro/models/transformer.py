"""Model assembly: decoder-only LMs (dense / MoE / MLA / SSM / hybrid) and
the whisper-style encoder-decoder — all scan-over-layers with stacked params.

Block taxonomy (cfg.family):
- dense | vlm : [attn_norm → attn → +res, mlp_norm → mlp → +res] × L
- moe         : same with MoE FFN (and MLA attention when cfg.use_mla)
- ssm (xLSTM) : [norm → mLSTM → +res, norm → sLSTM(+internal FFN) → +res] × L/2
- hybrid      : segments of `hybrid_period` Mamba2 blocks followed by ONE
                weight-shared attention+MLP block (zamba2)
- audio       : whisper enc-dec; encoder over precomputed frame embeddings
                (conv frontend stubbed per spec), decoder self+cross attn

Caches mirror the block structure, stacked along the layer axis so decode
scans over (params, cache) together — except the paged KV pool, which the
layer scan carries whole and updates in place (:func:`_paged_stack`).
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import layers as L
from repro.models import ssm as S
from repro.models.common import (Ctx, dense_init, embed_init, init_norm,
                                 linear, norm_apply, shard_hidden,
                                 sinusoid_positions)


# --------------------------------------------------------------- helpers
def _remat(cfg: ArchConfig, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "full":
        return jax.checkpoint(fn)
    if cfg.remat == "dots+moe":
        # save dot outputs AND the MoE all-to-all results (tagged
        # "moe_recv" in layers._moe_ep) so the backward pass does not
        # re-run the expensive dispatch collectives (§Perf pair B)
        pol = jax.checkpoint_policies.save_from_both_policies(
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            jax.checkpoint_policies.save_only_these_names("moe_recv"))
        return jax.checkpoint(fn, policy=pol)
    return jax.checkpoint(
        fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)


def _stack_init(key, n: int, init_fn):
    """Map an init over n layer keys; returns (stacked params, axes).

    ``lax.map`` (a loop) rather than vmap: under jit a layer's float32
    draws are then one layer's worth at a time, not the whole stack's."""
    keys = jax.random.split(key, n)
    params = jax.lax.map(lambda k: init_fn(k)[0], keys)
    _, axes = init_fn(key)
    axes = jax.tree.map(lambda ax: ("layers",) + tuple(ax), axes,
                        is_leaf=lambda x: isinstance(x, tuple))
    return params, axes


# ================================================================ blocks
def init_lm_block(key, cfg: ArchConfig):
    ks = jax.random.split(key, 4)
    p: dict[str, Any] = {}
    ax: dict[str, Any] = {}
    p["attn_norm"], ax["attn_norm"] = init_norm(cfg.norm, cfg.d_model,
                                                cfg.pdt)
    if cfg.use_mla:
        p["attn"], ax["attn"] = L.init_mla(ks[0], cfg)
    else:
        p["attn"], ax["attn"] = L.init_attention(ks[0], cfg)
    p["mlp_norm"], ax["mlp_norm"] = init_norm(cfg.norm, cfg.d_model, cfg.pdt)
    if cfg.is_moe:
        p["moe"], ax["moe"] = L.init_moe(ks[1], cfg)
    else:
        p["mlp"], ax["mlp"] = L.init_mlp(ks[1], cfg)
    return p, ax


def lm_block_apply(ctx: Ctx, cfg: ArchConfig, p, x, positions, cache):
    # the named scopes tag the ops of each part in the HLO metadata
    # (op_name), which is what a profiler trace groups device time by
    with jax.named_scope("attention"):
        h = norm_apply(cfg.norm, p["attn_norm"], x)
        if cfg.use_mla:
            a, cache = L.mla_attention(ctx, cfg, p["attn"], h, positions,
                                       cache)
        else:
            a, cache = L.attention(ctx, cfg, p["attn"], h, positions, cache)
    x = x + a
    with jax.named_scope("mlp"):
        h = norm_apply(cfg.norm, p["mlp_norm"], x)
        if cfg.is_moe:
            f = L.moe_ffn(ctx, cfg, p["moe"], h)
        else:
            f = L.mlp(ctx, cfg, p["mlp"], h)
    x = x + f
    return shard_hidden(ctx, x), cache


def init_xlstm_block(key, cfg: ArchConfig):
    """One xLSTM 'double block' = mLSTM block + sLSTM block."""
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p, ax = {}, {}
    p["norm_m"], ax["norm_m"] = init_norm(cfg.norm, cfg.d_model, cfg.pdt)
    p["mlstm"], ax["mlstm"] = S.init_mlstm(k1, cfg)
    p["norm_s"], ax["norm_s"] = init_norm(cfg.norm, cfg.d_model, cfg.pdt)
    p["slstm"], ax["slstm"] = S.init_slstm(k2, cfg)
    return p, ax


def xlstm_block_apply(ctx: Ctx, cfg: ArchConfig, p, x, positions, cache):
    mc = cache["mlstm"] if cache is not None else None
    sc = cache["slstm"] if cache is not None else None
    y, mc = S.mlstm_apply(ctx, cfg, p["mlstm"],
                          norm_apply(cfg.norm, p["norm_m"], x), mc)
    x = x + y
    y, sc = S.slstm_apply(ctx, cfg, p["slstm"],
                          norm_apply(cfg.norm, p["norm_s"], x), sc)
    x = x + y
    new_cache = None if cache is None else {"mlstm": mc, "slstm": sc}
    return shard_hidden(ctx, x), new_cache


def init_mamba_block(key, cfg: ArchConfig):
    p, ax = {}, {}
    p["norm"], ax["norm"] = init_norm(cfg.norm, cfg.d_model, cfg.pdt)
    p["mixer"], ax["mixer"] = S.init_mamba2(key, cfg)
    return p, ax


def mamba_block_apply(ctx: Ctx, cfg: ArchConfig, p, x, positions, cache):
    y, cache = S.mamba2_apply(ctx, cfg, p["mixer"],
                              norm_apply(cfg.norm, p["norm"], x), cache)
    return shard_hidden(ctx, x + y), cache


def init_shared_attn_block(key, cfg: ArchConfig):
    """zamba2's weight-shared attention+MLP block."""
    k1, k2 = jax.random.split(key)
    p, ax = {}, {}
    p["attn_norm"], ax["attn_norm"] = init_norm(cfg.norm, cfg.d_model,
                                                cfg.pdt)
    p["attn"], ax["attn"] = L.init_attention(k1, cfg)
    p["mlp_norm"], ax["mlp_norm"] = init_norm(cfg.norm, cfg.d_model, cfg.pdt)
    p["mlp"], ax["mlp"] = L.init_mlp(k2, cfg)
    return p, ax


# =============================================================== LM model
def padded_vocab(cfg: ArchConfig) -> int:
    m = cfg.pad_vocab_to_multiple
    if not m:
        return cfg.vocab_size
    return -(-cfg.vocab_size // m) * m


def init_lm(key, cfg: ArchConfig):
    """Any decoder-only family.  Returns (params, axes)."""
    ks = jax.random.split(key, 6)
    vp = padded_vocab(cfg)
    p: dict[str, Any] = {"embed": embed_init(ks[0], vp, cfg.d_model,
                                             cfg.pdt)}
    ax: dict[str, Any] = {"embed": ("vocab", "embed")}

    if cfg.family == "ssm":          # xLSTM: pairs of (mLSTM, sLSTM)
        n_pairs = cfg.n_layers // 2
        p["blocks"], ax["blocks"] = _stack_init(
            ks[1], n_pairs, lambda k: init_xlstm_block(k, cfg))
    elif cfg.family == "hybrid":     # zamba2
        p["blocks"], ax["blocks"] = _stack_init(
            ks[1], cfg.n_layers, lambda k: init_mamba_block(k, cfg))
        p["shared"], ax["shared"] = init_shared_attn_block(ks[2], cfg)
    else:                            # dense / moe / vlm
        p["blocks"], ax["blocks"] = _stack_init(
            ks[1], cfg.n_layers, lambda k: init_lm_block(k, cfg))

    p["final_norm"], ax["final_norm"] = init_norm(cfg.norm, cfg.d_model,
                                                  cfg.pdt)
    if not cfg.tie_embeddings:
        p["lm_head"] = dense_init(ks[3], cfg.d_model, vp, cfg.pdt)
        ax["lm_head"] = ("embed", "vocab")
    return p, ax


def _n_scan_blocks(cfg: ArchConfig) -> int:
    return cfg.n_layers // 2 if cfg.family == "ssm" else cfg.n_layers


def _block_apply_fn(cfg: ArchConfig):
    return {"ssm": xlstm_block_apply, "hybrid": mamba_block_apply}.get(
        cfg.family, lm_block_apply)


def lm_apply(ctx: Ctx, cfg: ArchConfig, params, tokens, positions=None,
             cache=None):
    """tokens: int32 (B,S) — or float (B,S,D) pre-embedded (vlm/audio stubs).

    Returns (logits, new_cache).
    """
    if tokens.ndim == 2:
        x = params["embed"][tokens].astype(cfg.adt)
    else:
        x = tokens.astype(cfg.adt)
    b, s = x.shape[:2]
    if positions is None:
        if cache is not None and ctx.decode:
            if "block_tables" in cache:
                # paged cache (one sentinel key for the whole-model dict,
                # matching the _paged_stack branch below; the per-layer
                # dict is detected by "k_pages" in layers.attention): ragged
                # batch, per-request positions.  The serving engine owns
                # the seq_lens increment (it knows which slots are
                # active); lm_apply only reads them.  During a batched
                # admission prefill seq_lens carries the shared-prefix
                # offsets, so the same helper positions both paths.
                positions = L.ragged_prefill_positions(cache["seq_lens"],
                                                       s)
            else:
                pos0 = _cache_pos(cfg, cache)
                positions = pos0 + jnp.arange(s)
        else:
            positions = jnp.arange(s)
    x = shard_hidden(ctx, x)

    block_fn = _block_apply_fn(cfg)

    if cfg.family == "hybrid":
        x, new_cache = _hybrid_stack(ctx, cfg, params, x, positions, cache)
    elif cache is not None and "block_tables" in cache:
        x, new_cache = _paged_stack(ctx, cfg, params, x, positions, cache)
    else:
        def body(xcarry, xs):
            lp, lc = xs
            return block_fn(ctx, cfg, lp, xcarry, positions, lc)

        body = _remat(cfg, body)
        scan_cache = cache["blocks"] if (cache is not None
                                         and cfg.family == "ssm") else cache
        if cfg.scan_layers:
            if cache is None:
                x, new_scan_cache = jax.lax.scan(
                    lambda c, lp: (body(c, (lp, None))[0], None),
                    x, params["blocks"])
            else:
                x, new_scan_cache = jax.lax.scan(
                    body, x, (params["blocks"], scan_cache))
        else:  # unrolled python loop (cost-analysis probes; see dryrun.py)
            nb = _n_scan_blocks(cfg)
            outs = []
            for i in range(nb):
                lp = jax.tree.map(lambda t: t[i], params["blocks"])
                lc = None if cache is None else jax.tree.map(
                    lambda t: t[i], scan_cache)
                x, nc = body(x, (lp, lc))
                outs.append(nc)
            new_scan_cache = None if cache is None else jax.tree.map(
                lambda *ts: jnp.stack(ts), *outs)
        if cache is None:
            new_cache = None
        elif cfg.family == "ssm":
            new_cache = {"blocks": new_scan_cache, "pos": cache["pos"] + s}
        else:
            new_cache = new_scan_cache

    with jax.named_scope("lm_head"):
        x = norm_apply(cfg.norm, params["final_norm"], x)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = linear(ctx, "lm_head", x, head)
    return logits, new_cache


def _paged_stack(ctx: Ctx, cfg: ArchConfig, params, x, positions, cache):
    """The layer stack over a paged cache.  The whole-model pools ride in
    the carry beside ``x``, and each layer writes and reads its own pages
    through its index, in place.  Scanned as xs/ys instead, every layer's
    pages would be sliced out of the pool and written back into a fresh
    one, which the enclosing decode loop then copies whole each step."""
    # block tables / seq_lens are batch state shared by every layer.
    # prefill_lens (per-request valid suffix lengths) rides along only
    # during a batched ragged admission prefill dispatch; its presence is
    # what routes layers.attention to the ragged-prefill branch.
    shared = {k: cache[k] for k in ("block_tables", "seq_lens",
                                    "prefill_lens") if k in cache}

    def body(carry, xs):
        xc, kp, vp = carry
        lp, layer = xs
        lc = dict(shared, k_pages=kp, v_pages=vp, layer=layer)
        y, nc = lm_block_apply(ctx, cfg, lp, xc, positions, lc)
        return (y, nc["k_pages"], nc["v_pages"]), None

    body = _remat(cfg, body)
    carry = (x, cache["blocks"]["k_pages"], cache["blocks"]["v_pages"])
    if cfg.scan_layers:
        carry, _ = jax.lax.scan(body, carry, (params["blocks"],
                                              jnp.arange(cfg.n_layers)))
    else:  # unrolled python loop (cost-analysis probes; see dryrun.py)
        for i in range(cfg.n_layers):
            lp = jax.tree.map(lambda t: t[i], params["blocks"])
            carry, _ = body(carry, (lp, i))
    x, kp, vp = carry
    return x, {"blocks": {"k_pages": kp, "v_pages": vp},
               "block_tables": cache["block_tables"],
               "seq_lens": cache["seq_lens"]}


def _cache_pos(cfg: ArchConfig, cache):
    """Current absolute position from any cache leaf named 'pos'."""
    if cfg.family == "ssm":
        return cache["pos"]
    if cfg.family == "hybrid":
        return cache["shared"]["pos"][0]
    return cache["pos"][0]


def _hybrid_stack(ctx: Ctx, cfg: ArchConfig, params, x, positions, cache):
    period = cfg.hybrid_period
    n_seg = cfg.n_layers // period
    shared = params["shared"]

    def seg_reshape(t):
        return t.reshape(n_seg, period, *t.shape[1:])

    mamba_params = jax.tree.map(seg_reshape, params["blocks"])

    def shared_apply(x, sc):
        h = norm_apply(cfg.norm, shared["attn_norm"], x)
        a, sc = L.attention(ctx, cfg, shared["attn"], h, positions, sc)
        x = x + a
        h = norm_apply(cfg.norm, shared["mlp_norm"], x)
        x = x + L.mlp(ctx, cfg, shared["mlp"], h)
        return shard_hidden(ctx, x), sc

    def inner(x, xs):
        lp, lc = xs
        return mamba_block_apply(ctx, cfg, lp, x, positions, lc)

    inner = _remat(cfg, inner)

    def outer(x, xs):
        seg_params, seg_cache, shared_cache = xs
        if not cfg.scan_layers:
            outs = []
            for j in range(period):
                lp = jax.tree.map(lambda t: t[j], seg_params)
                lc = None if seg_cache is None else jax.tree.map(
                    lambda t: t[j], seg_cache)
                x, nc = inner(x, (lp, lc))
                outs.append(nc)
            new_seg_cache = None if seg_cache is None else jax.tree.map(
                lambda *ts: jnp.stack(ts), *outs)
        elif seg_cache is None:
            x, _ = jax.lax.scan(
                lambda c, lp: (inner(c, (lp, None))[0], None),
                x, seg_params)
            new_seg_cache = None
        else:
            x, new_seg_cache = jax.lax.scan(inner, x,
                                            (seg_params, seg_cache))
        x, new_shared_cache = shared_apply(x, shared_cache)
        return x, (new_seg_cache, new_shared_cache)

    if not cfg.scan_layers:  # unrolled (cost-analysis probes)
        mamba_cache = None if cache is None else jax.tree.map(
            seg_reshape, cache["mamba"])
        new_m, new_s = [], []
        for i in range(n_seg):
            seg_p = jax.tree.map(lambda t: t[i], mamba_params)
            seg_c = None if cache is None else jax.tree.map(
                lambda t: t[i], mamba_cache)
            sh_c = None if cache is None else jax.tree.map(
                lambda t: t[i], cache["shared"])
            x, (nm, ns) = outer(x, (seg_p, seg_c, sh_c))
            new_m.append(nm)
            new_s.append(ns)
        if cache is None:
            return x, None
        new_mamba = jax.tree.map(lambda *ts: jnp.stack(ts), *new_m)
        new_shared = jax.tree.map(lambda *ts: jnp.stack(ts), *new_s)
        new_mamba = jax.tree.map(lambda t: t.reshape(-1, *t.shape[2:]),
                                 new_mamba)
        return x, {"mamba": new_mamba, "shared": new_shared}

    if cache is None:
        def outer_nc(x, seg_params):
            y, _ = outer(x, (seg_params, None, None))
            return y, None
        x, _ = jax.lax.scan(outer_nc, x, mamba_params)
        return x, None

    mamba_cache = jax.tree.map(seg_reshape, cache["mamba"])
    x, (new_mamba, new_shared) = jax.lax.scan(
        outer, x, (mamba_params, mamba_cache, cache["shared"]))
    new_mamba = jax.tree.map(lambda t: t.reshape(-1, *t.shape[2:]),
                             new_mamba)
    return x, {"mamba": new_mamba, "shared": new_shared}


# ----------------------------------------------------------------- cache
def init_lm_cache(cfg: ArchConfig, batch: int, seq_len: int,
                  dtype=jnp.bfloat16):
    """Stacked decode cache + logical axes for the whole model."""
    def stack(n, c, a):
        cs = jax.tree.map(lambda t: jnp.broadcast_to(t, (n,) + t.shape), c)
        axs = jax.tree.map(lambda ax: ("layers",) + tuple(ax), a,
                           is_leaf=lambda x: isinstance(x, tuple))
        return cs, axs

    if cfg.family == "ssm":
        n_pairs = cfg.n_layers // 2
        mc, ma = S.init_mlstm_state(cfg, batch)
        sc, sa = S.init_slstm_state(cfg, batch)
        c = {"mlstm": mc, "slstm": sc}
        a = {"mlstm": ma, "slstm": sa}
        cs, axs = stack(n_pairs, c, a)
        return ({"blocks": cs, "pos": jnp.zeros((), jnp.int32)},
                {"blocks": axs, "pos": ()})
    if cfg.family == "hybrid":
        mc, ma = S.init_mamba2_state(cfg, batch)
        mcs, maxs = stack(cfg.n_layers, mc, ma)
        n_seg = cfg.n_layers // cfg.hybrid_period
        ac, aa = L.init_attention_cache(cfg, batch, seq_len, dtype)
        acs, aaxs = stack(n_seg, ac, aa)
        return ({"mamba": mcs, "shared": acs},
                {"mamba": maxs, "shared": aaxs})
    if cfg.use_mla:
        c, a = L.init_mla_cache(cfg, batch, seq_len, dtype)
    else:
        c, a = L.init_attention_cache(cfg, batch, seq_len, dtype)
    return stack(cfg.n_layers, c, a)


# ======================================================== whisper enc-dec
def init_encdec(key, cfg: ArchConfig):
    ks = jax.random.split(key, 8)
    enc_cfg = cfg.replace(use_rope=False, sliding_window=0)
    p: dict[str, Any] = {}
    ax: dict[str, Any] = {}

    def enc_block(k):
        k1, k2 = jax.random.split(k)
        bp, bax = {}, {}
        bp["attn_norm"], bax["attn_norm"] = init_norm(cfg.norm, cfg.d_model,
                                                      cfg.pdt)
        bp["attn"], bax["attn"] = L.init_attention(k1, enc_cfg)
        bp["mlp_norm"], bax["mlp_norm"] = init_norm(cfg.norm, cfg.d_model,
                                                    cfg.pdt)
        bp["mlp"], bax["mlp"] = L.init_mlp(k2, cfg)
        return bp, bax

    def dec_block(k):
        k1, k2, k3 = jax.random.split(k, 3)
        bp, bax = enc_block(k)
        cp, cax = L.init_attention(k3, enc_cfg)
        bp["cross_norm"], bax["cross_norm"] = init_norm(cfg.norm,
                                                        cfg.d_model, cfg.pdt)
        bp["cross"], bax["cross"] = cp, cax
        return bp, bax

    p["enc_blocks"], ax["enc_blocks"] = _stack_init(ks[0], cfg.n_enc_layers,
                                                    enc_block)
    p["dec_blocks"], ax["dec_blocks"] = _stack_init(ks[1], cfg.n_layers,
                                                    dec_block)
    p["embed"] = embed_init(ks[2], cfg.vocab_size, cfg.d_model, cfg.pdt)
    ax["embed"] = ("vocab", "embed")
    p["enc_norm"], ax["enc_norm"] = init_norm(cfg.norm, cfg.d_model, cfg.pdt)
    p["dec_norm"], ax["dec_norm"] = init_norm(cfg.norm, cfg.d_model, cfg.pdt)
    return p, ax


def encdec_encode(ctx: Ctx, cfg: ArchConfig, params, frames):
    """frames: (B, n_frames, d) precomputed conv-frontend embeddings."""
    enc_cfg = cfg.replace(use_rope=False, sliding_window=0)
    b, s, d = frames.shape
    x = frames.astype(cfg.adt) + sinusoid_positions(s, d).astype(cfg.adt)
    positions = jnp.arange(s)
    x = shard_hidden(ctx, x)

    def body(xc, lp):
        h = norm_apply(cfg.norm, lp["attn_norm"], xc)
        a, _ = L.attention(ctx, enc_cfg, lp["attn"], h, positions,
                           causal=False)
        xc = xc + a
        h = norm_apply(cfg.norm, lp["mlp_norm"], xc)
        xc = xc + L.mlp(ctx, cfg, lp["mlp"], h)
        return shard_hidden(ctx, xc), None

    body = _remat(cfg, body)
    if cfg.scan_layers:
        x, _ = jax.lax.scan(body, x, params["enc_blocks"])
    else:
        for i in range(cfg.n_enc_layers):
            lp = jax.tree.map(lambda t: t[i], params["enc_blocks"])
            x, _ = body(x, lp)
    return norm_apply(cfg.norm, params["enc_norm"], x)


def encdec_decode(ctx: Ctx, cfg: ArchConfig, params, tokens, enc_out=None,
                  cache=None):
    """Decoder pass.  enc_out (B,F,d) for prefill; cache holds cross K/V
    after prefill so decode never re-touches the encoder."""
    enc_cfg = cfg.replace(use_rope=False, sliding_window=0)
    x = params["embed"][tokens].astype(cfg.adt)
    b, s = tokens.shape
    if cache is not None and ctx.decode:
        pos0 = cache["self"]["pos"][0]
        positions = pos0 + jnp.arange(s)
    else:
        positions = jnp.arange(s)
    x = x + sinusoid_positions(int(cfg.n_frames * 32),
                               cfg.d_model)[positions].astype(cfg.adt)
    x = shard_hidden(ctx, x)
    frame_pos = jnp.arange(cfg.n_frames)

    def body(xc, xs):
        lp, sc, ck, cv = xs
        h = norm_apply(cfg.norm, lp["attn_norm"], xc)
        a, sc = L.attention(ctx, enc_cfg, lp["attn"], h, positions, sc)
        xc = xc + a
        h = norm_apply(cfg.norm, lp["cross_norm"], xc)
        a, _ = L.attention(ctx, enc_cfg, lp["cross"], h, positions,
                           kv_override=(ck, cv, frame_pos))
        xc = xc + a
        h = norm_apply(cfg.norm, lp["mlp_norm"], xc)
        xc = xc + L.mlp(ctx, cfg, lp["mlp"], h)
        return shard_hidden(ctx, xc), sc

    body = _remat(cfg, body)

    if cache is None:
        # compute cross k/v on the fly from enc_out
        def body_nc(xc, lp):
            kq = L._qkv(ctx, enc_cfg, lp["cross"], enc_out)
            y, _ = body(xc, (lp, None, kq[1], kq[2]))
            return y, None
        if cfg.scan_layers:
            x, _ = jax.lax.scan(body_nc, x, params["dec_blocks"])
        else:
            for i in range(cfg.n_layers):
                lp = jax.tree.map(lambda t: t[i], params["dec_blocks"])
                x, _ = body_nc(x, lp)
        new_cache = None
    elif cfg.scan_layers:
        x, new_self = jax.lax.scan(
            body, x, (params["dec_blocks"], cache["self"],
                      cache["cross_k"], cache["cross_v"]))
        new_cache = dict(cache, self=new_self)
    else:
        outs = []
        for i in range(cfg.n_layers):
            take = lambda t: jax.tree.map(lambda a: a[i], t)  # noqa: E731
            x, sc = body(x, (take(params["dec_blocks"]),
                             take(cache["self"]), cache["cross_k"][i],
                             cache["cross_v"][i]))
            outs.append(sc)
        new_self = jax.tree.map(lambda *ts: jnp.stack(ts), *outs)
        new_cache = dict(cache, self=new_self)

    x = norm_apply(cfg.norm, params["dec_norm"], x)
    logits = linear(ctx, "lm_head", x, params["embed"].T)
    return logits, new_cache


def init_encdec_cache(ctx: Ctx, cfg: ArchConfig, params, batch: int,
                      seq_len: int, frames=None, dtype=jnp.bfloat16):
    """Self-attn cache + cross K/V (from encoder output if given)."""
    enc_cfg = cfg.replace(use_rope=False, sliding_window=0)
    sc, sa = L.init_attention_cache(enc_cfg, batch, seq_len, dtype)
    n = cfg.n_layers
    scs = jax.tree.map(lambda t: jnp.broadcast_to(t, (n,) + t.shape), sc)
    sas = jax.tree.map(lambda ax: ("layers",) + tuple(ax), sa,
                       is_leaf=lambda x: isinstance(x, tuple))
    kvshape = (n, batch, cfg.n_frames, cfg.n_kv_heads, cfg.hd)
    cache = {"self": scs,
             "cross_k": jnp.zeros(kvshape, dtype),
             "cross_v": jnp.zeros(kvshape, dtype)}
    axes = {"self": sas,
            "cross_k": ("layers", "batch", "frames", "kv_heads", "head_dim"),
            "cross_v": ("layers", "batch", "frames", "kv_heads", "head_dim")}
    if frames is not None:
        enc_out = encdec_encode(ctx, cfg, params, frames)
        def kv_of(lp):
            _, k, v = L._qkv(ctx, enc_cfg, lp["cross"], enc_out)
            return k.astype(dtype), v.astype(dtype)
        ks, vs = jax.vmap(kv_of)(params["dec_blocks"])
        cache["cross_k"], cache["cross_v"] = ks, vs
    return cache, axes
