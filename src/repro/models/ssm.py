"""Sub-quadratic sequence mixers: Mamba2 (SSD), mLSTM, sLSTM.

These power the `long_500k` shape: training/prefill uses chunked parallel
forms (O(S·L) with chunk L), decode carries an O(1) recurrent state.

Numerics notes:
- Mamba2 follows the minimal SSD formulation (chunked segsum) of the Mamba2
  paper, n_groups=1.
- mLSTM implements the stabilized exponential-gating chunkwise form of the
  xLSTM paper; tests validate the chunked form against the per-step
  recurrence (tests/test_ssm.py).
- sLSTM is inherently sequential (recurrent weights) — lax.scan over time.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models.common import Ctx, dense_init, linear, normal_draw, rmsnorm

NEG_INF = -1e30


# =====================================================================
# Mamba2
# =====================================================================
def mamba2_dims(cfg: ArchConfig):
    d_in = cfg.ssm_expand * cfg.d_model
    nh = d_in // cfg.ssm_head_dim
    return d_in, nh, cfg.ssm_state, cfg.ssm_head_dim


def init_mamba2(key, cfg: ArchConfig):
    d = cfg.d_model
    d_in, nh, n, p_hd = mamba2_dims(cfg)
    conv_dim = d_in + 2 * n
    ks = jax.random.split(key, 6)
    dt = cfg.pdt
    params: dict[str, Any] = {
        "in_proj": dense_init(ks[0], d, 2 * d_in + 2 * n + nh, dt),
        "conv_w": (normal_draw(ks[1], (cfg.ssm_conv, conv_dim))
                   * 0.1).astype(dt),
        "conv_b": jnp.zeros((conv_dim,), dt),
        # a host-side constant: the same table eagerly or under jit,
        # where XLA's fused log can differ from a lone log op by an ulp
        "A_log": jnp.asarray(np.log(np.arange(1, nh + 1,
                                              dtype=np.float32))),
        "D": jnp.ones((nh,), jnp.float32),
        "dt_bias": jnp.zeros((nh,), jnp.float32),
        "norm_scale": jnp.ones((d_in,), dt),
        "out_proj": dense_init(ks[2], d_in, d, dt,
                               scale=1.0 / math.sqrt(d_in)),
    }
    axes = {
        "in_proj": ("embed", "ssm_inner"),
        "conv_w": ("conv_k", "ssm_inner"),
        "conv_b": ("ssm_inner",),
        "A_log": ("ssm_heads",), "D": ("ssm_heads",),
        "dt_bias": ("ssm_heads",),
        "norm_scale": ("ssm_inner",),
        "out_proj": ("ssm_inner", "embed"),
    }
    return params, axes


def _causal_conv(xbc: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
                 state: jnp.ndarray | None = None):
    """Depthwise causal conv over time.  xbc: (B,S,C); w: (K,C).

    With ``state`` (B,K-1,C): single-step decode — returns (y, new_state).
    """
    k = w.shape[0]
    if state is not None:
        window = jnp.concatenate([state, xbc], axis=1)       # (B,K,C)
        # accumulate in tap order, exactly like the full-sequence branch
        # below: the decode step then produces bit-identical conv outputs
        # to prefill, so chunked-vs-stepwise comparisons see only SSD-core
        # differences, not conv reduction-order dust
        y = sum(window[:, i].astype(jnp.float32)
                * w[i].astype(jnp.float32) for i in range(k))
        y = y + b.astype(jnp.float32)
        return jax.nn.silu(y)[:, None, :].astype(xbc.dtype), window[:, 1:]
    pad = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    # sum of shifted copies — K is tiny (4), this lowers to K fused muls.
    y = sum(pad[:, i:i + xbc.shape[1]].astype(jnp.float32)
            * w[i].astype(jnp.float32) for i in range(k))
    y = y + b.astype(jnp.float32)
    return jax.nn.silu(y).astype(xbc.dtype), None


def _segsum(a: jnp.ndarray) -> jnp.ndarray:
    """segsum(a)[..., t, s] = sum_{j=s+1..t} a[..., j]; -inf for s>t.

    Computed by masked cumsum over the t axis, NOT as a difference of two
    inclusive cumsums: the decay exponents are same-signed and accumulate
    to O(chunk * |a|) magnitudes, so ``cs[t] - cs[s]`` loses
    ``eps * |cs|`` absolutely to cancellation — the worst case is a
    heavily padded final chunk, whose real steps all sit under the
    largest |cs| span (tests/test_ssm.py chunked-vs-stepwise[48]).  The
    masked-cumsum form builds each entry as a fresh short sum, keeping
    the chunked path ~an order of magnitude closer to the stepwise
    recurrence.
    """
    l = a.shape[-1]
    x = jnp.broadcast_to(a[..., :, None], a.shape + (l,))
    x = jnp.where(jnp.tril(jnp.ones((l, l), bool), k=-1), x, 0.0)
    seg = jnp.cumsum(x, axis=-2)
    mask = jnp.tril(jnp.ones((l, l), bool), k=0)
    return jnp.where(mask, seg, NEG_INF)


def mamba2_apply(ctx: Ctx, cfg: ArchConfig, p, x,
                 state: dict | None = None):
    """Mamba2 mixer.  Returns (y, new_state).

    Train/prefill: chunked SSD.  Decode (ctx.decode, state given): recurrent
    single step with x (B,1,d).
    """
    b, s, d = x.shape
    d_in, nh, n, hd = mamba2_dims(cfg)
    proj = linear(ctx, "ssm/in_proj", x, p["in_proj"])
    z, xr, b_in, c_in, dt_raw = jnp.split(
        proj, [d_in, 2 * d_in, 2 * d_in + n, 2 * d_in + 2 * n], axis=-1)
    xbc = jnp.concatenate([xr, b_in, c_in], axis=-1)
    a = -jnp.exp(p["A_log"].astype(jnp.float32))             # (H,) negative
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))  # (B,S,H)

    if state is not None and ctx.decode:
        xbc_c, conv_state = _causal_conv(xbc, p["conv_w"], p["conv_b"],
                                         state["conv"])
        xc, bc, cc = jnp.split(xbc_c[:, 0], [d_in, d_in + n], axis=-1)
        xh = xc.reshape(b, nh, hd).astype(jnp.float32)
        dt1 = dt[:, 0]                                        # (B,H)
        da = jnp.exp(dt1 * a[None, :])                        # (B,H)
        # h: (B,H,hd,N)
        h_new = state["ssd"] * da[..., None, None] + \
            (dt1[..., None, None] * xh[..., None]
             * bc.astype(jnp.float32)[:, None, None, :])
        y = jnp.einsum("bhpn,bn->bhp", h_new,
                       cc.astype(jnp.float32))
        y = y + p["D"].astype(jnp.float32)[None, :, None] * xh
        y = y.reshape(b, 1, d_in).astype(x.dtype)
        new_state = {"conv": conv_state, "ssd": h_new}
    else:
        xbc_c, _ = _causal_conv(xbc, p["conv_w"], p["conv_b"])
        xc, bc, cc = jnp.split(xbc_c, [d_in, d_in + n], axis=-1)
        y, h_last = _ssd_chunked(cfg, xc, bc, cc, dt, a, p["D"])
        new_state = state
        if state is not None:  # prefill: leave final state for decode
            k = cfg.ssm_conv
            new_state = {"conv": xbc[:, -(k - 1):].astype(
                state["conv"].dtype), "ssd": h_last}
    yz = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    yz = rmsnorm({"scale": p["norm_scale"]}, yz.astype(x.dtype))
    out = linear(ctx, "ssm/out_proj", yz, p["out_proj"])
    return out, new_state


def _ssd_chunked(cfg: ArchConfig, xc, bc, cc, dt, a, d_skip):
    """Chunked SSD.  xc: (B,S,d_in); bc/cc: (B,S,N); dt: (B,S,H)."""
    b, s, d_in = xc.shape
    _, nh, n, hd = mamba2_dims(cfg)
    l = min(cfg.ssm_chunk, s)
    s_orig = s
    if s % l:  # pad with dt=0 steps: decay=1, zero state contribution
        pad = l - s % l
        xc = jnp.pad(xc, ((0, 0), (0, pad), (0, 0)))
        bc = jnp.pad(bc, ((0, 0), (0, pad), (0, 0)))
        cc = jnp.pad(cc, ((0, 0), (0, pad), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        s = s + pad
    nc = s // l
    xh = xc.reshape(b, nc, l, nh, hd).astype(jnp.float32)
    bm = bc.reshape(b, nc, l, n).astype(jnp.float32)
    cm = cc.reshape(b, nc, l, n).astype(jnp.float32)
    dtc = dt.reshape(b, nc, l, nh)
    # per-step log decay (B,H,nc,L)
    da = (dtc * a[None, None, None, :]).transpose(0, 3, 1, 2)
    da_cs = jnp.cumsum(da, axis=-1)
    # intra-chunk (diagonal blocks)
    seg = _segsum(da)                                      # (B,H,nc,L,L)
    lmat = jnp.exp(seg)
    y_diag = jnp.einsum("bcln,bcsn,bhcls,bcsh,bcshp->bclhp",
                        cm, bm, lmat, dtc, xh)
    # chunk-final states: decay from step l to chunk end is segsum's last
    # row (sum_{j>l} da_j) — reusing it avoids the cancellation-prone
    # ``da_cs[-1] - da_cs`` subtraction of two large cumsums
    decay_states = jnp.exp(seg[..., -1, :])                # (B,H,nc,L)
    states = jnp.einsum("bcln,bhcl,bclh,bclhp->bchpn",
                        bm, decay_states, dtc, xh)
    # inter-chunk recurrence
    chunk_decay = jnp.exp(da_cs[..., -1])                  # (B,H,nc)

    def scan_fn(h, inp):
        st, dec = inp                                      # (B,H,P,N),(B,H)
        h_new = h * dec[..., None, None] + st
        return h_new, h

    states_t = states.transpose(1, 0, 2, 3, 4)             # (nc,B,H,P,N)
    dec_t = chunk_decay.transpose(2, 0, 1)                 # (nc,B,H)
    h0 = jnp.zeros((b, nh, hd, n), jnp.float32)
    h_last, h_prevs = jax.lax.scan(scan_fn, h0, (states_t, dec_t))
    h_prevs = h_prevs.transpose(1, 0, 2, 3, 4)             # (B,nc,H,P,N)
    state_decay = jnp.exp(da_cs)                           # (B,H,nc,L)
    y_off = jnp.einsum("bcln,bchpn,bhcl->bclhp", cm, h_prevs, state_decay)
    y = y_diag + y_off
    y = y + d_skip.astype(jnp.float32)[None, None, None, :, None] * xh
    return y.reshape(b, s, d_in)[:, :s_orig], h_last


def init_mamba2_state(cfg: ArchConfig, batch: int, dtype=jnp.float32):
    d_in, nh, n, hd = mamba2_dims(cfg)
    conv_dim = d_in + 2 * n
    return (
        {"conv": jnp.zeros((batch, cfg.ssm_conv - 1, conv_dim), dtype),
         "ssd": jnp.zeros((batch, nh, hd, n), jnp.float32)},
        {"conv": ("batch", None, "ssm_inner"),
         "ssd": ("batch", "ssm_heads", None, None)},
    )


# =====================================================================
# mLSTM (xLSTM matrix-memory cell)
# =====================================================================
def init_mlstm(key, cfg: ArchConfig):
    d = cfg.d_model
    h = cfg.n_heads
    d_in = 2 * d                       # up-projection factor 2
    dh = d_in // h
    ks = jax.random.split(key, 8)
    dt = cfg.pdt
    params = {
        "w_up": dense_init(ks[0], d, d_in, dt),
        "w_gate": dense_init(ks[1], d, d_in, dt),
        "conv_w": (normal_draw(ks[2], (cfg.ssm_conv, d_in))
                   * 0.1).astype(dt),
        "conv_b": jnp.zeros((d_in,), dt),
        "wq": dense_init(ks[3], d_in, d_in, dt),
        "wk": dense_init(ks[4], d_in, d_in, dt),
        "wv": dense_init(ks[5], d_in, d_in, dt),
        "w_if": dense_init(ks[6], d_in, 2 * h, dt),
        "b_if": jnp.concatenate([jnp.zeros((h,), jnp.float32),
                                 3.0 * jnp.ones((h,), jnp.float32)]
                                ).astype(dt),
        "norm_scale": jnp.ones((d_in,), dt),
        "w_down": dense_init(ks[7], d_in, d, dt,
                             scale=1.0 / math.sqrt(d_in)),
    }
    axes = {
        "w_up": ("embed", "ssm_inner"), "w_gate": ("embed", "ssm_inner"),
        "conv_w": ("conv_k", "ssm_inner"), "conv_b": ("ssm_inner",),
        "wq": ("ssm_inner", "heads"), "wk": ("ssm_inner", "heads"),
        "wv": ("ssm_inner", "heads"), "w_if": ("ssm_inner", None),
        "b_if": (None,), "norm_scale": ("ssm_inner",),
        "w_down": ("ssm_inner", "embed"),
    }
    return params, axes


def mlstm_apply(ctx: Ctx, cfg: ArchConfig, p, x, state: dict | None = None):
    """mLSTM block.  Returns (y, new_state)."""
    b, s, d = x.shape
    h = cfg.n_heads
    d_in = 2 * d
    dh = d_in // h
    up = linear(ctx, "mlstm/w_up", x, p["w_up"])
    gate = linear(ctx, "mlstm/w_gate", x, p["w_gate"])

    if state is not None and ctx.decode:
        upc, conv_state = _causal_conv(up, p["conv_w"], p["conv_b"],
                                       state["conv"])
    else:
        upc, conv_state = _causal_conv(up, p["conv_w"], p["conv_b"])

    q = linear(ctx, "mlstm/wq", upc, p["wq"]).reshape(b, s, h, dh)
    k = linear(ctx, "mlstm/wk", upc, p["wk"]).reshape(b, s, h, dh) \
        / math.sqrt(dh)
    v = linear(ctx, "mlstm/wv", up, p["wv"]).reshape(b, s, h, dh)
    if_raw = linear(ctx, "mlstm/w_if", upc, p["w_if"],
                    p["b_if"]).astype(jnp.float32)
    logi, logf = if_raw[..., :h], jax.nn.log_sigmoid(if_raw[..., h:])

    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)

    if state is not None and ctx.decode:
        # recurrent step: state C (B,H,dk,dv), n (B,H,dk), m (B,H)
        li, lf = logi[:, 0], logf[:, 0]                    # (B,H)
        m_new = jnp.maximum(lf + state["m"], li)
        fp = jnp.exp(lf + state["m"] - m_new)
        ip = jnp.exp(li - m_new)
        kv = kf[:, 0, :, :, None] * vf[:, 0, :, None, :]   # (B,H,dk,dv)
        c_new = state["C"] * fp[..., None, None] + ip[..., None, None] * kv
        n_new = state["n"] * fp[..., None] + ip[..., None] * kf[:, 0]
        num = jnp.einsum("bhk,bhkv->bhv", qf[:, 0], c_new)
        den = jnp.abs(jnp.einsum("bhk,bhk->bh", qf[:, 0], n_new))
        den = jnp.maximum(den, jnp.exp(-m_new))
        y = (num / den[..., None])[:, None]                # (B,1,H,dv)
        new_state = {"conv": conv_state, "C": c_new, "n": n_new,
                     "m": m_new}
    else:
        y, (c_f, n_f, m_f) = _mlstm_chunked(cfg, qf, kf, vf, logi, logf)
        new_state = state
        if state is not None:  # prefill → decode handoff
            kc = cfg.ssm_conv
            new_state = {"conv": up[:, -(kc - 1):].astype(
                state["conv"].dtype), "C": c_f, "n": n_f, "m": m_f}
    y = y.reshape(b, s, d_in)
    y = rmsnorm({"scale": p["norm_scale"]}, y.astype(x.dtype))
    y = y.astype(jnp.float32) * jax.nn.silu(gate.astype(jnp.float32))
    return linear(ctx, "mlstm/w_down", y.astype(x.dtype), p["w_down"]), \
        new_state


def _mlstm_chunked(cfg: ArchConfig, q, k, v, logi, logf):
    """Stabilized chunkwise mLSTM.  q/k/v: (B,S,H,dh); logi/f: (B,S,H).

    Validated against mlstm_recurrent_reference in tests/test_ssm.py.
    """
    b, s, h, dh = q.shape
    l = min(cfg.mlstm_chunk, s)
    s_orig = s
    if s % l:  # pad: logf=0 (decay 1), logi=-inf (no input)
        pad = l - s % l
        zpad = ((0, 0), (0, pad), (0, 0), (0, 0))
        q = jnp.pad(q, zpad)
        k = jnp.pad(k, zpad)
        v = jnp.pad(v, zpad)
        logi = jnp.pad(logi, ((0, 0), (0, pad), (0, 0)),
                       constant_values=NEG_INF)
        logf = jnp.pad(logf, ((0, 0), (0, pad), (0, 0)))
        s = s + pad
    nc = s // l
    qc = q.reshape(b, nc, l, h, dh).transpose(1, 0, 3, 2, 4)  # (nc,B,H,L,dh)
    kc = k.reshape(b, nc, l, h, dh).transpose(1, 0, 3, 2, 4)
    vc = v.reshape(b, nc, l, h, dh).transpose(1, 0, 3, 2, 4)
    lic = logi.reshape(b, nc, l, h).transpose(1, 0, 3, 2)     # (nc,B,H,L)
    lfc = logf.reshape(b, nc, l, h).transpose(1, 0, 3, 2)

    def chunk_fn(carry, inp):
        C, n, m = carry          # (B,H,dk,dv), (B,H,dk), (B,H)
        qj, kj, vj, li, lf = inp
        bcs = jnp.cumsum(lf, axis=-1)                         # (B,H,L)
        # D[t,s] = b_t - b_s + logi_s  (s<=t)
        dmat = bcs[..., :, None] - bcs[..., None, :] + li[..., None, :]
        mask = jnp.tril(jnp.ones((l, l), bool))
        dmat = jnp.where(mask, dmat, NEG_INF)
        m_intra = jnp.max(dmat, axis=-1)                      # (B,H,L)
        m_inter = m[..., None] + bcs
        m_row = jnp.maximum(m_intra, m_inter)                 # (B,H,L)
        sc = jnp.einsum("bhtd,bhsd->bhts", qj, kj) \
            * jnp.exp(dmat - m_row[..., None])
        inter_w = jnp.exp(m_inter - m_row)                    # (B,H,L)
        num = jnp.einsum("bhts,bhsv->bhtv", sc, vj) \
            + inter_w[..., None] * jnp.einsum("bhtd,bhdv->bhtv", qj, C)
        den = jnp.einsum("bhts->bht", sc) \
            + inter_w * jnp.einsum("bhtd,bhd->bht", qj, n)
        den = jnp.maximum(jnp.abs(den), jnp.exp(-m_row))
        y = num / den[..., None]                              # (B,H,L,dv)
        # carry update
        g = bcs[..., -1]                                      # (B,H)
        w_t = li + g[..., None] - bcs                         # (B,H,L)
        m_new = jnp.maximum(m + g, jnp.max(w_t, axis=-1))
        scale_old = jnp.exp(m + g - m_new)
        wexp = jnp.exp(w_t - m_new[..., None])
        C_new = C * scale_old[..., None, None] + jnp.einsum(
            "bhs,bhsd,bhsv->bhdv", wexp, kj, vj)
        n_new = n * scale_old[..., None] + jnp.einsum(
            "bhs,bhsd->bhd", wexp, kj)
        return (C_new, n_new, m_new), y

    c0 = jnp.zeros((b, h, dh, dh), jnp.float32)
    n0 = jnp.zeros((b, h, dh), jnp.float32)
    m0 = jnp.full((b, h), -1e9, jnp.float32)
    carry, ys = jax.lax.scan(chunk_fn, (c0, n0, m0), (qc, kc, vc, lic, lfc))
    y = ys.transpose(1, 0, 3, 2, 4).reshape(b, s, h, dh)      # (B,S,H,dh)
    return y[:, :s_orig], carry


def init_mlstm_state(cfg: ArchConfig, batch: int, dtype=jnp.float32):
    h = cfg.n_heads
    d_in = 2 * cfg.d_model
    dh = d_in // h
    return (
        {"conv": jnp.zeros((batch, cfg.ssm_conv - 1, d_in), dtype),
         "C": jnp.zeros((batch, h, dh, dh), jnp.float32),
         "n": jnp.zeros((batch, h, dh), jnp.float32),
         "m": jnp.full((batch, h), -1e9, jnp.float32)},
        {"conv": ("batch", None, "ssm_inner"),
         "C": ("batch", "heads", None, None),
         "n": ("batch", "heads", None),
         "m": ("batch", "heads")},
    )


def mlstm_recurrent_reference(cfg: ArchConfig, q, k, v, logi, logf):
    """Per-step recurrence — test oracle for the chunked form."""
    b, s, h, dh = q.shape

    def step(carry, inp):
        C, n, m = carry
        qt, kt, vt, li, lf = inp
        m_new = jnp.maximum(lf + m, li)
        fp = jnp.exp(lf + m - m_new)
        ip = jnp.exp(li - m_new)
        C_new = C * fp[..., None, None] + ip[..., None, None] * (
            kt[..., :, None] * vt[..., None, :])
        n_new = n * fp[..., None] + ip[..., None] * kt
        num = jnp.einsum("bhk,bhkv->bhv", qt, C_new)
        den = jnp.maximum(jnp.abs(jnp.einsum("bhk,bhk->bh", qt, n_new)),
                          jnp.exp(-m_new))
        return (C_new, n_new, m_new), num / den[..., None]

    c0 = jnp.zeros((b, h, dh, dh), jnp.float32)
    n0 = jnp.zeros((b, h, dh), jnp.float32)
    m0 = jnp.full((b, h), -1e9, jnp.float32)
    xs = (q.transpose(1, 0, 2, 3), k.transpose(1, 0, 2, 3),
          v.transpose(1, 0, 2, 3), logi.transpose(1, 0, 2),
          logf.transpose(1, 0, 2))
    _, ys = jax.lax.scan(step, (c0, n0, m0), xs)
    return ys.transpose(1, 0, 2, 3)


# =====================================================================
# sLSTM (scalar-memory cell with recurrent block-diagonal weights)
# =====================================================================
def init_slstm(key, cfg: ArchConfig):
    d = cfg.d_model
    h = cfg.n_heads
    dh = d // h
    ks = jax.random.split(key, 4)
    dt = cfg.pdt
    params = {
        "w_in": dense_init(ks[0], d, 4 * d, dt),
        # a runtime divisor: under jit XLA turns division by a constant
        # into a multiply by its rounded reciprocal
        "r": (normal_draw(ks[1], (4, h, dh, dh))
              / jax.lax.optimization_barrier(
                  jnp.float32(math.sqrt(dh)))).astype(dt),
        "b": jnp.concatenate(
            [jnp.zeros((2 * d,), jnp.float32),
             3.0 * jnp.ones((d,), jnp.float32),      # f-gate bias
             jnp.zeros((d,), jnp.float32)]).astype(dt),
        "gn_scale": jnp.ones((d,), dt),
        "w_out": dense_init(ks[2], d, d, dt),
        # post-cell gated FFN (xLSTM sLSTM block, pf=4/3)
        "w_ff_up": dense_init(ks[3], d, (4 * d) // 3 * 2, dt),
        "w_ff_down": dense_init(jax.random.fold_in(key, 7),
                                (4 * d) // 3, d, dt),
    }
    axes = {
        "w_in": ("embed", None), "r": (None, "heads", None, None),
        "b": (None,), "gn_scale": ("embed",),
        "w_out": ("embed", "embed"),
        "w_ff_up": ("embed", "ffn"), "w_ff_down": ("ffn", "embed"),
    }
    return params, axes


def slstm_apply(ctx: Ctx, cfg: ArchConfig, p, x, state: dict | None = None):
    """sLSTM block: sequential scan over time.  Returns (y, new_state)."""
    b, s, d = x.shape
    h = cfg.n_heads
    dh = d // h
    wx = linear(ctx, "slstm/w_in", x, p["w_in"], p["b"]).astype(jnp.float32)
    wx = wx.reshape(b, s, 4, h, dh)
    r = p["r"].astype(jnp.float32)

    if state is not None and ctx.decode:
        carry = (state["h"], state["c"], state["n"], state["m"])
    else:
        zero = jnp.zeros((b, h, dh), jnp.float32)
        carry = (zero, zero, jnp.ones((b, h, dh), jnp.float32),
                 jnp.full((b, h, dh), 0.0, jnp.float32))

    def step(carry, wx_t):
        hp, cp, np_, mp = carry
        rec = jnp.einsum("ghij,bhi->gbhj", r, hp)          # (4,B,H,dh)
        zt = jnp.tanh(wx_t[:, 0] + rec[0])
        it = wx_t[:, 1] + rec[1]
        ft = wx_t[:, 2] + rec[2]
        ot = jax.nn.sigmoid(wx_t[:, 3] + rec[3])
        logf = jax.nn.log_sigmoid(ft)
        m_new = jnp.maximum(logf + mp, it)
        ip = jnp.exp(it - m_new)
        fp = jnp.exp(logf + mp - m_new)
        c_new = fp * cp + ip * zt
        n_new = fp * np_ + ip
        h_new = ot * c_new / jnp.maximum(n_new, 1e-6)
        return (h_new, c_new, n_new, m_new), h_new

    wx_t = wx.transpose(1, 0, 2, 3, 4)                     # (S,B,4,H,dh)
    carry, ys = jax.lax.scan(step, carry, wx_t)
    new_state = state
    if state is not None:
        new_state = {"h": carry[0], "c": carry[1], "n": carry[2],
                     "m": carry[3]}
    y = ys.transpose(1, 0, 2, 3).reshape(b, s, d)
    # per-head group norm
    yh = y.reshape(b, s, h, dh)
    mu = jnp.mean(yh, axis=-1, keepdims=True)
    var = jnp.var(yh, axis=-1, keepdims=True)
    y = ((yh - mu) * jax.lax.rsqrt(var + 1e-6)).reshape(b, s, d)
    y = (y * p["gn_scale"].astype(jnp.float32)).astype(x.dtype)
    y = linear(ctx, "slstm/w_out", y, p["w_out"])
    # gated FFN
    ff = linear(ctx, "slstm/w_ff_up", y, p["w_ff_up"])
    f1, f2 = jnp.split(ff, 2, axis=-1)
    ffh = (jax.nn.gelu(f1.astype(jnp.float32))
           * f2.astype(jnp.float32)).astype(x.dtype)
    y = y + linear(ctx, "slstm/w_ff_down", ffh, p["w_ff_down"])
    return y, new_state


def init_slstm_state(cfg: ArchConfig, batch: int):
    h = cfg.n_heads
    dh = cfg.d_model // h
    zero = jnp.zeros((batch, h, dh), jnp.float32)
    return (
        {"h": zero, "c": zero, "n": jnp.ones_like(zero),
         "m": jnp.zeros_like(zero)},
        {k: ("batch", "heads", None) for k in ("h", "c", "n", "m")},
    )
