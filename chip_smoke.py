#!/usr/bin/env python3
"""Bring-up smoke of the paged serving path on one TPU chip.

    python chip_smoke.py [--seed N]

One process, one chip.  It drives the system's main serving path once,
through the construction a deployment uses (``ServingPlan.resolve`` ->
``PagedServingEngine.from_plan`` -> ``engine.run``), at the published
widths of qwen2-7b cut in depth only, with random weights from ``--seed``:

1. device  — fail unless JAX's first device is a TPU (no CPU fallback);
2. model   — qwen2-7b widths, ``LAYERS_KEPT`` of 28 layers, parameters
             initialised on the chip in bfloat16;
3. kernels — each paged Pallas kernel against a float32 jnp oracle at the
             serving shapes below, with the pages wholly past each
             request's length poisoned with NaN;
4. serve   — 8 requests (prompts of 128-512 tokens, 32 new tokens each;
             two share a page-aligned prefix in one admission, and a late
             copy of one prompt forks its tail page copy-on-write) through
             the kernel engine, then through the jnp engine on the same
             weights; every request must finish with its 32 tokens, and
             greedy agreement between the engines is reported only;
5. logits  — last-position logits of one admission dispatch and of the
             decode step after it, kernel path against jnp path.

A failed check exits non-zero; no phase catches its own failure.  The
facts are printed as ``name: value`` lines, and the last line of standard
output is the result, ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

ARCH = "qwen2-7b"
# Depth is the only cut.  At qwen2-7b widths a layer is 466 MB of bf16
# weights plus 2 KiB of K/V per cached token, the embedding and lm_head
# 2.18 GB.  The v5e AOT compile of the engine's admission dispatch (8 rows
# x 512 tokens) at 18 layers needs 11.79 GB of arguments (weights + a
# 33k-token pool) and 4.18 GB of temporaries (the (8, 512, 152064) float32
# logits, 2.49 GB, and a copy of the pool): 15.97 GB of the chip's 16.9.
# 20 layers would need 17.37 GB.
LAYERS_KEPT = 18
SLOTS = 8
MAX_PROMPT = 512
NEW_TOKENS = 32
POOL_TOKENS = 32768          # the pool holds at least this many tokens
PREFIX = 256                 # page-aligned shared prefix (16 pages of 16)
UNSHARED_LENS = (128, 200, 384, 450, 512)
OWNER_OWN, SHARER_OWN = 44, 100   # owner: 300 tokens, a 12-token tail page
# Tolerances, relative to the reference's largest magnitude.
# Kernel vs float32 oracle: the kernel writes bfloat16 (2^-9 relative
# rounding) and its float32 dots may run as bfloat16 MXU passes (2^-9 per
# operand); a wrong page, head or one-slot mask error at the short
# lengths checked moves the output by far more than 1e-2.
KERNEL_TOL = 1e-2
# Kernel path vs jnp path, end to end: both keep bfloat16 activations
# (8 significant bits, spacing 3.9e-3) and round each layer's attention
# output after differently ordered float32 reductions, so single-ulp
# flips feed forward through 18 layers; 5e-2 is about a dozen bf16 ulps
# of the largest logit, where a wrong page or head gives errors of order 1.
LOGIT_TOL = 5e-2


def say(name: str, value) -> None:
    print(f"{name}: {value}", flush=True)


def fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def phase(name: str, fn, *args):
    """Run one phase to a device sync and print its wall seconds."""
    print(f"--- {name}", flush=True)
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    say(f"{name}.wall_s", time.perf_counter() - t0)
    return out


class CompileLog:
    """Backend compile seconds per jitted function, from JAX's own
    compile-duration events."""

    def __init__(self):
        self.secs: dict[str, list[float]] = {}
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_dur(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs.setdefault(str(kw.get("fun_name")), []).append(
                float(duration))

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


# ------------------------------------------------------------------ phases
def build_model(seed: int, interpret: bool, cfg=None):
    """qwen2-7b cut to LAYERS_KEPT layers (``cfg`` substitutes another
    config for a rehearsal off the chip)."""
    from repro.configs.registry import get_config
    from repro.models.api import build_model as build
    full = get_config(ARCH)
    cfg = cfg or full.replace(n_layers=LAYERS_KEPT)
    say("config", cfg.name)
    say("widths", {"d_model": cfg.d_model, "n_heads": cfg.n_heads,
                   "n_kv_heads": cfg.n_kv_heads, "head_dim": cfg.hd,
                   "d_ff": cfg.d_ff, "vocab": cfg.vocab_size})
    say("layers_kept", f"{cfg.n_layers} of {full.n_layers}")
    model_k = build(cfg, use_kernels=True, interpret=interpret)
    model_j = build(cfg)
    params = model_k.init(jax.random.PRNGKey(seed))
    say("param_bytes", sum(x.nbytes for x in jax.tree.leaves(params)))
    return cfg, model_k, model_j, params


def resolve_plan(cfg):
    from repro.serving import ServingPlan

    def resolve(pool_slots=None):
        return ServingPlan.resolve(
            cfg, slots=SLOTS, max_prompt_len=MAX_PROMPT,
            max_new_tokens=NEW_TOKENS, pool_slots=pool_slots,
            # one suffix bucket: a boundary's admissions share one
            # dispatch shape however ragged their prompts
            prefill_bucket=MAX_PROMPT)

    # size the pool in whole request lifetimes at the resolved page size
    per_slot = resolve().cache
    plan = resolve(-(-POOL_TOKENS // (per_slot.max_blocks
                                      * per_slot.page_size)))
    pc = plan.cache
    say("pool", {"pages": pc.n_pages, "page_size": pc.page_size,
                 "tokens": pc.n_pages * pc.page_size,
                 "max_blocks": pc.max_blocks,
                 "segment_len": pc.segment_len})
    return plan


def tile_provenance(cfg, plan) -> dict:
    """Each tile as the autotune readback gives it, with its source."""
    from repro.kernels import autotune
    pc = plan.cache
    tile, src = autotune.tile_readback(
        "flash_prefill_ragged", autotune.flash_prefill_ragged_problem(
            SLOTS, MAX_PROMPT, cfg.n_heads, cfg.n_kv_heads, cfg.hd,
            pc.max_blocks * pc.page_size, pc.page_size, cfg.adt))
    say("tiles", {
        "flash_decode_paged.page_size": [pc.page_size,
                                         plan.provenance["page_size"]],
        "paged_segment.segment_len": [pc.segment_len,
                                      plan.provenance["segment_len"]],
        "flash_prefill_ragged.block_q": [tile["block_q"], src]})
    return tile


@jax.jit
def _attend_f32(q, k, v, mask):
    """Plain masked softmax attention in float32 at full matmul precision.
    q: (R,S,H,D); k/v: (R,n,KV,D); mask: (R,S,n).  Rows with no live slot
    give zeros."""
    from repro.models.layers import _expand_kv
    h, d = q.shape[2], q.shape[3]
    with jax.default_matmul_precision("highest"):
        s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                       _expand_kv(k, h).astype(jnp.float32)) / math.sqrt(d)
        s = jnp.where(mask[:, None], s, -1e30)
        w = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", w,
                         _expand_kv(v, h).astype(jnp.float32))
    return jnp.where(mask.any(-1)[..., None, None], out, 0.0)


@jax.jit
def _rel_err_dev(got, ref):
    got, ref = got.astype(jnp.float32), ref.astype(jnp.float32)
    return jnp.max(jnp.abs(got - ref)) / jnp.max(jnp.abs(ref))


def _rel_err(got, ref) -> float:
    """Largest absolute difference over the reference's largest
    magnitude."""
    return float(_rel_err_dev(got, ref))


def check_kernels(cfg, plan, tile, seed: int, interpret: bool) -> dict:
    """Both paged kernels against the float32 oracle at the serving shapes:
    SLOTS rows, the plan's block table and page size, bf16 pools, ragged
    lengths down to a single slot, a scrambled block table, and NaN in
    every page wholly past a request's length."""
    from repro.kernels.flash_decode_paged import flash_decode_paged
    from repro.kernels.flash_prefill_ragged import flash_prefill_ragged
    from repro.models import layers as L
    from repro.serving.paged_cache import gather_request_kv
    pc = plan.cache
    ps, blocks = pc.page_size, pc.max_blocks
    n = blocks * ps
    h, kvh, d = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    n_pages = SLOTS * blocks + 1
    ks = jax.random.split(jax.random.PRNGKey(seed + 1), 5)
    kp = jax.random.normal(ks[0], (n_pages, kvh, ps, d), jnp.bfloat16)
    vp = jax.random.normal(ks[1], (n_pages, kvh, ps, d), jnp.bfloat16)
    perm = jax.random.permutation(ks[2], n_pages - 1) + 1
    bt = perm[:SLOTS * blocks].reshape(SLOTS, blocks).astype(jnp.int32)
    kf, vf = gather_request_kv(kp, bt), gather_request_kv(vp, bt)

    def poisoned(last_live):
        """Pools with NaN in each row's pages past ``last_live[r]``."""
        dead = (jnp.arange(blocks)[None] * ps) > last_live[:, None]
        pages = jnp.where(dead, bt, 0).reshape(-1)    # page 0: unused here
        return (kp.at[pages].set(jnp.nan), vp.at[pages].set(jnp.nan))

    sl = jnp.asarray([0, 1, 15, 16, 17, 150, 299, n - 1][:SLOTS],
                     jnp.int32)
    q1 = jax.random.normal(ks[3], (SLOTS, 1, h, d), jnp.bfloat16)
    mask = L.paged_decode_attention_mask(L.paged_kv_positions(sl, n), sl)
    kpn, vpn = poisoned(sl)
    got = flash_decode_paged(q1, kpn[None], vpn[None], bt, sl, 0,
                             interpret=interpret)
    dec_err = _rel_err(got, _attend_f32(q1, kf, vf, mask[:, None]))

    s = MAX_PROMPT
    off = jnp.asarray([0, 0, 16, 3, 256, 0, 40, 0][:SLOTS], jnp.int32)
    lens = jnp.asarray([1, 17, 100, 200, 100, s, 0, 300][:SLOTS], jnp.int32)
    qs = jax.random.normal(ks[4], (SLOTS, s, h, d), jnp.bfloat16)
    pmask = L.ragged_prefill_attention_mask(off, lens, s, n)
    kpn, vpn = poisoned(off + lens - 1)
    got = flash_prefill_ragged(qs, kpn[None], vpn[None], bt, off, lens, 0,
                               interpret=interpret,
                               block_q=tile["block_q"])
    pre_err = _rel_err(got, _attend_f32(qs, kf, vf, pmask))
    res = {"flash_decode_paged": dec_err, "flash_prefill_ragged": pre_err,
           "tol": KERNEL_TOL}
    say("kernel_vs_oracle_rel_err", res)
    for name in ("flash_decode_paged", "flash_prefill_ragged"):
        if not res[name] <= KERNEL_TOL:     # NaN fails too
            fail(f"{name} differs from its oracle by {res[name]} "
                 f"> {KERNEL_TOL}")
    return res


def make_requests(cfg, seed: int):
    """8 requests: an owner and a sharer of a 256-token page-aligned
    prefix, five unshared prompts, and a late exact copy of the owner's
    prompt that matches its partly filled tail page (copy-on-write)."""
    from repro.serving import Request
    rng = np.random.default_rng(seed)

    def toks(k):
        return rng.integers(0, cfg.vocab_size, size=k).astype(np.int32)

    prefix = toks(PREFIX)
    owner = np.concatenate([prefix, toks(OWNER_OWN)])
    prompts = [owner, np.concatenate([prefix, toks(SHARER_OWN)])]
    prompts += [toks(k) for k in UNSHARED_LENS]
    reqs = [Request(rid=i, prompt=p, max_new_tokens=NEW_TOKENS)
            for i, p in enumerate(prompts)]
    # arrives after the first boundary has admitted (and made ready) the
    # owner's tail page
    reqs.append(Request(rid=len(reqs), prompt=owner.copy(),
                        max_new_tokens=NEW_TOKENS, arrival=1e-3))
    return reqs


def serve(model, plan, params, seed: int, label: str):
    """Serve the requests through the engine a deployment builds."""
    from repro.serving import PagedServingEngine
    cfg = model.cfg
    engine = PagedServingEngine.from_plan(model, plan)
    reqs = make_requests(cfg, seed)
    stats = engine.run(reqs, params)
    short = [(r.rid, len(r.tokens)) for r in reqs
             if len(r.tokens) != r.max_new_tokens or r.failure is not None]
    if stats["n_finished"] != len(reqs) or short:
        fail(f"{label} engine: {stats['n_finished']}/{len(reqs)} finished; "
             f"short or failed (rid, tokens): {short}")
    late = reqs[-1]
    say(f"{label}.served", {
        "requests": len(reqs), "prompt_lens": [r.prompt_len for r in reqs],
        "new_tokens": [len(r.tokens) for r in reqs],
        "shared_tokens": [r.shared_tokens for r in reqs],
        "late_cow_fork": late.cow_dst is not None,
        "prefix_hits": stats["prefix_hits"],
        "prefill_dispatches": stats["n_prefill_dispatches"],
        "segments": stats["n_segments"],
        "preemptions": stats["preemptions"]})
    if stats["prefix_hits"] < 2 or late.cow_dst is None:
        fail(f"{label} engine: the shared-prefix and copy-on-write paths "
             f"did not run (prefix_hits={stats['prefix_hits']}, "
             f"late cow_dst={late.cow_dst})")
    return {r.rid: list(r.tokens) for r in reqs}


def compare_logits(cfg, plan, model_k, model_j, params, seed: int) -> dict:
    """One admission dispatch (2 ragged prompts in one 512-token bucket)
    and the decode step after it, kernel path against jnp path.  The
    decode step of both paths reads the kernel admission's pages, so each
    comparison covers one dispatch."""
    from repro.serving import PagedCacheConfig, init_paged_cache
    pc = plan.cache
    reqs = make_requests(cfg, seed)
    picked = [reqs[0], reqs[3]]                   # 300 and 200 tokens
    r = len(picked)
    tokens = np.zeros((r, MAX_PROMPT), np.int32)
    for i, q in enumerate(picked):
        tokens[i, :q.prompt_len] = q.prompt
    lens = jnp.asarray([q.prompt_len for q in picked], jnp.int32)
    bt = 1 + jnp.arange(r * pc.max_blocks, dtype=jnp.int32).reshape(
        r, pc.max_blocks)
    small = PagedCacheConfig(page_size=pc.page_size,
                             n_pages=r * pc.max_blocks + 1, max_slots=r,
                             max_blocks=pc.max_blocks)
    blocks = init_paged_cache(cfg, small)[0]["blocks"]

    def admit_logits(model):
        def admit_last_logits(params, blocks, tokens, bt, lens):
            cache = {"blocks": blocks, "block_tables": bt,
                     "seq_lens": jnp.zeros_like(lens), "prefill_lens": lens}
            logits, cache = model.decode_step(params, cache, tokens)
            last = jnp.take_along_axis(logits, (lens - 1)[:, None, None],
                                       axis=1)[:, 0]
            return last.astype(jnp.float32), cache["blocks"]
        return jax.jit(admit_last_logits)

    def decode_logits(model):
        def decode_step_logits(params, blocks, tok, bt, seq_lens):
            cache = {"blocks": blocks, "block_tables": bt,
                     "seq_lens": seq_lens}
            logits, _ = model.decode_step(params, cache, tok)
            return logits[:, -1].astype(jnp.float32)
        return jax.jit(decode_step_logits)

    toks = jnp.asarray(tokens)
    adm_k, blocks_k = admit_logits(model_k)(params, blocks, toks, bt, lens)
    adm_j, _ = admit_logits(model_j)(params, blocks, toks, bt, lens)
    nxt = jnp.argmax(adm_k, axis=-1).astype(jnp.int32)[:, None]
    dec_k = decode_logits(model_k)(params, blocks_k, nxt, bt, lens)
    dec_j = decode_logits(model_j)(params, blocks_k, nxt, bt, lens)
    res = {"admission": _rel_err(adm_k, adm_j),
           "decode": _rel_err(dec_k, dec_j), "tol": LOGIT_TOL,
           "max_abs_logit": float(jnp.max(jnp.abs(adm_j))),
           "argmax_agree": {
               "admission": int(jnp.sum(jnp.argmax(adm_k, -1)
                                        == jnp.argmax(adm_j, -1))),
               "decode": int(jnp.sum(jnp.argmax(dec_k, -1)
                                     == jnp.argmax(dec_j, -1))),
               "of": r}}
    say("logits_kernel_vs_jnp_rel_err", res)
    for name in ("admission", "decode"):
        if not res[name] <= LOGIT_TOL:      # NaN fails too
            fail(f"{name} logits: kernel path differs from the jnp path "
                 f"by {res[name]} > {LOGIT_TOL}")
    return res


def greedy_agreement(tok_k: dict, tok_j: dict) -> dict:
    """Reported only: with random weights a near-tie may flip a token."""
    first_diff = {}
    for rid, a in tok_k.items():
        b = tok_j[rid]
        i = next((i for i, (x, y) in enumerate(zip(a, b)) if x != y), None)
        if i is not None:
            first_diff[rid] = i
    out = {"identical_requests": len(tok_k) - len(first_diff),
           "of": len(tok_k), "first_divergence": first_diff}
    say("greedy_agreement_kernel_vs_jnp", out)
    return out


def run(seed: int, *, interpret: bool = False, cfg=None) -> None:
    """Phases 2-5 (see the module docstring)."""
    cfg, model_k, model_j, params = phase("model", build_model, seed,
                                          interpret, cfg)
    plan = resolve_plan(cfg)
    tile = tile_provenance(cfg, plan)
    phase("kernels", check_kernels, cfg, plan, tile, seed, interpret)
    tok_k = phase("serve_kernels", serve, model_k, plan, params, seed,
                  "kernel")
    gc.collect()                  # the first engine's pool goes back first
    tok_j = phase("serve_jnp", serve, model_j, plan, params, seed, "jnp")
    gc.collect()
    greedy_agreement(tok_k, tok_j)
    phase("logits", compare_logits, cfg, plan, model_k, model_j, params,
          seed)
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    say("peak_bytes_in_use", stats.get("peak_bytes_in_use"))
    say("bytes_limit", stats.get("bytes_limit"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found: JAX's first device is "
                         f"{dev.platform!r}; this smoke runs only on a TPU")
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SystemExit(f"chip_smoke: {src}/repro not found: run it from "
                         "a checkout of the repository")
    sys.path.insert(0, src)
    # the autotune cache this run reads lives in the checkout too
    os.environ.setdefault("REPRO_AUTOTUNE_CACHE",
                          os.path.join(ROOT, ".autotune", "autotune.json"))
    from repro.launch.compile_cache import configure_compile_cache
    say("device", {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devs)})
    say("compile_cache_dir", configure_compile_cache())
    compiles = CompileLog()
    t0 = time.perf_counter()
    run(args.seed)
    say("compile_s", {name: secs
                      for name, secs in sorted(compiles.secs.items())
                      if sum(secs) >= 0.5})
    say("compile_s_total", sum(map(sum, compiles.secs.values())))
    say("compile_cache_hits", compiles.cache_hits)
    say("total_wall_s", time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
