"""Per-shape tile autotuner for the Pallas kernels.

MetaML's claim is "automating the selection and configuration of low-level
optimization techniques"; on the TPU stack the low-level knobs are Pallas
tile sizes.  This module closes that loop: for a concrete (kernel, shape,
dtype, flags) problem it

1. enumerates a *pruned* candidate space — tile sizes drawn from
   :data:`TILE_SIZES`, filtered by divisibility against the problem shape
   and by a VMEM-footprint model against :data:`VMEM_BUDGET` (a candidate
   that would not fit on-chip is never timed);
2. measures every surviving candidate with the benchmarks/common.py
   ``timeit`` harness (interpret mode on CPU, real timing on TPU);
3. memoizes the winner in a persistent on-disk JSON cache keyed by
   ``kernel|problem`` so later calls — including future processes — skip
   straight to the tuned config.

The default (128x128[,512]) config is always part of the candidate space,
so the tuned config is never slower than the fixed default *as measured*.

Cache file format (``REPRO_AUTOTUNE_CACHE`` or ~/.cache/repro/autotune.json)::

    {"version": 1,
     "entries": {
       "quant_matmul|{\"dtype\":\"float32\",\"k\":512,...}": {
         "config": {"block_m": 256, "block_n": 128, "block_k": 512},
         "us": 1234.5,
         "n_trials": 9,
         "backend": "cpu",
         "t": 1700000000.0}}}

The TUNE O-task (tasks/tune.py) drives :func:`tune` and republishes every
trial as a ``SearchStep`` in the MetaModel history; ``tuned_*`` wrappers
give kernels-layer callers transparent tune-on-miss dispatch.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import math
import os
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.search import SearchResult, exhaustive_search
from repro.kernels.block_sparse_matmul import block_sparse_matmul
from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_decode import flash_decode, pick_block_kv
from repro.kernels.flash_decode_paged import (DEFAULT_PAGE_SIZE,
                                              flash_decode_paged)
from repro.kernels.flash_prefill_ragged import BQ as BQ_PREFILL
from repro.kernels.flash_prefill_ragged import flash_prefill_ragged
from repro.kernels.quant_matmul import BK, BM, BN, quant_matmul

TILE_SIZES = (32, 64, 128, 256)
# Conservative per-step budget: half of the ~16 MB VMEM per TPU core,
# leaving headroom for double-buffered pipelining of the HBM->VMEM copies.
VMEM_BUDGET = 8 * 2 ** 20
CACHE_ENV = "REPRO_AUTOTUNE_CACHE"
CACHE_VERSION = 1


# --------------------------------------------------------------------- cache
def default_cache_path() -> str:
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    return os.path.join(os.path.expanduser("~"), ".cache", "repro",
                        "autotune.json")


_MEM: dict[str, dict[str, Any]] = {}   # path -> {"entries": {...}} (loaded once)


def _load(path: str) -> dict[str, Any]:
    if path not in _MEM:
        data: dict[str, Any] = {"version": CACHE_VERSION, "entries": {}}
        try:
            with open(path) as f:
                raw = json.load(f)
            if raw.get("version") == CACHE_VERSION:
                data = raw
        except (OSError, ValueError):
            pass
        _MEM[path] = data
    return _MEM[path]


def _store(path: str, key: str, entry: dict[str, Any]) -> None:
    # Merge against a fresh read of the file, not the process snapshot:
    # concurrent writers (pytest-xdist, a flow next to a bench) would
    # otherwise have their entries clobbered by our stale view.  The temp
    # name is per-writer so two simultaneous stores cannot interleave
    # inside one file; last os.replace wins.
    _MEM.pop(path, None)
    data = _load(path)
    data["entries"][key] = entry
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = f"{path}.{os.getpid()}.tmp"
    with open(tmp, "w") as f:
        json.dump(data, f, indent=1, sort_keys=True)
    os.replace(tmp, path)


def clear_memory_cache() -> None:
    """Drop the in-process view of every cache file (tests)."""
    _MEM.clear()
    _RESOLVED.clear()


def cache_key(kernel: str, problem: dict[str, Any]) -> str:
    return f"{kernel}|{json.dumps(problem, sort_keys=True)}"


# ------------------------------------------------------------------- results
@dataclasses.dataclass(frozen=True)
class Trial:
    config: dict[str, int]
    us: float
    vmem_bytes: int


@dataclasses.dataclass
class TuneResult:
    kernel: str
    key: str
    config: dict[str, int]
    us: float
    cached: bool
    trials: list[Trial] = dataclasses.field(default_factory=list)
    search: SearchResult | None = None   # None on a cache hit

    @property
    def default_us(self) -> float | None:
        default = KERNELS[self.kernel].default_config
        for t in self.trials:
            if t.config == default:
                return t.us
        return None


# ------------------------------------------------------- kernel descriptors
def _itemsize(dtype: str) -> int:
    return jnp.dtype(dtype).itemsize


def _divides(tile: int, dim: int) -> bool:
    return dim % min(tile, dim) == 0


def _axis(default: int, extra: tuple[int, ...] = ()) -> tuple[int, ...]:
    """Tile sizes for one dim, default first: when small problem dims clamp
    several nominal tiles to the same effective tile, the dedup in the
    candidate generators keeps the first-seen config — default-first makes
    that representative the literal default config, preserving the
    'default is always measured' invariant (and TuneResult.default_us)."""
    sizes = set(TILE_SIZES) | set(extra) | {default}
    return tuple(sorted(sizes, key=lambda t: (t != default, t)))


@dataclasses.dataclass(frozen=True)
class KernelEntry:
    """One tunable kernel: candidate model + benchmark-input factory."""

    name: str
    default_config: dict[str, int]
    candidates: Callable[[dict[str, Any]], list[tuple[dict[str, int], int]]]
    make_runner: Callable[[dict[str, Any], dict[str, int], bool],
                          Callable[[], Any]]


# flash attention ------------------------------------------------------------
def _fa_vmem(problem: dict[str, Any], cfg: dict[str, int]) -> int:
    d = problem["d"]
    bq = min(cfg["block_q"], problem["sq"])
    bkv = min(cfg["block_kv"], problem["skv"])
    item = _itemsize(problem["dtype"])
    blocks = (2 * bq * d + 2 * bkv * d) * item      # q, out, k, v tiles
    scratch = (2 * bq + bq * d) * 4                 # m, l, acc (f32)
    temps = 2 * bq * bkv * 4                        # s and p (f32)
    return blocks + scratch + temps


def _fa_candidates(problem: dict[str, Any]
                   ) -> list[tuple[dict[str, int], int]]:
    out, seen = [], set()
    for bq in _axis(128):
        for bkv in _axis(128):
            cfg = {"block_q": bq, "block_kv": bkv}
            eff = (min(bq, problem["sq"]), min(bkv, problem["skv"]))
            if eff in seen:     # clamped duplicates time identically
                continue
            seen.add(eff)
            out.append((cfg, _fa_vmem(problem, cfg)))
    return out


@functools.lru_cache(maxsize=8)
def _fa_inputs(problem_json: str):
    problem = json.loads(problem_json)
    dtype = jnp.dtype(problem["dtype"])
    q = jax.random.normal(
        jax.random.PRNGKey(0),
        (problem["b"], problem["sq"], problem["h"], problem["d"])
    ).astype(dtype)
    kv_shape = (problem["b"], problem["skv"], problem["kv_heads"],
                problem["d"])
    k = jax.random.normal(jax.random.PRNGKey(1), kv_shape).astype(dtype)
    v = jax.random.normal(jax.random.PRNGKey(2), kv_shape).astype(dtype)
    return q, k, v


def _fa_runner(problem: dict[str, Any], cfg: dict[str, int],
               interpret: bool) -> Callable[[], Any]:
    # inputs depend only on the problem: build once per search, not per
    # candidate (lru keyed on the canonical problem JSON)
    q, k, v = _fa_inputs(json.dumps(problem, sort_keys=True))
    return lambda: flash_attention(
        q, k, v, causal=problem["causal"], window=problem["window"],
        interpret=interpret, block_q=cfg["block_q"],
        block_kv=cfg["block_kv"])


def flash_attention_problem(q_shape, kv_shape, dtype, *,
                            causal: bool = True,
                            window: int = 0) -> dict[str, Any]:
    b, sq, h, d = (int(x) for x in q_shape)
    _, skv, kvh, _ = (int(x) for x in kv_shape)
    return {"b": b, "sq": sq, "h": h, "d": d, "skv": skv, "kv_heads": kvh,
            "dtype": jnp.dtype(dtype).name, "causal": bool(causal),
            "window": int(window)}


# flash decode ---------------------------------------------------------------
def _fd_vmem(problem: dict[str, Any], cfg: dict[str, int]) -> int:
    d = problem["d"]
    g = problem["h"] // problem["kv_heads"]
    bkv = pick_block_kv(cfg["block_kv"], problem["cache_len"])
    item = _itemsize(problem["dtype"])
    blocks = (2 * g * d + 2 * bkv * d) * item       # q, out, k, v tiles
    mask = bkv * 4                                  # int32 validity tile
    scratch = (2 * g + g * d) * 4                   # m, l, acc (f32)
    temps = 2 * g * bkv * 4                         # s and p (f32)
    return blocks + mask + scratch + temps


def _fd_candidates(problem: dict[str, Any]
                   ) -> list[tuple[dict[str, int], int]]:
    # block_kv IS the kv-split: cache_len / block_kv partial-softmax steps.
    # 512 joins the space for long caches where fewer, fatter tiles win.
    out, seen = [], set()
    for bkv in _axis(128, (512,)):
        cfg = {"block_kv": bkv}
        # dedup on the divisor-safe effective tile the kernel will run
        # (clamping and ragged-snap both collapse nominal candidates)
        eff = pick_block_kv(bkv, problem["cache_len"])
        if eff in seen:
            continue
        seen.add(eff)
        out.append((cfg, _fd_vmem(problem, cfg)))
    return out


@functools.lru_cache(maxsize=8)
def _fd_inputs(problem_json: str):
    problem = json.loads(problem_json)
    dtype = jnp.dtype(problem["dtype"])
    b, h, d = problem["b"], problem["h"], problem["d"]
    kvh, skv = problem["kv_heads"], problem["cache_len"]
    q = jax.random.normal(jax.random.PRNGKey(0), (b, 1, h, d)).astype(dtype)
    k = jax.random.normal(jax.random.PRNGKey(1),
                          (b, skv, kvh, d)).astype(dtype)
    v = jax.random.normal(jax.random.PRNGKey(2),
                          (b, skv, kvh, d)).astype(dtype)
    # a full cache is the steady-state (and worst-case) decode problem
    mask = jnp.ones((skv,), jnp.bool_)
    return q, k, v, mask


def _fd_runner(problem: dict[str, Any], cfg: dict[str, int],
               interpret: bool) -> Callable[[], Any]:
    q, k, v, mask = _fd_inputs(json.dumps(problem, sort_keys=True))
    return lambda: flash_decode(q, k, v, mask, interpret=interpret,
                                block_kv=cfg["block_kv"])


def flash_decode_problem(q_shape, kv_shape, dtype) -> dict[str, Any]:
    b, _, h, d = (int(x) for x in q_shape)
    _, skv, kvh, _ = (int(x) for x in kv_shape)
    return {"b": b, "h": h, "d": d, "kv_heads": kvh, "cache_len": skv,
            "dtype": jnp.dtype(dtype).name}


# paged flash decode ---------------------------------------------------------
def _fpd_vmem(problem: dict[str, Any], cfg: dict[str, int]) -> int:
    d = problem["d"]
    g = problem["h"] // problem["kv_heads"]
    # the K/V tile is always a full page — a page larger than max_len is
    # padded, not clamped (unlike the contiguous kernels' tiles)
    ps = cfg["page_size"]
    item = _itemsize(problem["dtype"])
    blocks = (2 * g * d + 2 * ps * d) * item        # q, out, k, v page tiles
    scratch = (2 * g + g * d) * 4                   # m, l, acc (f32)
    temps = 2 * g * ps * 4                          # s and p (f32)
    return blocks + scratch + temps


def _fpd_candidates(problem: dict[str, Any]
                    ) -> list[tuple[dict[str, int], int]]:
    # page_size IS the kv-split of the paged kernel AND the pool's
    # allocation granule: small pages fragment less (~page_size/2 wasted
    # tokens per request), big pages mean fewer grid steps per token.
    # The tuner times the kernel side; the engine reads the winner back
    # at pool-construction time (serving/paged_cache.preferred_page_size).
    # Ascending enumeration + effective-coverage dedup: page sizes whose
    # effective coverage min(ps, max_len) collapses are redundant grids,
    # and keeping the SMALLEST representative keeps pool padding minimal
    # (a covering page larger than max_len only wastes pool bytes).  The
    # default page size is force-included even when it collapses, so the
    # 'default is always measured' invariant (and the distance-sorted cap
    # in enumerate_candidates) holds like every other kernel.
    out, seen = [], set()
    for ps in sorted(set(TILE_SIZES) | {8, 16, DEFAULT_PAGE_SIZE}):
        eff = min(ps, problem["max_len"])
        if eff in seen and ps != DEFAULT_PAGE_SIZE:
            continue
        seen.add(eff)
        cfg = {"page_size": ps}
        out.append((cfg, _fpd_vmem(problem, cfg)))
    return out


@functools.lru_cache(maxsize=16)
def _fpd_inputs(problem_json: str, page_size: int):
    problem = json.loads(problem_json)
    dtype = jnp.dtype(problem["dtype"])
    slots, h, d = problem["slots"], problem["h"], problem["d"]
    kvh, max_len = problem["kv_heads"], problem["max_len"]
    blocks = -(-max_len // page_size)
    n_pages = slots * blocks + 1           # + the reserved scratch page
    q = jax.random.normal(jax.random.PRNGKey(0),
                          (slots, 1, h, d)).astype(dtype)
    # a one-layer whole-model pool, attended at layer 0
    kp = jax.random.normal(jax.random.PRNGKey(1),
                           (1, n_pages, kvh, page_size, d)).astype(dtype)
    vp = jax.random.normal(jax.random.PRNGKey(2),
                           (1, n_pages, kvh, page_size, d)).astype(dtype)
    bt = 1 + jnp.arange(slots * blocks, dtype=jnp.int32).reshape(
        slots, blocks)
    # steady-state (worst-case) decode: every request near capacity
    sl = jnp.full((slots,), max_len - 1, jnp.int32)
    return q, kp, vp, bt, sl


def _fpd_runner(problem: dict[str, Any], cfg: dict[str, int],
                interpret: bool) -> Callable[[], Any]:
    q, kp, vp, bt, sl = _fpd_inputs(
        json.dumps(problem, sort_keys=True), cfg["page_size"])
    return lambda: flash_decode_paged(q, kp, vp, bt, sl, 0,
                                      interpret=interpret)


def flash_decode_paged_problem(slots: int, h: int, kv_heads: int, d: int,
                               max_len: int, dtype) -> dict[str, Any]:
    return {"slots": int(slots), "h": int(h), "kv_heads": int(kv_heads),
            "d": int(d), "max_len": int(max_len),
            "dtype": jnp.dtype(dtype).name}


# paged decode segment -------------------------------------------------------
# Not a kernel tile but a *scheduler cadence*: the serving engine decodes
# in fixed-length lax.scan segments and wakes the host only at segment
# boundaries (retire/admit/grow/preempt).  Long segments amortize the
# host sync + dispatch overhead per token; short segments react faster
# (admissions wait less, finished slots idle less, and the resource
# manager's growth granule — the pages one segment consumes — shrinks,
# so an oversubscribed pool preempts less speculatively).  The timing
# harness can only see the first half of that trade, so candidates all
# generate the SAME token budget split into different dispatch sizes
# with a host sync between dispatches — exactly the engine's boundary
# pattern — and the winner is the cadence whose overhead amortization
# actually pays on this backend.  The engine reads it back through
# serving/paged_cache.py::preferred_segment_len.
SEGMENT_TOKENS = 32          # fixed token budget every candidate pays


def _pseg_vmem(problem: dict[str, Any], cfg: dict[str, int]) -> int:
    # per grid step the resident working set is flash_decode_paged's at
    # the pool's page size; segment_len moves dispatch count, not tiles
    d = problem["d"]
    g = problem["h"] // problem["kv_heads"]
    ps = problem["page_size"]
    item = _itemsize(problem["dtype"])
    blocks = (2 * g * d + 2 * ps * d) * item
    scratch = (2 * g + g * d) * 4
    temps = 2 * g * ps * 4
    return blocks + scratch + temps


def _pseg_candidates(problem: dict[str, Any]
                     ) -> list[tuple[dict[str, int], int]]:
    out = []
    for sl in (2, 4, 8, 16, SEGMENT_TOKENS):
        out.append(({"segment_len": sl}, _pseg_vmem(problem, {})))
    return out


@functools.lru_cache(maxsize=16)
def _pseg_fn(problem_json: str, seg_len: int, interpret: bool):
    problem = json.loads(problem_json)
    dtype = jnp.dtype(problem["dtype"])
    slots, h, d = problem["slots"], problem["h"], problem["d"]
    kvh, max_len, ps = (problem["kv_heads"], problem["max_len"],
                        problem["page_size"])
    blocks = -(-max_len // ps)
    n_pages = slots * blocks + 1           # + the reserved scratch page
    q = jax.random.normal(jax.random.PRNGKey(0),
                          (slots, 1, h, d)).astype(dtype)
    kp = jax.random.normal(jax.random.PRNGKey(1),
                           (1, n_pages, kvh, ps, d)).astype(dtype)
    vp = jax.random.normal(jax.random.PRNGKey(2),
                           (1, n_pages, kvh, ps, d)).astype(dtype)
    bt = 1 + jnp.arange(slots * blocks, dtype=jnp.int32).reshape(
        slots, blocks)
    # start half-full: the scan advances seq_lens like a real segment
    sl0 = jnp.full((slots,), max(1, max_len // 2), jnp.int32)

    def segment(sl):
        def step(carry, _):
            cur = carry
            out = flash_decode_paged(q, kp, vp, bt,
                                     jnp.minimum(cur, max_len - 1), 0,
                                     interpret=interpret)
            return jnp.minimum(cur + 1, max_len - 1), out[:, 0, 0, 0]
        sl, outs = jax.lax.scan(step, sl, None, length=seg_len)
        return sl, outs

    return jax.jit(segment), sl0


def _pseg_runner(problem: dict[str, Any], cfg: dict[str, int],
                 interpret: bool) -> Callable[[], Any]:
    seg_len = min(cfg["segment_len"], SEGMENT_TOKENS)
    fn, sl0 = _pseg_fn(json.dumps(problem, sort_keys=True), seg_len,
                       interpret)
    reps = SEGMENT_TOKENS // seg_len

    def run():
        sl, outs = sl0, None
        for _ in range(reps):
            sl, outs = fn(sl)
            # the engine pulls control state back at every boundary;
            # blocking here reproduces that sync cost per dispatch
            jax.block_until_ready(outs)
        return outs

    return run


def paged_segment_problem(slots: int, h: int, kv_heads: int, d: int,
                          max_len: int, page_size: int,
                          dtype) -> dict[str, Any]:
    return {"slots": int(slots), "h": int(h), "kv_heads": int(kv_heads),
            "d": int(d), "max_len": int(max_len),
            "page_size": int(page_size),
            "dtype": jnp.dtype(dtype).name}


# ragged paged prefill -------------------------------------------------------
def _fpr_vmem(problem: dict[str, Any], cfg: dict[str, int]) -> int:
    d = problem["d"]
    g = problem["h"] // problem["kv_heads"]
    # the wrapper clamps block_q to the suffix length; the K/V tile is
    # always one full page (fixed by the pool layout)
    bq = min(cfg["block_q"], problem["s"]) * g
    ps = problem["page_size"]
    item = _itemsize(problem["dtype"])
    blocks = (2 * bq * d + 2 * ps * d) * item       # q, out, k, v tiles
    scratch = (2 * bq + bq * d) * 4                 # m, l, acc (f32)
    temps = 2 * bq * ps * 4                         # s and p (f32)
    return blocks + scratch + temps


def _fpr_candidates(problem: dict[str, Any]
                    ) -> list[tuple[dict[str, int], int]]:
    # block_q tiles the suffix-query axis; the kv axis is walked page by
    # page (the pool's page size — the prefix-match granule — is part of
    # the problem, tuned through flash_decode_paged, not re-tuned here).
    out, seen = [], set()
    for bq in _axis(BQ_PREFILL, (8, 16)):
        cfg = {"block_q": bq}
        eff = min(bq, problem["s"])     # wrapper clamps: duplicates collapse
        if eff in seen:
            continue
        seen.add(eff)
        out.append((cfg, _fpr_vmem(problem, cfg)))
    return out


@functools.lru_cache(maxsize=16)
def _fpr_inputs(problem_json: str):
    problem = json.loads(problem_json)
    dtype = jnp.dtype(problem["dtype"])
    slots, s, h, d = (problem["slots"], problem["s"], problem["h"],
                      problem["d"])
    kvh, max_len, ps = (problem["kv_heads"], problem["max_len"],
                        problem["page_size"])
    blocks = -(-max_len // ps)
    n_pages = slots * blocks + 1           # + the reserved scratch page
    q = jax.random.normal(jax.random.PRNGKey(0),
                          (slots, s, h, d)).astype(dtype)
    kp = jax.random.normal(jax.random.PRNGKey(1),
                           (1, n_pages, kvh, ps, d)).astype(dtype)
    vp = jax.random.normal(jax.random.PRNGKey(2),
                           (1, n_pages, kvh, ps, d)).astype(dtype)
    bt = 1 + jnp.arange(slots * blocks, dtype=jnp.int32).reshape(
        slots, blocks)
    # worst case: every suffix sits at the end of a near-full prefix, so
    # each query attends the whole history
    off = jnp.full((slots,), max(0, max_len - s), jnp.int32)
    lens = jnp.full((slots,), s, jnp.int32)
    return q, kp, vp, bt, off, lens


def _fpr_runner(problem: dict[str, Any], cfg: dict[str, int],
                interpret: bool) -> Callable[[], Any]:
    q, kp, vp, bt, off, lens = _fpr_inputs(
        json.dumps(problem, sort_keys=True))
    return lambda: flash_prefill_ragged(q, kp, vp, bt, off, lens, 0,
                                        interpret=interpret,
                                        block_q=cfg["block_q"])


def flash_prefill_ragged_problem(slots: int, s: int, h: int, kv_heads: int,
                                 d: int, max_len: int, page_size: int,
                                 dtype) -> dict[str, Any]:
    """``s`` is the padded suffix bucket, ``max_len`` the logical slots
    per request (block-table width x page size)."""
    return {"slots": int(slots), "s": int(s), "h": int(h),
            "kv_heads": int(kv_heads), "d": int(d),
            "max_len": int(max_len), "page_size": int(page_size),
            "dtype": jnp.dtype(dtype).name}


# quant matmul ---------------------------------------------------------------
def _qmm_vmem(problem: dict[str, Any], cfg: dict[str, int]) -> int:
    bm = min(cfg["block_m"], problem["m"])
    bn = min(cfg["block_n"], problem["n"])
    bk = min(cfg["block_k"], problem["k"])
    blocks = bm * bk + bk * bn          # int8 tiles
    scales = (bm + bn) * 4
    acc = bm * bn * 4                   # int32 accumulator
    out = bm * bn * _itemsize(problem["out_dtype"])
    temps = bm * bn * 4                 # dequant f32 temporary
    return blocks + scales + acc + out + temps


def _qmm_candidates(problem: dict[str, Any]
                    ) -> list[tuple[dict[str, int], int]]:
    m, n, k = problem["m"], problem["n"], problem["k"]
    out, seen = [], set()
    for bm in _axis(BM):
        for bn in _axis(BN):
            for bk in _axis(BK):
                if not (_divides(bm, m) and _divides(bn, n)
                        and _divides(bk, k)):
                    continue
                eff = (min(bm, m), min(bn, n), min(bk, k))
                if eff in seen:
                    continue
                seen.add(eff)
                cfg = {"block_m": bm, "block_n": bn, "block_k": bk}
                out.append((cfg, _qmm_vmem(problem, cfg)))
    return out


@functools.lru_cache(maxsize=8)
def _mm_inputs(problem_json: str):
    problem = json.loads(problem_json)
    dtype = jnp.dtype(problem["dtype"])
    x = jax.random.normal(jax.random.PRNGKey(0),
                          (problem["m"], problem["k"])).astype(dtype)
    w = jax.random.normal(jax.random.PRNGKey(1),
                          (problem["k"], problem["n"])).astype(dtype)
    return x, w


def _qmm_runner(problem: dict[str, Any], cfg: dict[str, int],
                interpret: bool) -> Callable[[], Any]:
    x, w = _mm_inputs(json.dumps(problem, sort_keys=True))
    return lambda: quant_matmul(
        x, w, interpret=interpret, block_m=cfg["block_m"],
        block_n=cfg["block_n"], block_k=cfg["block_k"])


def quant_matmul_problem(x_shape, w_shape, dtype, *,
                         out_dtype=jnp.float32) -> dict[str, Any]:
    m, k = (int(v) for v in x_shape)
    _, n = (int(v) for v in w_shape)
    return {"m": m, "k": k, "n": n, "dtype": jnp.dtype(dtype).name,
            "out_dtype": jnp.dtype(out_dtype).name}


# block-sparse matmul --------------------------------------------------------
def _bsmm_vmem(problem: dict[str, Any], cfg: dict[str, int]) -> int:
    block = problem["block"]
    bm = min(cfg["block_m"], problem["m"])
    item = _itemsize(problem["dtype"])
    blocks = (bm * block + block * block) * item    # x, w tiles
    acc_out = 2 * bm * block * 4                    # acc scratch + out tile
    return blocks + acc_out


def _bsmm_candidates(problem: dict[str, Any]
                     ) -> list[tuple[dict[str, int], int]]:
    m = problem["m"]
    out, seen = [], set()
    for bm in _axis(128):
        if not _divides(bm, m):
            continue
        eff = min(bm, m)
        if eff in seen:
            continue
        seen.add(eff)
        cfg = {"block_m": bm}
        out.append((cfg, _bsmm_vmem(problem, cfg)))
    return out


def _bsmm_runner(problem: dict[str, Any], cfg: dict[str, int],
                 interpret: bool) -> Callable[[], Any]:
    block = problem["block"]
    x, w = _mm_inputs(json.dumps(problem, sort_keys=True))
    nb = problem["n"] // block
    live = min(problem["max_live"], problem["k"] // block)
    kidx = jnp.asarray(np.tile(np.arange(live, dtype=np.int32), (nb, 1)))
    return lambda: block_sparse_matmul(
        x, w, kidx, block=block, block_m=cfg["block_m"],
        interpret=interpret)


def block_sparse_matmul_problem(x_shape, w_shape, dtype, *,
                                max_live: int,
                                block: int = 128) -> dict[str, Any]:
    m, k = (int(v) for v in x_shape)
    _, n = (int(v) for v in w_shape)
    return {"m": m, "k": k, "n": n, "block": int(block),
            "max_live": int(max_live), "dtype": jnp.dtype(dtype).name}


KERNELS: dict[str, KernelEntry] = {
    "flash_attention": KernelEntry(
        "flash_attention", {"block_q": 128, "block_kv": 128},
        _fa_candidates, _fa_runner),
    "flash_decode": KernelEntry(
        "flash_decode", {"block_kv": 128},
        _fd_candidates, _fd_runner),
    "flash_decode_paged": KernelEntry(
        "flash_decode_paged", {"page_size": 16},
        _fpd_candidates, _fpd_runner),
    "paged_segment": KernelEntry(
        "paged_segment", {"segment_len": 8},
        _pseg_candidates, _pseg_runner),
    "flash_prefill_ragged": KernelEntry(
        "flash_prefill_ragged", {"block_q": BQ_PREFILL},
        _fpr_candidates, _fpr_runner),
    "quant_matmul": KernelEntry(
        "quant_matmul", {"block_m": BM, "block_n": BN, "block_k": BK},
        _qmm_candidates, _qmm_runner),
    "block_sparse_matmul": KernelEntry(
        "block_sparse_matmul", {"block_m": 128},
        _bsmm_candidates, _bsmm_runner),
}


# ------------------------------------------------------------------- tuning
def _fallback_timeit(fn, *, warmup: int = 1, iters: int = 5) -> float:
    """Same contract as benchmarks/common.py::timeit (median µs/call)."""
    for _ in range(warmup):
        fn()
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn()
        for leaf in jax.tree.leaves(out):
            if hasattr(leaf, "block_until_ready"):
                leaf.block_until_ready()
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2] * 1e6


def _default_timer(fn, *, warmup: int, iters: int) -> float:
    try:
        from benchmarks.common import timeit
    except ImportError:
        return _fallback_timeit(fn, warmup=warmup, iters=iters)
    return timeit(fn, warmup=warmup, iters=iters)


def _config_distance(cfg: dict[str, int], default: dict[str, int]) -> float:
    return sum(abs(math.log2(cfg[k]) - math.log2(default[k]))
               for k in default)


def enumerate_candidates(kernel: str, problem: dict[str, Any], *,
                         vmem_budget: int = VMEM_BUDGET,
                         max_trials: int | None = None
                         ) -> list[tuple[dict[str, int], int]]:
    """Pruned candidate list for ``kernel`` on ``problem``.

    Divisibility-infeasible and VMEM-over-budget configs are dropped; the
    remainder is ordered default-first (distance in log2-tile space) and
    optionally capped at ``max_trials`` — the default config survives any
    cap, which is what guarantees tuned-never-slower-than-default.
    """
    entry = KERNELS[kernel]
    cands = [(c, v) for c, v in entry.candidates(problem)
             if v <= vmem_budget]
    cands.sort(key=lambda cv: (_config_distance(cv[0], entry.default_config),
                               sorted(cv[0].items())))
    if max_trials is not None:
        cands = cands[:max(1, max_trials)]
    return cands


def tune(kernel: str, problem: dict[str, Any], *,
         cache_path: str | None = None,
         force: bool = False,
         interpret: bool | None = None,
         iters: int = 3, warmup: int = 1,
         max_trials: int | None = 16,
         vmem_budget: int = VMEM_BUDGET,
         timer: Callable[..., float] | None = None) -> TuneResult:
    """Find (or recall) the best tile config for ``kernel`` on ``problem``.

    On a cache hit the measurement loop is skipped entirely; on a miss every
    surviving candidate is timed and the winner is persisted.
    """
    if kernel not in KERNELS:
        raise KeyError(f"unknown tunable kernel {kernel!r}; "
                       f"have {sorted(KERNELS)}")
    path = cache_path or default_cache_path()
    key = cache_key(kernel, problem)
    if not force:
        entry = _load(path)["entries"].get(key)
        # A cached entry only counts if it is evidence for THIS request:
        # same backend (CPU-interpret timings say nothing about the MXU),
        # at least as deep a search, and at least as many timing iters as
        # now requested (a shallow/noisy bench sweep must not permanently
        # shadow a fuller TUNE search).
        if entry is not None and entry.get("backend") == \
                jax.default_backend() and entry.get("iters", 0) >= iters \
                and entry.get("vmem_budget", float("inf")) <= vmem_budget:
            requested = len(enumerate_candidates(
                kernel, problem, vmem_budget=vmem_budget,
                max_trials=max_trials))
            if entry.get("n_trials", 0) >= requested:
                return TuneResult(kernel, key, dict(entry["config"]),
                                  float(entry["us"]), cached=True)

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    timer = timer or _default_timer
    spec = KERNELS[kernel]
    cands = enumerate_candidates(kernel, problem, vmem_budget=vmem_budget,
                                 max_trials=max_trials)
    if not cands:
        raise ValueError(f"{kernel}: no feasible tile candidate for "
                         f"{problem} under vmem_budget={vmem_budget}")
    vmem_of = {json.dumps(c, sort_keys=True): v for c, v in cands}
    trials: list[Trial] = []

    def evaluate(cfg: dict[str, int]):
        vmem = vmem_of[json.dumps(cfg, sort_keys=True)]
        runner = spec.make_runner(problem, cfg, interpret)
        us = float(timer(runner, warmup=warmup, iters=iters))
        trials.append(Trial(dict(cfg), us, vmem))
        # maximize -latency; every pre-pruned candidate is feasible
        return True, -us, {"us": us, "vmem_bytes": vmem}

    search = exhaustive_search([c for c, _ in cands], evaluate)
    best_cfg, best_us = dict(search.best_x), -search.best_objective
    _store(path, key, {"config": best_cfg, "us": best_us,
                       "n_trials": len(trials), "iters": iters,
                       "vmem_budget": vmem_budget,
                       "backend": jax.default_backend(),
                       "t": time.time()})
    return TuneResult(kernel, key, best_cfg, best_us,
                      cached=False, trials=trials, search=search)


def cached_config(kernel: str, problem: dict[str, Any], *,
                  cache_path: str | None = None,
                  relax: tuple[str, ...] = ()) -> dict[str, int]:
    """Persisted tuned config for ``problem``, or the kernel default.

    Never tunes and never times — a pure (memoized) cache read, so it is
    safe on a model's trace path: layers consult it per kernel call to
    pick up whatever the TUNE task / ``tuned_*`` wrappers persisted,
    falling back to the default config on a miss or a backend mismatch.

    ``relax``: problem fields allowed to differ on fallback matching.  A
    TUNE run keys its decode problem on the arch's nominal cache length
    and a proxy batch, while serving builds ``prompt+gen+1``-length caches
    at the actual batch — relaxing ("b", "cache_len") lets the nearest
    tuned entry (log-distance over the relaxed dims) stand in, so tuning
    wins still reach serving shapes TUNE never saw exactly.  Configs stay
    valid across the relaxation because kernels clamp tiles to the
    problem dims.
    """
    cfg, _ = cached_config_info(kernel, problem, cache_path=cache_path,
                                relax=relax)
    return cfg


def cached_config_info(kernel: str, problem: dict[str, Any], *,
                       cache_path: str | None = None,
                       relax: tuple[str, ...] = ()
                       ) -> tuple[dict[str, int], str]:
    """:func:`cached_config` plus where the answer came from: ``"tuned"``
    (exact backend-matched hit), ``"relaxed"`` (nearest tuned entry over
    the relaxed fields), or ``"default"`` (kernel default on a miss).
    The provenance label is what a :class:`~repro.serving.plan.ServingPlan`
    records per resolved knob."""
    path = cache_path or default_cache_path()
    entries = _load(path)["entries"]
    entry = entries.get(cache_key(kernel, problem))
    if entry is not None and entry.get("backend") == jax.default_backend():
        return dict(entry["config"]), "tuned"
    if relax:
        strict = {k: v for k, v in problem.items() if k not in relax}
        prefix = f"{kernel}|"
        best: tuple[float, dict[str, Any]] | None = None
        for key, e in entries.items():
            if not key.startswith(prefix) or \
                    e.get("backend") != jax.default_backend():
                continue
            try:
                p = json.loads(key[len(prefix):])
            except ValueError:      # pragma: no cover - corrupt entry
                continue
            if {k: v for k, v in p.items() if k not in relax} != strict:
                continue
            dist = sum(abs(math.log(max(float(p.get(f, 1)), 1.0))
                           - math.log(max(float(problem.get(f, 1)), 1.0)))
                       for f in relax)
            if best is None or dist < best[0]:
                best = (dist, e)
        if best is not None:
            return dict(best[1]["config"]), "relaxed"
    return dict(KERNELS[kernel].default_config), "default"


# The one registry of relax keys per kernel: which problem fields a
# serving-time readback may differ from the TUNE run's proxy problem in
# (batch/slot count and cache length scale with deployment, tile choices
# don't).  Every cached-config consumer — pool construction
# (serving/plan.py resolve, paged_cache.preferred_*), the layer-dispatch
# sites in models/layers.py, and TUNE's problem derivation — goes through
# :func:`tile_readback` with this table instead of carrying its own copy
# of the relax tuple.
TILE_RELAX: dict[str, tuple[str, ...]] = {
    "flash_decode": ("b", "cache_len"),
    "flash_decode_paged": ("slots", "max_len"),
    "paged_segment": ("slots", "max_len"),
    "flash_prefill_ragged": ("slots", "s", "max_len"),
}


def tile_readback(kernel: str, problem: dict[str, Any], *,
                  cache_path: str | None = None
                  ) -> tuple[dict[str, int], str]:
    """Consolidated autotune-cache readback: ``cached_config`` under the
    kernel's registered :data:`TILE_RELAX` fields, returning
    ``(config, provenance)``.  Pure read — safe on the trace path."""
    return cached_config_info(kernel, problem, cache_path=cache_path,
                              relax=TILE_RELAX.get(kernel, ()))


_RESOLVED: dict[tuple, dict[str, int]] = {}   # per-process get_config memo


def get_config(kernel: str, problem: dict[str, Any],
               **tune_kwargs: Any) -> dict[str, int]:
    """Tuned config for ``problem``; tunes on cache miss.

    After the first call per process the lookup is a pure in-memory dict
    hit — no candidate enumeration, no file IO, no measurement — so
    routing every kernel call through here adds no measurable overhead.
    """
    memoizable = not tune_kwargs.get("force") \
        and "timer" not in tune_kwargs
    memo_key = (kernel, cache_key(kernel, problem),
                tune_kwargs.get("cache_path"),
                tune_kwargs.get("max_trials", 16),
                tune_kwargs.get("vmem_budget", VMEM_BUDGET),
                tune_kwargs.get("iters", 3))
    if memoizable and memo_key in _RESOLVED:
        return _RESOLVED[memo_key]
    cfg = tune(kernel, problem, **tune_kwargs).config
    if memoizable:
        _RESOLVED[memo_key] = cfg
    return cfg


# ------------------------------------------------------- tuned dispatchers
def tuned_flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                          interpret: bool = False,
                          cache_path: str | None = None,
                          **tune_kwargs: Any):
    cfg = get_config(
        "flash_attention",
        flash_attention_problem(q.shape, k.shape, q.dtype,
                                causal=causal, window=window),
        cache_path=cache_path, **tune_kwargs)
    return flash_attention(q, k, v, causal=causal, window=window,
                           interpret=interpret, block_q=cfg["block_q"],
                           block_kv=cfg["block_kv"])


def tuned_flash_decode(q, k, v, mask, *, interpret: bool = False,
                       cache_path: str | None = None,
                       **tune_kwargs: Any):
    cfg = get_config(
        "flash_decode",
        flash_decode_problem(q.shape, k.shape, q.dtype),
        cache_path=cache_path, **tune_kwargs)
    return flash_decode(q, k, v, mask, interpret=interpret,
                        block_kv=cfg["block_kv"])


def tuned_quant_matmul(x, w, *, interpret: bool = False,
                       out_dtype=jnp.float32,
                       cache_path: str | None = None,
                       **tune_kwargs: Any):
    cfg = get_config(
        "quant_matmul",
        quant_matmul_problem(x.shape, w.shape, x.dtype,
                             out_dtype=out_dtype),
        cache_path=cache_path, **tune_kwargs)
    return quant_matmul(x, w, interpret=interpret, out_dtype=out_dtype,
                        block_m=cfg["block_m"], block_n=cfg["block_n"],
                        block_k=cfg["block_k"])


def tuned_block_sparse_matmul(x, w, kindex, *, block: int = 128,
                              interpret: bool = False,
                              cache_path: str | None = None,
                              **tune_kwargs: Any):
    cfg = get_config(
        "block_sparse_matmul",
        block_sparse_matmul_problem(x.shape, w.shape, x.dtype,
                                    max_live=int(kindex.shape[1]),
                                    block=block),
        cache_path=cache_path, **tune_kwargs)
    return block_sparse_matmul(x, w, kindex, block=block,
                               block_m=cfg["block_m"], interpret=interpret)
