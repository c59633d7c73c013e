"""Serving-path benchmark: prefill vs decode tokens/s across decode
execution variants.

Three variants per smoke shape, all generating identical greedy tokens:

- ``loop_jnp``    — the seed path: per-token Python loop, jnp decode
                    attention (one host round-trip + dispatch per token);
- ``scan_jnp``    — the fused path: all decode steps in one
                    ``jax.lax.scan`` dispatch, jnp decode attention;
- ``scan_kernel`` — fused scan + the flash_decode Pallas kernel
                    (interpret mode on CPU; Mosaic on TPU).

Compile/warmup runs before any timed region and prefill is timed apart
from decode (launch/serve.py::timed_generate), so the rows are pure
serving-trajectory numbers.  The shape grid covers the two decode cache
layouts: linear (qwen2 GQA) and sliding-window ring buffer (danube).

Rows land in ``benchmarks/results/serve_bench.json`` with a
``not_slower_than_seed`` verdict per shape: the scan'd flash-decode path
must never lose to the seed Python-loop jnp path.

A second, load-driven suite (``_bench_load``) drives the paged
continuous-batching engine (src/repro/serving/) against the single-stream
scan path under request traffic: one burst row (8 requests arriving at
once — the concurrency acceptance row) and Poisson-arrival rows at rates
below and above the single-stream service capacity.  Each row reports
aggregate decode tokens/s and p50/p95 per-request latency
(completion − arrival).  Two gates: the paged burst row must reach >= 2x
the single-stream aggregate *decode* tokens/s (prefill is excluded from
the ratio — admissions are gated separately by the shared-prefix row
below; wall-clock speedup is reported alongside), and the engine's
greedy tokens must be identical, request by request, to the contiguous
jnp-oracle scan path (kernel-vs-oracle equivalence inside the engine is
pinned separately by tests/test_paged.py).

A third row (``_bench_prefix``) measures the admission path itself: 8
requests sharing a common system-prompt prefix, batched-ragged
prefill + prefix sharing (the default engine) vs the PR-3 serial batch-1
admission path.  It reports prefix hit-rate, pages saved vs an unshared
pool, and summed admission-prefill latency; the batched path must admit
the burst >= 1.5x faster than the serial path (gated), with
request-by-request token equality between the two engines (gated).

Two resource-manager rows exercise the quota-aware preemptive scheduler
(serving/resources.py):

- ``tenants2`` — two tenants on one pool, each budgeted half of it: a
  latency-sensitive tenant (weight 2) receives spaced requests while a
  batch tenant dumps an 8-request burst at t=0.  Gated: the protected
  tenant's p95 latency stays within 1.5x its solo run on the same
  engine (budgets make svc's pages unreachable by the burst, so the
  only interference left is shared segment dispatches), and the svc
  tenant is never preempted.  Per-tenant admitted/preempted/restored/
  pages_swapped counters from ``ResourceManager.stats()`` land in the
  row.
- ``oversubscribed`` — total lifetime page demand exceeds the pool, so
  growth-on-demand must run at least one host-swap preempt/restore
  cycle.  Gated: every request completes, >= 1 preemption actually
  happened, and per-request tokens are bit-identical to an
  unconstrained big-pool run.

A chaos row (``_bench_chaos``, also runnable alone via
``benchmarks/bench_chaos.py`` — the CI chaos smoke) replays the
undersized geometry under a fixed-seed FaultPlan (an injected
allocation failure + a poisoned decode segment) and gates the recovery
layer's contract: every request finishes token-identical to the
fault-free run within a bounded wall-overhead multiple.

Engine rows run with telemetry enabled (serving/observe.py): latency
percentiles come from the engine's own per-request records
(``result()["requests"]``), each row embeds a ``render_summary()``
metrics snapshot, and the chaos row additionally exports a Prometheus
text file + JSONL lifecycle trace of its best faulted run to
``benchmarks/results/chaos_telemetry/`` with every fault fire gated
attributable to a request span.  The cost of enabling telemetry is
itself gated by ``benchmarks/bench_obs.py``.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

try:
    from benchmarks.common import emit, save_json
except ImportError:
    from common import emit, save_json

# (arch, batch, prompt_len, gen): one linear-cache GQA arch, one
# sliding-window ring-buffer arch — the two decode masking regimes.
SERVE_SHAPES = [
    ("qwen2-7b", 2, 32, 16),
    ("h2o-danube-3-4b", 2, 32, 16),
]

VARIANTS = {                      # name -> (scan, kernels)
    "loop_jnp": (False, False),
    "scan_jnp": (True, False),
    "scan_kernel": (True, True),
}
ITERS = 3


def _bench_shape(arch: str, batch: int, prompt_len: int, gen: int) -> dict:
    from repro.configs.registry import get_config
    from repro.data.synthetic import lm_tokens
    from repro.launch.serve import generate, make_serve_fns, timed_generate
    from repro.models.api import build_model

    cfg = get_config(arch, smoke=True)
    prompts = jnp.asarray(lm_tokens(batch * prompt_len, cfg.vocab_size,
                                    seed=1).reshape(batch, prompt_len))
    cache_len = prompt_len + gen + 1
    interpret = jax.default_backend() != "tpu"
    row: dict = {"arch": cfg.name, "batch": batch,
                 "prompt_len": prompt_len, "gen": gen}

    # loop/scan is a call-time choice, so the two jnp variants share one
    # model + jitted fns; params are model-independent given the config
    models = {False: build_model(cfg),
              True: build_model(cfg, use_kernels=True,
                                interpret=interpret)}
    params = models[False].init(jax.random.PRNGKey(0))
    fns = {k: make_serve_fns(m) for k, m in models.items()}

    tokens = {}
    for name, (scan, kernels) in VARIANTS.items():
        model = models[kernels]
        out = generate(model, params, prompts, gen, cache_len,
                       scan=scan, fns=fns[kernels])  # compile (untimed)
        tokens[name] = [list(map(int, r)) for r in out.tolist()]
        best = None
        for _ in range(ITERS):
            _, t = timed_generate(model, params, prompts, gen, cache_len,
                                  scan=scan, fns=fns[kernels])
            best = t if best is None else {
                k: min(best[k], t[k]) for k in t}
        row[name] = {
            "prefill_s": best["prefill_s"],
            "decode_s": best["decode_s"],
            "prefill_tokens_per_s":
                batch * prompt_len / max(best["prefill_s"], 1e-9),
            "decode_tokens_per_s":
                batch * (gen - 1) / max(best["decode_s"], 1e-9),
        }

    # all variants must decode the same greedy tokens — the full (B, gen)
    # grid, not a truncated sample
    row["samples_agree"] = len({tuple(map(tuple, t))
                                for t in tokens.values()}) == 1
    row["sample"] = tokens["scan_kernel"][0][:8]
    base = row["loop_jnp"]["decode_tokens_per_s"]
    for name in ("scan_jnp", "scan_kernel"):
        row[name]["speedup_vs_loop_jnp"] = \
            row[name]["decode_tokens_per_s"] / max(base, 1e-9)
    row["not_slower_than_seed"] = \
        row["scan_kernel"]["decode_tokens_per_s"] >= base
    return row


# ---------------------------------------------------------- load suite
LOAD_ARCH = "qwen2-7b"          # linear cache: the paged-eligible shape
LOAD_PROMPT, LOAD_GEN = 32, 16
LOAD_SLOTS = 8                  # in-flight batch width = the 8-concurrent row
LOAD_BURST = 8                  # requests in the burst (acceptance) row
LOAD_POISSON_N = 10             # requests per Poisson row
POISSON_SEED = 7                # default Poisson-row profile seed (--seed)


def _load_requests(cfg, n, seed):
    # one TrafficProfile expansion — the same entry point the SERVE
    # task's replay scorer uses, so bench rows and searched plans are
    # measured on identical request streams
    from repro.serving.traffic import TrafficProfile
    return TrafficProfile(name=f"load{n}", n_requests=n,
                          prompt_len=LOAD_PROMPT,
                          max_new_tokens=LOAD_GEN,
                          seed=seed).requests(cfg.vocab_size)


def _poisson_profile(tag, rate, seed):
    from repro.serving.traffic import TrafficProfile
    return TrafficProfile(name=f"poisson_{tag}",
                          n_requests=LOAD_POISSON_N, arrival_rate=rate,
                          prompt_len=LOAD_PROMPT,
                          max_new_tokens=LOAD_GEN, seed=seed)


def _single_stream(model, fns, params, reqs):
    """FIFO baseline: one request at a time through the fused contiguous
    scan path (scan_jnp — the best pre-paging serving configuration)."""
    from repro.launch.serve import timed_generate
    cache_len = LOAD_PROMPT + LOAD_GEN + 1
    lat, tokens = [], {}
    decode_s = 0.0
    t0 = time.perf_counter()
    for req in sorted(reqs, key=lambda r: r.arrival):
        now = time.perf_counter() - t0
        if req.arrival > now:
            time.sleep(req.arrival - now)
        out, t = timed_generate(model, params,
                                jnp.asarray(req.prompt[None]), LOAD_GEN,
                                cache_len, scan=True, fns=fns)
        decode_s += t["decode_s"]
        tokens[req.rid] = [int(tk) for tk in np.asarray(out)[0]]
        lat.append((time.perf_counter() - t0) - req.arrival)
    wall = time.perf_counter() - t0
    return {"wall_s": wall, "decode_s": decode_s,
            "tokens_per_s": len(reqs) * LOAD_GEN / max(wall, 1e-9),
            "decode_tokens_per_s":
                len(reqs) * (LOAD_GEN - 1) / max(decode_s, 1e-9),
            "latency_p50_s": float(np.percentile(lat, 50)),
            "latency_p95_s": float(np.percentile(lat, 95))}, tokens


def _fresh_obs():
    """One enabled telemetry store per measured engine run: rows embed a
    render_summary() snapshot (TTFT/queue-wait percentiles, preemption
    and dead-letter counters) scoped to that run alone."""
    from repro.serving import Observability, ObservabilityPolicy
    return Observability.from_policy(ObservabilityPolicy(enabled=True))


def _paged(engine, params, reqs):
    stats = engine.run(reqs, params, obs=_fresh_obs())
    # per-request latency comes from the engine's own telemetry records
    # (result()["requests"]), not recomputed from Request fields
    lat = [rec["e2e_s"] for rec in stats["requests"]
           if rec["e2e_s"] is not None]
    wall = stats["wall_s"]
    return {"wall_s": wall, "decode_s": stats["decode_s"],
            "tokens_per_s": len(reqs) * LOAD_GEN / max(wall, 1e-9),
            "decode_tokens_per_s":
                len(reqs) * (LOAD_GEN - 1) / max(stats["decode_s"], 1e-9),
            "latency_p50_s": float(np.percentile(lat, 50)),
            "latency_p95_s": float(np.percentile(lat, 95)),
            "n_segments": stats["n_segments"],
            "metrics": stats["metrics"]}, \
        {r.rid: list(r.tokens) for r in reqs}


def _bench_load(profile=None, seed: int = POISSON_SEED) -> dict:
    import dataclasses

    from repro.configs.registry import get_config
    from repro.launch.serve import generate, make_serve_fns
    from repro.models.api import build_model
    from repro.serving import PagedCacheConfig, PagedServingEngine
    from repro.serving.engine import warmup
    from repro.serving.paged_cache import (preferred_page_size,
                                           preferred_segment_len)

    cfg = get_config(LOAD_ARCH, smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    fns = make_serve_fns(model)
    cap_tokens = LOAD_PROMPT + LOAD_GEN + 1
    # both serving-schedule knobs read back from the autotuner: the pool
    # granule (flash_decode_paged) and the boundary cadence
    # (paged_segment, whence the growth granule)
    page_size = preferred_page_size(cfg, LOAD_SLOTS, cap_tokens)
    segment_len = preferred_segment_len(cfg, LOAD_SLOTS, cap_tokens)
    blocks = -(-cap_tokens // page_size)
    pcfg = PagedCacheConfig(page_size=page_size,
                            n_pages=LOAD_SLOTS * blocks + 1,
                            max_slots=LOAD_SLOTS, max_blocks=blocks,
                            segment_len=segment_len)
    engine = PagedServingEngine(model, pcfg)

    # compile both paths outside every timed region
    generate(model, params,
             jnp.asarray(_load_requests(cfg, 1, 99)[0].prompt[None]),
             LOAD_GEN, cap_tokens, scan=True, fns=fns)
    warmup(engine, params, LOAD_PROMPT, LOAD_GEN)
    # the batched admission path compiles one dispatch per (row-bucket,
    # suffix-bucket) pair; Poisson arrivals hit boundaries of 1..8
    # admissions, so visit every power-of-two row bucket up front
    for k in (2, 3, LOAD_BURST):
        engine.run(_load_requests(cfg, k, seed=97), params)

    suite = {"arch": cfg.name, "prompt_len": LOAD_PROMPT, "gen": LOAD_GEN,
             "slots": LOAD_SLOTS, "page_size": page_size,
             "segment_len": segment_len, "rows": []}

    # burst row: 8 concurrent requests — the acceptance measurement
    # (best-of-ITERS per path, selected on the gated decode time:
    # single-run timings are noisy on CI)
    base_row = base_tok = paged_row = paged_tok = None
    for _ in range(ITERS):
        b_row, b_tok = _single_stream(
            model, fns, params, _load_requests(cfg, LOAD_BURST, 1))
        if base_row is None or b_row["decode_s"] < base_row["decode_s"]:
            base_row, base_tok = b_row, b_tok
        p_row, p_tok = _paged(
            engine, params, _load_requests(cfg, LOAD_BURST, 1))
        if paged_row is None or p_row["decode_s"] < paged_row["decode_s"]:
            paged_row, paged_tok = p_row, p_tok
    # the gated ratio is *aggregate decode* tokens/s: admission prefill
    # cost differs by design now (the engine batches admissions into one
    # ragged dispatch) and is gated on its own row (_bench_prefix), so it
    # is excluded here to keep this the pure continuous-batching decode
    # quantity; end-to-end wall speedup is reported alongside
    speedup = (paged_row["decode_tokens_per_s"]
               / max(base_row["decode_tokens_per_s"], 1e-9))
    wall_speedup = paged_row["tokens_per_s"] / max(
        base_row["tokens_per_s"], 1e-9)
    tokens_equal = paged_tok == base_tok
    suite["rows"].append({
        "load": f"burst{LOAD_BURST}", "rate_req_s": None,
        "single_stream": base_row, "paged": paged_row,
        "paged_decode_speedup": speedup,
        "paged_wall_speedup": wall_speedup,
        "tokens_equal_oracle": tokens_equal})

    # Poisson rows: rates relative to the measured single-stream service
    # capacity (machine-adaptive, seeded arrival patterns).  An explicit
    # --profile overrides the request mix (count, prefix share, tenants,
    # seed, and — when it sets one — the arrival rate); prompt/gen are
    # pinned to the bench geometry the engine pool was warmed for.
    service_rate = LOAD_BURST / base_row["wall_s"]        # req/s
    for tag, factor in (("underload", 0.75), ("overload", 1.5)):
        rate = factor * service_rate
        if profile is not None:
            prof = dataclasses.replace(
                profile, name=f"{profile.name}_{tag}",
                arrival_rate=profile.arrival_rate or rate,
                prompt_len=LOAD_PROMPT, max_new_tokens=LOAD_GEN)
        else:
            prof = _poisson_profile(tag, rate, seed)
        for name, runner in (("single_stream",
                              lambda rq: _single_stream(model, fns,
                                                        params, rq)),
                             ("paged",
                              lambda rq: _paged(engine, params, rq))):
            reqs = prof.requests(cfg.vocab_size,
                                 page_size=pcfg.page_size)
            row, _ = runner(reqs)
            suite["rows"].append({"load": prof.name,
                                  "rate_req_s": prof.arrival_rate,
                                  "profile": prof.to_dict(),
                                  "path": name, **row})

    suite["verdict"] = {
        "paged_2x_at_8_concurrent": speedup >= 2.0,
        "tokens_equal_oracle": tokens_equal,
    }

    suite["rows"].append(_bench_prefix(cfg, model, params))
    prow = suite["rows"][-1]
    suite["verdict"]["batched_admission_1p5x"] = \
        prow["admission_speedup"] >= 1.5
    suite["verdict"]["prefix_tokens_equal_serial"] = prow["tokens_equal"]

    suite["rows"].append(_bench_tenants(cfg, model, params))
    trow = suite["rows"][-1]
    suite["verdict"]["tenant_p95_isolated"] = trow["p95_isolated"]
    suite["verdict"]["tenant_svc_never_preempted"] = \
        trow["svc_preempted_all_iters"] == 0

    suite["rows"].append(_bench_oversubscribed(cfg, model, params))
    orow = suite["rows"][-1]
    suite["verdict"]["oversubscribed_tokens_equal"] = \
        orow["tokens_equal"] and orow["preemptions"] >= 1 \
        and orow["all_finished"]

    suite["rows"].append(_bench_chaos(cfg, model, params))
    crow = suite["rows"][-1]
    suite["verdict"]["chaos_tokens_equal"] = \
        crow["tokens_equal"] and crow["all_finished"] \
        and crow["faults_fired"] >= 2
    suite["verdict"]["chaos_overhead_bounded"] = \
        crow["chaos_overhead"] <= CHAOS_OVERHEAD_MAX
    return suite


# Resource-manager row geometry.  Tenant row: a 6-slot engine whose pool
# holds four whole lifetimes, split half/half between a weight-2 service
# tenant (spaced requests) and a weight-1 batch tenant (8-burst at t=0).
# Budgets sum to the pool, so neither tenant's growth can even reach the
# other's pages — svc isolation is structural, and the row measures that
# the *scheduling* layer (shared segments + admission dispatches) keeps
# its p95 within 1.5x of a solo run.
TEN_SLOTS = 6
TEN_SVC_N = 3
TEN_BATCH_N = 8


def _bench_tenants(cfg, model, params) -> dict:
    from repro.serving import (PagedCacheConfig, PagedServingEngine,
                               TenantConfig)
    from repro.serving.paged_cache import (preferred_page_size,
                                           preferred_segment_len)

    cap_tokens = LOAD_PROMPT + LOAD_GEN + 1
    page_size = preferred_page_size(cfg, TEN_SLOTS, cap_tokens)
    blocks = -(-cap_tokens // page_size)
    pcfg = PagedCacheConfig(page_size=page_size,
                            n_pages=4 * blocks + 1,
                            max_slots=TEN_SLOTS, max_blocks=blocks,
                            segment_len=preferred_segment_len(
                                cfg, TEN_SLOTS, cap_tokens))
    tenants = [TenantConfig("svc", weight=2.0, page_budget=2 * blocks),
               TenantConfig("batch", weight=1.0, page_budget=2 * blocks)]
    engine = PagedServingEngine(model, pcfg, tenants=tenants)

    def svc_reqs(arrivals):
        reqs = _load_requests(cfg, TEN_SVC_N, seed=3)
        for r, a in zip(reqs, arrivals):
            r.tenant = "svc"
            r.arrival = a
        return reqs

    def batch_reqs():
        reqs = _load_requests(cfg, TEN_BATCH_N, seed=4)
        for r in reqs:
            r.tenant = "batch"
        return reqs

    # warm every dispatch shape first (the calibration below must see
    # steady-state latency, not compile time), then set the svc arrival
    # spacing off a warmed single-request run — the pattern stays
    # identical between the solo and contended runs
    engine.run(svc_reqs([0.0])[:1], params)
    engine.run(svc_reqs([0.0] * TEN_SVC_N) + batch_reqs(), params)
    cal = svc_reqs([0.0])[:1]
    engine.run(cal, params)
    spacing = 1.2 * (cal[0].t_done - cal[0].arrival)
    arrivals = [i * spacing for i in range(TEN_SVC_N)]
    engine.run(svc_reqs(arrivals) + batch_reqs(), params)   # warm burst

    # the SLO gate reads measured end-to-end latency from the engine's
    # telemetry records (result()["requests"]), filtered by tenant —
    # both sides run with telemetry enabled so the ratio is apples-to-
    # apples
    def p95(run_stats, tenant):
        lat = [rec["e2e_s"] for rec in run_stats["requests"]
               if rec["tenant"] == tenant and rec["e2e_s"] is not None]
        return float(np.percentile(lat, 95))

    solo = multi = None
    stats = None
    svc_preempted_any = 0       # summed over ALL contended runs: the
    for _ in range(ITERS):      # isolation gate must not miss a flaky
        s_reqs = svc_reqs(arrivals)     # preemption in a non-best iter
        s_stats = engine.run(s_reqs, params, obs=_fresh_obs())
        solo = min(solo, p95(s_stats, "svc")) if solo is not None \
            else p95(s_stats, "svc")
        m_stats = engine.run(svc_reqs(arrivals) + batch_reqs(), params,
                             obs=_fresh_obs())
        svc_preempted_any += m_stats["tenants"]["svc"]["preempted"]
        cur = p95(m_stats, "svc")
        if multi is None or cur < multi:
            multi, stats = cur, m_stats
    return {
        "load": "tenants2",
        "prompt_len": LOAD_PROMPT, "gen": LOAD_GEN,
        "page_size": page_size, "segment_len": pcfg.segment_len,
        "pool_pages": 4 * blocks,
        "svc_budget_pages": 2 * blocks, "batch_budget_pages": 2 * blocks,
        "svc_arrival_spacing_s": spacing,
        "svc_p95_solo_s": solo,
        "svc_p95_contended_s": multi,
        "svc_p95_ratio": multi / max(solo, 1e-9),
        "p95_isolated": multi <= 1.5 * solo,
        "svc_preempted_all_iters": svc_preempted_any,
        "preemptions": stats["preemptions"],
        "restores": stats["restores"],
        "pages_grown": stats["pages_grown"],
        "tenants": stats["tenants"],
        "metrics": stats["metrics"],
    }


# Oversubscribed row: four requests whose lifetimes need 4 pages each on
# a pool of 4 x 3 — admissions all fit (3 pages under growth-on-demand),
# the lifetimes cannot, so finishing requires at least one preempt/
# restore cycle.  The gate is the resource manager's acceptance
# criterion: bit-identical tokens to the unconstrained run.
OS_N = 4


def _bench_oversubscribed(cfg, model, params) -> dict:
    from repro.serving import PagedCacheConfig, PagedServingEngine
    from repro.serving.paged_cache import (preferred_page_size,
                                           preferred_segment_len)

    cap_tokens = LOAD_PROMPT + LOAD_GEN + 1
    page_size = preferred_page_size(cfg, OS_N, cap_tokens)
    segment_len = preferred_segment_len(cfg, OS_N, cap_tokens)
    blocks = -(-cap_tokens // page_size)
    admit_blocks = -(-min(LOAD_PROMPT + segment_len + 1, cap_tokens)
                     // page_size)
    if admit_blocks >= blocks:       # degenerate geometry: force pressure
        admit_blocks = blocks - 1
    mk_pcfg = lambda pages: PagedCacheConfig(  # noqa: E731
        page_size=page_size, n_pages=pages, max_slots=OS_N,
        max_blocks=blocks, segment_len=segment_len)
    big = PagedServingEngine(model, mk_pcfg(OS_N * blocks + 1))
    small = PagedServingEngine(model,
                               mk_pcfg(OS_N * admit_blocks + 1))
    for eng in (big, small):         # warm every shape, untimed
        eng.run(_load_requests(cfg, OS_N, seed=5), params)

    best_u = best_s = None
    tok_u = tok_s = stats_s = None
    for _ in range(ITERS):
        ru = _load_requests(cfg, OS_N, seed=5)
        su = big.run(ru, params)
        if best_u is None or su["wall_s"] < best_u:
            best_u, tok_u = su["wall_s"], {r.rid: list(r.tokens)
                                           for r in ru}
        rs = _load_requests(cfg, OS_N, seed=5)
        ss = small.run(rs, params)
        if best_s is None or ss["wall_s"] < best_s:
            best_s, tok_s, stats_s = ss["wall_s"], \
                {r.rid: list(r.tokens) for r in rs}, ss
    return {
        "load": "oversubscribed",
        "prompt_len": LOAD_PROMPT, "gen": LOAD_GEN,
        "page_size": page_size, "segment_len": segment_len,
        "pool_pages": OS_N * admit_blocks,
        "lifetime_pages_demand": OS_N * blocks,
        "wall_unconstrained_s": best_u,
        "wall_oversubscribed_s": best_s,
        "swap_overhead": best_s / max(best_u, 1e-9),
        "preemptions": stats_s["preemptions"],
        "restores": stats_s["restores"],
        "pages_swapped_out": stats_s["pages_swapped_out"],
        "pages_swapped_in": stats_s["pages_swapped_in"],
        "n_restore_dispatches": stats_s["n_restore_dispatches"],
        "free_low_water": stats_s["free_low_water"],
        "all_finished": stats_s["n_finished"] == OS_N,
        "tokens_equal": tok_s == tok_u,
        "tenants": stats_s["tenants"],
    }


# Chaos row: the self-healing acceptance measurement.  The same
# undersized geometry as the oversubscribed row runs once fault-free and
# once under a fixed-seed FaultPlan that injects an allocation failure
# and a poisoned decode segment (NaN logits) mid-run.  The gates are the
# recovery layer's contract (serving/recovery.py): every request still
# finishes with tokens bit-identical to the fault-free run, nothing
# dead-letters under the default retry policy, and the wall cost of
# healing (rollback + backoff + restore) stays within a bounded multiple
# of the clean run.
CHAOS_OVERHEAD_MAX = 5.0


def _bench_chaos(cfg, model, params) -> dict:
    from repro.serving import (FaultPlan, PagedCacheConfig,
                               PagedServingEngine, RecoveryPolicy)
    from repro.serving.paged_cache import (preferred_page_size,
                                           preferred_segment_len)

    cap_tokens = LOAD_PROMPT + LOAD_GEN + 1
    page_size = preferred_page_size(cfg, OS_N, cap_tokens)
    segment_len = preferred_segment_len(cfg, OS_N, cap_tokens)
    blocks = -(-cap_tokens // page_size)
    admit_blocks = -(-min(LOAD_PROMPT + segment_len + 1, cap_tokens)
                     // page_size)
    if admit_blocks >= blocks:
        admit_blocks = blocks - 1
    pcfg = PagedCacheConfig(page_size=page_size,
                            n_pages=OS_N * admit_blocks + 1,
                            max_slots=OS_N, max_blocks=blocks,
                            segment_len=segment_len)
    engine = PagedServingEngine(model, pcfg)
    # the boundary invariant audit runs armed in the smoke: CI exercises
    # the checker itself, and anything it flags fails the token gate
    policy = RecoveryPolicy(check_invariants=True)
    # a FaultPlan is stateful (opportunity counters), so each run gets a
    # fresh copy of the same schedule — that IS the reproducibility
    mk_plan = lambda: FaultPlan.at(alloc=1, decode_poison=1)  # noqa: E731
    engine.run(_load_requests(cfg, OS_N, seed=5), params,
               recovery=policy)                               # warm
    engine.run(_load_requests(cfg, OS_N, seed=5), params,
               faults=mk_plan(),
               recovery=policy)         # warm the recovery path shapes

    best_c = best_f = None
    tok_c = tok_f = stats_f = obs_f = None
    for _ in range(ITERS):
        rc = _load_requests(cfg, OS_N, seed=5)
        sc = engine.run(rc, params, recovery=policy, obs=_fresh_obs())
        if best_c is None or sc["wall_s"] < best_c:
            best_c, tok_c = sc["wall_s"], {r.rid: list(r.tokens)
                                           for r in rc}
        rf = _load_requests(cfg, OS_N, seed=5)
        obs = _fresh_obs()
        sf = engine.run(rf, params, faults=mk_plan(), recovery=policy,
                        obs=obs)
        if best_f is None or sf["wall_s"] < best_f:
            best_f, tok_f, stats_f, obs_f = sf["wall_s"], \
                {r.rid: list(r.tokens) for r in rf}, sf, obs
    # the acceptance artifact: Prometheus + JSONL exports of the best
    # faulted run, with every fire attributable to a request span
    import os

    try:
        from benchmarks.common import RESULTS_DIR
    except ImportError:
        from common import RESULTS_DIR
    exports = obs_f.export(os.path.join(RESULTS_DIR, "chaos_telemetry"))
    return {
        "load": "chaos",
        "prompt_len": LOAD_PROMPT, "gen": LOAD_GEN,
        "page_size": page_size, "segment_len": segment_len,
        "pool_pages": OS_N * admit_blocks,
        "check_invariants": True,
        "wall_clean_s": best_c,
        "wall_chaos_s": best_f,
        "chaos_overhead": best_f / max(best_c, 1e-9),
        "faults_fired": len(stats_f["faults"]["fired"]),
        "faults": stats_f["faults"],
        "recovery": stats_f["recovery"],
        "invariant_violations": stats_f["recovery"].get(
            "invariant_violations", []),
        "all_finished": stats_f["n_finished"] == OS_N,
        "dead_lettered": stats_f["n_dead_lettered"],
        "dead_letter_records": stats_f["recovery"].get(
            "dead_letter_records", []),
        "tokens_equal": tok_f == tok_c,
        "metrics": stats_f["metrics"],
        "telemetry": {
            "exports": exports,
            "n_trace_events": len(obs_f.tracer.events),
            "faults_attributed": _faults_attributed(obs_f, stats_f),
        },
    }


def _faults_attributed(obs, stats) -> bool:
    """Every fired engine fault site must show up as a FAULT trace event,
    and every QUARANTINE/DEAD_LETTER must name a request and join back to
    a FAULT at the same site within one boundary (decode faults fire
    inside the segment and surface at its closing boundary)."""
    ev = obs.tracer.events
    fault_keys = {(e.detail["site"], e.boundary) for e in ev
                  if e.kind == "FAULT"}
    fired = {site for site, _ in stats["faults"]["fired"]}
    if not fired <= {s for s, _ in fault_keys}:
        return False
    for e in ev:
        if e.kind in ("QUARANTINE", "DEAD_LETTER"):
            if e.rid is None or not any(
                    (e.detail["site"], b) in fault_keys
                    for b in (e.boundary - 1, e.boundary)):
                return False
    return True


# Cluster row: replicated serving under replica loss.  An 8-request
# shared-prefix burst goes through the FrontDoor of a 3-replica
# ServingCluster three ways: single-engine oracle, fault-free cluster
# (tokens must be bit-identical — routing is invisible), and a chaos
# pass with the loaded replica crashed mid-burst (every request must
# finish bit-identical or dead-letter with a typed ReplicaLost, and no
# surviving replica may leak a page).  Affinity hit-rate is reported:
# the shared prefix should concentrate the burst on the replica that
# admitted it first.
CLUSTER_REPLICAS = 3


def _bench_cluster(cfg, model, params) -> dict:
    from repro.serving import (FaultPlan, PagedCacheConfig,
                               PagedServingEngine, ReplicaLost,
                               ServingCluster)
    from repro.serving.paged_cache import (preferred_page_size,
                                           preferred_segment_len)

    cap_tokens = PREFIX_PROMPT + PREFIX_GEN + 1
    page_size = min(preferred_page_size(cfg, LOAD_SLOTS, cap_tokens),
                    PREFIX_TARGET)
    blocks = -(-cap_tokens // page_size)
    pcfg = PagedCacheConfig(page_size=page_size,
                            n_pages=LOAD_SLOTS * blocks + 1,
                            max_slots=LOAD_SLOTS, max_blocks=blocks,
                            segment_len=preferred_segment_len(
                                cfg, LOAD_SLOTS, cap_tokens),
                            retain_pages=PREFIX_TARGET // page_size)
    engine = PagedServingEngine(model, pcfg)
    # single-engine oracle (also warms every compiled shape the replica
    # runs reuse — replicas multiply run-state, not compilations)
    _, oracle = _prefix_requests(cfg, pcfg, LOAD_BURST, seed=21)
    t0 = time.perf_counter()
    engine.run(oracle, params)
    wall_single = time.perf_counter() - t0
    base = {r.rid: list(r.tokens) for r in oracle}

    # fault-free cluster pass: routing must be invisible in the tokens.
    # Two waves through one cluster: the first lands on cold tries (the
    # whole burst routes before any replica admits, so it spreads
    # least-loaded); the second measures prefix affinity against the
    # retention-pinned tries the first wave warmed.
    cl_clean = ServingCluster(engine, params, n_replicas=CLUSTER_REPLICAS)
    prefix_len, reqs_c = _prefix_requests(cfg, pcfg, LOAD_BURST, seed=21)
    t0 = time.perf_counter()
    out_c = cl_clean.run(reqs_c)
    wall_clean = time.perf_counter() - t0
    fd_cold = dict(out_c["front_door"])
    _, reqs_w = _prefix_requests(cfg, pcfg, LOAD_BURST, seed=21)
    out_c = cl_clean.run(reqs_w)
    fd_warm = out_c["front_door"]
    warm_routed = fd_warm["routed"] - fd_cold["routed"]
    warm_hits = fd_warm["affinity_hits"] - fd_cold["affinity_hits"]
    tokens_equal_single = \
        {r.rid: list(r.tokens) for r in reqs_c} == base \
        and {r.rid: list(r.tokens) for r in reqs_w} == base

    # chaos pass: kill whichever replica the affinity routing loaded, at
    # its round-2 probe (opportunity CLUSTER_REPLICAS = r0 on round 2 —
    # affinity concentrates the shared-prefix burst on r0), mid-burst
    cl = ServingCluster(engine, params, n_replicas=CLUSTER_REPLICAS,
                        faults=FaultPlan.at(
                            replica_crash=CLUSTER_REPLICAS))
    _, reqs_f = _prefix_requests(cfg, pcfg, LOAD_BURST, seed=21)
    t0 = time.perf_counter()
    out_f = cl.run(reqs_f)
    wall_chaos = time.perf_counter() - t0
    chaos_ok = all(
        (list(r.tokens) == base[r.rid]) if r.failure is None
        else isinstance(r.failure, ReplicaLost) for r in reqs_f)
    leaks = []
    for rep in cl.replicas:
        if rep.fenced:
            continue
        s = rep.run.sched.rm.stats()
        if s["free_pages"] + s["pinned_pages"] \
                != pcfg.allocatable_pages \
                or s["held_pages"] != s["pinned_pages"]:
            leaks.append({"replica": rep.name,
                          "free": s["free_pages"],
                          "held": s["held_pages"],
                          "pinned": s["pinned_pages"]})
    return {
        "load": f"cluster{CLUSTER_REPLICAS}",
        "n_replicas": CLUSTER_REPLICAS,
        "burst": LOAD_BURST,
        "prefix_len": prefix_len, "prompt_len": PREFIX_PROMPT,
        "page_size": page_size,
        "wall_single_s": wall_single,
        "wall_clean_s": wall_clean,
        "wall_chaos_s": wall_chaos,
        "tokens_equal_single": tokens_equal_single,
        "clean_finished": out_c["n_finished"],
        "clean_dead_lettered": out_c["n_dead_lettered"],
        "affinity": {**fd_warm,
                     "warm_wave_hits": warm_hits,
                     "warm_wave_routed": warm_routed,
                     "affinity_rate": (warm_hits / warm_routed
                                       if warm_routed else 0.0)},
        "crash_fired": out_f["faults"]["fired"] == [["replica_crash",
                                                     CLUSTER_REPLICAS]],
        "replica_states": {k: v["state"]
                           for k, v in out_f["replicas"].items()},
        "n_migrated": out_f["n_migrated"],
        "n_restarted": out_f["n_restarted"],
        "chaos_finished": out_f["n_finished"],
        "chaos_dead_lettered": out_f["n_dead_lettered"],
        "dead_letter_records": out_f["dead_letter_records"],
        "chaos_ok": chaos_ok,
        "survivor_leaks": leaks,
        "survivors_drained": not leaks,
    }


# Shared-prefix admission row geometry: a system prompt worth several
# pages plus a short distinct user suffix per request — the workload the
# prefix cache exists for.  The prefix is aligned down to whole pages of
# the tuned page size at request-build time.
PREFIX_PROMPT = 56
PREFIX_TARGET = 48              # nominal system-prompt length
PREFIX_GEN = LOAD_GEN


def _prefix_requests(cfg, pcfg, n, seed):
    """``n`` requests sharing a page-aligned common system-prompt prefix
    with distinct user tails."""
    from repro.data.synthetic import lm_tokens
    from repro.serving import Request
    ps = pcfg.page_size
    prefix_len = (PREFIX_TARGET // ps) * ps or min(ps, PREFIX_PROMPT - 8)
    prefix = np.asarray(lm_tokens(prefix_len, cfg.vocab_size,
                                  seed=seed)).astype(np.int32)
    tails = np.asarray(
        lm_tokens(n * (PREFIX_PROMPT - prefix_len), cfg.vocab_size,
                  seed=seed + 1)).reshape(n, -1).astype(np.int32)
    return prefix_len, [
        Request(rid=i,
                prompt=np.concatenate([prefix, tails[i]]),
                max_new_tokens=PREFIX_GEN)
        for i in range(n)]


def _bench_prefix(cfg, model, params) -> dict:
    """Shared-prefix admission row: batched+sharing vs PR-3 serial."""
    from repro.serving import PagedCacheConfig, PagedServingEngine
    from repro.serving.paged_cache import (preferred_page_size,
                                           preferred_segment_len)

    cap_tokens = PREFIX_PROMPT + PREFIX_GEN + 1
    # tuned page size, capped so the pool can express the shared prefix
    # at page granularity (a geometric constraint, not a tuning override)
    page_size = min(preferred_page_size(cfg, LOAD_SLOTS, cap_tokens),
                    PREFIX_TARGET)
    blocks = -(-cap_tokens // page_size)
    pcfg = PagedCacheConfig(page_size=page_size,
                            n_pages=LOAD_SLOTS * blocks + 1,
                            max_slots=LOAD_SLOTS, max_blocks=blocks,
                            segment_len=preferred_segment_len(
                                cfg, LOAD_SLOTS, cap_tokens))
    engines = {
        "serial": PagedServingEngine(model, pcfg, prefill_mode="serial"),
        "batched": PagedServingEngine(model, pcfg,
                                      prefill_mode="batched"),
    }
    prefix_len, _ = _prefix_requests(cfg, pcfg, LOAD_BURST, seed=21)
    # one untimed run per engine visits every prefill shape it will
    # compile (serial: per page count; batched: per suffix bucket)
    for eng in engines.values():
        _, warm = _prefix_requests(cfg, pcfg, LOAD_BURST, seed=21)
        eng.run(warm, params)

    best: dict = {}
    tokens: dict = {}
    for name, eng in engines.items():
        for _ in range(ITERS):
            _, reqs = _prefix_requests(cfg, pcfg, LOAD_BURST, seed=21)
            stats = eng.run(reqs, params, obs=_fresh_obs())
            # summed admission wall: the engine's per-boundary histogram
            adm = stats["metrics"]["histograms"][
                "serving_admission_batch_seconds"]
            stats["prefill_s"] = adm["count"] * adm["mean"]
            if name not in best or stats["prefill_s"] < \
                    best[name]["prefill_s"]:
                best[name] = stats
                tokens[name] = {r.rid: list(r.tokens) for r in reqs}

    b, s = best["batched"], best["serial"]
    unshared_pages = LOAD_BURST * pcfg.pages_for(cap_tokens)
    return {
        "load": f"shared_prefix{LOAD_BURST}",
        "prefix_len": prefix_len,
        "prompt_len": PREFIX_PROMPT,
        "page_size": page_size,
        "admission_prefill_serial_s": s["prefill_s"],
        "admission_prefill_batched_s": b["prefill_s"],
        "admission_speedup": s["prefill_s"] / max(b["prefill_s"], 1e-9),
        "prefill_dispatches_serial": s["n_prefill_dispatches"],
        "prefill_dispatches_batched": b["n_prefill_dispatches"],
        "prefix_hit_rate": (b["prefix_hits"]
                            / max(b["prefix_lookups"], 1)),
        "prefix_tokens_matched": b["prefix_tokens_matched"],
        "pages_allocated": b["pages_allocated_total"],
        "pages_saved": unshared_pages - b["pages_allocated_total"],
        "tokens_equal": tokens["batched"] == tokens["serial"],
    }


def main(argv=None):
    import argparse
    import json

    ap = argparse.ArgumentParser(
        description="serving-path benchmark suite")
    ap.add_argument("--seed", type=int, default=POISSON_SEED,
                    help="profile seed for the Poisson load rows "
                         "(prompts = seed, arrivals = seed + 1)")
    ap.add_argument("--profile", type=str, default=None,
                    help="path to a TrafficProfile JSON "
                         "(serving/traffic.py) overriding the Poisson "
                         "rows' request mix — the SERVE design-flow "
                         "task's stage-2 scorer and CI share this "
                         "entry point")
    # run.py invokes main() programmatically: only a __main__ launch
    # (which passes sys.argv[1:] explicitly) reads the command line
    args = ap.parse_args(argv if argv is not None else [])
    profile = None
    if args.profile:
        from repro.serving.traffic import TrafficProfile
        with open(args.profile) as f:
            profile = TrafficProfile.from_dict(json.load(f))

    results = {"backend": jax.default_backend(), "t": time.time(),
               "shapes": []}
    for arch, batch, prompt_len, gen in SERVE_SHAPES:
        row = _bench_shape(arch, batch, prompt_len, gen)
        results["shapes"].append(row)
        tag = f"serve_{row['arch']}"
        emit(f"{tag}_prefill", row["loop_jnp"]["prefill_s"] * 1e6,
             f"prefill_tok_s="
             f"{row['loop_jnp']['prefill_tokens_per_s']:.1f}")
        for name in VARIANTS:
            v = row[name]
            derived = f"decode_tok_s={v['decode_tokens_per_s']:.1f}"
            if name != "loop_jnp":
                derived += (f";vs_loop_jnp="
                            f"{v['speedup_vs_loop_jnp']:.2f}x")
            emit(f"{tag}_decode_{name}", v["decode_s"] * 1e6, derived)
        emit(f"{tag}_verdict", 0.0,
             f"not_slower_than_seed={int(row['not_slower_than_seed'])};"
             f"samples_agree={int(row['samples_agree'])}")

    load = _bench_load(profile=profile, seed=args.seed)
    results["load"] = load
    for r in load["rows"]:
        if "paged_decode_speedup" in r:
            emit(f"serve_load_{r['load']}_paged",
                 r["paged"]["wall_s"] * 1e6,
                 f"decode_tok_s={r['paged']['decode_tokens_per_s']:.1f};"
                 f"vs_single_stream={r['paged_decode_speedup']:.2f}x;"
                 f"wall={r['paged_wall_speedup']:.2f}x;"
                 f"p95_s={r['paged']['latency_p95_s']:.3f};"
                 f"tokens_equal={int(r['tokens_equal_oracle'])}")
            emit(f"serve_load_{r['load']}_single_stream",
                 r["single_stream"]["wall_s"] * 1e6,
                 f"decode_tok_s="
                 f"{r['single_stream']['decode_tokens_per_s']:.1f};"
                 f"p95_s={r['single_stream']['latency_p95_s']:.3f}")
        elif "admission_speedup" in r:
            emit(f"serve_load_{r['load']}_admission",
                 r["admission_prefill_batched_s"] * 1e6,
                 f"vs_serial={r['admission_speedup']:.2f}x;"
                 f"hit_rate={r['prefix_hit_rate']:.2f};"
                 f"pages_saved={r['pages_saved']};"
                 f"tokens_equal={int(r['tokens_equal'])}")
        elif r["load"] == "tenants2":
            emit("serve_load_tenants2_svc_p95",
                 r["svc_p95_contended_s"] * 1e6,
                 f"vs_solo={r['svc_p95_ratio']:.2f}x;"
                 f"isolated={int(r['p95_isolated'])};"
                 f"svc_preempted={r['tenants']['svc']['preempted']};"
                 f"batch_preempted="
                 f"{r['tenants']['batch']['preempted']};"
                 f"batch_restored={r['tenants']['batch']['restored']}")
        elif r["load"] == "oversubscribed":
            emit("serve_load_oversubscribed",
                 r["wall_oversubscribed_s"] * 1e6,
                 f"overhead={r['swap_overhead']:.2f}x;"
                 f"preemptions={r['preemptions']};"
                 f"pages_swapped={r['pages_swapped_out']};"
                 f"tokens_equal={int(r['tokens_equal'])}")
        elif r["load"] == "chaos":
            # dead letters surface as structured (site, tenant, retries)
            # records, not a bare count — an empty list is the pass state
            dl = ",".join(f"{d['site']}@{d['tenant']}x{d['retries']}"
                          for d in r["dead_letter_records"]) or "none"
            emit("serve_load_chaos", r["wall_chaos_s"] * 1e6,
                 f"overhead={r['chaos_overhead']:.2f}x;"
                 f"faults_fired={r['faults_fired']};"
                 f"quarantines={r['recovery']['quarantines']};"
                 f"dead_letters={dl};"
                 f"tokens_equal={int(r['tokens_equal'])}")
        else:
            emit(f"serve_load_{r['load']}_{r['path']}",
                 r["wall_s"] * 1e6,
                 f"rate={r['rate_req_s']:.2f}req_s;"
                 f"tok_s={r['tokens_per_s']:.1f};"
                 f"p50_s={r['latency_p50_s']:.3f};"
                 f"p95_s={r['latency_p95_s']:.3f}")
    save_json("serve_bench.json", results)
    # the speed verdict gates CI, it is not just an artifact field.
    # samples_agree is reported but not gated: greedy argmax can
    # legitimately flip on float-reduction-order ties between the kernel
    # and the oracle — numerical equivalence is pinned (with tolerances)
    # by tests/test_decode_kernel.py, the right tool for that claim.
    slow = [r["arch"] for r in results["shapes"]
            if not r["not_slower_than_seed"]]
    if slow:
        raise SystemExit(f"serve bench regression on {slow}: the scan'd "
                         f"flash-decode path must never be slower than "
                         f"the seed Python-loop jnp path")
    verdict = load["verdict"]
    if not verdict["tokens_equal_oracle"]:
        # Gated (unlike samples_agree above): the acceptance criterion
        # for the paged engine is token-identical generation, and both
        # sides run the same jnp attention math (the paged path's extra
        # masked slots contribute exact zeros to the softmax sums).  A
        # residual flake mode exists — a near-tie in top-2 logits plus a
        # batch-8-vs-batch-1 reduction-grouping difference could flip one
        # argmax — so if this trips on an unchanged tree, diff the
        # per-request token grids in the JSON artifact before suspecting
        # the engine.
        raise SystemExit("paged engine tokens diverged from the "
                         "contiguous jnp-oracle scan path (see "
                         "benchmarks/results/serve_bench.json load row)")
    if not verdict["paged_2x_at_8_concurrent"]:
        raise SystemExit("continuous-batching paged decode fell below "
                         "2x single-stream aggregate decode tokens/s at "
                         f"{LOAD_BURST} concurrent requests")
    if not verdict["prefix_tokens_equal_serial"]:
        raise SystemExit("shared-prefix engine tokens diverged from the "
                         "serial non-shared admission path (see "
                         "benchmarks/results/serve_bench.json "
                         "shared_prefix row)")
    if not verdict["batched_admission_1p5x"]:
        raise SystemExit("batched ragged admission prefill fell below "
                         "1.5x the serial batch-1 path for the "
                         f"{LOAD_BURST}-request shared-prefix burst")
    if not verdict["oversubscribed_tokens_equal"]:
        raise SystemExit(
            "oversubscribed row failed: requests must all finish with "
            ">= 1 preempt/restore cycle and tokens bit-identical to the "
            "unconstrained run (see serve_bench.json oversubscribed row)")
    if not verdict["chaos_tokens_equal"]:
        raise SystemExit(
            "chaos row failed: with an injected allocation failure and a "
            "poisoned decode segment, every request must still finish "
            "with tokens bit-identical to the fault-free run (see "
            "serve_bench.json chaos row)")
    if not verdict["chaos_overhead_bounded"]:
        raise SystemExit(
            "chaos row failed: self-healing wall overhead exceeded "
            f"{CHAOS_OVERHEAD_MAX}x the fault-free run (see "
            "serve_bench.json chaos row)")
    if not (verdict["tenant_p95_isolated"]
            and verdict["tenant_svc_never_preempted"]):
        raise SystemExit(
            "tenant isolation row failed: the quota-protected tenant's "
            "p95 must stay within 1.5x of its solo run and it must "
            "never be preempted by the bursting tenant (see "
            "serve_bench.json tenants2 row)")
    return results


if __name__ == "__main__":
    import sys

    main(sys.argv[1:])
