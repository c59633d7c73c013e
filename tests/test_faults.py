"""Fault injection + request-level recovery: FaultPlan determinism and
termination, typed allocator errors, RecoveryManager bookkeeping
(quarantine/backoff/dead-letter/shedding/swap integrity/invariants), the
watchdog, and end-to-end chaos runs that must complete bit-identical to
the fault-free baseline."""

import subprocess
import sys

import numpy as np
import jax
import pytest

from repro.serving import (AllocatorError, ContinuousBatchingScheduler,
                           ENGINE_SITES, EngineStalledError, FAULT_SITES,
                           FaultPlan, FaultSpec, InjectedFault,
                           PageAllocator, PagedCacheConfig,
                           RecoveryManager, RecoveryPolicy, Request,
                           RequestFailed, SwapState,
                           diagnostic_snapshot)
from repro.serving.faults import corrupt_image, image_checksum

# a one-page swap image of a head-major pool: (L, pages, KV, page_size, hd)
_IMAGE = (2, 1, 1, 8, 8)


# -------------------------------------------------------------- FaultPlan
class TestFaultPlan:
    def test_replay_is_bit_exact(self):
        """Two plans with identical seed+specs make identical decisions
        over any opportunity sequence — the property chaos CI rests on."""
        mk = lambda: FaultPlan.seeded(7, rate=0.4, max_fires=3)  # noqa
        a, b = mk(), mk()
        sites = [FAULT_SITES[i % len(FAULT_SITES)] for i in range(200)]
        assert [a.should_fire(s) for s in sites] \
            == [b.should_fire(s) for s in sites]
        assert a.log == b.log

    def test_site_streams_are_independent(self):
        """Disarming sites never shifts another site's schedule: a subset
        plan fires the surviving sites at the same opportunities as the
        full plan (this is what makes fault-plan bisection work)."""
        full = FaultPlan.seeded(3, rate=0.3, max_fires=2)
        sub = FaultPlan.seeded(3, sites=("alloc",), rate=0.3, max_fires=2)
        for _ in range(100):
            for site in FAULT_SITES:
                full.should_fire(site)
                sub.should_fire(site)
        assert [e for e in full.log if e[0] == "alloc"] == sub.log

    def test_terminates_at_max_fires(self):
        plan = FaultPlan([FaultSpec(site="alloc", rate=1.0, max_fires=3)])
        fired = sum(plan.should_fire("alloc") for _ in range(50))
        assert fired == 3
        assert plan.total_fires == 3
        assert plan.opportunities["alloc"] == 50

    def test_at_schedules_exact_opportunity(self):
        plan = FaultPlan.at(alloc=2, decode_poison=0)
        hits = [k for k in range(6) if plan.should_fire("alloc")]
        assert hits == [2]
        assert plan.should_fire("decode_poison")
        assert plan.log == [("alloc", 2), ("decode_poison", 0)]

    def test_gate_raises_typed(self):
        plan = FaultPlan.at(dispatch_segment=0)
        with pytest.raises(InjectedFault) as ei:
            plan.gate("dispatch_segment")
        assert ei.value.site == "dispatch_segment"
        assert ei.value.opportunity == 0
        plan.gate("dispatch_segment")        # max_fires spent: no raise

    def test_spec_validation(self):
        with pytest.raises(ValueError):
            FaultSpec(site="nope")
        with pytest.raises(ValueError):
            FaultSpec(site="alloc", rate=1.5)
        with pytest.raises(ValueError):
            FaultSpec(site="alloc", max_fires=0)   # plans must terminate
        with pytest.raises(ValueError):
            FaultPlan([FaultSpec(site="alloc"), FaultSpec(site="alloc")])

    def test_summary_json_safe(self):
        import json
        plan = FaultPlan.at(alloc=0)
        plan.should_fire("alloc")
        s = json.loads(json.dumps(plan.summary()))
        assert s["fired"] == [["alloc", 0]]


# ------------------------------------------------------- image integrity
class TestImageIntegrity:
    def test_checksum_detects_corruption(self):
        rng = np.random.default_rng(0)
        k = rng.normal(size=(2, 3, 4)).astype(np.float32)
        v = rng.normal(size=(2, 3, 4)).astype(np.float32)
        crc = image_checksum(k, v)
        assert image_checksum(k, v) == crc
        bad = corrupt_image(k)
        assert bad.shape == k.shape and bad.dtype == k.dtype
        assert image_checksum(bad, v) != crc


# ------------------------------------------------------- typed allocator
class TestAllocatorErrors:
    def test_misuse_raises_allocator_error(self):
        a = PageAllocator(8)
        with pytest.raises(AllocatorError):
            a.alloc(-1)
        p = a.alloc(2)
        a.release(p)
        with pytest.raises(AllocatorError):
            a.release(p)                       # double free
        with pytest.raises(AllocatorError):
            a.share([p[0]])                    # sharing a free page
        assert issubclass(AllocatorError, ValueError)  # back-compat

    def test_checks_survive_python_O(self):
        """The misuse guards are raises, not asserts: they must fire
        under ``python -O`` too."""
        code = ("from repro.serving.paged_cache import PageAllocator, "
                "AllocatorError\n"
                "a = PageAllocator(4); p = a.alloc(2); a.release(p)\n"
                "try:\n    a.release(p)\nexcept AllocatorError:\n"
                "    raise SystemExit(0)\nraise SystemExit(1)\n")
        r = subprocess.run([sys.executable, "-O", "-c", code],
                           capture_output=True, text=True)
        assert r.returncode == 0, r.stderr

    def test_injected_alloc_failure(self):
        """An armed alloc site makes the pool look dry for exactly the
        scheduled opportunities; the allocator stays consistent."""
        a = PageAllocator(8, faults=FaultPlan.at(alloc=0))
        assert a.alloc(2) is None              # injected
        assert a.alloc_failures == 1
        assert a.alloc(2) == [1, 2]            # plan spent: back to normal
        assert a.n_free == 5


# --------------------------------------------------------- RecoveryManager
def _sched(**kw):
    pcfg = PagedCacheConfig(page_size=8, n_pages=9, max_slots=2,
                            max_blocks=4, segment_len=4)
    return ContinuousBatchingScheduler(pcfg, **kw)


def _req(rid=0, **kw):
    return Request(rid=rid, prompt=np.arange(8, dtype=np.int32),
                   max_new_tokens=4, **kw)


class TestRecoveryManager:
    def test_backoff_is_exponential_and_capped(self):
        rec = RecoveryManager(RecoveryPolicy(backoff_segments=2,
                                             backoff_factor=2.0,
                                             max_backoff_segments=12),
                              _sched())
        req = _req()
        expect = [2, 2, 4, 8, 12, 12]          # capped
        for n, want in enumerate(expect):
            req.n_retries = n
            assert rec.backoff(req) == want

    def test_hold_release_roundtrip_lanes(self):
        """Quarantined requests rejoin through the right lane: restart
        (no image) → pending, verified image → preempted (restore)."""
        sched = _sched()
        rec = RecoveryManager(RecoveryPolicy(backoff_segments=2), sched)
        restart, restore = _req(0), _req(1)
        restore.swap = SwapState(pages=[1], n_tokens=8, slot=0,
                                 host_k=np.zeros(_IMAGE),
                                 host_v=np.zeros(_IMAGE))
        assert rec.hold(restart, "x", boundary=1, now=0.0)
        assert rec.hold(restore, "x", boundary=1, now=0.0)
        assert rec.restarts == 1               # only the image-less one
        assert rec.release_due(2) == 0         # backoff not expired
        assert rec.release_due(3) == 2
        st = sched.rm.state(restart.tenant)
        assert list(st.pending) == [restart]
        assert list(st.preempted) == [restore]
        assert not rec.has_quarantined

    def test_retry_exhaustion_dead_letters(self):
        sched = _sched()
        rec = RecoveryManager(RecoveryPolicy(max_retries=1), sched)
        req = _req()
        assert rec.hold(req, "fault", boundary=1, now=0.0)
        rec._quarantine.clear()
        assert not rec.hold(req, "fault", boundary=2, now=1.0)
        assert isinstance(req.failure, RequestFailed)
        assert req.failure.retries == 2
        assert "retries exhausted" in req.failure.reason
        assert sched.rm.dead_letters == 1
        assert sched.rm.state(req.tenant).dead_lettered == 1
        assert sched.rm.stats()["dead_letters"] == 1

    def test_verify_swaps_converts_bad_images_to_restarts(self):
        sched = _sched()
        rec = RecoveryManager(RecoveryPolicy(), sched)
        good, corrupt, lost = _req(0), _req(1), _req(2)
        for req, (k, v) in ((good, (np.ones(_IMAGE), np.ones(_IMAGE))),
                            (corrupt, (np.ones(_IMAGE), np.ones(_IMAGE))),
                            (lost, (None, None))):
            req.swap = SwapState(pages=[1], n_tokens=8, slot=0,
                                 host_k=k, host_v=v)
            req.tokens = [5]
            sched.rm.state(req.tenant).preempted.append(req)
        good.swap.checksum = image_checksum(good.swap.host_k,
                                            good.swap.host_v)
        corrupt.swap.checksum = image_checksum(corrupt.swap.host_k,
                                               corrupt.swap.host_v)
        corrupt.swap.host_k = corrupt_image(corrupt.swap.host_k)
        assert rec.verify_swaps(boundary=1, now=0.0) == 2
        assert rec.swap_faults_detected == 2
        st = sched.rm.state(good.tenant)
        assert list(st.preempted) == [good]    # verified image kept
        assert good.swap.verified
        # bad images became quarantined restarts: stripped clean
        assert corrupt.swap is None and lost.swap is None
        assert corrupt.tokens == [] and lost.tokens == []
        assert rec.has_quarantined
        # verification happens exactly once per image
        assert rec.verify_swaps(boundary=2, now=0.0) == 0

    def test_shed_stalled_dead_letters_stale_queue(self):
        sched = _sched()
        rec = RecoveryManager(RecoveryPolicy(shed_after_boundaries=3),
                              sched)
        req = _req()
        sched.submit(req)
        for b in range(1, 4):
            assert rec.shed_stalled(boundary=b, now=float(b)) == 0
        assert rec.shed_stalled(boundary=4, now=4.0) == 1
        assert rec.shed == 1
        assert isinstance(req.failure, RequestFailed)
        assert "shed" in req.failure.reason
        assert not sched.has_work

    def test_shedding_disabled_by_default(self):
        sched = _sched()
        rec = RecoveryManager(RecoveryPolicy(), sched)
        sched.submit(_req())
        assert rec.shed_stalled(boundary=10 ** 6, now=0.0) == 0

    def test_invariant_checker_flags_corruption(self):
        sched = _sched()
        rec = RecoveryManager(RecoveryPolicy(check_invariants=True),
                              sched)
        sched.submit(_req())
        sched.plan_growth()
        (req,) = sched.try_admit()
        sched.finish_boundary([req])
        m = sched.pcfg.max_blocks
        bt = np.full((sched.pcfg.max_slots, m), 0, np.int32)
        bt[req.slot, :len(req.pages)] = req.pages
        seq = np.zeros((sched.pcfg.max_slots,), np.int32)
        seq[req.slot] = req.prompt_len
        bad, glob = rec.check_invariants(bt, seq)
        assert bad == [] and glob == []        # healthy state is quiet
        bt[req.slot, 0] += 1                   # corrupt the block table
        bad, _ = rec.check_invariants(bt, seq)
        assert [r.rid for r, _why in bad] == [req.rid]
        assert rec.invariant_violations

    def test_policy_validation(self):
        with pytest.raises(ValueError):
            RecoveryPolicy(max_retries=-1)
        with pytest.raises(ValueError):
            RecoveryPolicy(backoff_factor=0.5)
        with pytest.raises(ValueError):
            RecoveryPolicy(shed_after_boundaries=0)

    def test_diagnostic_snapshot_shape(self):
        import json
        sched = _sched()
        rec = RecoveryManager(RecoveryPolicy(), sched)
        req = _req()
        rec.hold(req, "x", boundary=3, now=0.0)
        snap = diagnostic_snapshot(sched, rec, boundary=3, no_progress=7)
        assert snap["boundary"] == 3 and snap["no_progress"] == 7
        assert snap["quarantined"][0]["rid"] == req.rid
        assert "free_pages" in snap and "queues" in snap
        json.dumps(snap)                       # structured == serializable


# -------------------------------------------------- engine chaos (integration)
_ENG = {}


def _engine():
    if not _ENG:
        from repro.configs.registry import get_config
        from repro.models.api import build_model
        from repro.serving import PagedServingEngine
        cfg = get_config("qwen2_7b", smoke=True)
        model = build_model(cfg)
        pcfg = PagedCacheConfig(page_size=8, n_pages=7, max_slots=2,
                                max_blocks=4, segment_len=4)
        _ENG["x"] = (cfg, model.init(jax.random.PRNGKey(0)),
                     PagedServingEngine(model, pcfg))
    return _ENG["x"]


def _mk_reqs(cfg, n=3):
    from repro.data.synthetic import lm_tokens
    return [Request(rid=i, prompt=np.asarray(
                lm_tokens(16, cfg.vocab_size, seed=40 + i)
            ).astype(np.int32), max_new_tokens=8) for i in range(n)]


def _baseline(cfg, params, eng):
    if "base" not in _ENG:
        reqs = _mk_reqs(cfg)
        eng.run(reqs, params)
        _ENG["base"] = {r.rid: list(r.tokens) for r in reqs}
    return _ENG["base"]


@pytest.mark.parametrize("site", ENGINE_SITES)
def test_engine_recovers_bit_identical(site):
    """A fault injected at every engine-level site in the stack: run()
    never raises, every request completes, and the tokens equal the
    fault-free run.  (Replica-level sites have no opportunities inside a
    single engine run — tests/test_cluster.py covers them.)"""
    cfg, params, eng = _engine()
    base = _baseline(cfg, params, eng)
    reqs = _mk_reqs(cfg)
    out = eng.run(reqs, params, faults=FaultPlan.at(**{site: 0}))
    assert out["n_finished"] == len(reqs)
    assert out["n_dead_lettered"] == 0
    assert {r.rid: list(r.tokens) for r in reqs} == base
    assert out["faults"]["fired"] == [[site, 0]]


def test_engine_seeded_chaos_bit_identical():
    cfg, params, eng = _engine()
    base = _baseline(cfg, params, eng)
    plan = FaultPlan.seeded(0, rate=0.3, max_fires=2)
    reqs = _mk_reqs(cfg)
    out = eng.run(reqs, params, faults=plan)
    assert plan.total_fires > 0                # the chaos actually ran
    assert out["n_finished"] == len(reqs)
    assert {r.rid: list(r.tokens) for r in reqs} == base


def test_engine_dead_letters_on_retry_exhaustion():
    """With zero retries allowed, a faulted request lands dead-lettered
    (typed terminal state, per-tenant accounting) while the healthy
    requests still finish bit-identical."""
    cfg, params, eng = _engine()
    base = _baseline(cfg, params, eng)
    reqs = _mk_reqs(cfg)
    out = eng.run(reqs, params, faults=FaultPlan.at(dispatch_admit=0),
                  recovery=RecoveryPolicy(max_retries=0))
    # a faulted admit fails every request in its dispatch wave (later
    # dispatches may alias its pages), so >= 1 dead-letters here
    dead = [r for r in reqs if r.failure is not None]
    assert dead and out["n_dead_lettered"] == len(dead)
    assert all(isinstance(r.failure, RequestFailed) for r in dead)
    assert out["n_finished"] == len(reqs) - len(dead)
    assert out["recovery"]["dead_lettered"] == len(dead)
    for r in reqs:
        if r.failure is None:
            assert list(r.tokens) == base[r.rid]


def test_engine_multi_tenant_chaos_sweep():
    """Fixed-seed miniature of the hypothesis chaos property
    (tests/test_property.py) that always runs, hypothesis installed or
    not: random fault plans over multi-tenant interleavings terminate
    with every request bit-identical-or-dead-lettered and the pool
    drained (no leaked pages)."""
    from repro.configs.registry import get_config
    from repro.models.api import build_model
    from repro.serving import PagedServingEngine, TenantConfig
    cfg = get_config("qwen2_7b", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    pcfg = PagedCacheConfig(page_size=8, n_pages=7, max_slots=2,
                            max_blocks=4, segment_len=4)
    eng = PagedServingEngine(model, pcfg,
                             tenants=[TenantConfig("a"), TenantConfig("b"),
                                      TenantConfig("c", weight=2.0)])
    cases = [(0, [8, 3, 6], ["a", "b", "c"]),
             (1, [2, 10, 5], ["c", "c", "a"]),
             (2, [7, 7], ["b", "a"])]
    from repro.data.synthetic import lm_tokens
    for fault_seed, gens, tenants in cases:
        prompts = [np.asarray(lm_tokens(16, cfg.vocab_size, seed=40 + i)
                              ).astype(np.int32) for i in range(len(gens))]
        mk = lambda: [Request(rid=i, prompt=prompts[i].copy(),  # noqa
                              max_new_tokens=g, tenant=t)
                      for i, (g, t) in enumerate(zip(gens, tenants))]
        base = mk()
        eng.run(base, params)
        want = {r.rid: r.tokens for r in base}
        chaos = mk()
        plan = FaultPlan.seeded(fault_seed, rate=0.2, max_fires=2)
        out = eng.run(chaos, params, faults=plan)
        for r in chaos:
            if r.failure is not None:
                assert isinstance(r.failure, RequestFailed)
            else:
                assert r.tokens == want[r.rid], \
                    f"rid {r.rid} diverged after faults {plan.log}"
        assert out["n_finished"] + out["n_dead_lettered"] == len(gens)
        assert out["free_pages"] + out["pinned_pages"] \
            == pcfg.allocatable_pages
        assert out["held_pages"] == out["pinned_pages"]


def test_engine_watchdog_raises_typed_with_snapshot():
    """A fault pattern that blocks all progress trips the watchdog: a
    typed EngineStalledError carrying the diagnostic snapshot — the only
    exception that escapes run()."""
    cfg, params, eng = _engine()
    plan = FaultPlan([FaultSpec(site="dispatch_admit", rate=1.0,
                                max_fires=200)])
    policy = RecoveryPolicy(max_retries=200, backoff_segments=0,
                            watchdog_boundaries=5)
    with pytest.raises(EngineStalledError) as ei:
        eng.run(_mk_reqs(cfg, n=1), params, faults=plan, recovery=policy)
    snap = ei.value.snapshot
    assert snap["no_progress"] > 5
    assert "queues" in snap and "recovery" in snap
    assert snap["recovery"]["quarantines"] > 0
