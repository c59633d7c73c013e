"""Durable serving: write-ahead journal framing/rotation/replay, the
wal fault sites, DurabilityPolicy plan wiring, and end-to-end
crash-restart recovery (single engine and cluster) that must finish
bit-identical to an uninterrupted run."""

import dataclasses
import json
import os
import struct

import numpy as np
import jax
import pytest

from repro.serving import (DurabilityPolicy, FaultPlan, JOURNAL_VERSION,
                           JournalError, JournalWriter, PagedCacheConfig,
                           PagedServingEngine, ProcessCrashed,
                           ReplicaLost, Request, RequestFailed,
                           RestartRecovery, ServingCluster, ServingPlan,
                           read_records, replay_journal)
from repro.serving.journal import (_load_image, _save_image)


def _seg_files(d):
    return sorted(f for f in os.listdir(d) if f.startswith("wal-"))


def _mk_req(rid, prompt_len=4, gen=5, tokens=()):
    req = Request(rid=rid,
                  prompt=np.arange(prompt_len, dtype=np.int32),
                  max_new_tokens=gen)
    req.tokens = list(tokens)
    return req


# ------------------------------------------------------------- framing
class TestFraming:
    def test_lifecycle_round_trip(self, tmp_path):
        d = str(tmp_path)
        w = JournalWriter(d)
        req = _mk_req(1)
        w.submit(req)
        req.tokens = [7, 8]
        w.admit(req, restore=False)
        w.checkpoint(1, [req])
        req.tokens = [7, 8, 9, 10, 11]
        w.complete(req)
        w.close()
        rp = replay_journal(d)
        assert not rp.truncated
        r = rp.requests[1]
        assert r.status == "completed"
        assert r.tokens == [7, 8, 9, 10, 11]
        assert r.prompt == [0, 1, 2, 3]
        assert r.max_new_tokens == 5

    def test_segment_rotation(self, tmp_path):
        d = str(tmp_path)
        w = JournalWriter(d, segment_bytes=256)
        for i in range(30):
            w.submit(_mk_req(i, prompt_len=8))
        w.close()
        assert len(_seg_files(d)) > 1
        rp = replay_journal(d)
        assert not rp.truncated
        assert sorted(rp.requests) == list(range(30))
        # records never split across segments: the whole dir parses clean
        recs, torn = read_records(d)
        assert len(recs) == 30 and not torn

    def test_torn_tail_dropped_not_fatal(self, tmp_path):
        d = str(tmp_path)
        w = JournalWriter(d)
        for i in range(3):
            w.submit(_mk_req(i))
        w.close()
        seg = os.path.join(d, _seg_files(d)[-1])
        with open(seg, "ab") as f:
            f.write(b"\x40\x00\x00\x00\xde\xad\xbe\xefhalf a rec")
        rp = replay_journal(d)
        assert rp.truncated
        assert sorted(rp.requests) == [0, 1, 2]

    def test_crc_corrupt_tail_dropped(self, tmp_path):
        d = str(tmp_path)
        w = JournalWriter(d)
        for i in range(3):
            w.submit(_mk_req(i))
        w.close()
        seg = os.path.join(d, _seg_files(d)[-1])
        data = bytearray(open(seg, "rb").read())
        data[-2] ^= 0xFF                # flip a byte in the last payload
        open(seg, "wb").write(bytes(data))
        rp = replay_journal(d)
        assert rp.truncated
        assert sorted(rp.requests) == [0, 1]

    def test_mid_journal_corruption_is_conservative_prefix(self, tmp_path):
        """Corruption in an EARLIER segment drops everything after it —
        resyncing past a bad frame could interleave crash states."""
        d = str(tmp_path)
        w = JournalWriter(d, segment_bytes=256)
        for i in range(30):
            w.submit(_mk_req(i, prompt_len=8))
        w.close()
        segs = _seg_files(d)
        assert len(segs) >= 3
        first = os.path.join(d, segs[0])
        data = bytearray(open(first, "rb").read())
        data[len(data) // 2] ^= 0xFF
        open(first, "wb").write(bytes(data))
        rp = replay_journal(d)
        assert rp.truncated
        # a strict prefix of request 0..k survives, nothing after
        rids = sorted(rp.requests)
        assert rids == list(range(len(rids)))
        assert len(rids) < 30

    def test_reopen_repairs_torn_tail_and_appends(self, tmp_path):
        d = str(tmp_path)
        w = JournalWriter(d)
        w.submit(_mk_req(0))
        w.close()
        seg = os.path.join(d, _seg_files(d)[-1])
        with open(seg, "ab") as f:
            f.write(b"\x10\x00\x00\x00torn")
        w2 = JournalWriter(d)
        w2.submit(_mk_req(1))
        w2.close()
        rp = replay_journal(d)
        assert not rp.truncated         # the tail was truncated away
        assert sorted(rp.requests) == [0, 1]

    def test_unknown_type_and_future_version_skipped(self, tmp_path):
        d = str(tmp_path)
        w = JournalWriter(d)
        w.submit(_mk_req(0))
        w.append("FROM_THE_FUTURE", {"rid": 99}, flush=True)
        w.close()
        frame = json.dumps({"v": JOURNAL_VERSION + 1, "t": "SUBMIT",
                            "rid": 98}).encode()
        import zlib
        with open(os.path.join(d, _seg_files(d)[-1]), "ab") as f:
            f.write(struct.pack("<II", len(frame), zlib.crc32(frame))
                    + frame)
        rp = replay_journal(d)
        assert sorted(rp.requests) == [0]
        assert rp.n_skipped == 2

    def test_closed_writer_raises(self, tmp_path):
        w = JournalWriter(str(tmp_path))
        w.close()
        with pytest.raises(JournalError):
            w.submit(_mk_req(0))

    def test_crash_drops_unflushed_buffer(self, tmp_path):
        d = str(tmp_path)
        w = JournalWriter(d, fsync_boundaries=100)
        w.submit(_mk_req(0))            # terminal: flushed immediately
        w.checkpoint(1, [_mk_req(0, tokens=[1])])   # buffered
        w.crash()
        rp = replay_journal(d)
        assert rp.requests[0].status == "submitted"
        assert rp.requests[0].n_tokens == 0


# ----------------------------------------------------------- wal faults
class TestWalFaults:
    def test_wal_torn_write(self, tmp_path):
        """The fired record lands truncated, everything before it whole,
        nothing after it at all — and replay degrades to the prefix."""
        d = str(tmp_path)
        fp = FaultPlan.at(wal_torn_write=2)
        w = JournalWriter(d, faults=fp)
        for i in range(5):
            w.submit(_mk_req(i))
        w.close()
        assert fp.fires["wal_torn_write"] == 1
        rp = replay_journal(d)
        assert rp.truncated
        assert sorted(rp.requests) == [0, 1]

    def test_wal_lost_fsync_is_a_hole_not_a_prefix(self, tmp_path):
        """A dropped fsync batch loses its records while later batches
        still land: framing stays intact, the records are just gone."""
        d = str(tmp_path)
        fp = FaultPlan.at(wal_lost_fsync=1)
        w = JournalWriter(d, faults=fp)
        for i in range(4):
            w.submit(_mk_req(i))        # each submit is its own flush
        w.close()
        assert fp.fires["wal_lost_fsync"] == 1
        rp = replay_journal(d)
        assert not rp.truncated
        assert sorted(rp.requests) == [0, 2, 3]


# ------------------------------------------------------- replay machine
class TestReplayStateMachine:
    def test_admit_resets_fresh_but_not_restore(self, tmp_path):
        d = str(tmp_path)
        w = JournalWriter(d)
        req = _mk_req(1, tokens=[5, 6])
        w.submit(req)
        w.admit(req, restore=False)
        w.checkpoint(1, [req])
        w.admit(req, restore=False)     # fresh re-admission: reset
        w.close()
        assert replay_journal(d).requests[1].n_tokens == 0
        w2 = JournalWriter(str(tmp_path / "b"))
        w2.submit(req)
        w2.admit(req, restore=False)
        w2.checkpoint(1, [req])
        w2.admit(req, restore=True)     # restore: progress survives
        w2.close()
        assert replay_journal(str(tmp_path / "b")).requests[1].n_tokens \
            == 2

    def test_dead_letter_round_trip(self, tmp_path):
        d = str(tmp_path)
        w = JournalWriter(d)
        rec = RequestFailed(rid=3, tenant="t", reason="boom", boundary=7,
                            retries=4, site="alloc", ckpt_tokens=2)
        w.dead_letter(rec.record())
        lost = ReplicaLost(rid=4, tenant="t", reason="gone", boundary=8,
                           retries=1, site="replica_crash",
                           ckpt_tokens=0, replica="r1")
        w.dead_letter(lost.record())
        w.close()
        rr = RestartRecovery(d)
        f3 = rr._failure(rr.replay.requests[3].failure)
        f4 = rr._failure(rr.replay.requests[4].failure)
        assert f3 == rec
        assert isinstance(f4, ReplicaLost) and f4 == lost

    def test_replay_is_idempotent(self, tmp_path):
        d = str(tmp_path)
        w = JournalWriter(d)
        req = _mk_req(1, tokens=[5])
        w.submit(req)
        w.admit(req, restore=False)
        w.checkpoint(1, [req])
        w.close()
        assert replay_journal(d).state() == replay_journal(d).state()

    def test_cluster_merge_prefers_terminal(self, tmp_path):
        """The same rid running in one replica stream and completed in
        another (post-migration) merges to completed, with the SUBMIT
        meta grafted across streams."""
        d = str(tmp_path)
        req = _mk_req(1, tokens=[9, 9])
        w0 = JournalWriter(os.path.join(d, "r0"))
        w0.submit(req)
        w0.admit(req, restore=False)
        w0.checkpoint(1, [req])
        w0.close()
        w1 = JournalWriter(os.path.join(d, "r1"))
        w1.admit(req, restore=True)     # migrated: no SUBMIT here
        w1.complete(req)
        w1.close()
        rp = replay_journal(d)
        r = rp.requests[1]
        assert r.status == "completed"
        assert r.tokens == [9, 9]
        assert r.prompt == [0, 1, 2, 3]     # grafted from r0's SUBMIT

    def test_image_save_load_round_trip_bfloat16(self, tmp_path):
        import ml_dtypes
        from repro.configs.registry import get_config
        from repro.serving.paged_cache import pool_shape, read_pages
        path = str(tmp_path / "img-00000000.npz")
        # a swap image as the engine takes it: two pages of a head-major
        # (L, P, KV, page_size, hd) pool
        shape = pool_shape(get_config("qwen2_7b", smoke=True),
                           PagedCacheConfig(page_size=4, n_pages=5))
        pool = np.arange(np.prod(shape), dtype=np.float32).reshape(shape) \
            .astype(ml_dtypes.bfloat16)
        k = np.asarray(read_pages(pool, np.array([3, 1])))
        assert k.shape == (shape[0], 2) + shape[2:]
        v = -k
        _save_image(path, k, v)
        k2, v2 = _load_image(path)
        assert k2.dtype == k.dtype and k2.shape == k.shape
        assert bytes(k2.tobytes()) == bytes(k.tobytes())
        assert bytes(v2.tobytes()) == bytes(v.tobytes())


# --------------------------------------------------- DurabilityPolicy
class TestDurabilityPolicy:
    def test_validation(self, tmp_path):
        with pytest.raises(ValueError):
            DurabilityPolicy(enabled=True)          # no journal_dir
        with pytest.raises(ValueError):
            DurabilityPolicy(fsync_boundaries=0)
        with pytest.raises(ValueError):
            DurabilityPolicy(segment_bytes=16)
        DurabilityPolicy(enabled=True, journal_dir=str(tmp_path))

    def test_plan_round_trip_and_provenance(self, tmp_path):
        pol = DurabilityPolicy(enabled=True, journal_dir=str(tmp_path),
                               fsync_boundaries=4, segment_bytes=4096)
        plan = ServingPlan(durability=pol)
        back = ServingPlan.from_dict(json.loads(
            json.dumps(plan.to_dict())))
        assert back.durability == pol
        # unknown durability keys dropped, missing defaulted
        d = plan.to_dict()
        d["durability"]["flux_capacitor"] = 1
        del d["durability"]["segment_bytes"]
        back2 = ServingPlan.from_dict(d)
        assert back2.durability.segment_bytes \
            == DurabilityPolicy().segment_bytes
        assert ServingPlan().durability == DurabilityPolicy()

    def test_resolve_records_provenance(self, tmp_path):
        from repro.configs.registry import get_config
        cfg = get_config("qwen2_7b", smoke=True)
        p1 = ServingPlan.resolve(cfg, slots=2, max_prompt_len=16,
                                 max_new_tokens=8)
        assert p1.provenance["durability"] == "default"
        pol = DurabilityPolicy(enabled=True, journal_dir=str(tmp_path))
        p2 = ServingPlan.resolve(cfg, slots=2, max_prompt_len=16,
                                 max_new_tokens=8, durability=pol)
        assert p2.provenance["durability"] == "explicit"
        assert p2.durability == pol


# ------------------------------------------------------- end to end
_E2E = {}       # compile cache: one model, engines per pool geometry


def _engine(n_pages=8, durability=None):
    if "model" not in _E2E:
        from repro.configs.registry import get_config
        from repro.models.api import build_model
        cfg = get_config("qwen2_7b", smoke=True)
        model = build_model(cfg)
        _E2E["model"] = (cfg, model, model.init(jax.random.PRNGKey(0)))
    cfg, model, params = _E2E["model"]
    key = n_pages
    if key not in _E2E:
        pcfg = PagedCacheConfig(page_size=8, n_pages=n_pages,
                                max_slots=2, max_blocks=5, segment_len=4)
        _E2E[key] = PagedServingEngine(model, pcfg)
    eng = _E2E[key]
    if durability is not None:
        plan = dataclasses.replace(eng.plan, durability=durability)
        # share the compiled entry points: from_plan only re-reads plan
        # geometry, which is identical here
        eng = PagedServingEngine.from_plan(model, plan)
        eng._prefill = _E2E[key]._prefill
        eng._write_pages = _E2E[key]._write_pages
        eng._admit_batch = _E2E[key]._admit_batch
        eng._segment = _E2E[key]._segment
    return cfg, model, params, eng


def _burst(cfg, n=3, gen=24):
    rng = np.random.default_rng(0)
    return [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size, size=12)
                    .astype(np.int32), max_new_tokens=gen)
            for i in range(n)]


def _oracle():
    if "oracle" not in _E2E:
        cfg, _, params, eng = _engine()
        reqs = _burst(cfg)
        eng.run(reqs, params)
        _E2E["oracle"] = {r.rid: list(r.tokens) for r in reqs}
    return _E2E["oracle"]


class TestCrashRestart:
    def test_fault_free_journaled_run_replays_completed(self, tmp_path):
        d = str(tmp_path)
        pol = DurabilityPolicy(enabled=True, journal_dir=d)
        cfg, _, params, eng = _engine(durability=pol)
        reqs = _burst(cfg)
        stats = eng.run(reqs, params)
        assert stats["journal"]["n_appended"] > 0
        rp = replay_journal(d)
        assert not rp.truncated
        assert all(r.status == "completed"
                   for r in rp.requests.values())
        assert {rid: r.tokens for rid, r in rp.requests.items()} \
            == _oracle()
        assert not [f for f in os.listdir(d) if f.startswith("img-")]

    def test_crash_restart_bit_identical(self, tmp_path):
        """kill at a mid-burst boundary (preemptions in flight), cold
        restart from plan.json + journal: every request finishes with
        exactly the oracle's tokens, no images leak, and a second replay
        shows every request terminal."""
        d = str(tmp_path)
        pol = DurabilityPolicy(enabled=True, journal_dir=d)
        cfg, model, params, eng = _engine(durability=pol)
        with pytest.raises(ProcessCrashed):
            eng.run(_burst(cfg), params,
                    faults=FaultPlan.at(process_crash=5))
        rr = RestartRecovery(d)
        out = rr.resume(model, params, engine=_engine()[3])
        got = {r.rid: list(r.tokens) for r in out["requests"]
               if r.failure is None}
        assert got == _oracle()
        assert not [f for f in os.listdir(d) if f.startswith("img-")]
        rp = replay_journal(d)
        assert all(r.status in ("completed", "dead")
                   for r in rp.requests.values())

    def test_truncated_tail_degrades_to_restart(self, tmp_path):
        """Chop bytes off the post-crash journal tail: replay drops the
        damage and recovery still finishes bit-identical (the lost
        records were progress markers, not acknowledgements... unless a
        SUBMIT is lost, in which case the request was never acked and is
        legitimately absent)."""
        d = str(tmp_path)
        pol = DurabilityPolicy(enabled=True, journal_dir=d)
        cfg, model, params, eng = _engine(durability=pol)
        with pytest.raises(ProcessCrashed):
            eng.run(_burst(cfg), params,
                    faults=FaultPlan.at(process_crash=5))
        seg = sorted(f for f in os.listdir(d)
                     if f.startswith("wal-"))[-1]
        path = os.path.join(d, seg)
        with open(path, "r+b") as f:
            f.truncate(max(0, os.path.getsize(path) - 7))
        rr = RestartRecovery(d)
        acked = set(rr.replay.requests)
        out = rr.resume(model, params, engine=_engine()[3])
        got = {r.rid: list(r.tokens) for r in out["requests"]
               if r.failure is None}
        oracle = _oracle()
        assert got == {rid: oracle[rid] for rid in acked}

    def test_resume_journals_into_same_dir(self, tmp_path):
        """A crash DURING recovery recovers too: the resumed run appends
        to the same journal, so a second replay sees the completions."""
        d = str(tmp_path)
        pol = DurabilityPolicy(enabled=True, journal_dir=d)
        cfg, model, params, eng = _engine(durability=pol)
        with pytest.raises(ProcessCrashed):
            eng.run(_burst(cfg), params,
                    faults=FaultPlan.at(process_crash=3))
        n_before = replay_journal(d).n_records
        RestartRecovery(d).resume(model, params, engine=_engine()[3])
        rp = replay_journal(d)
        assert rp.n_records > n_before
        out2 = RestartRecovery(d).resume(model, params,
                                         engine=_engine()[3])
        c = out2["recovered"]
        assert c["replayed_completed"] + c["replayed_dead"] \
            == len(rp.requests)

    def test_cluster_crash_restart_bit_identical(self, tmp_path):
        d = str(tmp_path)
        cfg, model, params, eng = _engine()
        oracle_reqs = _burst(cfg, n=5)
        cl0 = ServingCluster(eng, params, n_replicas=2)
        cl0.run(oracle_reqs)
        oracle = {r.rid: list(r.tokens) for r in oracle_reqs}
        pol = DurabilityPolicy(enabled=True, journal_dir=d)
        deng = _engine(durability=pol)[3]
        cl = ServingCluster(deng, params, n_replicas=2,
                            faults=FaultPlan.at(process_crash=4))
        with pytest.raises(ProcessCrashed):
            cl.run(_burst(cfg, n=5))
        assert os.path.isdir(os.path.join(d, "r0"))
        out = RestartRecovery(d).resume(model, params)
        got = {r.rid: list(r.tokens) for r in out["requests"]
               if r.failure is None}
        assert got == oracle
