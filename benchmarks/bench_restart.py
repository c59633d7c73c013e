"""Crash-restart smoke suite: the durable-serving gate, standalone.

The only chaos gate that kills a real OS process.  A child interpreter
deploys a durable :class:`~repro.serving.plan.ServingPlan` (journal at
``benchmarks/results/restart_journal``), serves a preempting burst, and
dies mid-flight on a seeded ``process_crash`` (``os._exit(137)`` — no
atexit, no flushes beyond what the journal already fsync'd).  The
parent then does what an operator would: cold
:class:`~repro.serving.journal.RestartRecovery` from nothing but the
journal directory (plan JSON + WAL + spilled swap images), and gates

- the child actually died by injected crash (exit 137), leaving a
  parseable journal behind;
- recovery finishes EVERY journal-acknowledged request with tokens
  bit-identical to an uninterrupted oracle run, or as a typed dead
  letter (none expected under the default retry policy);
- the rebuilt engine's pool drains (free + pinned == allocatable) and
  no spilled swap image outlives recovery;
- a second replay of the post-recovery journal shows every request
  terminal — the journal converges, it doesn't grow open ends;
- a torn-tail variant (bytes chopped off the last segment of a copy of
  the crashed journal) degrades to restart-from-checkpoint and still
  recovers bit-identically — tail damage is a legal crash state, never
  a replay failure.

The post-crash journal directory is preserved verbatim for the CI
artifact; recovery runs against copies.  Results land in
``benchmarks/results/restart_bench.json``.
"""

from __future__ import annotations

import os
import shutil
import subprocess
import sys
import tempfile
import time

import numpy as np

try:
    from benchmarks.bench_serve import LOAD_ARCH
    from benchmarks.common import RESULTS_DIR, emit, save_json
except ImportError:
    from bench_serve import LOAD_ARCH
    from common import RESULTS_DIR, emit, save_json

JOURNAL_DIR = os.path.join(RESULTS_DIR, "restart_journal")
CRASH_BOUNDARY = 5      # mid-burst: admissions done, preemptions live
N_REQUESTS = 4
PROMPT_LEN = 12
GEN = 24


def _plan(journal_dir: str):
    """A deliberately undersized pool (2 slots, 8 pages for 4 requests'
    lifetimes) so the crash lands with preempted requests' swap images
    spilled beside the journal — the hardest recovery lane."""
    from repro.serving import (DurabilityPolicy, PagedCacheConfig,
                               ServingPlan)
    return ServingPlan(
        arch=LOAD_ARCH,
        cache=PagedCacheConfig(page_size=8, n_pages=8, max_slots=2,
                               max_blocks=5, segment_len=4),
        max_prompt_len=PROMPT_LEN, max_new_tokens=GEN,
        durability=DurabilityPolicy(enabled=True,
                                    journal_dir=journal_dir))


def _model():
    import jax
    from repro.configs.registry import get_config
    from repro.models.api import build_model
    cfg = get_config(LOAD_ARCH, smoke=True)
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def _requests(cfg):
    rng = np.random.default_rng(0)
    from repro.serving import Request
    return [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        size=PROMPT_LEN).astype(np.int32),
                    max_new_tokens=GEN)
            for i in range(N_REQUESTS)]


def _child(journal_dir: str) -> None:
    """The process that dies: serve the burst under a seeded crash."""
    from repro.serving import (FaultPlan, PagedServingEngine,
                               ProcessCrashed)
    cfg, model, params = _model()
    engine = PagedServingEngine.from_plan(model, _plan(journal_dir))
    try:
        engine.run(_requests(cfg), params,
                   faults=FaultPlan.at(process_crash=CRASH_BOUNDARY))
    except ProcessCrashed:
        os._exit(137)                   # kill -9 semantics: no cleanup
    os._exit(3)                         # crash never fired: gate failure


def _recover(journal_dir: str, model, params, *, engine=None) -> dict:
    from repro.serving import RestartRecovery
    t0 = time.perf_counter()
    rr = RestartRecovery(journal_dir)
    out = rr.resume(model, params, engine=engine)
    out["wall_s"] = time.perf_counter() - t0
    out["acked"] = sorted(rr.replay.requests, key=str)
    return out


def _gate_recovery(tag: str, out: dict, oracle: dict,
                   journal_dir: str, allocatable: int) -> dict:
    """The bit-identical-or-typed-dead-letter contract + leak audit."""
    from repro.serving import RequestFailed, replay_journal
    got = {r.rid: r for r in out["requests"]}
    if sorted(got, key=str) != out["acked"]:
        raise SystemExit(
            f"restart smoke [{tag}]: recovery returned rids "
            f"{sorted(got, key=str)} != journal-acknowledged "
            f"{out['acked']}")
    dead, mismatched = [], []
    for rid, r in got.items():
        if r.failure is not None:
            if not isinstance(r.failure, RequestFailed):
                raise SystemExit(
                    f"restart smoke [{tag}]: rid {rid} failed without "
                    f"a typed record: {r.failure!r}")
            dead.append(rid)
        elif r.tokens != oracle[rid]:
            mismatched.append(rid)
    if mismatched:
        raise SystemExit(
            f"restart smoke [{tag}]: rids {mismatched} finished with "
            "tokens diverging from the uninterrupted oracle run — "
            "crash-restart recovery must be bit-identical (see "
            "benchmarks/results/restart_bench.json)")
    if dead:
        raise SystemExit(
            f"restart smoke [{tag}]: rids {dead} dead-lettered; the "
            "default retry policy must absorb one process crash")
    s = out["stats"]
    if s["free_pages"] + s["pinned_pages"] != allocatable:
        raise SystemExit(
            f"restart smoke [{tag}]: leaked pages after recovery — "
            f"free={s['free_pages']} pinned={s['pinned_pages']} "
            f"allocatable={allocatable}")
    orphans = [f for f in os.listdir(journal_dir)
               if f.startswith("img-")]
    if orphans:
        raise SystemExit(
            f"restart smoke [{tag}]: spilled swap images outlived "
            f"recovery: {orphans}")
    rp = replay_journal(journal_dir)
    open_ends = [str(rid) for rid, r in rp.requests.items()
                 if r.status not in ("completed", "dead")]
    if open_ends:
        raise SystemExit(
            f"restart smoke [{tag}]: post-recovery journal replay "
            f"leaves rids {open_ends} non-terminal")
    return {"acked": [str(a) for a in out["acked"]],
            "recovered": out["recovered"], "wall_s": out["wall_s"],
            "journal": s.get("journal", {})}


def main():
    import jax
    from repro.serving import PagedServingEngine, replay_journal

    # ---- the crash first: a child interpreter dies mid-burst --------
    # It runs to its end before this process initialises any backend:
    # one process at a time may hold an accelerator, so parent and child
    # must never both have one.
    if os.path.isdir(JOURNAL_DIR):
        shutil.rmtree(JOURNAL_DIR)
    src_root = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "src")
    env = dict(os.environ)
    env["PYTHONPATH"] = src_root + os.pathsep + env.get("PYTHONPATH", "")
    t0 = time.perf_counter()
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child",
         JOURNAL_DIR], env=env, capture_output=True, text=True)
    child_wall = time.perf_counter() - t0
    if proc.returncode != 137:
        raise SystemExit(
            f"restart smoke: child exited {proc.returncode}, expected "
            f"137 (injected process_crash at boundary {CRASH_BOUNDARY})"
            f"\n--- child stderr ---\n{proc.stderr[-2000:]}")
    crashed = replay_journal(JOURNAL_DIR)
    if not crashed.requests:
        raise SystemExit("restart smoke: the crashed child left an "
                         "empty journal — nothing was acknowledged")
    if crashed.plan is None:
        raise SystemExit("restart smoke: no serving_plan.json beside "
                         "the crashed journal")

    # ---- oracle: the uninterrupted run (durability off) -------------
    cfg, model, params = _model()
    plan = _plan(JOURNAL_DIR)
    import dataclasses
    from repro.serving import DurabilityPolicy
    engine = PagedServingEngine.from_plan(
        model, dataclasses.replace(plan, durability=DurabilityPolicy()))
    oracle_reqs = _requests(cfg)
    oracle_stats = engine.run(oracle_reqs, params)
    oracle = {r.rid: list(r.tokens) for r in oracle_reqs}
    if oracle_stats["preemptions"] < 1:
        raise SystemExit("restart smoke: the burst must preempt so the "
                         "crash leaves spilled swap images to recover")

    # ---- recovery gates run on copies; JOURNAL_DIR stays the -------
    # ---- pristine post-crash state for the CI artifact -------------
    allocatable = plan.cache.allocatable_pages
    rows = {}
    with tempfile.TemporaryDirectory() as tmp:
        # cold restart: nothing but the journal directory (plan JSON
        # decides the engine — the operator path)
        cold = os.path.join(tmp, "cold")
        shutil.copytree(JOURNAL_DIR, cold)
        rows["cold"] = _gate_recovery(
            "cold", _recover(cold, model, params), oracle, cold,
            allocatable)
        # torn tail: chop bytes off the last WAL segment of another
        # copy — must degrade to restart-from-checkpoint, not fail
        torn = os.path.join(tmp, "torn")
        shutil.copytree(JOURNAL_DIR, torn)
        segs = sorted(f for f in os.listdir(torn)
                      if f.startswith("wal-"))
        last = os.path.join(torn, segs[-1])
        with open(last, "r+b") as f:
            f.truncate(max(0, os.path.getsize(last) - 17))
        rows["torn"] = _gate_recovery(
            "torn", _recover(torn, model, params, engine=engine),
            oracle, torn, allocatable)

    results = {"backend": jax.default_backend(), "t": time.time(),
               "crash_boundary": CRASH_BOUNDARY,
               "child_exit": proc.returncode,
               "child_wall_s": child_wall,
               "oracle_preemptions": int(oracle_stats["preemptions"]),
               "crashed_journal": {
                   "n_records": crashed.n_records,
                   "truncated": crashed.truncated,
                   "by_status": {
                       str(rid): r.status
                       for rid, r in sorted(crashed.requests.items(),
                                            key=lambda kv: str(kv[0]))},
               },
               "cold": rows["cold"], "torn": rows["torn"]}
    save_json("restart_bench.json", results)
    rec = rows["cold"]["recovered"]
    emit("serve_restart", rows["cold"]["wall_s"] * 1e6,
         f"child_exit=137;boundary={CRASH_BOUNDARY};"
         f"acked={len(rows['cold']['acked'])};"
         f"replayed_completed={rec['replayed_completed']};"
         f"image_restores={rec['image_restores']};"
         f"restarts={rec['restarts']};requeued={rec['requeued']};"
         f"torn_tail_ok=1;bit_identical=1")
    return results


if __name__ == "__main__":
    if len(sys.argv) >= 3 and sys.argv[1] == "--child":
        _child(sys.argv[2])
    else:
        main()
