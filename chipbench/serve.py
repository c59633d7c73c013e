"""Build the engine a deployment builds, warm the cell's shapes, and drive
the measured window through ``EngineRun.submit`` / ``EngineRun.step``.

The engine comes from ``ServingPlan.resolve`` ->
``PagedServingEngine.from_plan`` with ``build_model(cfg, use_kernels=True)``;
tile sizes (page size, segment length) are the plan's own.  The window
loop is ``PagedServingEngine.run``'s, driven from here so that the harness
owns the clock: each request is submitted once its due time has passed,
and its delivered tokens are stamped on the host after every ``step()``.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights
from chipbench.cell import Cell, arch, program_config
from chipbench.traffic import Schedule


def resolve_plan(cfg, p: dict, extra: dict):
    """The program's plan for this configuration: slots, prompt and output
    caps and the prefill bucket from the configuration file, the mix's own
    plan inputs (``extra``), and a pool of at least ``pool_tokens``."""
    from repro.serving import ServingPlan

    def resolve(pool_slots=None):
        return ServingPlan.resolve(
            cfg, slots=p["slots"], max_prompt_len=p["max_prompt"],
            max_new_tokens=p["max_output"], pool_slots=pool_slots,
            prefill_bucket=p["prefill_bucket"], **extra)

    per = resolve().cache
    return resolve(-(-p["pool_tokens"] // (per.max_blocks * per.page_size)))


@dataclasses.dataclass
class Served:
    """Set-up of one cell: the program under test and its weights."""
    arch: object
    cfg: object
    plan: object
    engine: object
    params: object


def build(cell: Cell, seed: int, *, smoke: bool = False,
          interpret: bool = False) -> Served:
    from repro.models.api import build_model
    from repro.serving import PagedServingEngine
    a = arch(cell.config)
    cfg = program_config(cell.config, smoke)
    model = build_model(cfg, use_kernels=True, interpret=interpret)
    plan = resolve_plan(cfg, cell.config["plan"],
                        cell.traffic.get("plan", {}))
    engine = PagedServingEngine.from_plan(model, plan)
    params = weights.program_params(a, model, weights.seed_key(seed))
    return Served(a, cfg, plan, engine, params)


def _buckets(lo: int, hi: int, bucket: int) -> list[int]:
    return sorted({-(-n // bucket) * bucket
                   for n in range(max(1, lo), hi + 1)})


def admission_shapes(plan, mix: dict) -> list[tuple[int, int]]:
    """(rows, padded suffix) of every admission dispatch the mix can issue:
    any power-of-two row count up to the slots, at each bucket its prompts
    (with sessions: the suffixes after their prefixes) fall in.  Session
    prefixes stay in the prefix cache through the window (the mix's
    ``retain_pages`` pins them, and its pool has room for every page it
    pins), so no whole prompt is admitted cold there."""
    pc = plan.cache
    rows = [1 << i for i in range(int(math.log2(pc.max_slots)) + 1)]
    pr = mix["prompt"]
    return [(r, s) for r in rows
            for s in _buckets(pr["min"], pr["max"], pc.prefill_bucket)]


def warm(run, shapes) -> None:
    """Compile and run each admission shape and the decode segment once, on
    idle rows whose writes all land on the scratch page."""
    from repro.serving.paged_cache import TRASH_PAGE
    eng, pc, params = run.engine, run.pcfg, run.params
    blocks = run.cache["blocks"]
    for rows, s in shapes:
        z = np.zeros((rows,), np.int32)
        trash = np.full((rows,), TRASH_PAGE, np.int32)
        tok, blocks = eng._admit_batch(
            params, blocks, jnp.asarray(np.zeros((rows, s), np.int32)),
            jnp.asarray(np.full((rows, pc.max_blocks), TRASH_PAGE,
                                np.int32)),
            jnp.asarray(z), jnp.asarray(z), jnp.asarray(trash),
            jnp.asarray(trash))
        np.asarray(tok)
    r = pc.max_slots
    cache = dict(run.cache, blocks=blocks, block_tables=jnp.asarray(run.bt),
                 seq_lens=jnp.asarray(np.zeros((r,), np.int32)))
    out = eng._segment(params, cache, jnp.asarray(np.zeros((r, 1), np.int32)),
                       jnp.asarray(np.zeros((r,), bool)),
                       jnp.asarray(np.zeros((r,), np.int32)),
                       jnp.asarray(np.ones((r,), np.int32)),
                       jnp.asarray(np.zeros((r,), np.float32)))
    np.asarray(out[4])
    run.cache = dict(run.cache, blocks=out[0]["blocks"])


def fill_prefixes(run, prefixes) -> None:
    """Admit each session prefix once, so that the prefix cache holds it
    (the mix's ``retain_pages`` keeps it pinned between uses)."""
    from repro.serving import Request
    for i, p in enumerate(prefixes):
        run.submit(Request(rid=f"prefix{i}", prompt=p, max_new_tokens=1))
        while run.has_work:
            run.step()


@dataclasses.dataclass
class Track:
    """What the host saw of one request."""
    item: object
    req: object
    n: int = 0                   # tokens delivered so far
    t_first: float | None = None
    t_last: float | None = None

    @property
    def done(self) -> bool:
        return self.req.t_done is not None


@dataclasses.dataclass
class Window:
    tracks: list
    admissions: list             # per boundary: rows for work.admission
    segments: list               # per segment: rows for work.segment
    late_s: list                 # how late each submission was
    seconds: float
    end_s: float                 # when the loop stopped (drain included)
    delivered_in_window: int     # tokens delivered by ``seconds``
    boundaries: int
    preemptions: int


def annotate_with(on: bool):
    if on:
        return jax.profiler.TraceAnnotation
    return lambda name: contextlib.nullcontext()


def drive(run, clock, sched: Schedule, seconds: float, *, offline: bool,
          drain_s: float, span=annotate_with(False)) -> Window:
    """The measured window.  Serve mixes submit every request due before
    ``seconds`` and then drain them until ``seconds + drain_s``; offline
    mixes stop at ``seconds``."""
    from repro.serving import Request
    pending = collections.deque(sorted(sched.items, key=lambda i: i.due))
    tracks, live = [], []
    admissions, segments, late = [], [], []
    delivered = 0
    boundaries = 0
    deadline = seconds + drain_s
    while True:
        now = clock()
        if offline and now >= seconds:
            break
        while pending and pending[0].due <= now:
            it = pending.popleft()
            req = Request(rid=it.rid, prompt=it.prompt,
                          max_new_tokens=it.max_new, arrival=it.due)
            run.submit(req)
            late.append(now - it.due)
            tr = Track(it, req)
            tracks.append(tr)
            live.append(tr)
        if not pending and not live:
            break
        if not offline and now >= deadline:
            break
        if not live:
            with span("chipbench.wait"):
                time.sleep(max(0.0, pending[0].due - clock()))
            continue
        with span("chipbench.boundary"):
            status = run.step()
        boundaries += 1
        t = clock()
        adm, seg = [], []
        for tr in live:
            req = tr.req
            n = len(req.tokens)
            if n > tr.n:
                group = tr.item.session if req.shared_pages else None
                if tr.n == 0:
                    tr.t_first = req.t_first
                    adm.append({"offset": req.shared_tokens,
                                "length": req.prompt_len - req.shared_tokens,
                                "group": group})
                before = max(tr.n, 1)
                if n > before:
                    seg.append({"context0": req.prompt_len + before - 1,
                                "emitted": n - before,
                                "shared": req.shared_tokens, "group": group})
                if t <= seconds:
                    delivered += n - tr.n
                tr.t_last = t
                tr.n = n
        if adm:
            admissions.append(adm)
        if status == "ran":
            segments.append(seg)
        live = [tr for tr in live if not tr.done]
    stats = run.sched.stats()
    return Window(tracks=tracks, admissions=admissions, segments=segments,
                  late_s=late, seconds=seconds, end_s=clock(),
                  delivered_in_window=delivered, boundaries=boundaries,
                  preemptions=int(stats.get("preemptions", 0)))
