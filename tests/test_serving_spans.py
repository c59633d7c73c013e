"""Profiler spans and named scopes of the serving engine, its decode
counters, and the queue-wait stamp.

- the engine's two programs keep their names (``jit__admit_batch_impl``,
  ``jit__segment_impl``) and their compiled HLO carries the five named
  scopes in its ``op_name`` metadata;
- ``EngineRun.step`` under ``jax.profiler.trace`` leaves one
  ``serving.step`` span per boundary with every phase span inside it;
- ``serving_decode_row_steps_total`` / ``serving_decode_tokens_total``
  count what the segments ran and emitted;
- ``Request.t_admitted`` is stamped at the first admission, before the
  prefill, so queue wait is below TTFT and a restore keeps the stamp.
"""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.data.synthetic import lm_tokens
from repro.models.api import build_model
from repro.serving import (Observability, ObservabilityPolicy,
                           PagedCacheConfig, PagedServingEngine, Request)
from repro.serving.engine import EngineRun
from repro.serving.observe import span
from repro.serving.paged_cache import TRASH_PAGE

SCOPES = ("attention", "mlp", "kv_write", "lm_head", "sample")
PHASES = ("serving.preflight", "serving.growth", "serving.admit.plan",
          "serving.restore", "serving.admit", "serving.admit.inputs",
          "serving.admit.wait", "serving.checkpoint",
          "serving.segment.inputs", "serving.segment.wait",
          "serving.commit")
PROMPT, GEN, N = 16, 12, 4
BLOCKS = -(-(PROMPT + GEN + 1) // 8)
# the pool covers every admission (3 pages each) but not the lifetimes
# (4 each): growth preempts, and the victims come back as restores
TIGHT = PagedCacheConfig(page_size=8, n_pages=N * 3 + 1, max_slots=N,
                         max_blocks=BLOCKS, segment_len=4)
_E: dict = {}


def _setup():
    if not _E:
        cfg = get_config("qwen2_7b", smoke=True)
        model = build_model(cfg)
        _E["x"] = (cfg, model, model.init(jax.random.PRNGKey(0)))
    return _E["x"]


def _requests(cfg):
    prompts = np.asarray(lm_tokens(N * PROMPT, cfg.vocab_size, seed=23)
                         ).reshape(N, PROMPT).astype(np.int32)
    return [Request(rid=i, prompt=prompts[i], max_new_tokens=GEN)
            for i in range(N)]


def _serve(run, reqs):
    for r in reqs:
        run.submit(r)
    while run.has_work:
        run.step()


def _compiled_text(eng, run, params, program):
    pc = eng.pcfg
    r = pc.max_slots
    z = jnp.zeros((r,), jnp.int32)
    if program == "admit":
        trash = jnp.full((r,), TRASH_PAGE, jnp.int32)
        low = eng._admit_batch.lower(
            params, run.cache["blocks"], jnp.zeros((r, 16), jnp.int32),
            jnp.asarray(run.bt), z, z, trash, trash)
    else:
        cache = dict(run.cache, block_tables=jnp.asarray(run.bt),
                     seq_lens=z)
        low = eng._segment.lower(params, cache, jnp.zeros((r, 1), jnp.int32),
                                 jnp.zeros((r,), bool), z,
                                 jnp.ones((r,), jnp.int32),
                                 jnp.zeros((r,), jnp.float32))
    return low.compile().as_text()


@pytest.mark.parametrize("program,module", [
    ("admit", "jit__admit_batch_impl"), ("segment", "jit__segment_impl")])
def test_programs_keep_their_names_and_carry_the_scopes(program, module):
    cfg, model, params = _setup()
    eng = PagedServingEngine(model, TIGHT)
    text = _compiled_text(eng, EngineRun(eng, params), params, program)
    assert text.split("\n", 1)[0].startswith(f"HloModule {module}")
    op_names = [line.split('op_name="', 1)[1].split('"', 1)[0]
                for line in text.splitlines() if 'op_name="' in line]
    for scope in SCOPES:
        assert any(f"/{scope}/" in n for n in op_names), scope
    # the head and the sampling sit outside the layers' scopes
    assert not any("/attention/" in n and "/lm_head/" in n
                   for n in op_names)


def _host_spans(trace_dir):
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serving."):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return out


def test_step_phases_nest_inside_serving_step(tmp_path):
    cfg, model, params = _setup()
    eng = PagedServingEngine(model, TIGHT)
    run = EngineRun(eng, params)
    reqs = _requests(cfg)
    with jax.profiler.trace(str(tmp_path)):
        _serve(run, reqs)
    spans = _host_spans(str(tmp_path))
    steps = [s for s in spans if s[0] == "serving.step"]
    assert [s[3]["boundary"] for s in steps] == \
        list(range(1, run.boundary + 1))
    assert {s[0] for s in spans} == {"serving.step", *PHASES}
    for name, lo, hi, args in spans:
        if name == "serving.step":
            continue
        parents = [s for s in steps if s[1] <= lo and hi <= s[2]]
        assert len(parents) == 1, name
        if name.startswith("serving.admit."):
            if name != "serving.admit.plan":
                assert any(s[0] == "serving.admit" and s[1] <= lo
                           and hi <= s[2] for s in spans), name
    admits = [s[3] for s in spans if s[0] == "serving.admit"]
    assert sum(a["rows"] for a in admits) == N
    assert all(a["bucket"] % eng.pcfg.prefill_bucket == 0 for a in admits)
    commits = [s[3] for s in spans if s[0] == "serving.commit"]
    assert len(commits) == run.n_segments
    assert all(c["steps"] == TIGHT.segment_len
               and c["rows"] == TIGHT.max_slots for c in commits)
    assert sum(c["tokens"] for c in commits) == \
        sum(len(r.tokens) - 1 for r in reqs)
    assert run.sched.stats()["restores"] >= 1


def test_decode_counters_count_row_steps_and_tokens():
    cfg, model, params = _setup()
    eng = PagedServingEngine(model, TIGHT)
    obs = Observability.disabled()
    reqs = _requests(cfg)
    stats = eng.run(reqs, params, obs=obs)
    c = stats["metrics"]["counters"]
    assert c["serving_decode_row_steps_total"] == \
        stats["n_segments"] * TIGHT.segment_len * TIGHT.max_slots
    # every token after the admission's first comes from a segment
    assert c["serving_decode_tokens_total"] == N * (GEN - 1)
    assert "prefill_s" not in stats


def test_queue_wait_ends_at_first_admission():
    cfg, model, params = _setup()
    eng = PagedServingEngine(model, TIGHT)
    obs = Observability.from_policy(ObservabilityPolicy(enabled=True))
    reqs = _requests(cfg)
    stats = eng.run(reqs, params, obs=obs)
    recs = {r["rid"]: r for r in stats["requests"]}
    for req in reqs:
        # stamped before the prefill, which the first token waits for
        assert req.t_admitted < req.t_first
        assert recs[req.rid]["queue_wait_s"] < recs[req.rid]["ttft_s"]
        admits = [e.t for e in obs.tracer.trace(req.rid)
                  if e.kind == "ADMIT"]
        assert req.t_admitted <= admits[0]
    # a preempted request's restore is a second admission; its stamp
    # stays at the first
    again = [r for r in reqs if r.n_preempted]
    assert again
    for req in again:
        admits = [e.t for e in obs.tracer.trace(req.rid)
                  if e.kind == "ADMIT"]
        assert len(admits) >= 2 and req.t_admitted < admits[1]


def test_span_is_a_profiler_annotation():
    with span("serving.test", boundary=1) as s:
        assert isinstance(s, jax.profiler.TraceAnnotation)
