#!/usr/bin/env python3
"""Serve one cell of BENCHMARK.json once on the chip and print its metrics.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The run refuses anything but a TPU with as many chips as the cell asks for
(and a device whose peaks ``peaks.py`` does not know).  Set-up builds the
engine a deployment builds, draws the weights from ``--seed`` on the
device, fills the mix's session prefixes and warms every shape the mix
uses; then the window serves the mix's schedule for ``--seconds``
(draining what came due, for a serve mix).  After it, the served tokens
are compared with the float32 reference (``check.py``).

Facts go to earlier lines as ``name: value``; the numbers compared, each
beside its limit, are the last lines of standard error; the last line of
standard output is the result, one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics`` and ``device`` (with ``--trace 1``
also ``breakdown``), and last the key ``compared``.  With ``--trace 0``
the metrics are the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics, read from a profiler trace of the window.
"""

from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# scratch output of a traced run, inside the checkout
OUT = os.path.join(ROOT, ".chipbench")


def say(name: str, value) -> None:
    print(f"{name}: {json.dumps(value) if not isinstance(value, str) else value}",
          flush=True)


class Compiles:
    """Backend compiles and persistent-cache loads, from JAX's own events."""

    def __init__(self):
        import jax
        self.n = 0
        self.loads = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._ev)

    def _dur(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    def _ev(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.loads += 1


def percentile(values: list[float], q: float) -> float:
    """Nearest-rank percentile, ``q`` in (0, 1]."""
    v = sorted(values)
    return v[max(0, math.ceil(q * len(v)) - 1)]


def end_to_end(window, setup_s: float) -> dict:
    """Every end-to-end metric this harness knows, over all requests and
    all time of the window.  A request unfinished at the drain's end counts
    with the latency it had run up by then, a lower bound."""
    ttft, tpot = [], []
    end = window.end_s
    for t in window.tracks:
        due = t.item.due
        ttft.append((t.t_first if t.t_first is not None else end) - due)
        if t.done and t.n > 1:
            tpot.append((t.t_last - t.t_first) / (t.n - 1))
        elif not t.done:
            first = t.t_first if t.t_first is not None else end
            tpot.append((end - first) / max(1, t.n - 1))
    out = {"setup_s": (setup_s, "s"),
           "output_tok_per_s": (window.delivered_in_window / window.seconds,
                                "tokens/s")}
    if ttft:
        out["ttft_p80_ms"] = (percentile(ttft, 0.8) * 1e3, "ms")
    if tpot:
        out["tpot_p80_ms"] = (percentile(tpot, 0.8) * 1e3, "ms")
    return out


class Record:
    """What a per-layer metric's reader reads."""

    def __init__(self, arch, peak, window, trace):
        from chipbench import work
        self.arch, self.peak, self.window, self.trace = arch, peak, window, trace
        self.prefill = work.Work()
        for rows in window.admissions:
            self.prefill += work.admission(arch, rows)
        self.decode = work.Work()
        for rows in window.segments:
            self.decode += work.segment(arch, rows)


def run_cell(cell, seed: int, seconds: float, trace_on: bool, *, peak: dict,
             device: dict, smoke: bool = False, interpret: bool = False,
             break_engine=None, keep_trace: str | None = None,
             t_start: float = T_START) -> dict:
    """One run of ``cell`` past the device check; returns the result."""
    import jax
    from repro.serving.engine import EngineRun
    from chipbench import cell as cellmod, check, serve, trace as tr
    from chipbench.traffic import generate

    served = serve.build(cell, seed, smoke=smoke, interpret=interpret)
    plan = served.plan
    pc = plan.cache
    say("config", {"name": cell.config["name"], "layers": served.cfg.n_layers,
                   "d_model": served.cfg.d_model, "vocab": served.cfg.vocab_size})
    say("plan", {"slots": pc.max_slots, "pages": pc.n_pages,
                 "page_size": pc.page_size, "max_blocks": pc.max_blocks,
                 "segment_len": pc.segment_len,
                 "prefill_bucket": pc.prefill_bucket,
                 "retain_pages": pc.retain_pages})
    say("tiles", {k: [getattr(pc, k), plan.provenance.get(k)]
                  for k in ("page_size", "segment_len")})
    if break_engine is not None:
        break_engine(served.engine)
    t0 = [time.perf_counter()]
    clock = lambda: time.perf_counter() - t0[0]  # noqa: E731
    run = EngineRun(served.engine, served.params, clock=clock)
    mix = cell.traffic
    sched = generate(mix, seed, seconds, served.cfg.vocab_size)
    if sched.prefixes:
        serve.fill_prefixes(run, sched.prefixes)
    shapes = serve.admission_shapes(plan, mix)
    serve.warm(run, shapes)
    say("warmed_admission_shapes", shapes)
    compiles = device["compiles"]
    n0, l0 = compiles.n, compiles.loads
    offline = mix["arrivals"]["kind"] == "offline"
    trace_dir = os.path.join(OUT, "trace")
    if trace_on:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    span = serve.annotate_with(trace_on)
    setup_s = time.time() - t_start
    t0[0] = time.perf_counter()
    with span("chipbench.window"):
        window = serve.drive(run, clock, sched, seconds, offline=offline,
                             drain_s=float(mix.get("drain_s", 0)), span=span)
    if trace_on:
        jax.profiler.stop_trace()
        if keep_trace:
            os.makedirs(keep_trace, exist_ok=True)
            shutil.copy(tr.xplane_file(trace_dir), keep_trace)
    in_window = {"compiles": compiles.n - n0, "cache_loads": compiles.loads - l0}
    dev = jax.devices()[0]
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    late = sorted(window.late_s) or [0.0]
    done = sum(t.done for t in window.tracks)
    dead = sum(t.req.failure is not None for t in window.tracks)
    say("generator_late_s", {"max": late[-1], "p90": percentile(late, 0.9)})
    say("requests", {"due": len(window.tracks), "finished": done,
                     "unfinished": len(window.tracks) - done, "dead": dead,
                     "boundaries": window.boundaries,
                     "segments": len(window.segments),
                     "admission_boundaries": len(window.admissions),
                     "preemptions": window.preemptions})
    say("compiles_in_window", in_window)
    say("peak_bytes_in_use", mem)

    # the program's state goes before the reference runs
    tracks = window.tracks
    a, limit = served.arch, float(cell.config["correct"]["max_gap"])
    del run, served
    gc.collect()
    t_ref = time.perf_counter()
    picked = check.sample(tracks, seed, int(mix.get("sample_tokens", 256)))
    cmp = check.compare(a, seed, picked)
    say("reference", {"requests": cmp["requests"], "tokens": cmp["tokens"],
                      "per_request_widest_gap": cmp["per_request"],
                      "seconds": time.perf_counter() - t_ref})
    gap = cmp["widest_gap"]
    correct = bool(picked) and gap <= limit     # NaN fails too

    e2e = end_to_end(window, setup_s)
    result = {"correct": correct}
    if offline:
        result.update(attempted=len(tracks), failed=dead)
    else:
        result.update(attempted=len(tracks),
                      failed=len(tracks) - done + dead)
    device_out = {"platform": device["platform"], "kind": device["kind"],
                  "count": device["count"], "memory_peak_bytes": int(mem)}
    if trace_on:
        trc = tr.load(tr.xplane_file(trace_dir))
        rec = Record(a, peak, window, trc)
        metrics = {}
        for m in cell.per_layer:
            v = cellmod.metric_reader(m["name"]).read(rec)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        say("work", {"prefill_flops": rec.prefill.flops,
                     "prefill_attn_flops": rec.prefill.attn_flops,
                     "prefill_attn_bytes": rec.prefill.attn_bytes,
                     "decode_flops": rec.decode.flops,
                     "decode_attn_flops": rec.decode.attn_flops,
                     "decode_attn_bytes": rec.decode.attn_bytes})
        device_out.update(busy_s=trc.busy_s(), window_s=trc.window_s)
        result["metrics"] = metrics
        result["device"] = device_out
        result["breakdown"] = {"device_ops": trc.top_ops(),
                               "idle_gaps": trc.idle_gaps()}
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        result["metrics"] = {m["name"]: {"value": e2e[m["name"]][0],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end if m["name"] in e2e}
        result["device"] = device_out
    result["compared"] = {"widest_gap": {"value": gap, "limit": limit}}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the traced run's .xplane.pb into this directory")
    args = ap.parse_args(argv)

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chipbench: {src}/repro not found: the benchmark runs from a "
              "checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    sys.path.insert(0, ROOT)
    os.environ.setdefault("REPRO_AUTOTUNE_CACHE",
                          os.path.join(ROOT, ".autotune", "autotune.json"))
    from chipbench.cell import find_cell
    from chipbench.peaks import peaks_for
    cell = find_cell(args.workload, ROOT)

    import jax
    devs = jax.devices()
    dev = devs[0]
    say("device", {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devs)})
    if dev.platform != "tpu" or len(devs) < cell.chips:
        print(f"chipbench: {args.workload} needs {cell.chips} TPU chip(s); "
              f"JAX found {len(devs)} {dev.platform!r} device(s)",
              file=sys.stderr)
        return 3
    peak = peaks_for(dev.device_kind)
    from repro.launch.compile_cache import configure_compile_cache
    say("compile_cache_dir", configure_compile_cache())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "compiles": Compiles()}
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      peak=peak, device=device, keep_trace=args.keep_trace)
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
