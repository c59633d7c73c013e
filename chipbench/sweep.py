#!/usr/bin/env python3
"""Find the highest rate a serve cell sustains: one set-up, then a window
at each offered rate, one after another, in one process.

    python3 chipbench/sweep.py --workload <cell> --seeds 1,2 \
        --seconds <s> --rates 1.0,1.5,2.0

For each rate and each seed, the mix's schedule at that rate is served
and drained; a line gives the requests, TTFT and TPOT medians and 80th
and 90th percentiles, and how long the drain took after the window
closed.  The weights are the first seed's.  A rate the system
sustains drains in about one request's lifetime; past it the queue grows
all through the window, and TTFT and the drain grow with it.  The cell's
rate is then fixed in its traffic file, at about 0.8 of the highest rate
sustained.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, ROOT)
    os.environ.setdefault("REPRO_AUTOTUNE_CACHE",
                          os.path.join(ROOT, ".autotune", "autotune.json"))
    import jax
    if jax.devices()[0].platform != "tpu":
        print("chipbench sweep: needs a TPU", file=sys.stderr)
        return 3
    from repro.launch.compile_cache import configure_compile_cache
    from repro.serving.engine import EngineRun
    from chipbench import serve
    from chipbench.cell import find_cell
    from chipbench.run import percentile
    from chipbench.traffic import generate
    configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = find_cell(args.workload, ROOT)
    seeds = [int(s) for s in args.seeds.split(",")]
    served = serve.build(cell, seeds[0])
    t0 = [time.perf_counter()]
    clock = lambda: time.perf_counter() - t0[0]  # noqa: E731
    run = EngineRun(served.engine, served.params, clock=clock)
    mix = dict(cell.traffic)
    sched = generate(mix, seeds[0], args.seconds, served.cfg.vocab_size)
    if sched.prefixes:
        serve.fill_prefixes(run, sched.prefixes)
    serve.warm(run, serve.admission_shapes(served.plan, mix))
    for rate in (float(r) for r in args.rates.split(",")):
        for seed in seeds:
            mix["arrivals"] = {"kind": "poisson", "rate_per_s": rate}
            sched = generate(mix, seed, args.seconds, served.cfg.vocab_size)
            t0[0] = time.perf_counter()
            w = serve.drive(run, clock, sched, args.seconds, offline=False,
                            drain_s=float(mix.get("drain_s", 30)))
            ttft = [t.t_first - t.item.due for t in w.tracks
                    if t.t_first is not None]
            tpot = [(t.t_last - t.t_first) / (t.n - 1) for t in w.tracks
                    if t.done and t.n > 1]
            row = {"rate": rate, "seed": seed, "due": len(w.tracks),
                   "finished": sum(t.done for t in w.tracks),
                   "drain_s": w.end_s - args.seconds,
                   "preemptions": w.preemptions,
                   "prefix_hit_share": (
                       sum(r["offset"] for b in w.admissions for r in b)
                       / max(1, sum(r["offset"] + r["length"]
                                    for b in w.admissions for r in b)))}
            for name, v in (("ttft", ttft), ("tpot", tpot)):
                for q in (0.5, 0.8, 0.9):
                    row[f"{name}_p{round(q * 100)}_ms"] = (
                        percentile(v, q) * 1e3 if v else None)
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
