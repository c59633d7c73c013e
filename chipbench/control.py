#!/usr/bin/env python3
"""Readings for the limit of ``correct``: the program's widest gap and its
control's, on many seeds in one process.

    python3 chipbench/control.py --workload <cell> --seconds <s> \
        --seeds 1,2,3 [--control-seeds 1,2,3] [--levels fp8,int8]

Each reading is one whole run of the cell past the device check
(``run.run_cell``: set-up, a window of ``--seconds`` at the cell's own
load, drained, and the comparison that decides ``correct``).  The
program's reading is a sound run's widest gap.  The control's is the same
run with the program's own lower-precision path switched on in the served
engine (``--levels``; ``fp8`` is the step below the configuration's
bfloat16), so the harness's own comparison judges it at the
configuration's limit.  The benchmark's own runs never run this.  Prints
one JSON line per run and a summary line.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def precision_path(level: str):
    """A ``break_engine`` hook: the engine's model with the program's own
    ``level`` precision policy in place of its bfloat16 one, compiled
    anew for the same plan."""
    def switch(engine):
        from repro.models.api import DEFAULT_EXEMPT
        from repro.quant.policy import PrecisionPolicy
        model = dataclasses.replace(
            engine.model,
            policy=PrecisionPolicy(default=level, exempt=DEFAULT_EXEMPT))
        engine._init_from_plan(model, engine.plan, engine.faults,
                               engine.recovery)
    return switch


def reading(cell, seed: int, seconds: float, level: str | None, **kw) -> dict:
    from chipbench.run import run_cell
    res = run_cell(cell, seed, seconds, False, t_start=time.time(),
                   break_engine=precision_path(level) if level else None,
                   **kw)
    gap = res["compared"]["widest_gap"]
    return {"seed": seed, "path": level or "program",
            "correct": res["correct"], "widest_gap": gap["value"],
            "limit": gap["limit"], "attempted": res["attempted"],
            "failed": res["failed"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--levels", default="fp8")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, ROOT)
    os.environ.setdefault("REPRO_AUTOTUNE_CACHE",
                          os.path.join(ROOT, ".autotune", "autotune.json"))
    import jax
    from chipbench.cell import find_cell
    from chipbench.peaks import peaks_for
    from chipbench.run import Compiles
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("chipbench control: needs a TPU", file=sys.stderr)
        return 3
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = find_cell(args.workload, ROOT)
    kw = dict(peak=peaks_for(dev.device_kind),
              device={"platform": dev.platform, "kind": dev.device_kind,
                      "count": len(jax.devices()), "compiles": Compiles()})
    runs = [(int(s), None) for s in args.seeds.split(",") if s]
    runs += [(int(s), lv) for lv in args.levels.split(",") if lv
             for s in args.control_seeds.split(",") if s]
    rows = []
    for seed, level in runs:
        r = reading(cell, seed, args.seconds, level, **kw)
        rows.append(r)
        print(json.dumps(r), flush=True)
    summary = {}
    for path in sorted({r["path"] for r in rows}):
        gaps = [r["widest_gap"] for r in rows if r["path"] == path]
        summary[path] = {"min": min(gaps), "max": max(gaps), "runs": len(gaps),
                         "correct": sum(r["correct"] for r in rows
                                        if r["path"] == path)}
    print(json.dumps({"summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
