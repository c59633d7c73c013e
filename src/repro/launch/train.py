"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-7b --smoke \
        --steps 50 --ckpt-dir /tmp/ckpt [--mesh 1x1] [--inject-failures]

Full-size configs target the production mesh (run under the dry-run env);
--smoke runs the reduced config end-to-end on local devices — the same
loop, checkpointing, failure handling and data pipeline as at scale.
"""

from __future__ import annotations

import argparse
import json
import os
import tempfile

import jax

from repro.checkpoint.manager import CheckpointManager
from repro.configs.registry import get_config
from repro.data.pipeline import ShardedBatcher, TokenSource
from repro.launch.compile_cache import configure_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models.api import build_model
from repro.optim.optimizers import adamw, cosine_schedule
from repro.parallel.sharding import ShardingRules
from repro.runtime.train_loop import FailureInjector, train_loop


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--mesh", default="1x1",
                    help="dataxmodel, e.g. 2x4 (local devices)")
    ap.add_argument("--inject-failures", default="",
                    help="comma-separated steps to fail at")
    ap.add_argument("--grad-compression", action="store_true")
    args = ap.parse_args(argv)
    configure_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    dp, tp = (int(t) for t in args.mesh.split("x"))
    mesh = make_host_mesh(dp, tp)
    rules = ShardingRules.default(mesh)
    model = build_model(cfg, mesh=mesh)
    source = TokenSource(cfg.vocab_size, args.batch, args.seq_len)
    batcher = ShardedBatcher(source, rules)
    ckpt_dir = args.ckpt_dir or tempfile.mkdtemp(prefix="repro_ckpt_")
    ckpt = CheckpointManager(ckpt_dir)
    injector = None
    if args.inject_failures:
        injector = FailureInjector(
            tuple(int(s) for s in args.inject_failures.split(",")))
    optimizer = adamw(cosine_schedule(args.lr, 10, args.steps))

    with mesh:
        report = train_loop(
            model, steps=args.steps, batcher=batcher, ckpt=ckpt,
            optimizer=optimizer, ckpt_every=args.ckpt_every,
            injector=injector,
            grad_compression=args.grad_compression,
            log=print)

    print(json.dumps({
        "arch": cfg.name, "steps_run": report.steps_run,
        "restarts": report.restarts,
        "straggler_events": report.straggler_events,
        "first_loss": report.losses[0] if report.losses else None,
        "final_loss": report.final_loss,
        "ckpt_dir": ckpt_dir,
        "devices": len(jax.devices()),
    }, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
