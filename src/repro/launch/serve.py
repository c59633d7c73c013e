"""Serving launcher: batched prefill + fused-scan decode with a KV cache.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-7b --smoke \
        --batch 4 --prompt-len 32 --gen 16 [--kernels] [--no-scan]

Implements the inference half of the shape grid: one prefill step fills the
cache, then ``--gen`` greedy tokens are generated.  The decode loop is a
single ``jax.lax.scan`` inside one jit — greedy sampling carried in-graph —
so an N-token generation is one dispatch instead of N host round-trips
(``--no-scan`` keeps the legacy per-token Python loop for comparison;
``--kernels`` routes decode attention through the flash_decode Pallas
kernel).

Timing: compile/warmup runs outside the timed region, and prefill is timed
separately from decode — ``prefill_s`` and ``decode_tokens_per_s`` are
independent numbers (a wall clock that includes jit compilation made the
old ``tokens_per_s`` meaningless for small ``--gen``).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.configs.registry import get_config
from repro.data.synthetic import lm_tokens
from repro.launch.compile_cache import configure_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models.api import build_model


def _greedy(logits) -> jnp.ndarray:
    return jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)


@dataclasses.dataclass(frozen=True)
class ServeFns:
    """Jitted serving entry points, built once so recompilation never
    leaks into a timed region."""
    prefill: Callable[..., Any]
    decode_scan: Callable[..., Any]   # (params, cache, tok, steps) -> ...
    decode_one: Callable[..., Any]    # (params, cache, tok) -> ...


def make_serve_fns(model) -> ServeFns:
    def _decode_scan(params, cache, tok, steps: int):
        def step(carry, _):
            cache, tok = carry
            logits, cache = model.decode_step(params, cache, tok)
            nxt = _greedy(logits)
            return (cache, nxt), nxt

        (cache, _), toks = jax.lax.scan(step, (cache, tok), None,
                                        length=steps)
        # (steps, B, 1) -> (B, steps)
        return toks.transpose(1, 0, 2)[..., 0], cache

    return ServeFns(
        prefill=jax.jit(model.prefill),
        decode_scan=jax.jit(_decode_scan, static_argnums=(3,),
                            donate_argnums=(1,)),
        decode_one=jax.jit(model.decode_step, donate_argnums=(1,)),
    )


def generate(model, params, prompts, gen: int, cache_len: int, *,
             scan: bool = True, fns: ServeFns | None = None):
    """Greedy-generate ``gen`` tokens after prefilling ``prompts``.

    ``scan=True`` (default) runs all decode steps as one fused
    ``lax.scan`` dispatch; ``scan=False`` is the legacy per-token Python
    loop (kept as the dispatch-overhead baseline for bench_serve).
    """
    fns = fns or make_serve_fns(model)
    return timed_generate(model, params, prompts, gen, cache_len,
                          scan=scan, fns=fns)[0]


def timed_generate(model, params, prompts, gen: int, cache_len: int, *,
                   fns: ServeFns, scan: bool = True):
    """One timed prefill+decode pass.

    ``fns`` is required and must already be warm (run :func:`generate`
    once with the same shapes first) — building or compiling inside the
    timed region is exactly the bug this split exists to keep out.
    Returns (tokens, {"prefill_s", "decode_s"}) with the argmax of the
    prefill logits counted on the decode side of the split.
    """
    b, _ = prompts.shape
    cache, _ = model.init_cache(b, cache_len)
    jax.block_until_ready(cache)
    t0 = time.perf_counter()
    logits, cache = fns.prefill(params, {"tokens": prompts}, cache)
    jax.block_until_ready(logits)
    t1 = time.perf_counter()
    tok = _greedy(logits)
    out = [tok]
    if gen > 1:
        if scan:
            rest, _ = fns.decode_scan(params, cache, tok, gen - 1)
            out.append(rest)
        else:
            for _ in range(gen - 1):
                logits, cache = fns.decode_one(params, cache, tok)
                tok = _greedy(logits)
                out.append(tok)
    toks = jnp.concatenate(out, axis=1)
    toks.block_until_ready()
    t2 = time.perf_counter()
    return toks, {"prefill_s": t1 - t0, "decode_s": t2 - t1}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--mesh", default="1x1")
    ap.add_argument("--kernels", action="store_true",
                    help="decode attention via the flash_decode Pallas "
                         "kernel (interpret mode off-TPU)")
    ap.add_argument("--no-scan", action="store_true",
                    help="legacy per-token Python decode loop")
    args = ap.parse_args(argv)
    configure_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    if cfg.enc_dec:
        raise SystemExit("use examples/whisper_serve.py for enc-dec")
    dp, tp = (int(t) for t in args.mesh.split("x"))
    mesh = make_host_mesh(dp, tp)
    interpret = jax.default_backend() != "tpu"
    model = build_model(cfg, mesh=mesh, use_kernels=args.kernels,
                        interpret=args.kernels and interpret)
    params = model.init(jax.random.PRNGKey(0))
    toks = lm_tokens(args.batch * args.prompt_len, cfg.vocab_size,
                     seed=1).reshape(args.batch, args.prompt_len)
    prompts = jnp.asarray(toks)
    cache_len = args.prompt_len + args.gen + 1
    scan = not args.no_scan

    with mesh:
        fns = make_serve_fns(model)
        # warmup: compile prefill + decode outside the timed region
        generate(model, params, prompts, args.gen, cache_len,
                 scan=scan, fns=fns).block_until_ready()
        out, t = timed_generate(model, params, prompts, args.gen,
                                cache_len, scan=scan, fns=fns)

    decode_tokens = args.batch * (out.shape[1] - 1)
    print(json.dumps({
        "arch": cfg.name, "batch": args.batch,
        "prompt_len": args.prompt_len, "generated": int(out.shape[1]),
        "scan": scan, "kernels": args.kernels,
        "prefill_s": round(t["prefill_s"], 4),
        "prefill_tokens_per_s": round(
            args.batch * args.prompt_len / max(t["prefill_s"], 1e-9), 1),
        "decode_s": round(t["decode_s"], 4),
        "decode_tokens_per_s": round(
            decode_tokens / max(t["decode_s"], 1e-9), 1),
        "sample": out[0, :8].tolist(),
    }, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
