"""Ahead-of-time compiles of the paged serving kernels for a TPU v5e.

Interpret mode, which every other kernel test runs in, checks none of
the TPU compiler's constraints on block shapes or on-chip memory.  These
tests hand both paged kernels to the TPU compiler at published widths
(hd 128; qwen2-7b with a GQA group of 7, starcoder2-3b with a group of
12) for a v5e chip that is described, not attached, and assert that a
Mosaic custom call is in the compiled program.  Two more compile the
engine's decode segment and admission dispatch and read their optimized
HLO: the page pool must be updated in place, never copied, sliced per
layer or written back.  Nothing runs: the tests say what compiles, not
that it is right or fast.

The topology is described inside a module fixture, never at import, and
the fixture skips where the TPU compiler cannot be loaded.
"""

import math
import re

import pytest

import jax
import jax.numpy as jnp

from repro.configs.registry import get_config
from repro.kernels.flash_decode_paged import flash_decode_paged
from repro.kernels.flash_prefill_ragged import flash_prefill_ragged

PAGE_SIZE = 16      # the engine's page size on a fresh chip (autotune default)
SLOTS = 8
BLOCKS = 36         # a 576-token block table: 512-token prompt + 32 new + 1
N_PAGES = SLOTS * BLOCKS + 1
LAYERS = 2          # the kernels take the whole-model pool and a layer
SUFFIX = 512

ARCHS = ["qwen2-7b", "starcoder2-3b"]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _pool(cfg, sharding):
    return _spec((LAYERS, N_PAGES, cfg.n_kv_heads, PAGE_SIZE, cfg.hd),
                 jnp.bfloat16, sharding)


def _assert_mosaic(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("arch", ARCHS)
def test_flash_decode_paged_compiles_for_v5e(arch, one_chip,
                                             no_persistent_cache):
    cfg = get_config(arch)
    assert cfg.hd == 128
    q = _spec((SLOTS, 1, cfg.n_heads, cfg.hd), jnp.bfloat16, one_chip)
    bt = _spec((SLOTS, BLOCKS), jnp.int32, one_chip)
    sl = _spec((SLOTS,), jnp.int32, one_chip)
    layer = _spec((), jnp.int32, one_chip)
    compiled = jax.jit(flash_decode_paged).lower(
        q, _pool(cfg, one_chip), _pool(cfg, one_chip), bt, sl,
        layer).compile()
    _assert_mosaic(compiled)


@pytest.mark.parametrize("arch", ARCHS)
def test_flash_prefill_ragged_compiles_for_v5e(arch, one_chip,
                                               no_persistent_cache):
    cfg = get_config(arch)
    q = _spec((SLOTS, SUFFIX, cfg.n_heads, cfg.hd), jnp.bfloat16, one_chip)
    bt = _spec((SLOTS, BLOCKS), jnp.int32, one_chip)
    off = _spec((SLOTS,), jnp.int32, one_chip)
    layer = _spec((), jnp.int32, one_chip)
    compiled = jax.jit(flash_prefill_ragged).lower(
        q, _pool(cfg, one_chip), _pool(cfg, one_chip), bt, off, off,
        layer).compile()
    _assert_mosaic(compiled)


# ------------------------------------- the engine's programs keep the pool
# 6151 pages: a prime that no weight or activation width of qwen2-7b
# shares, so an array with a whole pool's or one layer's count of elements
# is the pool, whatever shape a bitcast has given it; and 202 MB a pool,
# too large for the compiler to prefetch into VMEM whole, as it does a
# 68 MB pool and cannot the chat cell's 614 MB
POOL = dict(page_size=PAGE_SIZE, n_pages=6151, max_slots=4, max_blocks=4,
            prefill_bucket=16)
_MOVES = ("copy", "dynamic-slice", "dynamic-update-slice")
_HLO_OP = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*?)\s([\w\-]+)\(")


def _pool_moves(hlo: str, counts: set[int]) -> list[str]:
    """Instructions that copy, slice or write back an array holding
    ``counts`` elements: by opcode, or by a fusion named after one."""
    found = []
    for line in hlo.splitlines():
        m = _HLO_OP.match(line)
        if m is None:
            continue
        name, typ, op = m.groups()
        if not (op in _MOVES or any(w in name for w in _MOVES)):
            continue
        for dims in re.findall(r"\w+\[([\d,]*)\]", typ):
            if math.prod(int(d) for d in dims.split(",") if d) in counts:
                found.append(line.strip()[:160])
    return found


@pytest.fixture(scope="module")
def engine_programs(one_chip, no_persistent_cache):
    """The engine's decode segment and one admission dispatch, compiled
    for the v5e at qwen2-7b widths with two layers."""
    from repro.models.api import build_model
    from repro.serving import PagedCacheConfig, PagedServingEngine
    from repro.serving.paged_cache import pool_shape
    cfg = get_config("qwen2-7b").replace(n_layers=2)
    pcfg = PagedCacheConfig(**POOL)
    eng = PagedServingEngine(build_model(cfg, use_kernels=True), pcfg)
    params = jax.tree.map(lambda s: _spec(s.shape, s.dtype, one_chip),
                          eng.model.abstract_params())
    shape = pool_shape(cfg, pcfg)
    blocks = {"k_pages": _spec(shape, jnp.bfloat16, one_chip),
              "v_pages": _spec(shape, jnp.bfloat16, one_chip)}
    r, m, s = pcfg.max_slots, pcfg.max_blocks, pcfg.prefill_bucket
    i32 = lambda *dims: _spec(dims, jnp.int32, one_chip)  # noqa: E731
    cache = {"blocks": blocks, "block_tables": i32(r, m),
             "seq_lens": i32(r)}
    segment = eng._segment.lower(
        params, cache, i32(r, 1), _spec((r,), jnp.bool_, one_chip), i32(r),
        i32(r), _spec((r,), jnp.float32, one_chip)).compile()
    admit = eng._admit_batch.lower(
        params, blocks, i32(r, s), i32(r, m), i32(r), i32(r), i32(r),
        i32(r)).compile()
    return {"segment": segment.as_text(), "admit": admit.as_text(),
            "counts": {math.prod(shape), math.prod(shape[1:])}}


@pytest.mark.parametrize("program", ["segment", "admit"])
def test_engine_program_moves_no_pool(program, engine_programs):
    """The pool is updated in place: no copy, slice or write-back of the
    whole pool or of one layer's pages in either serving program."""
    hlo = engine_programs[program]
    assert "tpu_custom_call" in hlo
    assert _pool_moves(hlo, engine_programs["counts"]) == []
