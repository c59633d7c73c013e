"""Ahead-of-time compiles of the paged serving kernels for a TPU v5e.

Interpret mode, which every other kernel test runs in, checks none of
the TPU compiler's constraints on block shapes or on-chip memory.  These
tests hand both paged kernels to the TPU compiler at published widths
(hd 128; qwen2-7b with a GQA group of 7, starcoder2-3b with a group of
12) for a v5e chip that is described, not attached, and assert that a
Mosaic custom call is in the compiled program.  Nothing runs: the tests
say the kernels compile, not that they are right or fast.

The topology is described inside a module fixture, never at import, and
the fixture skips where the TPU compiler cannot be loaded.
"""

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


@pytest.fixture
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
    return _spec((N_PAGES, cfg.n_kv_heads, PAGE_SIZE, cfg.hd),
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
    compiled = jax.jit(flash_decode_paged).lower(
        q, _pool(cfg, one_chip), _pool(cfg, one_chip), bt, sl).compile()
    _assert_mosaic(compiled)


@pytest.mark.parametrize("arch", ARCHS)
def test_flash_prefill_ragged_compiles_for_v5e(arch, one_chip,
                                               no_persistent_cache):
    cfg = get_config(arch)
    q = _spec((SLOTS, SUFFIX, cfg.n_heads, cfg.hd), jnp.bfloat16, one_chip)
    bt = _spec((SLOTS, BLOCKS), jnp.int32, one_chip)
    off = _spec((SLOTS,), jnp.int32, one_chip)
    compiled = jax.jit(flash_prefill_ragged).lower(
        q, _pool(cfg, one_chip), _pool(cfg, one_chip), bt, off,
        off).compile()
    _assert_mosaic(compiled)
