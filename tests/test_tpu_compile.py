"""The serving kernels compile for a TPU v5e at published widths.

Each case lowers a Pallas kernel with ``interpret=False`` for one chip of
a described ``v5e:2x2`` topology and asserts that the compiled program
holds the Mosaic kernel (``tpu_custom_call``).  Nothing runs: this is the
chip's compiler refusing what interpret mode accepts (unaligned tiles,
too much VMEM), caught without a chip.  The topology is described inside
a fixture, never at import time, so every test worker collects the same
tests and only the worker given this file loads the TPU compiler.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention.ops import (flash_attention,
                                               flash_decode_attention,
                                               paged_flash_decode_attention)
from repro.kernels.rglru.ops import rglru_scan
from repro.models.transformer import ragged_kv_block

DECODE_ARCHS = ("qwen2-0.5b", "smollm-360m")
SLOTS, MAX_LEN, PAGE_SIZE = 8, 512, 64


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(fn, *args, **static):
    text = fn.lower(*args, **static).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("arch", DECODE_ARCHS)
def test_ragged_decode_compiles(one_chip, arch):
    cfg = get_config(arch)
    bf = jnp.bfloat16
    q = _sds((SLOTS, 1, cfg.n_heads, cfg.head_dim), bf, one_chip)
    kv = _sds((SLOTS, MAX_LEN, cfg.n_kv_heads, cfg.head_dim), bf, one_chip)
    idx = _sds((SLOTS,), jnp.int32, one_chip)
    _assert_kernel(flash_decode_attention, q, kv, kv, idx,
                   kv_block=ragged_kv_block(MAX_LEN), interpret=False)


@pytest.mark.parametrize("arch", DECODE_ARCHS)
def test_paged_decode_compiles(one_chip, arch):
    cfg = get_config(arch)
    bf = jnp.bfloat16
    n_pages = SLOTS * MAX_LEN // PAGE_SIZE
    q = _sds((SLOTS, 1, cfg.n_heads, cfg.head_dim), bf, one_chip)
    pages = _sds((n_pages, PAGE_SIZE, cfg.n_kv_heads, cfg.head_dim), bf,
                 one_chip)
    pt = _sds((SLOTS, MAX_LEN // PAGE_SIZE), jnp.int32, one_chip)
    idx = _sds((SLOTS,), jnp.int32, one_chip)
    _assert_kernel(paged_flash_decode_attention, q, pages, pages, pt, idx,
                   interpret=False)


def test_flash_prefill_compiles_at_2048(one_chip):
    cfg = get_config("qwen2-0.5b")
    bf = jnp.bfloat16
    q = _sds((1, 2048, cfg.n_heads, cfg.head_dim), bf, one_chip)
    kv = _sds((1, 2048, cfg.n_kv_heads, cfg.head_dim), bf, one_chip)
    _assert_kernel(flash_attention, q, kv, kv, interpret=False)


def test_rglru_scan_compiles_at_recurrentgemma_width(one_chip):
    cfg = get_config("recurrentgemma-2b")
    x = _sds((1, 2048, cfg.lru_width), jnp.float32, one_chip)
    _assert_kernel(rglru_scan, x, x, interpret=False)
