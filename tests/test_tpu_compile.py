"""The serving kernels compile for a TPU v5e at published widths.

Each case lowers a Pallas kernel with ``interpret=False`` for one chip of
a described ``v5e:2x2`` topology and asserts that the compiled program
holds the Mosaic kernel (``tpu_custom_call``).  Nothing runs: this is the
chip's compiler refusing what interpret mode accepts (unaligned tiles,
too much VMEM), caught without a chip.  The topology is described inside
a fixture, never at import time, so every test worker collects the same
tests and only the worker given this file loads the TPU compiler.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention.ops import (flash_attention,
                                               flash_decode_attention,
                                               paged_flash_decode_attention)
from repro.kernels.rglru.ops import rglru_scan
from repro.models.params import serving_params
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


# the serving cell's cache: 32 slots of 2048 positions
CELL_SLOTS, CELL_LEN = 32, 2048
INSTR = re.compile(r"^\s*(?:ROOT )?%[\w.-]+ = (.+?) ([a-z][\w-]*)\(")
HEADER = re.compile(r"^(?:ENTRY )?%([\w.-]+) \(.*\{$")


def _cache_rewrites(text, shapes):
    """Instructions of the ``kv_write`` scope that produce an array of one
    of ``shapes`` other than by a scatter, directly or as the root of a
    fusion (a scatter updates its operand in place)."""
    roots, comp = {}, None
    for line in text.splitlines():
        head = HEADER.match(line)
        if head:
            comp = head.group(1)
        m = INSTR.match(line)
        if m and line.lstrip().startswith("ROOT"):
            roots[comp] = m.group(2)
    bad = []
    for line in text.splitlines():
        m = INSTR.match(line)
        if not m or "kv_write" not in line:
            continue
        shape, op = m.groups()
        if not any(shape.startswith(f"bf16[{s}]") for s in shapes):
            continue
        called = re.search(r"calls=%([\w.-]+)", line)
        if op == "fusion" and called:
            op = roots.get(called.group(1), op)
        if op not in ("scatter", "parameter", "get-tuple-element",
                      "bitcast"):
            bad.append(line.strip()[:160])
    return bad


@pytest.fixture(scope="module")
def cell_decode(one_chip):
    """The serving cell's decode programs at qwen2-0.5b's published
    widths, bfloat16 weights bound as serving binds them, compiled for
    one v5e: ``{name: (compiled, cache bytes)}``."""
    from repro.serve.engine import _shared_steps
    arch = get_config("qwen2-0.5b")
    steps = _shared_steps(arch, False)
    model = steps.model

    def shaped(tree):
        return jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip),
                            tree)

    params = shaped(jax.eval_shape(
        lambda p: serving_params(p, arch, model.plan)[0],
        model.abstract_params()))
    cache = shaped(jax.eval_shape(lambda: model.init_cache(
        CELL_SLOTS, CELL_LEN, per_slot=True)))
    i32 = _sds((CELL_SLOTS,), jnp.int32, one_chip)
    flag = _sds((CELL_SLOTS,), jnp.bool_, one_chip)
    state = {"tok": i32, "remaining": i32, "finished": flag, "eos": i32,
             "has_eos": flag}
    kv_bytes = sum(a.size * a.dtype.itemsize
                   for a in jax.tree.leaves(cache["stack"]))
    return {"decode": (steps.decode.lower(params, cache, i32).compile(),
                       kv_bytes),
            "horizon": (steps.horizon.lower(params, cache, state, 8,
                                            CELL_LEN).compile(), kv_bytes)}


@pytest.mark.parametrize("program", ["decode", "horizon"])
def test_decode_programs_write_the_cache_in_place(cell_decode, program):
    """The decode step and the K=8 horizon alias their donated cache to
    the returned one, allocate no second cache, and write the step's K/V
    rows by scatter: no ``kv_write`` op rewrites a layer's or the whole
    stack's (32, 2048) cache."""
    compiled, kv_bytes = cell_decode[program]
    mem = compiled.memory_analysis()
    assert kv_bytes == 24 * 2 * CELL_SLOTS * CELL_LEN * 2 * 64 * 2
    assert mem.alias_size_in_bytes >= kv_bytes
    limit = 64 * 2 ** 20       # one layer's K and V: 33.5 MB
    assert mem.temp_size_in_bytes < limit
    assert mem.output_size_in_bytes - mem.alias_size_in_bytes < limit
    shapes = [f"{lead}{CELL_SLOTS},{CELL_LEN},{tail}"
              for lead in ("", "24,") for tail in ("128", "2,64")]
    assert _cache_rewrites(compiled.as_text(), shapes) == []
