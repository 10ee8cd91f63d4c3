"""RG-LRU linear recurrence  h_t = a_t * h_{t-1} + x_t  as a Pallas TPU
kernel.

TPU-native blocking (DESIGN.md §5): channels are embarrassingly parallel
(VPU lanes), time is sequential — so the grid is
(batch, channel_blocks, time_blocks) with the time dim "arbitrary" and the
(1, channel_block) fp32 carry held in VMEM scratch across time blocks.  Each
program instance streams one (time_block x channel_block) tile HBM->VMEM
and walks its rows; channel_block should be a multiple of 128 lanes on
real hardware.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(a_ref, x_ref, o_ref, h_ref, *, t_block: int):
    tj = pl.program_id(2)

    @pl.when(tj == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    # rows are read as (1, c_block) tiles: Mosaic lays out 2-D vectors
    # only, and a 1-D row crashes its layout inference
    def step(i, h):
        a_i = a_ref[0, pl.ds(i, 1), :].astype(jnp.float32)
        x_i = x_ref[0, pl.ds(i, 1), :].astype(jnp.float32)
        h = a_i * h + x_i
        o_ref[0, pl.ds(i, 1), :] = h.astype(o_ref.dtype)
        return h

    h_ref[...] = jax.lax.fori_loop(0, t_block, step, h_ref[...])


def rglru_scan_btc(a, x, *, t_block: int = 256, c_block: int = 128,
                   interpret: bool = False):
    """a, x: (B, T, C) -> h: (B, T, C) with h_t = a_t * h_{t-1} + x_t."""
    b, t, c = a.shape
    t_block = min(t_block, t)
    c_block = min(c_block, c)
    assert t % t_block == 0 and c % c_block == 0, (t, t_block, c, c_block)
    nt, nc = t // t_block, c // c_block

    kernel = functools.partial(_rglru_kernel, t_block=t_block)
    grid = (b, nc, nt)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, t_block, c_block),
                         lambda bi, ci, tj: (bi, tj, ci)),
            pl.BlockSpec((1, t_block, c_block),
                         lambda bi, ci, tj: (bi, tj, ci)),
        ],
        out_specs=pl.BlockSpec((1, t_block, c_block),
                               lambda bi, ci, tj: (bi, tj, ci)),
        out_shape=jax.ShapeDtypeStruct((b, t, c), x.dtype),
        scratch_shapes=[pltpu.VMEM((1, c_block), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, x)
