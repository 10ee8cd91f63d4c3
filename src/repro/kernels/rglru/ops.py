"""Public jit'd wrapper for the RG-LRU scan kernel."""

from __future__ import annotations

from functools import partial

import jax

from repro.kernels.mode import interpret_mode
from repro.kernels.rglru.kernel import rglru_scan_btc


@partial(jax.jit, static_argnames=("t_block", "c_block", "interpret"))
def rglru_scan(a, x, *, t_block: int = 256, c_block: int = 128,
               interpret: bool = None):
    """a, x: (B, T, C) -> h with h_t = a_t h_{t-1} + x_t.

    Pallas TPU kernel on TPU; interpreter on the CPU (tests)."""
    if interpret is None:
        interpret = interpret_mode()
    return rglru_scan_btc(a, x, t_block=t_block, c_block=c_block,
                          interpret=interpret)
