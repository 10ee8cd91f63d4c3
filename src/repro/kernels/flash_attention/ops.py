"""Public jit'd wrappers: (B, S, H, dh) layout, interpreted on the CPU."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import (flash_attention_bhsd,
                                                  paged_decode_bhsd,
                                                  ragged_decode_bhsd)
from repro.kernels.mode import interpret_mode


@partial(jax.jit, static_argnames=("causal", "window", "softcap", "q_block",
                                   "kv_block", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    softcap: float = 0.0, q_block: int = 512,
                    kv_block: int = 1024, interpret: bool = None):
    """q: (B, Sq, Hq, dh); k/v: (B, Sk, Hkv, dh) -> (B, Sq, Hq, dh).

    Lowers the Pallas TPU kernel on TPU and runs the kernel body under
    the Pallas interpreter on the CPU (``kernels.mode.interpret_mode``).
    """
    if interpret is None:
        interpret = interpret_mode()
    b, sq, hq, dh = q.shape
    hkv = k.shape[2]
    # (B, S, H, dh) -> heads-major (B*H, S, dh)
    qh = q.transpose(0, 2, 1, 3).reshape(b * hq, sq, dh)
    kh = k.transpose(0, 2, 1, 3).reshape(b * hkv, k.shape[1], dh)
    vh = v.transpose(0, 2, 1, 3).reshape(b * hkv, v.shape[1], dh)
    out = flash_attention_bhsd(qh, kh, vh, causal=causal, window=window,
                               softcap=softcap, q_block=q_block,
                               kv_block=kv_block, interpret=interpret)
    return out.reshape(b, hq, sq, dh).transpose(0, 2, 1, 3)


@partial(jax.jit, static_argnames=("softcap", "kv_block", "interpret"))
def flash_decode_attention(q, k_cache, v_cache, cur_index, *,
                           softcap: float = 0.0, kv_block: int = 256,
                           interpret: bool = None):
    """Ragged-length decode attention (continuous batching / slot pools).

    q: (B, 1, Hq, dh); k_cache/v_cache: (B, Smax, Hkv, dh); cur_index:
    (B,) int32 — row b attends to cache positions [0, cur_index[b]]
    (``models.attention.attention_decode`` with a vector index is the
    oracle).  -> (B, 1, Hq, dh)."""
    if interpret is None:
        interpret = interpret_mode()
    b, _, hq, dh = q.shape
    hkv = k_cache.shape[2]
    g = hq // hkv
    # (B, 1, Hq, dh) -> kv-head-major (B*Hkv, G, dh): the G query heads of
    # one kv head become the MXU rows of one program instance
    qh = q.reshape(b, hkv, g, dh).reshape(b * hkv, g, dh)
    kh = k_cache.transpose(0, 2, 1, 3).reshape(b * hkv, -1, dh)
    vh = v_cache.transpose(0, 2, 1, 3).reshape(b * hkv, -1, dh)
    out = ragged_decode_bhsd(qh, kh, vh, jnp.asarray(cur_index, jnp.int32),
                             softcap=softcap, kv_block=kv_block,
                             interpret=interpret)
    return out.reshape(b, 1, hq, dh)


@partial(jax.jit, static_argnames=("softcap", "interpret"))
def paged_flash_decode_attention(q, k_pages, v_pages, page_table,
                                 cur_index, *, softcap: float = 0.0,
                                 interpret: bool = None):
    """Page-table-gather decode attention over a PAGED KV cache
    (DESIGN.md §13).

    q: (B, 1, Hq, dh); k_pages/v_pages: (N, page_size, Hkv, dh) shared
    physical pages; page_table: (B, max_pages) int32 — logical page j of
    slot b lives in physical page ``page_table[b, j]`` (sentinel N =
    unmapped); cur_index: (B,) int32.  Bit-checked against the jnp
    gather oracle ``models.attention.attention_decode_paged``.
    -> (B, 1, Hq, dh)."""
    if interpret is None:
        interpret = interpret_mode()
    b, _, hq, dh = q.shape
    n, ps, hkv = k_pages.shape[0], k_pages.shape[1], k_pages.shape[2]
    g = hq // hkv
    qh = q.reshape(b, hkv, g, dh).reshape(b * hkv, g, dh)
    # (N, ps, Hkv, dh) -> kv-head-major pages (N*Hkv, ps, dh): physical
    # page p, kv head hk at block row p * Hkv + hk (the index_map key)
    kh = k_pages.transpose(0, 2, 1, 3).reshape(n * hkv, ps, dh)
    vh = v_pages.transpose(0, 2, 1, 3).reshape(n * hkv, ps, dh)
    # sentinel entries clip to a real page: its block gets FETCHED for
    # the skipped grid steps but never computed on (length mask)
    pt = jnp.clip(page_table.astype(jnp.int32), 0, n - 1)
    out = paged_decode_bhsd(qh, kh, vh, pt,
                            jnp.asarray(cur_index, jnp.int32),
                            softcap=softcap, interpret=interpret)
    return out.reshape(b, 1, hq, dh)
