"""Fused streaming-softmax GQA attention (FlashAttention on TPU).

TPU-native layout (DESIGN.md §5): one program instance owns a
(q_block x head_dim) output tile in VMEM and streams kv blocks HBM->VMEM
along the innermost ("arbitrary") grid dim, keeping the running max /
normalizer / accumulator in VMEM scratch across that dim.  The MXU sees
(q_block x head_dim) @ (head_dim x kv_block) matmuls; q_block / kv_block
default to 512/1024 with head_dim expected 128-aligned on real hardware.

Grid: (B * Hq, nq, nk); GQA maps query head h to kv head h // group via the
k/v index_maps — no materialized head broadcast.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  causal: bool, window: int, softcap: float, scale: float,
                  q_block: int, kv_block: int, nk: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_pos = qi * q_block + jax.lax.broadcasted_iota(
        jnp.int32, (q_block, kv_block), 0)
    k_pos = kj * kv_block + jax.lax.broadcasted_iota(
        jnp.int32, (q_block, kv_block), 1)
    # skip fully-masked kv blocks: causal -> blocks strictly in the future;
    # window -> blocks entirely left of the window for every q row
    needed = jnp.asarray(True)
    if causal:
        needed &= kj * kv_block <= qi * q_block + q_block - 1
        if window > 0:
            needed &= (kj + 1) * kv_block - 1 > qi * q_block - window

    @pl.when(needed)
    def _step():
        q = q_ref[0].astype(jnp.float32) * scale          # (qb, dh)
        k = k_ref[0].astype(jnp.float32)                  # (kb, dh)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if softcap > 0:
            s = jnp.tanh(s / softcap) * softcap
        valid = jnp.ones((q_block, kv_block), jnp.bool_)
        if causal:
            valid &= k_pos <= q_pos
        if window > 0:
            valid &= k_pos > q_pos - window
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=1)
        v = v_ref[0].astype(jnp.float32)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + pv
        m_ref[...] = m_new

    @pl.when(kj == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def _ragged_decode_kernel(idx_ref, q_ref, k_ref, v_ref, o_ref,
                          acc_ref, m_ref, l_ref, *, kv_block: int, nk: int,
                          softcap: float, scale: float, hkv: int):
    h = pl.program_id(0)                 # b * Hkv + kv head
    kj = pl.program_id(1)
    cur = idx_ref[h // hkv]              # this row's last valid kv position

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # kv blocks entirely past the slot's length are skipped — the ragged
    # analogue of the causal block skip above
    @pl.when(kj * kv_block <= cur)
    def _step():
        q = q_ref[0].astype(jnp.float32) * scale           # (g, dh)
        k = k_ref[0].astype(jnp.float32)                   # (kb, dh)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if softcap > 0:
            s = jnp.tanh(s / softcap) * softcap
        g = q_ref.shape[1]
        k_pos = kj * kv_block + jax.lax.broadcasted_iota(
            jnp.int32, (g, kv_block), 1)
        s = jnp.where(k_pos <= cur, s, NEG_INF)
        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=1)
        v = v_ref[0].astype(jnp.float32)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + pv
        m_ref[...] = m_new

    @pl.when(kj == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def ragged_decode_bhsd(q, k, v, cur_index, *, softcap: float = 0.0,
                       kv_block: int = 256, interpret: bool = False):
    """Single-position decode attention over ragged per-slot cache lengths.

    q: (B*Hkv, G, dh) — the G query heads of one kv head packed as MXU rows
    (GQA, heads-major); k/v: (B*Hkv, Smax, dh) shared caches; cur_index:
    (B,) int32 — batch row b attends to positions [0, cur_index[b]], its
    slot's occupied prefix of the cache.  The per-row length rides in as a
    scalar-prefetch operand (SMEM) so whole kv blocks past a slot's length
    are skipped, giving each slot decode cost proportional to ITS length,
    not the pool-wide max — the continuous-batching analogue of the causal
    block skip.  -> (B*Hkv, G, dh)."""
    bhkv, g, dh = q.shape
    smax = k.shape[1]
    b = cur_index.shape[0]
    assert bhkv % b == 0, (bhkv, b)
    hkv = bhkv // b
    kv_block = min(kv_block, smax)
    assert smax % kv_block == 0, (smax, kv_block)
    nk = smax // kv_block
    kernel = functools.partial(
        _ragged_decode_kernel, kv_block=kv_block, nk=nk, softcap=softcap,
        scale=dh ** -0.5, hkv=hkv)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bhkv, nk),
        in_specs=[
            pl.BlockSpec((1, g, dh), lambda h, kj, idx: (h, 0, 0)),
            pl.BlockSpec((1, kv_block, dh), lambda h, kj, idx: (h, kj, 0)),
            pl.BlockSpec((1, kv_block, dh), lambda h, kj, idx: (h, kj, 0)),
        ],
        out_specs=pl.BlockSpec((1, g, dh), lambda h, kj, idx: (h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, dh), jnp.float32),
            pltpu.VMEM((g,), jnp.float32),
            pltpu.VMEM((g,), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bhkv, g, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(cur_index.astype(jnp.int32), q, k, v)


def _paged_decode_kernel(idx_ref, pt_ref, q_ref, k_ref, v_ref, o_ref,
                         acc_ref, m_ref, l_ref, *, page_size: int,
                         n_logical: int, softcap: float, scale: float,
                         hkv: int):
    h = pl.program_id(0)                 # b * Hkv + kv head
    j = pl.program_id(1)                 # logical page of THIS slot
    cur = idx_ref[h // hkv]              # this row's last valid kv position

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # logical pages entirely past the slot's length are skipped — this
    # covers every UNMAPPED (sentinel) page-table entry too: a slot only
    # writes inside the pages it owns, so cur < j * page_size there
    @pl.when(j * page_size <= cur)
    def _step():
        q = q_ref[0].astype(jnp.float32) * scale           # (g, dh)
        k = k_ref[0].astype(jnp.float32)                   # (ps, dh)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if softcap > 0:
            s = jnp.tanh(s / softcap) * softcap
        g = q_ref.shape[1]
        k_pos = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (g, page_size), 1)
        s = jnp.where(k_pos <= cur, s, NEG_INF)
        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=1)
        v = v_ref[0].astype(jnp.float32)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + pv
        m_ref[...] = m_new

    @pl.when(j == n_logical - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def paged_decode_bhsd(q, k, v, page_table, cur_index, *,
                      softcap: float = 0.0, interpret: bool = False):
    """Paged decode attention: the page table rides in as a SECOND
    scalar-prefetch operand, so each program instance's k/v index_map
    dereferences it to fetch the slot's j-th logical page from the shared
    physical page array — gather by BlockSpec, no materialized
    contiguous cache.

    q: (B*Hkv, G, dh) kv-head-major as in ``ragged_decode_bhsd``;
    k/v: (N*Hkv, page_size, dh) physical pages, page-major;
    page_table: (B, max_pages) int32, CLIPPED to [0, N-1] by the caller
    (sentinel pages fetch a real block whose compute the length skip
    drops); cur_index: (B,) int32.  -> (B*Hkv, G, dh)."""
    bhkv, g, dh = q.shape
    ps = k.shape[1]
    b, max_pages = page_table.shape
    assert bhkv % b == 0, (bhkv, b)
    hkv = bhkv // b
    kernel = functools.partial(
        _paged_decode_kernel, page_size=ps, n_logical=max_pages,
        softcap=softcap, scale=dh ** -0.5, hkv=hkv)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bhkv, max_pages),
        in_specs=[
            pl.BlockSpec((1, g, dh), lambda h, j, idx, pt: (h, 0, 0)),
            pl.BlockSpec(
                (1, ps, dh),
                lambda h, j, idx, pt, k=hkv: (pt[h // k, j] * k + h % k,
                                              0, 0)),
            pl.BlockSpec(
                (1, ps, dh),
                lambda h, j, idx, pt, k=hkv: (pt[h // k, j] * k + h % k,
                                              0, 0)),
        ],
        out_specs=pl.BlockSpec((1, g, dh), lambda h, j, idx, pt: (h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, dh), jnp.float32),
            pltpu.VMEM((g,), jnp.float32),
            pltpu.VMEM((g,), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bhkv, g, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(cur_index.astype(jnp.int32), page_table.astype(jnp.int32), q, k, v)


def flash_attention_bhsd(q, k, v, *, causal: bool = True, window: int = 0,
                         softcap: float = 0.0, q_block: int = 512,
                         kv_block: int = 1024, interpret: bool = False):
    """q: (BHq, Sq, dh); k/v: (BHkv, Sk, dh) with BHq % BHkv == 0
    (GQA group = BHq // BHkv, heads-major layout) -> (BHq, Sq, dh)."""
    bhq, sq, dh = q.shape
    bhkv, sk = k.shape[0], k.shape[1]
    assert bhq % bhkv == 0
    group = bhq // bhkv
    q_block = min(q_block, sq)
    kv_block = min(kv_block, sk)
    assert sq % q_block == 0 and sk % kv_block == 0
    nq, nk = sq // q_block, sk // kv_block
    scale = dh ** -0.5

    kernel = functools.partial(
        _flash_kernel, causal=causal, window=window, softcap=softcap,
        scale=scale, q_block=q_block, kv_block=kv_block, nk=nk)

    grid = (bhq, nq, nk)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, q_block, dh), lambda h, qi, kj: (h, qi, 0)),
            pl.BlockSpec((1, kv_block, dh),
                         lambda h, qi, kj, g=group: (h // g, kj, 0)),
            pl.BlockSpec((1, kv_block, dh),
                         lambda h, qi, kj, g=group: (h // g, kj, 0)),
        ],
        out_specs=pl.BlockSpec((1, q_block, dh),
                               lambda h, qi, kj: (h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((bhq, sq, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((q_block, dh), jnp.float32),
            pltpu.VMEM((q_block,), jnp.float32),
            pltpu.VMEM((q_block,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return out
