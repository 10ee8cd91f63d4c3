"""Composable block stack: layer planning, block dispatch, scan-over-layers.

``LayerPlan`` decomposes the per-layer block descriptors into
(prefix, periodic body, no tail) so homogeneous runs compile as ONE traced
period under ``lax.scan`` (HLO stays O(period), not O(n_layers)) while
irregular heads (DeepSeekMoE's dense layer 0, RecurrentGemma's 26 = 2 + 3*8
pattern) unroll only the minimal prefix.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.attention import (attention_decode, attn_specs, project_kv,
                                    project_q)
from repro.models.layers import (apply_ffn, apply_norm, apply_rope,
                                 ffn_specs, norm_specs)
from repro.models.moe import apply_moe, moe_specs
from repro.models.recurrent import (apply_rglru_block, init_rglru_cache,
                                    rglru_specs)
from repro.models.xlstm import (apply_mlstm_block, apply_slstm_block,
                                init_mlstm_cache, init_slstm_cache,
                                mlstm_specs, slstm_specs)
from repro.models.params import stack_specs

ATTN_KINDS = ("attn", "attn_local")


def _remat_group(n_periods: int) -> int:
    """Largest divisor of n_periods not exceeding sqrt(n_periods)."""
    if n_periods < 4:
        return 1
    best = 1
    d = 1
    while d * d <= n_periods:
        if n_periods % d == 0:
            best = d
        d += 1
    return best


@dataclasses.dataclass(frozen=True)
class LayerDesc:
    kind: str                 # attn | attn_local | rglru | mlstm | slstm
    ffn: str                  # dense | dense0 | moe | none
    cross: bool = False       # decoder cross-attention (enc-dec)


@dataclasses.dataclass(frozen=True)
class LayerPlan:
    prefix: tuple             # LayerDescs unrolled before the periodic body
    period: tuple             # LayerDescs of one period
    n_periods: int

    @property
    def n_layers(self):
        return len(self.prefix) + len(self.period) * self.n_periods


def _descriptors(cfg: ArchConfig, n_layers: int, cross: bool) -> list:
    pattern = cfg.pattern_for(n_layers)
    descs = []
    for i, kind in enumerate(pattern):
        if kind in ("mlstm", "slstm"):
            ffn = "none"
        elif cfg.moe is not None:
            ffn = "moe" if i >= cfg.moe.first_moe_layer else "dense0"
        else:
            ffn = "dense"
        descs.append(LayerDesc(kind=kind, ffn=ffn, cross=cross))
    return descs


def make_plan(cfg: ArchConfig, n_layers: Optional[int] = None,
              cross: bool = False) -> LayerPlan:
    descs = _descriptors(cfg, n_layers or cfg.n_layers, cross)
    best = None
    for prefix_len in range(len(descs)):
        rest = descs[prefix_len:]
        if not rest:
            break
        for p in range(1, len(rest) + 1):
            if len(rest) % p:
                continue
            if all(rest[i] == rest[i % p] for i in range(len(rest))):
                cand = LayerPlan(prefix=tuple(descs[:prefix_len]),
                                 period=tuple(rest[:p]),
                                 n_periods=len(rest) // p)
                cost = prefix_len + p          # traced layers
                if best is None or cost < best[0]:
                    best = (cost, cand)
                break
    assert best is not None
    return best[1]


# --------------------------------------------------------------------------
# Per-block specs / apply
# --------------------------------------------------------------------------

def block_specs(cfg: ArchConfig, desc: LayerDesc):
    s: dict = {"norm1": norm_specs(cfg)}
    if desc.kind in ATTN_KINDS:
        s["attn"] = attn_specs(cfg)
    elif desc.kind == "rglru":
        s["rglru"] = rglru_specs(cfg)
    elif desc.kind == "mlstm":
        s["mlstm"] = mlstm_specs(cfg)
    elif desc.kind == "slstm":
        s["slstm"] = slstm_specs(cfg)
    else:
        raise ValueError(desc.kind)
    if desc.cross:
        s["norm_cross"] = norm_specs(cfg)
        s["cross"] = attn_specs(cfg, cross=True)
    if desc.ffn == "dense":
        s["norm2"] = norm_specs(cfg)
        s["ffn"] = ffn_specs(cfg)
    elif desc.ffn == "dense0":
        s["norm2"] = norm_specs(cfg)
        s["ffn"] = ffn_specs(cfg, d_ff=cfg.moe.dense_d_ff or cfg.d_ff)
    elif desc.ffn == "moe":
        s["norm2"] = norm_specs(cfg)
        s["moe"] = moe_specs(cfg)
    return s


@dataclasses.dataclass
class BlockCtx:
    """Trace-time context threaded through every block."""
    cfg: ArchConfig
    mode: str                         # train | prefill | decode
    positions: Any                    # (B,S) or (B,S,3); decode: current idx
    attn_fn: Any
    causal: bool = True
    enc_out: Any = None               # encoder memory for cross-attn
    shard_fn: Any = staticmethod(lambda a, *names: a)
    decode_idx: Any = None            # scalar int32 in decode/prefill-resume
    window_cache: bool = False        # rolling window KV cache
    ragged_kernel: bool = False       # per-slot decode via Pallas kernel
    decode_write_mask: Any = None     # (B,) bool: rows allowed to write
    page_table: Any = None            # (B, max_pages) int32: paged KV cache
    #                                   (DESIGN.md §13); None = contiguous


def _attn_cache_write(cache, k_new, v_new, idx, window: int, rolling: bool,
                      write_mask=None):
    idx = jnp.asarray(idx)
    if idx.ndim == 1:
        # per-slot write positions (continuous batching): batch row b lands
        # at idx[b]; rows whose index ran past the buffer end write nowhere
        # (retired slots decoding into the masked void).  ``write_mask``
        # additionally gates whole rows — the fused decode horizon passes
        # the live-slot mask so finished slots stop writing mid-horizon.
        slot = idx % window if (rolling and window > 0) else idx
        smax = cache["k"].shape[1]
        hit = jnp.arange(smax)[None, :] == slot[:, None]     # (B, Smax)
        if write_mask is not None:
            hit &= write_mask[:, None]
        k = jnp.where(hit[..., None, None], k_new, cache["k"])
        v = jnp.where(hit[..., None, None], v_new, cache["v"])
        return {"k": k, "v": v}
    if rolling and window > 0:
        slot = idx % window
    else:
        slot = idx
    k = jax.lax.dynamic_update_slice_in_dim(cache["k"], k_new, slot, axis=1)
    v = jax.lax.dynamic_update_slice_in_dim(cache["v"], v_new, slot, axis=1)
    return {"k": k, "v": v}


def _attn_cache_write_paged(cache, k_new, v_new, idx, page_table,
                            write_mask=None):
    """Scatter one decode step's k/v into a PAGED cache.

    ``cache``: {"k": (N, page_size, Hkv, dh), "v": ...} physical pages
    shared by every slot; ``idx``: (B,) per-slot positions;
    ``page_table``: (B, max_pages) int32 mapping each slot's logical page
    j to a physical page (sentinel N = unmapped).  Row b lands at flat
    position ``pt[b, idx[b]//ps] * ps + idx[b] % ps``; rows that must not
    write — retired slots past max_len, write_mask-off rows, sentinel
    pages — are sent out of bounds, where ``mode="drop"`` discards them.
    No aliasing: live slots own pairwise-disjoint pages (PagePool
    invariant), so distinct rows always scatter to distinct flat rows."""
    n, ps = cache["k"].shape[0], cache["k"].shape[1]
    max_pages = page_table.shape[1]
    max_len = max_pages * ps
    idx = jnp.asarray(idx)
    logical = jnp.clip(idx // ps, 0, max_pages - 1)
    phys = jnp.take_along_axis(page_table.astype(jnp.int32),
                               logical[:, None], axis=1)[:, 0]
    flat = phys * ps + idx % ps
    oob = jnp.int32(n * ps)
    flat = jnp.where(idx < max_len, flat, oob)
    if write_mask is not None:
        flat = jnp.where(write_mask, flat, oob)
    tail = cache["k"].shape[2:]
    k = cache["k"].reshape((n * ps,) + tail).at[flat].set(
        k_new[:, 0], mode="drop").reshape(cache["k"].shape)
    v = cache["v"].reshape((n * ps,) + tail).at[flat].set(
        v_new[:, 0], mode="drop").reshape(cache["v"].shape)
    return {"k": k, "v": v}


def ragged_kv_block(smax: int, target: int = 256) -> int:
    """Largest divisor of the cache length <= ``target`` — the ragged
    decode kernel requires kv_block | Smax, and Smax (= engine max_len)
    is static.  Raises when that divisor degrades below
    ``min(64, Smax)``: a near-prime Smax has only tiny divisors, and a
    1-wide kv block means Smax sequential grid steps per layer."""
    kb = next(d for d in range(min(target, smax), 0, -1) if smax % d == 0)
    if kb < min(64, smax):
        raise ValueError(
            f"the ragged decode kernel cannot take max_len={smax}: its "
            f"largest divisor <= {target} is {kb}, below the "
            f"{min(64, smax)}-wide kv block the kernel needs; pick a "
            f"max_len with a divisor in [64, {target}] (a multiple of "
            f"64) or serve without use_ragged_kernel")
    return kb


def _decode_valid_mask(smax, idx, window: int, rolling: bool):
    j = jnp.arange(smax)
    if rolling and window > 0:
        # entries are the last `window` absolute positions; before the
        # buffer wraps, slots beyond idx are empty
        return j <= jnp.maximum(idx, window - 1) if False else (
            (j <= idx) | (idx >= window))
    return j <= idx


def _self_attention(p, h, ctx: BlockCtx, window: int, cache):
    cfg = ctx.cfg
    q = project_q(p, h, cfg)
    k, v = project_kv(p, h, cfg)
    if cfg.pos != "none":
        if ctx.mode == "decode":
            pos = ctx.positions  # (B, 1) or (B, 1, 3) absolute
        else:
            pos = ctx.positions
        q = apply_rope(q, pos, cfg)
        k = apply_rope(k, pos, cfg)

    new_cache = cache
    if ctx.mode == "decode" and ctx.page_table is not None:
        # paged KV cache (DESIGN.md §13): scatter through the page table,
        # attend via the page-gather kernel (TPU) or its jnp oracle.
        # Engine-side eligibility (Model.supports_paged_cache) guarantees
        # full-context attention only — no rolling windows here.
        from repro.models.attention import attention_decode_paged
        new_kv = _attn_cache_write_paged(
            cache, k, v, ctx.decode_idx, ctx.page_table,
            write_mask=ctx.decode_write_mask)
        ps = new_kv["k"].shape[1]
        if ctx.ragged_kernel and jnp.ndim(ctx.decode_idx) == 1:
            from repro.kernels.flash_attention.ops import \
                paged_flash_decode_attention
            out = paged_flash_decode_attention(
                q, new_kv["k"], new_kv["v"], ctx.page_table,
                ctx.decode_idx, softcap=cfg.attn_logit_softcap)
        else:
            out = attention_decode_paged(
                q, new_kv["k"], new_kv["v"], ctx.page_table,
                ctx.decode_idx, page_size=ps,
                max_len=ctx.page_table.shape[1] * ps,
                softcap=cfg.attn_logit_softcap)
        return jnp.einsum("bshk,hkd->bsd", out,
                          p["wo"].astype(h.dtype)), new_kv
    if ctx.mode == "decode":
        rolling = ctx.window_cache and window > 0
        new_kv = _attn_cache_write(cache, k, v, ctx.decode_idx, window,
                                   rolling, write_mask=ctx.decode_write_mask)
        if rolling:
            # every live slot holds one of the last `window` positions; only
            # not-yet-written slots (buffer not full) are invalid
            smax = cache["k"].shape[1]
            idx = jnp.asarray(ctx.decode_idx)
            j = jnp.arange(smax)
            if idx.ndim == 1:           # per-slot ragged positions
                valid = (j[None, :] <= idx[:, None]) | (idx[:, None] >= smax)
            else:
                valid = (j <= idx) | (idx >= smax)
            out = attention_decode(q, new_kv["k"], new_kv["v"],
                                   ctx.decode_idx, valid_mask=valid,
                                   softcap=cfg.attn_logit_softcap)
        elif (ctx.ragged_kernel and window == 0
                and jnp.ndim(ctx.decode_idx) == 1):
            # per-slot full-context decode: the ragged Pallas kernel skips
            # whole kv blocks past each slot's length (TPU data path;
            # interpret mode on CPU — kernels.mode decides per backend)
            from repro.kernels.flash_attention.ops import \
                flash_decode_attention
            out = flash_decode_attention(
                q, new_kv["k"], new_kv["v"], ctx.decode_idx,
                softcap=cfg.attn_logit_softcap,
                kv_block=ragged_kv_block(new_kv["k"].shape[1]))
        else:
            out = attention_decode(q, new_kv["k"], new_kv["v"],
                                   ctx.decode_idx, window=window,
                                   softcap=cfg.attn_logit_softcap)
        new_cache = new_kv
    else:
        out = ctx.attn_fn(q, k, v, causal=ctx.causal, window=window,
                          softcap=cfg.attn_logit_softcap)
        if ctx.mode == "prefill":
            if ctx.window_cache and window > 0:
                s = k.shape[1]
                if s >= window:
                    # keep the last `window` positions at slot = pos % window
                    # so decode's rolling writes line up
                    idx0 = s - window
                    k_tail = jnp.roll(k[:, idx0:], idx0 % window, axis=1)
                    v_tail = jnp.roll(v[:, idx0:], idx0 % window, axis=1)
                else:
                    pad = [(0, 0), (0, window - s), (0, 0), (0, 0)]
                    k_tail, v_tail = jnp.pad(k, pad), jnp.pad(v, pad)
                new_cache = {"k": k_tail, "v": v_tail}
            else:
                # write the prompt into the (possibly longer) decode buffer
                new_cache = {
                    "k": jax.lax.dynamic_update_slice_in_dim(
                        cache["k"], k, 0, axis=1),
                    "v": jax.lax.dynamic_update_slice_in_dim(
                        cache["v"], v, 0, axis=1)}
    return jnp.einsum("bshk,hkd->bsd", out,
                      p["wo"].astype(h.dtype)), new_cache


def _cross_attention(p, h, ctx: BlockCtx, cache):
    cfg = ctx.cfg
    q = project_q(p, h, cfg)
    if ctx.mode == "decode":
        k, v = cache["k"], cache["v"]
        new_cache = cache
    else:
        k, v = project_kv(p, ctx.enc_out, cfg)
        new_cache = {"k": k, "v": v} if ctx.mode == "prefill" else cache
    out = ctx.attn_fn(q, k, v, causal=False, window=0, softcap=0.0) \
        if ctx.mode != "decode" else attention_decode(
            q, k, v, jnp.asarray(k.shape[1] - 1, jnp.int32))
    return jnp.einsum("bshk,hkd->bsd", out,
                      p["wo"].astype(h.dtype)), new_cache


def apply_block(p, x, desc: LayerDesc, ctx: BlockCtx, cache=None):
    """-> (x, new_cache, aux_loss)."""
    cfg = ctx.cfg
    aux = jnp.zeros((), jnp.float32)
    h = apply_norm(p["norm1"], x, cfg.norm)
    window = cfg.attn_window if desc.kind == "attn_local" else 0

    sub_cache = cache or {}
    new_cache = dict(sub_cache)
    if desc.kind in ATTN_KINDS:
        out, c = _self_attention(p["attn"], h, ctx, window,
                                 sub_cache.get("attn"))
        if c is not None and ctx.mode != "train":
            new_cache["attn"] = c
    elif desc.kind == "rglru":
        out, c = apply_rglru_block(p["rglru"], h, cfg,
                                   sub_cache.get("rglru"))
        if c is not None:
            new_cache["rglru"] = c
    elif desc.kind == "mlstm":
        out, c = apply_mlstm_block(p["mlstm"], h, cfg,
                                   sub_cache.get("mlstm"))
        if c is not None:
            new_cache["mlstm"] = c
    else:  # slstm
        out, c = apply_slstm_block(p["slstm"], h, cfg,
                                   sub_cache.get("slstm"))
        if c is not None:
            new_cache["slstm"] = c
    x = x + out

    if desc.cross:
        hc = apply_norm(p["norm_cross"], x, cfg.norm)
        out, c = _cross_attention(p["cross"], hc, ctx,
                                  sub_cache.get("cross"))
        if c is not None and ctx.mode != "train":
            new_cache["cross"] = c
        x = x + out

    if desc.ffn in ("dense", "dense0"):
        h2 = apply_norm(p["norm2"], x, cfg.norm)
        x = x + apply_ffn(p["ffn"], h2, cfg.act)
    elif desc.ffn == "moe":
        h2 = apply_norm(p["norm2"], x, cfg.norm)
        out, aux = apply_moe(p["moe"], h2, cfg, shard_fn=ctx.shard_fn)
        x = x + out
    return x, (new_cache or None), aux


# --------------------------------------------------------------------------
# Stack: prefix (unrolled) + body (scanned periods)
# --------------------------------------------------------------------------

def stack_specs_tree(cfg: ArchConfig, plan: LayerPlan):
    prefix = [block_specs(cfg, d) for d in plan.prefix]
    period = [block_specs(cfg, d) for d in plan.period]
    body = [stack_specs(s, plan.n_periods) for s in period]
    return {"prefix": prefix, "body": body}


def init_stack_cache(cfg: ArchConfig, plan: LayerPlan, batch: int,
                     max_len: int, enc_len: int = 0,
                     window_cache: bool = False, page_size: int = 0,
                     n_pages: int = 0):
    """Materialized (zeros) cache for the whole stack.

    ``page_size > 0`` selects the PAGED layout (DESIGN.md §13): each
    attention layer's k/v become ``(n_pages, page_size, Hkv, dh)``
    physical pages with no batch axis — slots address them through the
    shared page table the model threads via ``BlockCtx.page_table``."""
    def one(desc: LayerDesc):
        c = {}
        if desc.kind in ATTN_KINDS:
            window = cfg.attn_window if desc.kind == "attn_local" else 0
            s = min(max_len, window) if (window_cache and window) else max_len
            dt = jnp.dtype(cfg.compute_dtype)
            if page_size > 0:
                assert not (window_cache and window), \
                    "paged cache excludes rolling-window layers"
                shape = (n_pages, page_size, cfg.n_kv_heads, cfg.head_dim)
                c["attn"] = {"k": jnp.zeros(shape, dt),
                             "v": jnp.zeros(shape, dt)}
                return c
            c["attn"] = {
                "k": jnp.zeros((batch, s, cfg.n_kv_heads, cfg.head_dim), dt),
                "v": jnp.zeros((batch, s, cfg.n_kv_heads, cfg.head_dim), dt)}
        elif desc.kind == "rglru":
            c["rglru"] = init_rglru_cache(cfg, batch)
        elif desc.kind == "mlstm":
            c["mlstm"] = init_mlstm_cache(cfg, batch)
        elif desc.kind == "slstm":
            c["slstm"] = init_slstm_cache(cfg, batch)
        if desc.cross:
            dt = jnp.dtype(cfg.compute_dtype)
            c["cross"] = {
                "k": jnp.zeros((batch, enc_len, cfg.n_kv_heads, cfg.head_dim),
                               dt),
                "v": jnp.zeros((batch, enc_len, cfg.n_kv_heads, cfg.head_dim),
                               dt)}
        return c

    prefix = [one(d) for d in plan.prefix]
    body = [jax.tree.map(
        lambda a: jnp.broadcast_to(a, (plan.n_periods,) + a.shape).copy(),
        one(d)) for d in plan.period]
    return {"prefix": prefix, "body": body}


def apply_stack(params, x, cfg: ArchConfig, plan: LayerPlan, ctx: BlockCtx,
                cache=None, remat: bool = True):
    """-> (x, new_cache, aux_sum)."""
    def reshard(a):
        # residual-stream constraint: batch over data; seq over model when
        # the rule set enables sequence parallelism (no-op otherwise)
        return ctx.shard_fn(a, "batch", "seq", None)

    aux_total = jnp.zeros((), jnp.float32)
    x = reshard(x)
    new_prefix_cache = []
    for i, desc in enumerate(plan.prefix):
        c = cache["prefix"][i] if cache is not None else None
        fn = partial(apply_block, desc=desc, ctx=ctx)
        if remat and ctx.mode == "train":
            fn = jax.checkpoint(fn, static_argnums=())
        x, c_new, aux = fn(params["prefix"][i], x, cache=c)
        x = reshard(x)
        new_prefix_cache.append(c_new)
        aux_total = aux_total + aux

    # one scan over periods; each step applies every position of the period
    # in layer order
    has_cache = cache is not None
    p_body = tuple(params["body"])
    c_body = tuple(cache["body"]) if has_cache else None

    def body_fn(carry, xs):
        xx, aux_acc = carry
        p_list, c_list = xs if has_cache else (xs, (None,) * len(p_body))
        c_news = []
        for pos, desc in enumerate(plan.period):
            blk = partial(apply_block, desc=desc, ctx=ctx)
            if remat and ctx.mode == "train" and len(plan.period) > 1:
                # nested remat: the period recompute re-checkpoints each
                # block so only one block's inner-scan residuals are ever
                # live during the backward pass
                blk = jax.checkpoint(blk)
            xx, c_new, aux = blk(p_list[pos], xx, cache=c_list[pos])
            xx = reshard(xx)
            aux_acc = aux_acc + aux
            c_news.append(c_new)
        return (xx, aux_acc), (tuple(c_news) if has_cache else 0)

    train_remat = remat and ctx.mode == "train"
    group = _remat_group(plan.n_periods) if train_remat else 1
    if plan.n_periods and group > 1 and not has_cache:
        # sqrt-remat: outer scan over groups (saves only group-boundary
        # activations), inner scan over the group's periods, each period
        # itself checkpointed.  Residual memory ~ (n/g + g) layer inputs
        # instead of n.
        n_groups = plan.n_periods // group
        p_grouped = jax.tree.map(
            lambda a: a.reshape((n_groups, group) + a.shape[1:]), p_body)

        def group_fn(carry, xs_g):
            return jax.lax.scan(jax.checkpoint(body_fn), carry, xs_g)

        (x, aux_total), _ = jax.lax.scan(
            jax.checkpoint(group_fn), (x, aux_total), p_grouped)
        c_out = ()
    elif plan.n_periods:
        scan_fn = jax.checkpoint(body_fn) if train_remat else body_fn
        xs = (p_body, c_body) if has_cache else p_body
        (x, aux_total), c_out = jax.lax.scan(scan_fn, (x, aux_total), xs)
    else:
        c_out = ()

    new_cache = None
    if has_cache:
        new_cache = {"prefix": new_prefix_cache,
                     "body": list(c_out) if plan.n_periods else []}
    return x, new_cache, aux_total
