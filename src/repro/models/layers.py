"""Shared layers: norms, rotary embeddings, FFN/GLU, embedding tables."""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.params import ParamSpec

# --------------------------------------------------------------------------
# Norms
# --------------------------------------------------------------------------

def norm_specs(cfg: ArchConfig, d: Optional[int] = None):
    d = d or cfg.d_model
    if cfg.norm == "rmsnorm":
        return {"scale": ParamSpec((d,), ("embed",), init="ones")}
    return {"scale": ParamSpec((d,), ("embed",), init="ones"),
            "bias": ParamSpec((d,), ("embed",), init="zeros")}


NORM_EPS = 1e-6


def _row_stats(x, kind):
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        return None, jax.lax.rsqrt(var + NORM_EPS)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True) - jnp.square(mean)
    return mean, jax.lax.rsqrt(var + NORM_EPS)


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _norm_core(x, scale, bias, kind):
    mean, inv = _row_stats(x, kind)
    if kind == "rmsnorm":
        return x * inv.astype(x.dtype) * scale.astype(x.dtype)
    xhat = (x - mean.astype(x.dtype)) * inv.astype(x.dtype)
    return xhat * scale.astype(x.dtype) + bias.astype(x.dtype)


def _norm_fwd(x, scale, bias, kind):
    # save the (B, S, 1) fp32 row stats: recomputing them in the backward
    # would convert(x) per step, which XLA commutes with the residual-stack
    # slice and hoists into a whole-stack fp32 copy (+100% memory)
    mean, inv = _row_stats(x, kind)
    return _norm_core(x, scale, bias, kind), (x, scale, bias, mean, inv)


def _match_vma(cot, primal_like, data_like):
    """Under shard_map, the cotangent of a replicated (unvarying) primal
    must itself be unvarying: psum over the axes the data varies on —
    which is exactly the correct gradient reduction for replicated
    parameters."""
    extra = tuple(sorted(jax.typeof(cot).vma - jax.typeof(primal_like).vma))
    if extra:
        cot = jax.lax.psum(cot, extra)
    return cot


def _norm_bwd(kind, res, dy):
    """Backward in terms of the bf16 x and f32 ROW statistics only.

    Autodiff of a norm needs the full fp32 copy of x (d var/dx); inside a
    remat'd scan-over-layers XLA then hoists one whole-stack bf16->f32
    convert out of the backward loop (+100% saved-residual memory, measured
    on the 72B cell).  This custom VJP is the standard fused-norm backward:
      rms:  dx = inv*g - x * inv^3/N * sum(g*x);        g = dy*scale
      ln :  dx = inv*(g - mean(g) - xhat*mean(g*xhat))
    with every full-size tensor in x.dtype and only (B,S,1) stats in fp32.
    """
    x, scale, bias, mean, inv = res
    n = x.shape[-1]
    g = dy * scale.astype(dy.dtype)
    if kind == "rmsnorm":
        s = jnp.sum((g * x).astype(jnp.float32), axis=-1, keepdims=True)
        coef = (inv ** 3 / n) * s
        dx = (g * inv.astype(g.dtype) - x * coef.astype(g.dtype)
              ).astype(x.dtype)
        xhat_scaled = x * inv.astype(x.dtype)
        dscale = jnp.sum((dy * xhat_scaled).astype(jnp.float32),
                         axis=tuple(range(dy.ndim - 1)))
        dscale = _match_vma(dscale.astype(scale.dtype), scale, dy)
        return dx, dscale, _match_vma(jnp.zeros_like(bias), bias, dy)
    xhat = (x - mean.astype(x.dtype)) * inv.astype(x.dtype)
    gm = jnp.mean(g.astype(jnp.float32), axis=-1, keepdims=True)
    gxm = jnp.mean((g * xhat).astype(jnp.float32), axis=-1, keepdims=True)
    dx = ((g - gm.astype(g.dtype) - xhat * gxm.astype(g.dtype))
          * inv.astype(g.dtype)).astype(x.dtype)
    dscale = jnp.sum((dy * xhat).astype(jnp.float32),
                     axis=tuple(range(dy.ndim - 1)))
    dbias = jnp.sum(dy.astype(jnp.float32), axis=tuple(range(dy.ndim - 1)))
    return (dx, _match_vma(dscale.astype(scale.dtype), scale, dy),
            _match_vma(dbias.astype(scale.dtype), bias, dy))


_norm_core.defvjp(_norm_fwd, _norm_bwd)


def apply_norm(p, x, kind: str, eps: float = 1e-6):
    bias = p.get("bias")
    if bias is None:
        bias = jnp.zeros((), x.dtype)
    return _norm_core(x, p["scale"], bias, kind)


def rms_group_norm(x, scale, n_groups: int, eps: float = 1e-6):
    """Head-wise group RMS norm (used by the xLSTM cells)."""
    b, s, d = x.shape
    xf = x.astype(jnp.float32).reshape(b, s, n_groups, d // n_groups)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    out = (xf * jax.lax.rsqrt(var + eps)).reshape(b, s, d)
    return (out * scale.astype(jnp.float32)).astype(x.dtype)


# --------------------------------------------------------------------------
# Rotary position embeddings (RoPE / partial RoPE / M-RoPE)
# --------------------------------------------------------------------------

def _rope_angles(positions, dim: int, theta: float):
    """positions (...,) -> cos/sin (..., dim/2)."""
    freqs = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.cos(ang), jnp.sin(ang)


def _rotate(x, cos, sin):
    # x: (..., dim); rotate-half convention
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def apply_rope(x, positions, cfg: ArchConfig):
    """x: (B, S, H, Dh); positions: (B, S) or (B, S, 3) for M-RoPE."""
    dh = x.shape[-1]
    rot = int(dh * cfg.rope_fraction)
    rot -= rot % 2
    x_rot, x_pass = x[..., :rot], x[..., rot:]

    if cfg.pos == "mrope":
        # Multimodal RoPE (Qwen2-VL): the rotary half-dims are split into
        # (t, h, w) sections, each rotated by its own position stream.
        # positions: (B, S, 3).
        sections = cfg.mrope_sections or (rot // 2,)
        assert sum(sections) == rot // 2, (sections, rot)
        cos_parts, sin_parts = [], []
        for si, sec in enumerate(sections):
            pos = positions[..., si]
            freqs_idx = jnp.arange(sum(sections[:si]) * 2,
                                   sum(sections[:si + 1]) * 2, 2)
            freqs = cfg.rope_theta ** (
                -freqs_idx.astype(jnp.float32) / rot)
            ang = pos[..., None].astype(jnp.float32) * freqs
            cos_parts.append(jnp.cos(ang))
            sin_parts.append(jnp.sin(ang))
        cos = jnp.concatenate(cos_parts, -1)[:, :, None, :]
        sin = jnp.concatenate(sin_parts, -1)[:, :, None, :]
    else:
        cos, sin = _rope_angles(positions, rot, cfg.rope_theta)
        cos, sin = cos[:, :, None, :], sin[:, :, None, :]

    # split-half rotation over the rotary slice
    x1, x2 = jnp.split(x_rot.astype(jnp.float32), 2, axis=-1)
    rotated = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)
    return jnp.concatenate([rotated, x_pass], axis=-1) if rot < dh else rotated


# --------------------------------------------------------------------------
# FFN (dense)
# --------------------------------------------------------------------------

def ffn_specs(cfg: ArchConfig, d_ff: Optional[int] = None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    if cfg.act in ("swiglu", "geglu"):
        return {"w_gate": ParamSpec((d, f), ("embed", "mlp")),
                "w_up": ParamSpec((d, f), ("embed", "mlp")),
                "w_down": ParamSpec((f, d), ("mlp", "embed"))}
    return {"w_up": ParamSpec((d, f), ("embed", "mlp")),
            "w_down": ParamSpec((f, d), ("mlp", "embed"))}


def apply_ffn(p, x, act: str):
    dt = x.dtype
    if act in ("swiglu", "geglu"):
        gate = x @ p["w_gate"].astype(dt)
        up = x @ p["w_up"].astype(dt)
        h = (jax.nn.silu(gate) if act == "swiglu"
             else jax.nn.gelu(gate, approximate=True)) * up
    else:
        h = jax.nn.gelu(x @ p["w_up"].astype(dt), approximate=True)
    return h @ p["w_down"].astype(dt)


# --------------------------------------------------------------------------
# Embeddings / LM head
# --------------------------------------------------------------------------

def embed_specs(cfg: ArchConfig):
    out = {"tok": ParamSpec((cfg.vocab, cfg.d_model), ("vocab", "embed"),
                            init="normal", scale=0.02)}
    if not cfg.tie_embeddings:
        out["head"] = ParamSpec((cfg.d_model, cfg.vocab),
                                ("embed", "vocab"))
    return out


def embed_tokens(p, tokens, cfg: ArchConfig):
    emb = jnp.take(p["tok"], tokens, axis=0)
    return emb.astype(cfg.compute_dtype)


def head_matrix(p, cfg: ArchConfig):
    """(d_model, vocab) projection, tied or untied."""
    if cfg.tie_embeddings:
        return p["tok"].T
    return p["head"]
