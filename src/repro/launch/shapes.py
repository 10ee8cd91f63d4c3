"""The four assigned shape cells and per-(arch x shape) input specs.

Every spec is a ShapeDtypeStruct with a NamedSharding attached, so
``jit(step).lower(**specs)`` needs no separate in_shardings and allocates
nothing.  ``decode_*`` / ``long_*`` describe one serve_step with a KV cache
of the given context length; ``long_500k`` applies only to sub-quadratic
architectures (DESIGN.md §4).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.launch.sharding import batch_spec, kv_cache_spec
from repro.models.model import Model

ENC_STUB_LEN = 4096      # encoder memory length for enc-dec decode cells


@dataclasses.dataclass(frozen=True)
class ShapeCell:
    name: str
    kind: str            # train | prefill | decode
    seq: int
    batch: int


SHAPES = {
    "train_4k": ShapeCell("train_4k", "train", 4096, 256),
    "prefill_32k": ShapeCell("prefill_32k", "prefill", 32768, 32),
    "decode_32k": ShapeCell("decode_32k", "decode", 32768, 128),
    "long_500k": ShapeCell("long_500k", "decode", 524288, 1),
}


def cell_applicable(cfg: ArchConfig, cell: ShapeCell) -> tuple:
    """-> (applicable, reason)."""
    if cell.name == "long_500k" and not cfg.sub_quadratic:
        return False, ("full-attention architecture: 500k dense-attention "
                       "decode has no algorithmic support (designed skip, "
                       "DESIGN.md §4)")
    return True, ""


def _sds(shape, dtype, mesh, spec: P):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, spec))


def batch_specs(cfg: ArchConfig, cell: ShapeCell, mesh, rules=None) -> dict:
    """Model-input ShapeDtypeStructs for a cell (training / prefill)."""
    b, s = cell.batch, cell.seq
    bs = batch_spec(mesh, b, rules=rules)
    bax = bs[0] if len(bs) else None
    i32, cd = jnp.int32, jnp.dtype(cfg.compute_dtype)
    out = {}
    if cfg.is_encdec:
        enc_s = s if cell.kind == "train" else min(s, ENC_STUB_LEN)
        out["enc_embeds"] = _sds((b, enc_s, cfg.d_model), cd, mesh,
                                 P(bax, None, None))
        out["tokens"] = _sds((b, s), i32, mesh, P(bax, None))
    elif cfg.input_mode == "embeddings":
        out["embeds"] = _sds((b, s, cfg.d_model), cd, mesh, P(bax, None, None))
        if cfg.pos == "mrope":
            out["positions"] = _sds((b, s, 3), i32, mesh, P(bax, None, None))
    else:
        out["tokens"] = _sds((b, s), i32, mesh, P(bax, None))
    if cell.kind == "train":
        out["labels"] = _sds((b, s), i32, mesh, P(bax, None))
    return out


def _cache_spec_for(path_keys, leaf, cfg: ArchConfig, mesh, batch: int) -> P:
    """Sharding for one cache leaf, identified by its key path."""
    stacked = "body" in path_keys          # leading n_periods dim
    lead = (None,) if stacked else ()
    shape = leaf.shape[1:] if stacked else leaf.shape
    name = path_keys[-1]
    msize = mesh.shape.get("model", 1)
    bs = batch_spec(mesh, batch)
    bax = bs[0] if len(bs) else None

    if name in ("k", "v") and len(shape) == 3:
        # self-attention rows (B, S, width): whole heads over model, when
        # the row holds no lane padding
        spec = kv_cache_spec(mesh, batch, cfg.n_kv_heads, cfg.head_dim)
        heads = (len(spec) == 4 and spec[2] == "model"
                 and shape[2] == cfg.n_kv_heads * cfg.head_dim)
        return P(*lead, bax, None, "model" if heads else None)
    if name in ("k", "v"):
        spec = kv_cache_spec(mesh, batch, shape[2], shape[3])
        return P(*lead, *spec)
    # recurrent states: shard the (last) channel-ish dim over model if it
    # divides; batch over data
    parts = [bax] + [None] * (len(shape) - 1)
    for di in range(len(shape) - 1, 0, -1):
        if shape[di] % msize == 0:
            parts[di] = "model"
            break
    return P(*lead, *parts)


def cache_specs(model: Model, cell: ShapeCell, mesh) -> dict:
    cfg = model.cfg
    b = cell.batch
    enc_len = ENC_STUB_LEN if cfg.is_encdec else 0
    abstract = jax.eval_shape(
        lambda: model.init_cache(b, max_len=cell.seq, enc_len=enc_len))

    def one(path, leaf):
        keys = tuple(getattr(k, "key", getattr(k, "idx", None))
                     for k in path)
        keys = tuple(str(k) for k in keys)
        if keys[-1] == "idx":
            return _sds(leaf.shape, leaf.dtype, mesh, P())
        spec = _cache_spec_for(keys, leaf, cfg, mesh, b)
        return _sds(leaf.shape, leaf.dtype, mesh, spec)

    return jax.tree_util.tree_map_with_path(one, abstract)


def decode_token_specs(cfg: ArchConfig, cell: ShapeCell, mesh) -> dict:
    b = cell.batch
    bs = batch_spec(mesh, b)
    bax = bs[0] if len(bs) else None
    if cfg.input_mode == "embeddings" and not cfg.is_encdec:
        return {"embeds": _sds((b, cfg.d_model),
                               jnp.dtype(cfg.compute_dtype), mesh,
                               P(bax, None))}
    return {"tokens": _sds((b,), jnp.int32, mesh, P(bax))}
