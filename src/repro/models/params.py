"""Parameter specification system with logical sharding axes.

Every parameter is declared once as a :class:`ParamSpec` carrying its shape,
initializer, and *logical axis names* (``"embed"``, ``"q_heads"``,
``"mlp"``, ``"vocab"``, ``"expert"``, ``"layers"``, ...).  The sharding
rules (launch/sharding.py) map logical axes onto mesh axes per run — the
MaxText-style separation that makes re-sharding a config change rather than
a code change.

``materialize`` builds real arrays, ``abstract`` builds ShapeDtypeStructs
(for eval_shape-free dry runs), ``axes_tree`` extracts the logical axes.
``serving_params`` binds a tree for serving in the compute dtype.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.trace import host_span

#: Wall-clock span of the one jitted cast ``serving_params`` makes (args
#: ``leaves``, ``bytes_before``, ``bytes_after``).
SPAN_BIND_WEIGHTS = "engine.bind_weights"

#: Block kinds whose subtree reads gates, biases and recurrences in
#: float32 (``recurrent.py``, ``xlstm.py``): a compute-dtype copy of it
#: would change their numbers, so serving keeps its stored dtype.
FLOAT32_READ_KINDS = ("rglru", "mlstm", "slstm")


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: tuple
    axes: tuple                     # logical axis names; len == rank
    init: str = "fan_in"            # fan_in | zeros | ones | normal | lambda_rglru
    dtype: Any = jnp.float32
    scale: Optional[float] = None   # stddev override for normal inits
    fan_in: Optional[int] = None    # override for fan_in init

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


def _tree_map(f, tree):
    return jax.tree.map(f, tree, is_leaf=is_spec)


def stack_specs(tree, n: int, axis_name: str = "layers"):
    """Add a leading stacking dimension (for scan-over-layers)."""
    return _tree_map(
        lambda s: dataclasses.replace(s, shape=(n,) + s.shape,
                                      axes=(axis_name,) + s.axes), tree)


def _init_one(spec: ParamSpec, key):
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, spec.dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, spec.dtype)
    if spec.init == "lambda_rglru":
        # RG-LRU Lambda param: a in [0.9, 0.999] -> log-space param
        # (Griffin/Orbax initialization range)
        u = jax.random.uniform(key, spec.shape, jnp.float32,
                               minval=0.9**2, maxval=0.999**2)
        val = jnp.log(jnp.exp(-jnp.log(u) / 2) - 1.0)  # softplus^-1
        return val.astype(spec.dtype)
    if spec.init == "normal":
        std = spec.scale if spec.scale is not None else 0.02
        return std * jax.random.normal(key, spec.shape, spec.dtype)
    if spec.init == "fan_in":
        # stacked specs: fan-in excludes the leading stack dims
        rank = len(spec.shape)
        fan_in = spec.fan_in or (
            spec.shape[-2] if rank >= 2 else spec.shape[-1])
        std = spec.scale if spec.scale is not None else fan_in ** -0.5
        return std * jax.random.normal(key, spec.shape, spec.dtype)
    raise ValueError(spec.init)


def materialize(spec_tree, key):
    leaves, treedef = jax.tree.flatten(spec_tree, is_leaf=is_spec)
    keys = jax.random.split(key, len(leaves))
    vals = [_init_one(s, k) for s, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, vals)


def abstract(spec_tree):
    return _tree_map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype),
                     spec_tree)


def axes_tree(spec_tree):
    return _tree_map(lambda s: s.axes, spec_tree)


def n_params(spec_tree) -> int:
    return sum(int(np.prod(s.shape))
               for s in jax.tree.leaves(spec_tree, is_leaf=is_spec))


@functools.partial(jax.jit, static_argnums=1)
def _astype(leaves, dtype):
    return [a.astype(dtype) for a in leaves]


def _read_in_compute_dtype(params, plan):
    """Tree of bools shaped like ``params``: True where the decoder only
    ever reads the leaf through ``.astype(compute_dtype)`` (embedding,
    untied head, final norm, and every block subtree but the recurrent
    ones).  Stacks the serving engines never run (an encoder) stay
    False."""
    served = ("embed", "final_norm", "decoder")
    mask = {k: jax.tree.map(lambda _: k in served, v)
            for k, v in params.items()}
    dec = mask["decoder"]
    for blocks, descs in ((dec["prefix"], plan.prefix),
                          (dec["body"], plan.period)):
        for blk, desc in zip(blocks, descs):
            if desc.kind in FLOAT32_READ_KINDS:
                blk[desc.kind] = jax.tree.map(lambda _: False,
                                              blk[desc.kind])
    return mask


def serving_params(params, cfg, plan):
    """Bind ``params`` for serving: -> ``(tree, binding)``.

    Every float32 leaf the decoder reads only through
    ``.astype(cfg.compute_dtype)`` is replaced by its compute-dtype copy,
    made in one jitted call, so no serving program converts weights on
    each step.  The model read each of them only through that cast, so
    no number it computes changes.  Other leaves, and leaves already
    in the compute dtype, are returned as they are: binding a bound tree
    makes no second copy.  ``plan`` is the model's ``LayerPlan``.
    ``binding`` counts the cast ``leaves`` and their ``bytes_before`` and
    ``bytes_after``."""
    dt = jnp.dtype(cfg.compute_dtype)
    leaves, treedef = jax.tree.flatten(params)
    idx = []
    if leaves and dt != jnp.float32:
        wanted = treedef.flatten_up_to(_read_in_compute_dtype(params, plan))
        idx = [i for i, (a, w) in enumerate(zip(leaves, wanted))
               if w and a.dtype == jnp.float32]
    binding = {"leaves": len(idx),
               "bytes_before": sum(leaves[i].nbytes for i in idx),
               "bytes_after": sum(leaves[i].size * dt.itemsize
                                  for i in idx)}
    if not idx:
        return params, binding
    with host_span(SPAN_BIND_WEIGHTS, **binding):
        cast = _astype(tuple(leaves[i] for i in idx), dt)
    for i, a in zip(idx, cast):
        leaves[i] = a
    return jax.tree.unflatten(treedef, leaves), binding
