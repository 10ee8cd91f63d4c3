"""GradSyncEngine: scalable endpoints applied to gradient synchronization.

Realizes the six endpoint categories as collective schedules for the
data-parallel gradient reduction inside a ``shard_map``ped train step:

  MPI everywhere  -> one psum per gradient tensor (max independence: many
                     small collectives, maximal overlap, alpha-dominated)
  2xDynamic       -> k byte-balanced buckets, double-buffered channels
  Dynamic         -> k byte-balanced buckets, one collective each
  Shared Dynamic  -> k/2 buckets
  Static          -> k/4 buckets
  MPI+threads     -> ONE fused collective for everything (min resources,
                     fully serialized behind a single dependency)

All categories are numerically identical (property-tested); they differ only
in the collective schedule the compiler sees, which is what the paper's
tradeoff is about.  ``sync_stride`` (Unsignaled analogue) optionally chains
every q-th bucket with a data dependency to bound in-flight buffers.
"""

from __future__ import annotations

from typing import Sequence, Union

import jax
import jax.numpy as jnp

from repro.core.channels import ChannelPlan, plan_for
from repro.core.endpoints import Category
from repro.comm.bucketing import (BucketPlan, make_bucket_plan, pack_buckets,
                                  unpack_buckets)
from repro.comm.compression import NoCompressor


class GradSyncEngine:
    """Bucketed gradient psum per the endpoint category.

    Usage (inside shard_map):
        engine = GradSyncEngine(Category.TWO_X_DYNAMIC, axis_names=("data",))
        plan = engine.make_plan(grads_shape)        # outside jit
        synced, comp_state = engine(grads, comp_state)   # inside
    """

    def __init__(self, category_or_plan: Union[Category, ChannelPlan],
                 axis_names: Sequence[str] = ("data",),
                 lanes: int = 16, sync_stride: int = 1,
                 compressor=None, mean: bool = True):
        if isinstance(category_or_plan, Category):
            self.plan = plan_for(category_or_plan, lanes=lanes,
                                 sync_stride=sync_stride)
        else:
            self.plan = category_or_plan
        self.axis_names = tuple(axis_names)
        self.compressor = compressor or NoCompressor()
        self.mean = mean

    # -- static planning (works on ShapeDtypeStructs) --------------------
    def make_plan(self, grads_tree) -> BucketPlan:
        return make_bucket_plan(grads_tree, self.plan)

    def init_compressor_state(self, grads_tree):
        if isinstance(self.compressor, NoCompressor):
            return ()
        bplan = self.make_plan(grads_tree)
        packed = pack_buckets(jax.tree.map(
            lambda l: jnp.zeros(l.shape, l.dtype), grads_tree), bplan)
        return [{name: jnp.zeros(arr.shape, jnp.float32)
                 for name, arr in b.items()} for b in packed]

    # -- the collective schedule -----------------------------------------
    def _psum(self, x):
        for ax in self.axis_names:
            x = jax.lax.psum(x, ax)
        return x

    def _pmax(self, x):
        for ax in self.axis_names:
            x = jax.lax.pmax(x, ax)
        return x

    def world_size(self):
        n = 1
        for ax in self.axis_names:
            n *= jax.lax.axis_size(ax)
        return n

    def __call__(self, grads, compressor_state=()):
        bplan = self.make_plan(grads)
        packed = pack_buckets(grads, bplan)

        new_state = []
        reduced = []
        prev_token = None
        for bi, per_dtype in enumerate(packed):
            out_b = {}
            st_b = {}
            for name, flat in per_dtype.items():
                # Unsignaled analogue: chain every sync_stride-th bucket on
                # the previous one so only q buckets are ever in flight.
                if (prev_token is not None and self.plan.sync_stride > 1
                        and bi % self.plan.sync_stride == 0):
                    flat = _add_dependency(flat, prev_token)
                if isinstance(self.compressor, NoCompressor):
                    out = self._psum(flat)
                else:
                    res = compressor_state[bi][name]
                    out, res = self.compressor.reduce(
                        flat, res, self._psum, self._pmax)
                    st_b[name] = res
                out_b[name] = out
                prev_token = out
            reduced.append(out_b)
            new_state.append(st_b)

        if self.mean:
            inv = 1.0 / self.world_size()
            reduced = [{n: (a * jnp.asarray(inv, a.dtype)) for n, a in
                        b.items()} for b in reduced]
        synced = unpack_buckets(reduced, bplan)
        if isinstance(self.compressor, NoCompressor):
            return synced, ()
        return synced, new_state


def _add_dependency(x, token):
    """Create a data dependency from ``token`` to ``x`` without changing
    ``x``'s value (forces the compiler to order the collectives)."""
    zero = (jnp.sum(token[:1]) * 0).astype(x.dtype)
    return x + zero


def sync_gradients(grads, category: Category,
                   axis_names: Sequence[str] = ("data",), **kw):
    """One-shot functional wrapper."""
    eng = GradSyncEngine(category, axis_names=axis_names, **kw)
    out, _ = eng(grads)
    return out
