"""Step builders: jitted train / prefill / decode steps with sharding, plus
the shard_map DDP step whose gradient sync goes through the endpoint engine
(the paper's technique as a first-class feature)."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.comm.engine import GradSyncEngine
from repro.core.endpoints import Category
from repro.launch.mesh import data_axes
from repro.models.model import Model
from repro.optim.adamw import AdamW


def make_train_step(model: Model, opt: AdamW, shard_fn=None,
                    remat: bool = True, accum_steps: int = 1,
                    cast_params_once: bool = False):
    """Jitted train step; ``accum_steps`` > 1 splits the global batch into
    microbatches scanned with fp32 gradient accumulation (bounds the live
    activation set to one microbatch — required at 72B/48L scales)."""
    shard_fn = shard_fn or (lambda a, *n: a)

    def grad_fn(params, batch):
        def loss_fn(p):
            return model.loss_fn(p, batch, shard_fn=shard_fn, remat=remat,
                                 cast_params_once=cast_params_once)
        return jax.value_and_grad(loss_fn, has_aux=True)(params)

    def train_step(params, opt_state, batch):
        if accum_steps == 1:
            (_, metrics), grads = grad_fn(params, batch)
        else:
            micro = jax.tree.map(
                lambda x: shard_fn(
                    x.reshape((accum_steps, x.shape[0] // accum_steps)
                              + x.shape[1:]),
                    None, "batch", *([None] * (x.ndim - 1))), batch)

            def body(acc, mb):
                mb = jax.tree.map(
                    lambda x: shard_fn(x, "batch",
                                       *([None] * (x.ndim - 1))), mb)
                (_, metrics), grads = grad_fn(params, mb)
                g_acc, m_acc = acc
                g_acc = jax.tree.map(
                    lambda a, g: a + g.astype(jnp.float32), g_acc, grads)
                m_acc = jax.tree.map(lambda a, m: a + m, m_acc, metrics)
                return (g_acc, m_acc), None

            zeros_g = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            # metrics accumulator built from a structural eval_shape
            metrics_shape = jax.eval_shape(
                grad_fn, params, jax.tree.map(lambda x: x[0], micro))[0][1]
            zeros_m = jax.tree.map(
                lambda s: jnp.zeros(s.shape, s.dtype), metrics_shape)
            (grads, metrics), _ = jax.lax.scan(
                body, (zeros_g, zeros_m), micro)
            inv = 1.0 / accum_steps
            grads = jax.tree.map(lambda g: g * inv, grads)
            metrics = jax.tree.map(lambda m: m * inv, metrics)

        new_params, new_state, gnorm = opt.step(grads, opt_state, params)
        metrics = dict(metrics)
        metrics["grad_norm"] = gnorm
        return new_params, new_state, metrics

    return train_step


def make_prefill_step(model: Model, shard_fn=None,
                      skip_future: bool = False):
    """skip_future=False keeps the dry-run/roofline records on the
    paper-faithful masked schedule; the serving engine enables the
    triangular schedule (Model.prefill default)."""
    shard_fn = shard_fn or (lambda a, *n: a)

    def prefill_step(params, batch, cache):
        return model.prefill(params, batch, cache, shard_fn=shard_fn,
                             skip_future=skip_future)

    return prefill_step


def make_decode_step(model: Model, shard_fn=None):
    shard_fn = shard_fn or (lambda a, *n: a)
    uses_embeds = (model.cfg.input_mode == "embeddings"
                   and not model.cfg.is_encdec)

    if uses_embeds:
        def decode_step(params, cache, embeds):
            return model.decode_step(params, cache, embeds=embeds,
                                     shard_fn=shard_fn)
    else:
        def decode_step(params, cache, tokens):
            return model.decode_step(params, cache, tokens=tokens,
                                     shard_fn=shard_fn)
    return decode_step


# --------------------------------------------------------------------------
# Explicit-DP (shard_map) step with endpoint-engine gradient sync
# --------------------------------------------------------------------------

def make_ddp_train_step(model: Model, opt: AdamW, mesh,
                        category: Category = Category.TWO_X_DYNAMIC,
                        lanes: int = 16, compressor=None):
    """Data-parallel train step where the gradient reduction is scheduled by
    the scalable-endpoints engine (params replicated; batch sharded over the
    data axes).  Used by the small-model paths and the §Perf endpoint
    experiments."""
    axes = data_axes(mesh)
    engine = GradSyncEngine(category, axis_names=axes, lanes=lanes,
                            compressor=compressor, mean=True)

    def step(params, opt_state, batch, comp_state):
        def loss_fn(p):
            return model.loss_fn(p, batch)
        (_, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        grads, comp_state = engine(grads, comp_state)
        new_params, new_state, gnorm = opt.step(grads, opt_state, params)
        metrics = dict(metrics)
        metrics["grad_norm"] = gnorm
        metrics = jax.tree.map(lambda m: jax.lax.pmean(m, axes[0]), metrics)
        return new_params, new_state, metrics, comp_state

    batch_rank_specs = P(axes if len(axes) > 1 else axes[0])
    shard = partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(), P(), batch_rank_specs, P()),
        out_specs=(P(), P(), P(), P()))
    return shard(step), engine
