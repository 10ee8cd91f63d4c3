"""Serving binds its weights in the compute dtype once (DESIGN.md §6.2).

``serving_params`` hands the engines a tree whose float32 leaves the
decoder reads only through ``.astype(compute_dtype)`` are already in the
compute dtype, so no serving program converts weights on each step.  The
values every matmul consumes are the same, so an engine given the float32
tree serves tokens, and computes first-round logits, bit-identical to the
same programs called with the float32 tree itself.  Recurrent subtrees
read their gates in float32 and keep it; training keeps its own cast.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import serve
from repro.configs import ARCHS, get_smoke_config
from repro.models.model import Model
from repro.models.params import FLOAT32_READ_KINDS, serving_params
from repro.serve.engine import ContinuousEngine, Request

MAX_LEN = 32
LENGTHS = (5, 9, 12)                # one admission round, bucket 16
MAX_NEW = 6

#: every smoke arch the continuous engine serves (decoder-only tokens)
SERVED = [a for a in ARCHS
          if get_smoke_config(a).input_mode == "tokens"
          and not get_smoke_config(a).is_encdec]


@functools.lru_cache(maxsize=None)
def _served(arch):
    cfg = get_smoke_config(arch)
    return cfg, Model(cfg).init(jax.random.PRNGKey(0))


def _prompts(cfg):
    rng = np.random.default_rng(7)
    return [rng.integers(1, cfg.vocab, n).astype(np.int32) for n in LENGTHS]


def _serve(eng, prompts):
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=MAX_NEW))
    return {r.rid: list(r.output) for r in eng.run()}


def _first_round_logits(model, params, prompts):
    """The logits of the engine's first admission round: one padded
    batched prefill gathered at each row's last real token where the model
    allows padding, else one exact-length prefill per prompt."""
    if model.supports_padded_prefill:
        toks = np.zeros((len(prompts), 16), np.int32)
        for i, p in enumerate(prompts):
            toks[i, :len(p)] = p
        last = np.array([len(p) - 1 for p in prompts], np.int32)
        fn = jax.jit(lambda p, t, li: model.prefill(
            p, {"tokens": t}, model.init_cache(t.shape[0], MAX_LEN),
            last_index=li)[0])
        return [fn(params, toks, last)]
    fn = jax.jit(lambda p, t: model.prefill(
        p, {"tokens": t}, model.init_cache(1, MAX_LEN))[0])
    return [fn(params, p[None]) for p in prompts]


def _blocks(tree, plan):
    dec = tree["decoder"]
    return list(zip(dec["prefix"], plan.prefix)) + \
        list(zip(dec["body"], plan.period))


@pytest.mark.parametrize("horizon", [1, 4])
@pytest.mark.parametrize("arch", SERVED)
def test_bound_weights_serve_bit_identical(arch, horizon):
    cfg, params = _served(arch)
    prompts = _prompts(cfg)
    bound = ContinuousEngine(cfg, params, n_slots=2, max_len=MAX_LEN,
                             decode_horizon=horizon)
    parent = ContinuousEngine(cfg, params, n_slots=2, max_len=MAX_LEN,
                              decode_horizon=horizon)
    parent.params = params          # the same programs on the float32 tree
    assert _serve(bound, prompts) == _serve(parent, prompts)
    model = bound.model
    for got, want in zip(_first_round_logits(model, bound.params, prompts),
                         _first_round_logits(model, params, prompts)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    # recurrent subtrees keep their float32 leaves, the very arrays given;
    # every other block leaf is bound in the compute dtype
    dt = jnp.dtype(cfg.compute_dtype)
    for (blk, desc), (given, _) in zip(_blocks(bound.params, model.plan),
                                       _blocks(params, model.plan)):
        for key, sub in blk.items():
            kept = desc.kind in FLOAT32_READ_KINDS and key == desc.kind
            for a, b in zip(jax.tree.leaves(sub),
                            jax.tree.leaves(given[key])):
                if kept:
                    assert a is b and a.dtype == jnp.float32, (key, desc)
                else:
                    assert a.dtype == dt, (key, desc)


@functools.lru_cache(maxsize=None)
def _qwen_engine():
    cfg, params = _served("qwen2-0.5b")
    eng = ContinuousEngine(cfg, params, n_slots=2, max_len=MAX_LEN)
    eng.start()
    return eng


@pytest.mark.parametrize("program,name", [
    ("decode", "jit_decode_step"), ("admit_packed", "jit_admit_packed")])
def test_serving_programs_take_no_float32_weights(program, name):
    eng = _qwen_engine()
    ids = np.zeros(eng.n_slots, np.int32)
    no = np.zeros(eng.n_slots, bool)
    if program == "decode":
        lowered = eng._steps.decode.lower(eng.params, eng._cache, ids)
    else:
        state = {"tok": ids, "remaining": ids, "finished": no, "eos": ids,
                 "has_eos": no}
        lowered = eng._steps.admit_packed.lower(
            eng.params, eng._cache, state,
            np.zeros((eng.n_slots, 16), np.int32), ids, ids, no, ids, ids,
            ids, no, MAX_LEN)
    assert lowered.as_text().startswith(f"module @{name} ")
    weights = jax.tree.leaves(lowered.in_avals[0][0])
    assert len(weights) == len(jax.tree.leaves(eng.params))
    assert [a for a in weights
            if a.dtype == jnp.float32 and a.ndim >= 2] == []


def test_weight_binding_counts_the_cast():
    cfg, params = _served("qwen2-0.5b")
    eng = _qwen_engine()
    leaves = jax.tree.leaves(params)
    assert eng.weight_binding == {
        "leaves": len(leaves),
        "bytes_before": sum(a.nbytes for a in leaves),
        "bytes_after": sum(a.nbytes for a in leaves) // 2}
    # a bound tree binds to itself: no second copy, nothing counted
    again, binding = serving_params(eng.params, cfg, eng.model.plan)
    assert again is eng.params
    assert binding == {"leaves": 0, "bytes_before": 0, "bytes_after": 0}
    twin = ContinuousEngine(cfg, eng.params, n_slots=2, max_len=MAX_LEN)
    assert twin.params is eng.params


@pytest.mark.parametrize("executor,n_workers", [("continuous", 1),
                                                ("fleet", 2)])
def test_client_binds_once_for_its_engines(executor, n_workers):
    cfg, params = _served("qwen2-0.5b")
    client = serve.connect(cfg, "mpi_everywhere", params=params,
                           executor=executor, n_workers=n_workers,
                           n_slots=2, max_len=MAX_LEN)
    client.submit(_prompts(cfg)[0], max_new_tokens=2)
    client.run()
    engines = ([client.engine] if executor == "continuous"
               else [w.engine for w in client.workers])
    assert len(engines) == n_workers
    assert client.weight_binding["leaves"] == len(jax.tree.leaves(params))
    for eng in engines:
        assert eng.params is client.params
        assert eng.weight_binding == client.weight_binding


#: ``Model.loss_fn`` on a 2 x 16 batch of the smoke configs, recorded
#: before serving bound its weights: (cast_params_once=False, True).
TRAIN_LOSS = {"qwen2-0.5b": (4.909411907196045, 4.909411907196045),
              "recurrentgemma-2b": (4.885705471038818, 4.885477066040039)}


@pytest.mark.parametrize("arch", sorted(TRAIN_LOSS))
def test_training_keeps_its_own_cast(arch):
    """Training casts every float32 leaf of rank >= 2 (recurrent ones
    too) and nothing else when ``cast_params_once``, reads the float32
    tree otherwise, and computes the losses it always did."""
    cfg, params = _served(arch)
    model = Model(cfg)
    toks = np.random.default_rng(3).integers(
        1, cfg.vocab, (2, 17)).astype(np.int32)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    dt = jnp.dtype(cfg.compute_dtype)

    def loss(p, cast):
        return model.loss_fn(p, batch, cast_params_once=cast)[0]

    plain = jax.jit(functools.partial(loss, cast=False))
    once = jax.jit(functools.partial(loss, cast=True))
    rule = jax.tree.map(lambda p: p.astype(dt) if p.ndim >= 2 else p,
                        params)
    assert np.asarray(once(params)) == np.asarray(plain(rule))
    want = TRAIN_LOSS[arch]
    np.testing.assert_allclose(
        [float(plain(params)), float(once(params))], want, rtol=1e-6)
    grads = jax.jit(jax.grad(functools.partial(loss, cast=True)))(params)
    assert all(g.dtype == jnp.float32 for g in jax.tree.leaves(grads))
