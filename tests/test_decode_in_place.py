"""The decode programs write each step's K/V rows into their cache in place.

``_shared_steps(...).decode`` and ``.horizon`` donate their cache: the
layer scan carries the stacked cache and each layer scatters one row per
slot into it.  These tests hold that path to the former one, a ``where``
over the layer's whole buffer kept here as an oracle, bit for bit, on the
smoke configs that exercise each kind of cache: full-context attention,
a rolling window with recurrent blocks, the paged cache, and the ragged
decode kernel (interpret mode on the CPU).
"""

import dataclasses
import functools
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.plan import EndpointPlan, SharingVector
from repro.models import transformer
from repro.models.model import Model
from repro.serve.engine import ContinuousEngine, Request, _shared_steps

N_SLOTS, MAX_LEN, PAGE_SIZE, STEPS = 3, 32, 8, 6

# case -> (arch, ragged kernel, paged)
CASES = {"full": ("qwen2-0.5b", False, False),
         "rolling_recurrent": ("recurrentgemma-2b", False, False),
         "paged": ("qwen2-0.5b", False, True),
         "ragged_kernel": ("qwen2-0.5b", True, False)}


@functools.lru_cache(maxsize=None)
def _served(arch):
    cfg = get_smoke_config(arch)
    return cfg, Model(cfg).init(jax.random.PRNGKey(0))


def _where_write(cache, k_new, v_new, idx, window, rolling,
                 write_mask=None, layer=None):
    """The former decode write: one ``where`` over the layer's whole
    buffer, hitting each row's position (nothing past the buffer end)."""
    def one(buf, new):
        smax = buf.shape[1]
        pos = jnp.broadcast_to(jnp.asarray(idx), (buf.shape[0],))
        slot = pos % window if (rolling and window > 0) else pos
        hit = jnp.arange(smax)[None, :] == slot[:, None]
        if write_mask is not None:
            hit &= write_mask[:, None]
        rows = new[:, 0].reshape(new.shape[0], 1, -1)
        rows = jnp.pad(rows, [(0, 0), (0, 0),
                              (0, buf.shape[-1] - rows.shape[-1])])
        return jnp.where(hit[..., None], rows, buf)

    out = {}
    for name, new in (("k", k_new), ("v", v_new)):
        buf = cache[name]
        if layer is None:
            out[name] = one(buf, new)
        else:
            out[name] = buf.at[layer].set(one(buf[layer], new))
    return out


def _engine_cache(arch, ragged, paged):
    """A started engine with three admitted requests of ragged lengths."""
    cfg, params = _served(arch)
    plan = EndpointPlan(
        vector=SharingVector(slots=1, pages=4 if paged else 1),
        n_slots=N_SLOTS, max_len=MAX_LEN, use_ragged_kernel=ragged,
        page_size=PAGE_SIZE if paged else 0, executor="continuous")
    eng = ContinuousEngine(cfg, params, plan=plan)
    for i, n in enumerate((3, 9, 14)):
        eng.submit(Request(rid=i, prompt=np.arange(1, n + 1, dtype=np.int32),
                           max_new_tokens=STEPS + 2))
    eng.admit_waiting()
    return eng


@pytest.mark.parametrize("case", list(CASES))
def test_donated_steps_match_the_where_oracle_bit_for_bit(case,
                                                          monkeypatch):
    arch, ragged, paged = CASES[case]
    eng = _engine_cache(arch, ragged, paged)
    model = eng.model
    toks = [jnp.asarray(np.arange(N_SLOTS) * 7 + 3 + t, jnp.int32)
            for t in range(STEPS)]
    if paged:
        # the paged cache's oracle is the contiguous one: the same rows
        # behind a page table attend to the same values
        oracle = _engine_cache(arch, ragged, False)._cache
    else:
        oracle = jax.tree.map(jnp.copy, eng._cache)
    got_cache, got = eng._cache, []
    for t in toks:
        logits, got_cache = eng._decode(eng.params, got_cache, t)
        got.append(np.asarray(logits))
    monkeypatch.setattr(transformer, "_attn_cache_write", _where_write)
    step = jax.jit(lambda p, c, t: model.decode_step(
        p, c, tokens=t, use_ragged_kernel=ragged))     # traced patched
    for t, logits in zip(toks, got):
        want, oracle = step(eng.params, oracle, t)
        np.testing.assert_array_equal(logits, np.asarray(want))
    assert np.array_equal(np.asarray(got_cache["idx"]),
                          np.asarray(oracle["idx"]))
    if not paged:
        for a, b in zip(jax.tree.leaves(got_cache["stack"]),
                        jax.tree.leaves(oracle["stack"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _serve(arch, horizon, ragged, paged):
    cfg, params = _served(arch)
    plan = EndpointPlan(
        vector=SharingVector(slots=1, pages=4 if paged else 1),
        n_slots=N_SLOTS, max_len=MAX_LEN, decode_horizon=horizon,
        use_ragged_kernel=ragged, page_size=PAGE_SIZE if paged else 0,
        executor="continuous")
    eng = ContinuousEngine(cfg, params, plan=plan)
    rng = np.random.default_rng(3)
    for i in range(5):
        eng.submit(Request(rid=i, prompt=rng.integers(
            1, 60, size=int(rng.integers(2, 12))).astype(np.int32),
            max_new_tokens=int(rng.integers(2, 9))))
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        done = {r.rid: list(r.output) for r in eng.run()}
    return done, eng, [str(w.message) for w in caught]


@pytest.mark.parametrize("case", list(CASES))
def test_greedy_streams_match_across_horizons(case):
    arch, ragged, paged = CASES[case]
    k1, _, _ = _serve(arch, 1, ragged, paged)
    k4, _, _ = _serve(arch, 4, ragged, paged)
    assert k1 == k4 and len(k1) == 5


@pytest.mark.parametrize("horizon", [1, 4])
@pytest.mark.parametrize("case", list(CASES))
def test_every_decode_launch_consumes_its_cache(case, horizon):
    arch, ragged, paged = CASES[case]
    _, eng, caught = _serve(arch, horizon, ragged, paged)
    assert not [m for m in caught if "donated" in m], caught
    assert eng.stats["decode_calls"] > 0
    assert eng.stats["decode_cache_in_place"] == eng.stats["decode_calls"]


def test_wave_engine_rebinds_the_donated_cache():
    """The wave engine decodes through the same donating program."""
    from repro.serve.engine import ServeEngine
    cfg, params = _served("qwen2-0.5b")
    eng = ServeEngine(cfg, params, n_slots=2, max_len=MAX_LEN)
    for i in range(2):
        eng.submit(Request(rid=i, prompt=np.arange(1, 6, dtype=np.int32),
                           max_new_tokens=4))
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        done = eng.run()
    assert not [w for w in caught if "donated" in str(w.message)]
    assert [len(r.output) for r in done] == [4, 4]
    assert done[0].output == done[1].output


def test_shared_decode_programs_donate_the_cache():
    cfg, _ = _served("qwen2-0.5b")
    steps = _shared_steps(dataclasses.replace(cfg, name="donate-probe"),
                          False)
    model = steps.model
    params = model.abstract_params()
    cache = jax.eval_shape(
        lambda: model.init_cache(N_SLOTS, MAX_LEN, per_slot=True))
    tok = jax.ShapeDtypeStruct((N_SLOTS,), jnp.int32)
    text = steps.decode.lower(params, cache, tok).as_text()
    assert "tf.aliasing_output" in text
