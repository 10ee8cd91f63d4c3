"""Fused decode horizon + bucketed batched prefill (DESIGN.md §10).

Two invariant families guard the serving hot path:

* **Equivalence** — the fused K-step horizon and the bucketed admission
  batch host interactions, never token values: outputs are bit-identical
  to the per-step oracle (horizon 1), admission order is identical, and
  (when nothing queues behind a busy pool) retirement steps are
  identical, across every Category sharing level.
* **Bounded specialization** — a trace with 30 distinct prompt lengths
  compiles at most ``len(prefill_buckets)`` admission executables, and
  the fused decode compiles exactly once per (config, horizon).  The
  lowering counter is jit's own per-shape cache size, observed on a
  config private to this module so counts are exact.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_smoke_config
from repro.core.endpoints import Category
from repro.models.model import Model
from repro.serve.engine import (ContinuousEngine, Request, ServeEngine,
                                _shared_steps, pow2_buckets)
from repro.serve.fabric import EngineWorker, Router, bursty_trace
from repro.serve.slots import SlotPool

LEVELS = (Category.MPI_EVERYWHERE, Category.SHARED_DYNAMIC,
          Category.STATIC, Category.MPI_THREADS)       # levels 1..4


@functools.lru_cache(maxsize=None)
def _served():
    cfg = get_smoke_config("qwen2-0.5b")
    return cfg, Model(cfg).init(jax.random.PRNGKey(0))


def _requests(seed: int, n: int, eos=None):
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(
                        1, 100, size=int(rng.integers(2, 20))
                    ).astype(np.int32),
                    max_new_tokens=int(rng.integers(1, 8)), eos_id=eos)
            for i in range(n)]


def _clone(reqs):
    return [Request(rid=r.rid, prompt=r.prompt,
                    max_new_tokens=r.max_new_tokens, eos_id=r.eos_id)
            for r in reqs]


def _run(reqs, horizon, *, slot_level=1,
         buckets="auto", n_slots=3, max_len=48):
    cfg, params = _served()
    eng = ContinuousEngine(cfg, params, n_slots=n_slots, max_len=max_len,
                           slot_level=slot_level, decode_horizon=horizon,
                           prefill_buckets=buckets)
    for r in _clone(reqs):
        eng.submit(r)
    done = {r.rid: r.output for r in eng.run()}
    return done, eng


# ----- horizon equivalence -------------------------------------------------

@given(seed=st.integers(0, 10_000), n=st.integers(2, 8))
@settings(max_examples=4, deadline=None)
def test_horizon_equivalence_property(seed, n):
    """K in {1,4,16} produce bit-identical outputs, identical admission
    order, and — whenever every request fits the pool at once — latencies
    keyed to the same retirement step, across all four sharing levels."""
    n_slots = 3
    reqs = _requests(seed, n, eos=7)
    for category in LEVELS:
        base = None
        for horizon in (1, 4, 16):
            done, eng = _run(reqs, horizon, slot_level=category.level,
                             n_slots=n_slots)
            key = (done, eng.admit_order)
            if base is None:
                base = (key, eng.retire_steps)
                continue
            assert key == base[0], (category, horizon)
            if n <= n_slots:
                # no queueing: retirement lands on the same engine
                # token-step no matter how many steps fuse per sync
                assert eng.retire_steps == base[1], (category, horizon)


def test_horizon_matches_oracle_on_eos_and_cache_budget():
    """Deterministic companion to the property test: EOS early-exit and
    cache-budget (bonus-token) retirement both reproduce the oracle."""
    cfg, params = _served()
    probe, _ = _run(_requests(3, 1), 1, max_len=24)
    eos = probe[0][1]              # forces an EOS hit mid-decode
    reqs = [Request(rid=0, prompt=_requests(3, 1)[0].prompt,
                    max_new_tokens=12, eos_id=eos),
            Request(rid=1, prompt=np.arange(1, 19, dtype=np.int32),
                    max_new_tokens=50),      # hits max_len=24 first
            Request(rid=2, prompt=np.arange(2, 10, dtype=np.int32),
                    max_new_tokens=4)]
    expect, _ = _run(reqs, 1, max_len=24, n_slots=2)
    for horizon in (4, 16):
        got, _ = _run(reqs, horizon, max_len=24, n_slots=2)
        assert got == expect, horizon


def test_horizon_equivalent_with_and_without_buckets():
    """The two admission paths (bucketed batch, exact-length chain) feed
    identical first tokens and cache rows: outputs match bit-for-bit and
    bucketing strictly reduces prefill calls."""
    reqs = _requests(11, 9)
    base, eng_exact = _run(reqs, 4, buckets=None)
    got, eng_b = _run(reqs, 4, buckets="auto")
    assert got == base
    assert eng_exact.stats["prefills"] == len(reqs)
    assert eng_b.stats["prefills"] < len(reqs)
    assert eng_b.stats["prefilled_requests"] == len(reqs)


def test_fused_horizon_cuts_host_syncs():
    """The doorbell-batching contract: K=8 needs <= 1/4 host sync per
    generated token (one drain per horizon, fire-and-forget admission)."""
    reqs = _requests(5, 10)
    _, eng1 = _run(reqs, 1)
    tok1 = sum(len(r.output) for r in eng1.done)
    assert eng1.stats["host_syncs"] >= tok1 / eng1.n_slots  # per-step sync
    _, eng8 = _run(reqs, 8)
    tok8 = sum(len(r.output) for r in eng8.done)
    assert tok8 == tok1
    assert eng8.stats["host_syncs"] / tok8 <= 0.25
    assert eng8.stats["decode_calls"] < eng1.stats["decode_calls"]


def test_write_mask_freezes_finished_rows():
    """decode_step with a write mask leaves masked rows' attention cache
    bit-untouched while unmasked rows write at their own position."""
    cfg, params = _served()
    model = _shared_steps(cfg, False).model
    cache = model.init_cache(2, 16, per_slot=True)
    cache = dict(cache, idx=jnp.asarray([3, 5], jnp.int32))
    _, out = model.decode_step(params, cache,
                               tokens=jnp.asarray([7, 9], jnp.int32),
                               write_mask=jnp.asarray([True, False]))

    def rows(tree, b):
        # prefix leaves carry the batch axis first, scanned body leaves
        # behind their leading layer axis
        return ([np.asarray(leaf[b]) for leaf in jax.tree.leaves(
                    tree["prefix"])]
                + [np.asarray(leaf[:, b]) for leaf in jax.tree.leaves(
                    tree["body"])])

    for before, after in zip(rows(cache["stack"], 1),
                             rows(out["stack"], 1)):
        assert np.array_equal(before, after)       # masked row frozen
    changed = any(not np.array_equal(b, a)
                  for b, a in zip(rows(cache["stack"], 0),
                                  rows(out["stack"], 0)))
    assert changed                                 # live row wrote
    assert np.array_equal(np.asarray(out["idx"]), [4, 6])


# ----- bounded specialization ---------------------------------------------

def test_compile_counts_bounded():
    """30 distinct prompt lengths: at most len(buckets) admission
    compilations, exactly one fused-decode compilation per (cfg, K), and
    zero exact-length prefill specializations.  Runs on a config private
    to this test so jit cache sizes are exact counters."""
    cfg = dataclasses.replace(get_smoke_config("qwen2-0.5b"), d_ff=96)
    params = Model(cfg).init(jax.random.PRNGKey(0))
    steps = _shared_steps(cfg, False)
    if not hasattr(steps.prefill, "_cache_size"):
        pytest.skip("jax private jit cache counter unavailable")

    def serve(horizon):
        eng = ContinuousEngine(cfg, params, n_slots=4, max_len=64,
                               decode_horizon=horizon)
        for i, ln in enumerate(range(2, 32)):      # 30 distinct lengths
            eng.submit(Request(rid=i,
                               prompt=np.arange(1, 1 + ln,
                                                dtype=np.int32),
                               max_new_tokens=3))
        eng.run()
        return eng

    eng = serve(4)
    assert eng.prefill_buckets == pow2_buckets(64)
    assert steps.admit_packed._cache_size() <= len(eng.prefill_buckets)
    assert steps.prefill._cache_size() == 0        # no exact-length path
    assert steps.horizon._cache_size() == 1        # one per (cfg, K=4)
    serve(4)                                       # same K: no recompile
    assert steps.horizon._cache_size() == 1
    serve(16)                                      # new K: exactly one more
    assert steps.horizon._cache_size() == 2
    assert steps.admit_packed._cache_size() <= len(eng.prefill_buckets)


def test_wave_engine_shares_executables():
    """ServeEngine instances of one config reuse the same jitted
    decode/prefill (the fleet's N-fold-compile fix, applied to the wave
    baseline too)."""
    cfg, params = _served()
    a = ServeEngine(cfg, params, n_slots=2, max_len=32)
    b = ServeEngine(cfg, params, n_slots=4, max_len=64)
    c = ContinuousEngine(cfg, params, n_slots=2, max_len=32)
    assert a._decode is b._decode and a._prefill is b._prefill
    assert a.model is b.model
    assert a._decode is c._decode                  # wave/continuous share


def test_slot_pool_groups_memoized():
    """groups (walked every admissible() call) is computed once per pool
    and the frozen dataclass stays externally immutable."""
    pool = SlotPool(Category.SHARED_DYNAMIC.level, 8)
    assert pool.groups is pool.groups
    assert pool.group_size == 2
    with pytest.raises(dataclasses.FrozenInstanceError):
        pool.n_slots = 4
    # equality/hash still follow the fields, not the cache
    assert pool == SlotPool(Category.SHARED_DYNAMIC.level, 8)


# ----- fabric accounting ---------------------------------------------------

def test_engine_worker_accounts_horizon_steps():
    """An EngineWorker over a fused-horizon engine charges virtual time
    for every executed decode step (K per external step, minus early
    exit) and still serves exactly the solo oracle's tokens."""
    cfg, params = _served()
    trace = bursty_trace(5, burst_size=3, prompt_lens=(8, 16),
                         new_tokens=(2, 5), seed=1)
    worker = EngineWorker(
        0, ContinuousEngine(cfg, params, n_slots=2, max_len=64,
                            decode_horizon=4))
    router = Router([worker], Category.MPI_EVERYWHERE)
    rep = router.run(trace)
    eng = worker.engine
    assert sorted(c.rid for c in rep.completions) \
        == sorted(a.rid for a in trace)
    assert worker.stats["steps"] == eng.stats["decode_steps"]
    assert worker.stats["busy_slot_steps"] == eng.stats["busy_slot_steps"]
    assert worker.stats["tokens"] == eng.stats["busy_slot_steps"]
    for c in rep.completions:
        arr = next(a for a in trace if a.rid == c.rid)
        solo = ContinuousEngine(cfg, params, n_slots=1, max_len=64)
        solo.submit(Request(rid=arr.rid, prompt=worker.prompt_fn(arr),
                            max_new_tokens=arr.max_new_tokens))
        assert c.output == solo.run()[0].output, c.rid


# ----- bucket eligibility --------------------------------------------------

def test_buckets_disable_on_recurrent_models():
    """Auto bucketing turns itself off where trailing padding would
    corrupt state (recurrent blocks); asking for it explicitly errors."""
    cfg = get_smoke_config("recurrentgemma-2b")
    params = Model(cfg).init(jax.random.PRNGKey(0))
    eng = ContinuousEngine(cfg, params, n_slots=2, max_len=32)
    assert eng.prefill_buckets == ()
    with pytest.raises(ValueError):
        ContinuousEngine(cfg, params, n_slots=2, max_len=32,
                         prefill_buckets=(8, 16))


def test_pow2_buckets_cover_max_len():
    assert pow2_buckets(64) == (8, 16, 32, 64)
    assert pow2_buckets(100) == (8, 16, 32, 64, 100)
    eng_buckets = pow2_buckets(256)
    assert eng_buckets[-1] == 256 and all(
        b < 256 or b == 256 for b in eng_buckets)
