"""Adaptive re-planning invariants (DESIGN.md §12): the ``Replanner``
hysteresis policy (property-tested), live ``SlotPool.regroup`` — and the
memoization-staleness bug it would hide without cache invalidation —
fleet migration through the router, and the ``ServeClient.replan`` /
``adaptive=True`` surfaces."""

import dataclasses

import pytest
from hypothesis import given, settings, strategies as st

from repro.core.adapt import Replanner, WindowStats
from repro.core.plan import RESOURCES, SharingVector
from repro.serve.slots import SlotPool

LEVELS = st.integers(1, 4)
VECTORS = st.builds(SharingVector, slots=LEVELS, channels=LEVELS,
                    execs=LEVELS)

#: Raw telemetry saturating each resource's pressure to exactly 0 or 1:
#: occupancy drives slots, queue depth drives channels (and slots),
#: compiles drive execs.
IDLE = WindowStats()
BUSY = WindowStats(occupancy=1.0, queue_depth=8.0, jit_compiles=16)


def stats_for(pressure: float, *, scale: float = 1.0) -> WindowStats:
    """Telemetry hitting every resource with the same pressure."""
    return WindowStats(occupancy=pressure,
                       queue_depth=pressure * 2.0 * scale,
                       jit_compiles=int(pressure * 4 * scale))


def drive(rp: Replanner, stats: WindowStats, windows: int):
    for _ in range(windows):
        rp.observe(stats)
    return rp.vector


# ----- hysteresis properties ------------------------------------------------

@given(vector=VECTORS, pressure=st.floats(0.0, 1.0),
       windows=st.integers(1, 40))
@settings(max_examples=40, deadline=None)
def test_constant_telemetry_never_oscillates(vector, pressure, windows):
    """Constant telemetry pins a constant direction: every resource's
    level trajectory is monotone (no level is ever revisited), and once
    the trajectory stops moving it stays stopped."""
    rp = Replanner(vector, n_workers=8, n_slots=8)
    prev = {r: getattr(rp.vector, r) for r in RESOURCES}
    deltas = {r: set() for r in RESOURCES}
    for _ in range(windows):
        rp.observe(stats_for(pressure))
        for r in RESOURCES:
            cur = getattr(rp.vector, r)
            if cur != prev[r]:
                deltas[r].add(1 if cur > prev[r] else -1)
            prev[r] = cur
    for r in RESOURCES:
        assert len(deltas[r]) <= 1, \
            f"{r} moved both directions under constant telemetry"
    # convergence: within the trajectory's worst-case horizon the levels
    # stop moving, and no further transitions fire on the same telemetry
    # (after only `windows` windows a demotion chain may still be paying
    # its patience and cooldown)
    horizon = rp.max_windows_to_reach(3) + 1
    drive(rp, stats_for(pressure), horizon)
    settled = rp.vector
    drive(rp, stats_for(pressure), horizon)
    assert rp.vector == settled


@given(p_hi=st.floats(0.0, 1.0), p_lo=st.floats(0.0, 1.0),
       vector=VECTORS, windows=st.integers(1, 30))
@settings(max_examples=40, deadline=None)
def test_transitions_monotone_in_contention(p_hi, p_lo, vector, windows):
    """Higher pressure never yields a MORE shared level than lower
    pressure over the same horizon from the same start."""
    lo, hi = sorted((p_lo, p_hi))
    a = Replanner(vector, n_workers=8, n_slots=8)
    b = Replanner(vector, n_workers=8, n_slots=8)
    drive(a, stats_for(hi), windows)
    drive(b, stats_for(lo), windows)
    for r in RESOURCES:
        assert getattr(a.vector, r) <= getattr(b.vector, r)


@given(vector=VECTORS, budget=st.floats(0.2, 1.0),
       seq=st.lists(st.floats(0.0, 1.0), min_size=1, max_size=30))
@settings(max_examples=40, deadline=None)
def test_budget_never_exceeded(vector, budget, seq):
    """Whenever the fully shared vector fits the budget, the
    controller's vector fits it after EVERY observed window — including
    the starting clamp of an over-budget hand-built vector."""
    floor = SharingVector.diagonal(4).footprint_score(8, 8)
    rp = Replanner(vector, n_workers=8, n_slots=8, budget=budget)
    if budget >= floor:
        assert rp.footprint_score() <= budget
    for pressure in seq:
        rp.observe(stats_for(pressure))
        if budget >= floor:
            assert rp.footprint_score() <= budget


@given(resource=st.sampled_from(RESOURCES), start_level=LEVELS,
       target_level=LEVELS)
@settings(max_examples=40, deadline=None)
def test_any_level_reachable_within_bound(resource, start_level,
                                          target_level):
    """Any level is reachable from any other within
    ``max_windows_to_reach(distance)`` windows, given telemetry that
    saturates the resource's pressure in the needed direction —
    adaptation can never strand a deployment."""
    start = dataclasses.replace(SharingVector.diagonal(2),
                                **{resource: start_level})
    rp = Replanner(start, n_workers=8, n_slots=8)
    saturate = {
        "slots": WindowStats(occupancy=1.0),
        "channels": WindowStats(queue_depth=8.0),
        "execs": WindowStats(jit_compiles=16),
    }[resource] if target_level < start_level else IDLE
    bound = rp.max_windows_to_reach(abs(target_level - start_level))
    visited = {start_level}
    for _ in range(bound):
        rp.observe(saturate)
        visited.add(getattr(rp.vector, resource))
    assert target_level in visited, \
        (resource, start_level, target_level, sorted(visited), bound)


def test_promote_fast_demote_lazy():
    """The asymmetry the serving story needs: one hot window promotes
    (patience=1 default), while demotion needs a sustained idle stretch
    plus a cooldown between releases."""
    rp = Replanner(SharingVector.diagonal(2), n_workers=8, n_slots=8)
    assert rp.observe(BUSY) is not None          # immediate promotion
    assert rp.vector.slots == 1
    rp = Replanner(SharingVector.diagonal(2), n_workers=8, n_slots=8)
    for _ in range(rp.demote_patience - 1):
        assert rp.observe(IDLE) is None          # not yet sustained
    assert rp.observe(IDLE) is not None          # now demote by one
    assert rp.vector.slots == 3
    assert rp.observe(IDLE) is None              # cooldown holds


def test_direction_flip_restarts_streak():
    rp = Replanner(SharingVector.diagonal(3), n_workers=8, n_slots=8,
                   demote_patience=2)
    rp.observe(IDLE)                             # demote streak 1
    rp.observe(BUSY)                             # flip: promote fires
    assert rp.vector.slots == 2
    # demotion needs the window MEAN back at idle (one idle sample
    # after the spike is not "sustained") AND a fresh streak
    assert rp.observe(IDLE) is None
    assert rp._streak["slots"] == 0              # mean still mid-band
    assert rp.observe(IDLE) is None
    assert rp._streak["slots"] == 1              # restarted from scratch
    assert rp.observe(IDLE) is not None          # demote_patience=2 met
    assert rp.vector.slots == 3


def test_budget_withholds_promotion_until_paid_for():
    """A promotion that would overrun the budget is withheld; once
    another resource demotes and frees footprint, it lands."""
    budget = SharingVector(slots=2, channels=4, execs=4) \
        .footprint_score(8, 8)
    rp = Replanner(SharingVector(slots=2, channels=4, execs=4),
                   n_workers=8, n_slots=8, budget=budget)
    hot_slots = WindowStats(occupancy=1.0)       # slots pressure only
    assert rp.observe(hot_slots) is None         # would exceed budget
    assert rp.vector.slots == 2
    assert rp.footprint_score() <= budget


def test_budget_sacrifices_cheapest_promotion_first():
    """When the budget can afford only SOME of a window's promotions,
    the cheapest-benefit one (execs: bit-exact, compile locality only)
    is withheld and the slots promotion — actual scheduling freedom —
    lands."""
    start = SharingVector(slots=2, channels=4, execs=2)
    both = WindowStats(occupancy=1.0, jit_compiles=16)
    budget = 0.6             # fits (1,4,2) or (2,4,1), not (1,4,1)
    assert SharingVector(slots=1, channels=4, execs=1) \
        .footprint_score(8, 8) > budget
    rp = Replanner(start, n_workers=8, n_slots=8, budget=budget)
    assert rp.observe(both) == SharingVector(slots=1, channels=4,
                                             execs=2)
    assert rp.footprint_score() <= budget


def test_replanner_validation():
    with pytest.raises(ValueError):
        Replanner(hi=0.2, lo=0.7)
    with pytest.raises(ValueError):
        Replanner(window=0)
    with pytest.raises(ValueError):
        Replanner(budget=0.0)
    rp = Replanner(SharingVector.diagonal(1), n_workers=8, n_slots=8,
                   budget=0.3)
    # the starting clamp follows the planner's bump order
    assert rp.footprint_score() <= 0.3


# ----- SlotPool.regroup: the memoization-staleness fix ---------------------

def test_regroup_invalidates_memoized_groups():
    """The bug the harness would hide: ``groups``/``group_size`` are
    ``cached_property`` memos keyed into the instance ``__dict__`` —
    without explicit invalidation, a regrouped pool would keep admitting
    by the OLD level's groups forever."""
    pool = SlotPool(1, 4)
    assert pool.group_size == 1                  # memoize at level 1
    assert [list(g) for g in pool.groups] == [[0], [1], [2], [3]]
    pool.regroup(4)
    assert pool.level == 4
    assert pool.group_size == 4                  # stale memo would say 1
    assert [list(g) for g in pool.groups] == [[0, 1, 2, 3]]
    # and the admission behavior actually changed: a half-occupied pool
    # admits nothing at level 4, everything free at level 1
    occupied = [True, False, False, False]
    assert pool.admissible(occupied) == []
    pool.regroup(1)
    assert pool.admissible(occupied) == [1, 2, 3]


def test_regroup_in_flight_slots_survive():
    """Regrouping never evicts: the occupied pattern is caller state and
    the pool only re-keys FUTURE admissions."""
    pool = SlotPool(4, 4)
    occupied = [False, True, False, False]
    assert pool.admissible(occupied) == []       # wave: group not drained
    pool.regroup(2)                              # pairs
    assert pool.admissible(occupied) == [2, 3]   # drained pair admits
    with pytest.raises(ValueError):
        pool.regroup(0)
    same = pool.regroup(2)                       # no-op returns self
    assert same is pool and pool.level == 2


def test_engine_regroup_reuses_shared_steps(monkeypatch):
    """Engine regroup swaps the executable set lazily through the
    ``_shared_steps`` cache and re-keys the pool in place."""
    import jax
    from repro.configs import get_smoke_config
    from repro.models.model import Model
    from repro.serve.engine import ContinuousEngine, _shared_steps

    cfg = get_smoke_config("qwen2-0.5b")
    cfg = dataclasses.replace(cfg, d_ff=72)      # private: no real compiles
    eng = ContinuousEngine(cfg, None, n_slots=2, max_len=32)
    assert eng.exec_group == 0
    base_decode = eng._decode
    assert not eng.regroup()                     # no-op
    assert eng.regroup(slot_level=4, exec_group=1)
    assert eng.pool.level == 4
    assert eng.plan.vector.slots == 4 and eng.plan.preset is None
    assert eng._decode is _shared_steps(cfg, False, 1).decode
    assert eng._decode is not base_decode
    assert eng.stats["regroups"] == 1
    # regrouping BACK rejoins the original shared set (identity)
    eng.regroup(exec_group=0)
    assert eng._decode is base_decode


# ----- fleet migration through the router ----------------------------------

def _trace_and_phases():
    from repro.serve.fabric import canonical_phased_trace
    return canonical_phased_trace()


def test_router_migration_conserves_requests():
    """An adaptive sim fleet under the canonical phased trace migrates
    (promote on burst, demote through idle) and still completes every
    request exactly once, deterministically."""
    from repro.serve.fabric import build_sim_fleet
    trace, _ = _trace_and_phases()

    def run():
        start = SharingVector.diagonal(2)
        adapt = Replanner(start, n_workers=8, n_slots=4)
        return build_sim_fleet(8, start, adapt=adapt,
                               adapt_window_ns=100_000.0).run(trace)

    rep = run()
    assert rep.n_completed == rep.n_arrivals == len(trace)
    assert sorted(c.rid for c in rep.completions) \
        == sorted(a.rid for a in trace)
    assert len(rep.transitions) > 0 and rep.n_windows > 0
    # both directions actually exercised across the phases
    dirs = set()
    prev = SharingVector.diagonal(2)
    for _, vec in rep.transitions:
        for r in RESOURCES:
            d = getattr(vec, r) - getattr(prev, r)
            if d:
                dirs.add(d > 0)
        prev = vec
    assert dirs == {True, False}
    # time-weighted footprint sits well below the frozen dedicated
    # diagonal's (the plan that matches the bursts' throughput)
    assert rep.mean_footprint < 0.75 * SharingVector.diagonal(1) \
        .footprint_score(8, 4)
    # determinism: an identical run replays the identical schedule
    rep2 = run()
    assert [(c.rid, c.t_done_ns) for c in rep2.completions] \
        == [(c.rid, c.t_done_ns) for c in rep.completions]
    assert rep2.transitions == rep.transitions


def test_router_channel_rebuild_preserves_queued_arrival_order():
    """A channels-axis migration drains queued work and re-places it in
    arrival order — nothing lost, nothing reordered at equal depth."""
    from repro.serve.fabric import Router, SimWorker
    from repro.serve.fabric.traffic import Arrival

    start = SharingVector(slots=1, channels=4, execs=4)
    workers = [SimWorker(w, n_slots=1) for w in range(2)]
    router = Router(workers, start)
    arrs = [Arrival(rid=i, t_ns=float(i), prompt_len=4,
                    max_new_tokens=30) for i in range(8)]
    for a in arrs:
        router._on_arrival(a.t_ns, a)
    # both workers busy, six requests queued on the one shared channel
    for w in (0, 1):
        router._on_wake(0.0, w)
    queued_before = [a.rid for c in router.channels for a in c._q]
    assert len(queued_before) == 6
    router.apply_vector(10.0, SharingVector(slots=1, channels=1,
                                            execs=4))
    assert router.plan.n_queues == 2             # dedicated channels now
    queued_after = [a.rid for c in router.channels for a in c._q]
    assert sorted(queued_after) == sorted(queued_before)
    assert router.vector.channels == 1
    assert router.transitions == [(10.0, SharingVector(
        slots=1, channels=1, execs=4))]


def test_fresh_router_baselines_ignore_prior_run_history():
    """Workers (and their engines' jit caches) persist across a client's
    runs while each run builds a fresh router — the first adaptation
    window of run N+1 must see only ITS window, not run N's whole
    history as one giant delta."""
    from repro.serve.fabric import Router, SimWorker
    start = SharingVector.diagonal(2)
    workers = [SimWorker(w, n_slots=4, slot_level=2) for w in range(2)]
    for w in workers:                      # a "previous run" of history
        w.stats["slot_steps"] += 1000
        w.stats["busy_slot_steps"] += 1000
    router = Router(workers, start,
                    adapt=Replanner(start, n_workers=2, n_slots=4))
    stats = router._window_stats(0.0)
    assert stats.occupancy == 0.0          # idle window reads as idle
    assert stats.jit_compiles == 0
    assert stats.tokens == 0


def test_router_rejects_mismatched_replanner():
    from repro.serve.fabric import SimWorker, Router
    from repro.core.endpoints import Category
    workers = [SimWorker(0)]
    with pytest.raises(ValueError):
        Router(workers, SharingVector.diagonal(1),
               adapt=Replanner(SharingVector.diagonal(2)))
    with pytest.raises(ValueError):
        Router(workers, Category.DYNAMIC,
               adapt=Replanner(SharingVector.diagonal(2)))


# ----- the client surfaces --------------------------------------------------

def _client(**overrides):
    import functools
    import jax
    from repro import serve
    from repro.configs import get_smoke_config
    from repro.models.model import Model

    @functools.lru_cache(maxsize=None)
    def _served():
        cfg = get_smoke_config("qwen2-0.5b")
        return cfg, Model(cfg).init(jax.random.PRNGKey(0))

    cfg, params = _served()
    return serve.connect(cfg, overrides.pop("plan", None), params=params,
                         n_slots=2, max_len=64, **overrides)


def test_client_replan_guards_structural_fields():
    from repro.core.plan import EndpointPlan
    client = _client(plan="shared_dynamic")
    with pytest.raises(ValueError):
        client.replan(EndpointPlan(n_workers=4, n_slots=2, max_len=64))
    with pytest.raises(ValueError):
        client.replan(None, max_len=128)
    new = client.replan(SharingVector(slots=1, channels=3, execs=4))
    assert client.plan.vector == new.vector
    assert client.engine.pool.level == 1
    client.close()
    with pytest.raises(RuntimeError):
        client.replan("mpi_threads")


def test_client_replan_wave_refuses():
    client = _client(executor="wave")
    with pytest.raises(ValueError):
        client.replan("mpi_threads")


def test_adaptive_plan_refuses_wave():
    from repro.core.plan import EndpointPlan
    with pytest.raises(ValueError):
        EndpointPlan(executor="wave", adaptive=True)
    with pytest.raises(ValueError):
        EndpointPlan(adapt_window_ns=0.0)


def test_client_replan_hints_resolve_against_live_shape():
    from repro.core.plan import Hints
    client = _client(plan="shared_dynamic")
    new = client.replan(Hints(latency_target_ms=10.0))
    assert new.vector.slots == 1                 # tight target dedicates
    assert new.n_slots == 2 and new.max_len == 64
    assert new.placement == "round_robin"        # no ordering hint: kept
    assert client.transitions and client.transitions[-1][1] == new.vector
    # a session-ordering hint resolves its own placement — the live
    # plan's round_robin must not silently override it
    new = client.replan(Hints(latency_target_ms=10.0,
                              session_ordering=True))
    assert new.placement == "session_affinity"
    # and a budget hint must reach the live controller, not only the
    # one-shot vector clamp
    new = client.replan(Hints(footprint_budget=0.4))
    assert new.adapt_budget == 0.4


def test_engine_emits_compile_telemetry():
    """The execs pressure signal is real: after serving, the engine's
    jit caches report nonzero specializations, so an adaptive window
    can see fresh compiles (jit_compiles is not a test-only field)."""
    client = _client(plan="mpi_everywhere")
    import numpy as np
    client.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=2)
    client.run()
    assert client.engine.compile_count() >= 1
    # fabric probes: a real worker exposes its step-set identity so the
    # router counts each SHARED executable set once; sims report none
    from repro.serve.fabric import EngineWorker, Router, SimWorker
    assert SimWorker(0).compile_probe() == (None, 0)
    worker = EngineWorker(0, client.engine)
    key, count = worker.compile_probe()
    assert key is not None and count == client.engine.compile_count()
    # a fresh router over this already-warm worker baselines the compile
    # counter at construction: an idle first window reports 0 compiles
    vec = client.engine.plan.vector
    router = Router([worker], vec,
                    adapt=Replanner(vec, n_workers=1, n_slots=2))
    assert router._window_stats(0.0).jit_compiles == 0


def test_launcher_rejects_explicit_wave_with_adaptive():
    import argparse
    from repro.launch.serve import build_plan
    from tests.test_deprecations import _legacy_args
    with pytest.raises(SystemExit):
        build_plan(_legacy_args(engine="wave", adaptive=True),
                   argparse.ArgumentParser())
