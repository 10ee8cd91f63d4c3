"""Wall-clock spans, counters and program names of ``ContinuousEngine``.

A ``jax.profiler`` trace captured on the CPU around a smoke-config engine
holds the engine's ``engine.*`` spans in the host plane, nested as named;
the admission counters and the queue-wait histogram are exact for known
rounds; the jitted programs carry stable names and the model's named
scopes reach the decode program's op metadata.
"""

import functools
import glob
import time

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models.model import Model
from repro.obs import MetricsRegistry, enabled_obs, host_span
from repro.serve import connect
from repro.serve import engine as E
from repro.serve.engine import ContinuousEngine, Request

N_SLOTS, MAX_LEN = 3, 48            # auto buckets (8, 16, 32, 48)
LENGTHS = (5, 7, 12)                # one round, bucket 16

# mode -> (admission children, decode span, decode children)
ADMIT_PACKED = (E.SPAN_ADMIT_PACK, E.SPAN_ADMIT_PUT, E.SPAN_ADMIT_LAUNCH,
                E.SPAN_ADMIT_SYNC, E.SPAN_ADMIT_BIND)
ADMIT_EXACT = (E.SPAN_ADMIT_PUT, E.SPAN_ADMIT_LAUNCH, E.SPAN_ADMIT_SYNC,
               E.SPAN_ADMIT_BIND)
DECODE = (E.SPAN_DECODE, (E.SPAN_DECODE_PUT, E.SPAN_DECODE_LAUNCH,
                          E.SPAN_DECODE_SYNC, E.SPAN_DECODE_EMIT))
HORIZON = (E.SPAN_HORIZON, (E.SPAN_HORIZON_LAUNCH, E.SPAN_HORIZON_SYNC,
                            E.SPAN_HORIZON_EMIT))
MODES = {
    "k1-bucketed": (1, "auto", ADMIT_PACKED, DECODE),
    # the fused round is fire-and-forget: no admission readback
    "k4-bucketed": (4, "auto", tuple(s for s in ADMIT_PACKED
                                     if s != E.SPAN_ADMIT_SYNC), HORIZON),
    "k1-exact": (1, None, ADMIT_EXACT, DECODE),
    "k4-exact": (4, None, ADMIT_EXACT, HORIZON),
}


@functools.lru_cache(maxsize=None)
def _served():
    cfg = get_smoke_config("qwen2-0.5b")
    return cfg, Model(cfg).init(jax.random.PRNGKey(0))


def _engine(horizon=1, buckets="auto"):
    cfg, params = _served()
    return ContinuousEngine(cfg, params, n_slots=N_SLOTS, max_len=MAX_LEN,
                            decode_horizon=horizon, prefill_buckets=buckets)


def _requests(first_rid=10, **kw):
    return [Request(rid=first_rid + i,
                    prompt=np.arange(1, n + 1, dtype=np.int32),
                    max_new_tokens=3, **kw)
            for i, n in enumerate(LENGTHS)]


def _host_spans(directory):
    """[(start, end, name, args)] of every ``engine.*`` event on the
    profile's host plane, one list per thread line."""
    (path,) = glob.glob(f"{directory}/**/*.xplane.pb", recursive=True)
    profile = jax.profiler.ProfileData.from_file(path)
    (host,) = [p for p in profile.planes if p.name == "/host:CPU"]
    lines = []
    for line in host.lines:
        spans = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name,
                  dict(ev.stats))
                 for ev in line.events if ev.name.startswith("engine.")]
        if spans:
            lines.append(sorted(spans))
    return lines


@functools.lru_cache(maxsize=None)
def _traced(mode, tmp_root):
    """Warm the engine's programs, then trace one admission round of
    ``LENGTHS`` and its decoding to the end."""
    horizon, buckets, _, _ = MODES[mode]
    eng = _engine(horizon, buckets)
    for r in _requests(first_rid=0):
        eng.submit(r)
    eng.run()
    directory = f"{tmp_root}/{mode}"
    with jax.profiler.trace(directory):
        for r in _requests():
            eng.submit(r)
        eng.run()
    return eng, _host_spans(directory)


@pytest.fixture(scope="module")
def tmp_root(tmp_path_factory):
    return str(tmp_path_factory.mktemp("profiles"))


@pytest.mark.parametrize("mode", sorted(MODES))
def test_engine_spans_nest_as_named(mode, tmp_root):
    _, _, admit_children, (decode, decode_children) = MODES[mode]
    _, lines = _traced(mode, tmp_root)
    assert len(lines) == 1, "the engine's spans lie on the caller's thread"
    spans = lines[0]
    names = {name for _, _, name, _ in spans}
    assert {E.SPAN_ADMIT, decode} <= names
    assert set(admit_children) <= names and set(decode_children) <= names

    def parent_of(s, e):
        outer = [n for ps, pe, n, _ in spans
                 if ps <= s and e <= pe and (ps, pe) != (s, e)]
        return outer[-1] if outer else None

    for s, e, name, _ in spans:
        if name in admit_children:
            assert parent_of(s, e) == E.SPAN_ADMIT, name
        elif name in decode_children:
            assert parent_of(s, e) == decode, name
        else:
            assert name in (E.SPAN_ADMIT, decode), name
            assert parent_of(s, e) is None, name


@pytest.mark.parametrize("mode", sorted(MODES))
def test_admit_span_names_its_round(mode, tmp_root):
    buckets = MODES[mode][1]
    _, (spans,) = _traced(mode, tmp_root)
    rounds = [args for _, _, name, args in spans if name == E.SPAN_ADMIT]
    assert len(rounds) == 1
    (args,) = rounds
    assert args["rows"] == len(LENGTHS)
    assert str(args["rids"]).split() == ["10", "11", "12"]
    assert args["bucket"] == (16 if buckets else 0)


@pytest.mark.parametrize("buckets,positions", [("auto", N_SLOTS * 16),
                                               (None, sum(LENGTHS))])
def test_admit_counters_exact_for_a_known_round(buckets, positions):
    eng = _engine(1, buckets)
    for r in _requests():
        eng.submit(r)
    eng.admit_waiting()
    assert eng.stats["admit_positions"] == positions
    assert eng.stats["admit_real_tokens"] == sum(LENGTHS)
    eng.run()
    assert eng.stats["admit_positions"] == positions


def test_queue_wait_observes_the_given_arrival():
    eng = _engine()
    now = time.perf_counter()
    reqs = _requests(arrival_s=now - 2.0)
    for r in reqs:
        eng.submit(r)
    assert all(r.arrival_s == now - 2.0 for r in reqs)
    eng.admit_waiting()
    waited = time.perf_counter() - (now - 2.0)
    sketch = eng.queue_wait.sketch
    assert sketch.n == len(reqs)
    assert 2.0 <= sketch.min <= sketch.max <= waited
    # the histogram's estimate is within its 1% relative error
    assert 2.0 * 0.99 <= eng.queue_wait.quantile(0.95) <= waited * 1.01


def test_submit_stamps_arrival_when_the_caller_gave_none():
    eng = _engine()
    before = time.perf_counter()
    (req,) = _requests()[:1]
    eng.submit(req)
    assert before <= req.arrival_s <= time.perf_counter()
    eng.run()
    assert eng.queue_wait.sketch.n == 1


def test_publish_metrics_windows_the_queue_wait_and_counters():
    eng = _engine()
    reg = MetricsRegistry()
    eng.publish_metrics(reg)
    win = reg.window()
    for r in _requests():
        eng.submit(r)
    eng.run()
    eng.publish_metrics(reg)
    assert win.delta_histogram("engine.queue_wait_s", axis="slots",
                               worker=0).n == len(LENGTHS)
    assert win.delta("engine.admit_positions", axis="execs",
                     worker=0) == N_SLOTS * 16
    assert win.delta("engine.admit_real_tokens", axis="execs",
                     worker=0) == sum(LENGTHS)


def test_host_span_builds_args_only_while_collecting(tmp_path):
    calls = []

    def arg():
        calls.append(1)
        return 7

    with host_span("engine.probe", n=arg):
        pass
    assert calls == []
    with jax.profiler.trace(str(tmp_path)):
        with host_span("engine.probe", n=arg):
            pass
    assert calls == [1]
    (spans,) = _host_spans(str(tmp_path))
    assert [(name, args) for _, _, name, args in spans] == [
        ("engine.probe", {"n": 7})]


def test_binding_records_its_cast_span(tmp_path):
    cfg, params = _served()
    with jax.profiler.trace(str(tmp_path)):
        eng = ContinuousEngine(cfg, params, n_slots=N_SLOTS,
                               max_len=MAX_LEN)
    (spans,) = _host_spans(str(tmp_path))
    assert [(name, args) for _, _, name, args in spans] == [
        (E.SPAN_BIND_WEIGHTS, eng.weight_binding)]
    assert eng.weight_binding["leaves"] == len(jax.tree.leaves(params))


@pytest.mark.parametrize("program,name", [
    ("decode", "jit_decode_step"), ("horizon", "jit_decode_horizon"),
    ("admit_packed", "jit_admit_packed"), ("prefill", "jit_prefill"),
    ("merge", "jit_merge")])
def test_programs_carry_stable_names(program, name):
    eng = _engine(4)
    eng.start()
    cache, state, b = eng._cache, eng._dev_state, N_SLOTS
    ids = np.zeros(b, np.int32)
    args = {
        "decode": (eng.params, cache, ids),
        "horizon": (eng.params, cache, state, 4, MAX_LEN),
        "admit_packed": (eng.params, cache, state,
                         np.zeros((b, 8), np.int32), ids, ids,
                         np.zeros(b, bool), ids, ids, ids,
                         np.zeros(b, bool), MAX_LEN),
        "prefill": (eng.params, {"tokens": np.zeros((1, 8), np.int32)},
                    eng.model.init_cache(1, MAX_LEN)),
        "merge": (cache, eng.model.init_cache(1, MAX_LEN), np.int32(0)),
    }[program]
    text = getattr(eng._steps, program).lower(*args).as_text()
    assert text.startswith(f"module @{name} ")


@pytest.mark.parametrize("horizon", [1, 4])
def test_named_scopes_reach_the_decode_program(horizon):
    eng = _engine(horizon)
    text = eng.decode_program_text()
    for scope in ("embed", "attn", "attn/kv_write", "mlp", "norm",
                  "lm_head"):
        assert f"/{scope}/" in text, scope


def test_single_engine_records_no_virtual_spans():
    cfg, params = _served()
    obs = enabled_obs()
    client = connect(cfg, "mpi_everywhere", params=params, obs=obs,
                     executor="continuous", n_slots=2, max_len=MAX_LEN)
    for n in LENGTHS:
        client.submit(np.arange(1, n + 1, dtype=np.int32),
                      max_new_tokens=2)
    out = client.run()
    assert len(out) == len(LENGTHS)
    assert obs.recorder.events == []
    assert obs.metrics.merged_histogram("engine.queue_wait_s").n == \
        len(LENGTHS)


def test_single_engine_trace_out_is_a_profiler_trace(tmp_path):
    from repro.launch import serve as launch

    out = tmp_path / "prof"
    launch.main(["--arch", "qwen2-0.5b", "--smoke", "--engine",
                 "continuous", "--requests", "3", "--prompt-len", "6",
                 "--max-new", "3", "--max-len", "32",
                 "--trace-out", str(out)])
    (spans,) = _host_spans(str(out))
    names = {name for _, _, name, _ in spans}
    assert {E.SPAN_ADMIT, E.SPAN_DECODE} <= names
