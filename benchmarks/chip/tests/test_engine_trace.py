"""The reduction by engine span and by named scope, and the readers of the
metrics that read it, on the committed TPU trace (recorded before the
engine had spans), on a hand-made trace, and on an HLO snippet."""

import pytest

import engine_trace
import run
import trace_reduce
from conftest import HERE
from repro.obs import QuantileSketch

TRACE = HERE / "testdata" / "serve_window.xplane.pb"
NEW_READERS = ("queue_wait_p95_ms.engine", "idle_share.admit_host",
               "idle_share.decode_host", "admit_pad_share",
               "decode_kv_write_ms")


# ----- a hand-made trace: the profile's shape as trace_reduce reads it ----

class Ev:
    def __init__(self, name, start, end, **stats):
        self.name, self.start_ns, self.duration_ns = name, start, end - start
        self.stats = list(stats.items())


class Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


class Profile:
    def __init__(self, planes):
        self.planes = planes


HLO = """\
HloModule jit_decode_step, entry_computation_layout={...}

%fc.1 (param_0: bf16[2,8], param_1: pred[2,8]) -> bf16[2,8] {
  %param_0 = bf16[2,8]{1,0} parameter(0)
  %param_1 = pred[2,8]{1,0} parameter(1)
  %select_n.4 = bf16[2,8]{1,0} select(%param_1, %param_0, %param_0), metadata={op_name="jit(decode_step)/while/body/closed_call/attn/kv_write/jit(_where)/select_n" stack_frame_id=86}
  ROOT %dynamic_update_slice.5 = bf16[2,8]{1,0} dynamic-update-slice(%param_0, %select_n.4), metadata={op_name="jit(decode_step)/while/body/dynamic_update_slice" stack_frame_id=17}
}

ENTRY %main.9 (p.1: f32[2]) -> f32[2] {
  %fusion.1 = bf16[2,8]{1,0} fusion(%p.1, %p.2), kind=kLoop, calls=%fc.1, metadata={op_name="jit(decode_step)/while/body/dynamic_update_slice" stack_frame_id=17}
  %convert.2 = bf16[2,8]{1,0} convert(%p.1), metadata={op_name="jit(decode_step)/while/body/closed_call/mlp/convert_element_type" stack_frame_id=2}
  %convert.6 = bf16[2,8]{1,0} convert(%p.1)
  ROOT %copy.3 = f32[2]{0} copy(%p.1)
}
"""


def synthetic():
    """Window [0, 1000) ns; device busy [100, 200), [400, 500) and
    [700, 900), so 600 ns idle; an admission round over the second gap
    and a decode step over the third."""
    device = Plane("/device:TPU:0", [
        Line("XLA Modules", [Ev("jit_admit_packed(1)", 100, 200, run_id=1),
                             Ev("jit_admit_packed(1)", 400, 500, run_id=2),
                             Ev("jit_decode_step(2)", 700, 900, run_id=3)]),
        Line("XLA Ops", [Ev("%fusion.9 = f32[2] fusion()", 100, 200),
                         Ev("%fusion.9 = f32[2] fusion()", 400, 500),
                         Ev("%fusion.1 = bf16[2,8] fusion()", 700, 800),
                         Ev("%convert.2 = bf16[2,8] convert()", 800, 900)]),
    ])
    host = Plane("/host:CPU", [Line("python", [
        Ev("bench.window", 0, 1000),
        Ev("bench.admit", 140, 460),
        Ev("engine.admit", 150, 450, rows=2, rids="3 4", bucket=16),
        Ev("engine.admit.put", 160, 250),
        Ev("engine.admit.launch", 250, 260),
        Ev("backend_compile_and_load", 255, 258),
        Ev("engine.admit.sync", 260, 420),
        Ev("bench.step", 470, 695),
        Ev("engine.decode", 480, 690, rows=2),
        Ev("engine.decode.launch", 480, 500),
        Ev("engine.decode.sync", 500, 650),
        Ev("engine.decode.emit", 650, 690),
    ])])
    return Profile([device, host])


@pytest.fixture(scope="module")
def committed():
    return trace_reduce.reduce(TRACE), engine_trace.reduce(TRACE)


def test_old_keys_read_what_they_read_before(committed):
    """The committed trace's reduction, as the benchmark first recorded
    it: adding the engine reduction moves none of it."""
    old, _ = committed
    assert old["by_span"] == pytest.approx({"step": 0.168799449})
    assert old["busy_s"] == pytest.approx(0.168793344)
    assert old["window_s"] == pytest.approx(0.210112411)
    assert old["idle_by_span"] == pytest.approx(
        {"step": 0.041319012, "host": 5.5e-08})
    assert old["idle_gaps"] == [
        ["step", pytest.approx(s)] for s in (
            0.002686827, 0.002419549, 0.002414804, 0.002393754,
            0.002383434, 0.002376258, 0.002361277, 0.00231197,
            0.002249134, 0.002161798)]


def test_a_trace_without_engine_spans_reads_all_idle_as_host(committed):
    old, new = committed
    idle = old["window_s"] - old["busy_s"]
    assert new["idle_by_engine_span"] == {"host": pytest.approx(idle)}
    assert [label for label, _ in new["idle_gaps_engine"]] == \
        ["step/host"] * 10
    assert [s for _, s in new["idle_gaps_engine"]] == pytest.approx(
        [s for _, s in old["idle_gaps"]])
    assert new["compiles_by_span"] == {} and new["by_scope"] == {}


def test_engine_idle_parts_and_host_sum_to_the_idle_time():
    got = engine_trace.reduce_profile(synthetic(),
                                      {"jit_decode_step": HLO})
    idle = got["idle_by_engine_span"]
    assert idle == pytest.approx({
        "engine.admit.put": 50e-9, "engine.admit.launch": 10e-9,
        "engine.admit.sync": 140e-9, "engine.decode.sync": 150e-9,
        "engine.decode.emit": 40e-9, "host": 210e-9})
    old = trace_reduce.Trace(synthetic())
    assert old.window() == (0, 1000)
    assert sum(idle.values()) == pytest.approx(600e-9)
    assert got["idle_gaps_engine"] == [
        ["admit/engine.admit.sync", pytest.approx(200e-9)],
        ["step/engine.decode.sync", pytest.approx(200e-9)],
        ["host/host", pytest.approx(100e-9)],
        ["host/host", pytest.approx(100e-9)]]
    assert got["compiles_by_span"] == {
        "engine.admit.launch": {"n": 1, "s": pytest.approx(3e-9)}}
    assert got["by_scope"] == {"jit_decode_step": pytest.approx(
        {"kv_write": 100e-9, "mlp": 100e-9})}


def test_innermost_segments_of_nested_spans():
    spans = [(0, 10, "a"), (2, 4, "a.b"), (5, 9, "a.c"), (6, 7, "a.c.d"),
             (12, 13, "e")]
    assert engine_trace.innermost(spans) == [
        (0, 2, "a"), (2, 4, "a.b"), (4, 5, "a"), (5, 6, "a.c"),
        (6, 7, "a.c.d"), (7, 9, "a.c"), (9, 10, "a"), (12, 13, "e")]


def test_scope_map_attributes_a_fusion_from_hlo_text():
    """The fusion's own metadata is the layer loop's stacking, outside any
    scope; the instructions it calls vote it into ``kv_write``."""
    got = engine_trace.scope_map(HLO)
    assert got["fusion.1"] == "kv_write"
    assert got["select_n.4"] == "kv_write"
    assert got["convert.2"] == "mlp"
    assert got["convert.6"] == got["copy.3"] == "other"


def _read(name, readings):
    reader = run.load_module(HERE / "metrics" / f"{name}.py")
    return reader.read(readings, {}, {})


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_reader_returns_none_without_the_new_keys(name, committed):
    old, _ = committed
    parent = {"queued_at_close": 0, "active_at_close": 3,
              "queue_wait_p95_ms": 12.5, "kv_filled": 0.1,
              "counters": {"live_rows": 40, "context_sum": 4000,
                           "prefill_ops": 1e9, "decode_steps": 20},
              "trace": old}
    assert _read(name, parent) is None
    assert _read(name, {}) is None


def test_new_readers_read_the_new_keys():
    trace = engine_trace.reduce_profile(synthetic(),
                                        {"jit_decode_step": HLO})
    trace["window_s"] = 1e-6
    window = QuantileSketch()
    for wait in (0.010, 0.020, 0.040):
        window.add(wait)
    readings = {"trace": trace, "queue_wait_engine": window,
                "counters": {"decode_steps": 2, "admit_positions": 64,
                             "admit_real_tokens": 16}}
    assert _read("idle_share.admit_host", readings) == pytest.approx(20.0)
    assert _read("idle_share.decode_host", readings) == pytest.approx(19.0)
    assert _read("admit_pad_share", readings) == pytest.approx(75.0)
    assert _read("decode_kv_write_ms", readings) == pytest.approx(5e-5)
    assert _read("queue_wait_p95_ms.engine", readings) == pytest.approx(
        20.0, rel=0.01)
