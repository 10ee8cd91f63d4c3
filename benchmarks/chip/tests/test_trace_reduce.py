"""The trace reduction, on a trace recorded on a TPU v5e (a quarter of a
second of the serving cell's window, ``testdata/serve_window.xplane.pb``)
and on hand-made intervals."""

import pytest

import trace_reduce
from conftest import HERE

TRACE = HERE / "testdata" / "serve_window.xplane.pb"


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce(TRACE)


def test_recorded_trace_is_small():
    assert TRACE.stat().st_size < 1 << 20


def test_busy_within_window(reduced):
    assert reduced["n_chips"] == 1
    assert 0 < reduced["busy_s"] <= reduced["window_s"]


def test_every_program_is_attributed_by_its_launch(reduced):
    assert reduced["unattributed_s"] == 0
    assert set(reduced["by_span"]) <= {"admit", "step", "wait", "host"}
    assert reduced["by_span"]["step"] > 0
    assert sum(reduced["by_span"].values()) == pytest.approx(
        sum(reduced["by_program"].values()))


def test_idle_gaps_add_up(reduced):
    idle = sum(reduced["idle_by_span"].values())
    assert idle == pytest.approx(reduced["window_s"] - reduced["busy_s"],
                                 rel=1e-6)
    longest = [s for _, s in reduced["idle_gaps"]]
    assert longest == sorted(longest, reverse=True)


def test_device_ops_are_self_times(reduced):
    total = sum(s for _, s in reduced["device_ops"])
    assert 0 < total <= reduced["busy_s"] * (1 + 1e-9)


def test_union_and_self_times():
    assert trace_reduce.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [
        (0, 3), (5, 8)]
    # a while loop [0, 10) around two ops keeps what they leave uncovered
    ops = [(0, 10, "while"), (1, 4, "a"), (5, 9, "b"), (12, 13, "c")]
    got = {op: own for _, _, op, own in
           trace_reduce._self_times(ops, 0, 12.5)}
    assert got == {"while": 3, "a": 3, "b": 4, "c": 0.5}
