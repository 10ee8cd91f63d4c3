"""Plumbing shared by the drivers: compile counting, the percentile, the
device's memory peak, the profiler, and what a driver hands back."""

from __future__ import annotations

import dataclasses

# the events JAX reports when it traces or compiles a program (a cache
# hit of the persistent compilation cache still traces and lowers)
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")


class CompileCounter:
    """Counts the programs JAX traces and compiles in this process."""

    def __init__(self, jax):
        self.traced = 0
        self.compiled = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, seconds, **_):
        if event == COMPILE_EVENTS[0]:
            self.traced += 1
        elif event == COMPILE_EVENTS[1]:
            self.compiled += 1
            self.compile_s += seconds

    def mark(self) -> tuple:
        return self.traced, self.compiled


def quantile(values, q: float) -> float:
    """Nearest-rank quantile, ``sorted(v)[int(q * (n - 1))]`` (copied from
    ``repro.obs.metrics.quantile``)."""
    vals = sorted(values)
    if not vals:
        raise ValueError("quantile of no values")
    return vals[int(min(1.0, max(0.0, q)) * (len(vals) - 1))]


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices``."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


@dataclasses.dataclass
class Outcome:
    """What a driver hands back to ``run.py``.

    ``end_to_end``: metric name -> value (host clock); ``readings``: what
    the per-layer metric readers read; ``compared``: the correctness
    numbers, name -> (value, limit), each passing when value <= limit;
    ``notes``: lines printed before the result."""

    correct: bool
    attempted: int
    failed: int
    end_to_end: dict
    memory_peak_bytes: int
    compared: dict
    notes: list
    readings: dict = dataclasses.field(default_factory=dict)


def start_trace(jax, directory: str) -> None:
    """The profiler with the device and the host's TraceMe spans, and
    without the Python function tracer (which multiplies the trace's
    size and slows the host loop it is measuring)."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(directory, profiler_options=options)


def trace_file(directory: str) -> str:
    import glob

    found = glob.glob(f"{directory}/**/*.xplane.pb", recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"expected one trace under {directory}, found "
                           f"{found}")
    return found[0]
