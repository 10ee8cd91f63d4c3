"""Unified observability layer for the serving stack (DESIGN.md §14).

Two halves, bundled by :class:`Observability`:

* :mod:`repro.obs.trace` — the flight recorder: per-request lifecycle
  spans and resource instant events in virtual time, exported as
  Chrome trace-event / Perfetto JSON; and ``host_span``, the engine's
  wall-clock spans on the JAX profiler's trace;
* :mod:`repro.obs.metrics` — the metrics registry: named counters /
  gauges / histograms keyed by (resource axis, sharing group, worker),
  histograms backed by a deterministic streaming quantile sketch.

Everything defaults to the no-op singletons (``NOOP_OBS``), so the
serving hot path pays nothing unless a caller opts in via
``enabled_obs()`` / ``--trace-out`` / ``--metrics-out``.
"""

from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               MetricsWindow, NOOP_REGISTRY, QuantileSketch,
                               quantile)
from repro.obs.trace import (host_span, FlightRecorder, NoopRecorder, NOOP_RECORDER,
                             Observability, NOOP_OBS, enabled_obs,
                             PID_FLEET, PID_RESOURCES, PID_REQUESTS,
                             TID_ROUTER, TID_WORKER0, TID_CHANNEL0,
                             TID_PAGES0)
from repro.obs.validate import validate_trace

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "MetricsWindow",
    "NOOP_REGISTRY", "QuantileSketch", "quantile",
    "host_span", "FlightRecorder", "NoopRecorder", "NOOP_RECORDER",
    "Observability", "NOOP_OBS", "enabled_obs",
    "PID_FLEET", "PID_RESOURCES", "PID_REQUESTS",
    "TID_ROUTER", "TID_WORKER0", "TID_CHANNEL0", "TID_PAGES0",
    "validate_trace",
]
