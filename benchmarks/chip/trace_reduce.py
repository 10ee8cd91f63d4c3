"""From a profiler trace (``.xplane.pb``) to device time, busy share,
time per program and per host span, and idle gaps by host span.

What the trace holds on a TPU (JAX 0.9, libtpu 0.0.34), and what this
reads of it:

* each chip is a plane ``/device:TPU:<n>``; its line ``XLA Modules`` has
  one event per program execution (``jit_<name>(<hash>)``, stat
  ``run_id``), its line ``XLA Ops`` one event per operation;
* the host plane ``/host:CPU`` has the harness's spans (``bench.<what>``,
  written by ``jax.profiler.TraceAnnotation`` on the Python thread) and
  the runtime's launch chain: ``tpu::System::Execute`` on the launching
  thread carries a flow id (stat ``_p``) that an
  ``IssueSequencedEvent`` on a runtime thread consumes (stat ``_c``);
  inside it ``DoEnqueueProgram`` carries the ``run_id`` of the device
  program.  When the program is enqueued at once, ``DoEnqueueProgram``
  sits on the launching thread itself.

A device program is attributed to the harness span that was open on the
host when it was launched: by that link, never by overlap in time on the
device, because a program may run long after the call that launched it
returned.  Busy time is the union of the ``XLA Ops`` intervals.
"""

from __future__ import annotations

import bisect
import collections
import re

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
HOST_LABEL = "host"          # an idle gap under no harness span


def _stats(ev) -> dict:
    return dict(ev.stats)


def _short(name: str) -> str:
    """``jit__lambda(5493…)`` -> ``jit__lambda``; an HLO op's text
    ``%fusion.12 = bf16[..] fusion(..)`` -> ``fusion.12``."""
    name = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\(\d+\)$", "", name)


def union(intervals):
    """Sorted, merged (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


class Trace:
    """The parts of one trace the reduction needs, in nanoseconds."""

    def __init__(self, profile):
        self.devices = {}          # plane name -> {"modules", "ops"}
        self.spans = []            # (start, end, name) harness spans
        launches = {}              # flow id -> launch start
        enqueues = []              # (line key, start, end, run_id)
        consumers = collections.defaultdict(list)  # line -> [(s, e, flow)]
        for plane in profile.planes:
            if plane.name.startswith("/device:TPU:"):
                modules, ops = [], []
                for line in plane.lines:
                    if line.name == "XLA Modules":
                        for ev in line.events:
                            st = _stats(ev)
                            modules.append((ev.start_ns,
                                            ev.start_ns + ev.duration_ns,
                                            _short(ev.name),
                                            st.get("run_id")))
                    elif line.name == "XLA Ops":
                        for ev in line.events:
                            ops.append((ev.start_ns,
                                        ev.start_ns + ev.duration_ns,
                                        _short(ev.name)))
                self.devices[plane.name] = {"modules": modules, "ops": ops}
            elif plane.name == "/host:CPU":
                for li, line in enumerate(plane.lines):
                    for ev in line.events:
                        s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                        if ev.name.startswith(SPAN_PREFIX):
                            self.spans.append((s, e, ev.name))
                            continue
                        if ev.name == "tpu::System::Execute":
                            flow = _stats(ev).get("_p")
                            if flow is not None:
                                launches[flow] = s
                        elif ev.name == "DoEnqueueProgram":
                            rid = _stats(ev).get("run_id")
                            if rid is not None:
                                enqueues.append((li, s, e, rid))
                        elif "_c" in (st := _stats(ev)):
                            consumers[li].append((s, e, st["_c"]))
        self.spans.sort()
        # run_id -> host time at which the program was launched
        self.launch_of = {}
        for li, s, e, rid in enqueues:
            t = s
            for cs, ce, flow in consumers.get(li, ()):
                if cs <= s and e <= ce and flow in launches:
                    t = launches[flow]
                    break
            self.launch_of.setdefault(rid, t)

    def window(self):
        """(start, end) of the harness's traced window span, else of all
        device activity."""
        for s, e, name in self.spans:
            if name == WINDOW_SPAN:
                return s, e
        ops = [o for d in self.devices.values() for o in d["ops"]]
        if not ops:
            raise ValueError("the trace has no device operation")
        return min(o[0] for o in ops), max(o[1] for o in ops)

    def span_at(self, t) -> str:
        """The innermost harness span (other than the window) open at host
        time ``t``, without its prefix; ``HOST_LABEL`` under none."""
        best = None
        for s, e, name in self.spans:
            if s > t:
                break
            if e > t and name != WINDOW_SPAN:
                if best is None or s >= best[0]:
                    best = (s, name)
        return best[1][len(SPAN_PREFIX):] if best else HOST_LABEL


def _self_times(ops, lo, hi):
    """-> (start, end, op, self time) of each op clipped to [lo, hi]; an
    op that encloses others (a ``while`` around its body) keeps only the
    time none of them covers."""
    out, stack = [], []
    for s, e, op in sorted(ops, key=lambda o: (o[0], -o[1])):
        s, e = _clip(s, e, lo, hi)
        if e <= s:
            continue
        while stack and stack[-1][1] <= s:
            stack.pop()
        if stack:
            out[stack[-1][2]][3] -= e - s
        stack.append((s, e, len(out)))
        out.append([s, e, op, e - s])
    return out


def reduce(path, top: int = 10) -> dict:
    """Reduce the trace at ``path``; times in seconds, each device-time
    figure the mean over the chips in the trace.

    -> {"window_s", "busy_s", "n_chips",
        "by_span": {span: program seconds launched under it},
        "by_program": {program: seconds},
        "unattributed_s": program seconds with no launch link,
        "device_ops": [[program/op, seconds]] (top ``top``),
        "idle_gaps": [[span, seconds]] (the ``top`` longest),
        "idle_by_span": {span: idle seconds}}"""
    import jax

    tr = Trace(jax.profiler.ProfileData.from_file(str(path)))
    if not tr.devices:
        raise ValueError(f"{path}: no TPU device plane in the trace")
    lo, hi = tr.window()
    n = len(tr.devices)
    busy = 0.0
    by_span = collections.Counter()
    by_program = collections.Counter()
    ops_time = collections.Counter()
    unattributed = 0.0
    gaps = []
    for dev in tr.devices.values():
        mods = sorted(dev["modules"])
        starts = [m[0] for m in mods]
        for s, e, name, rid in mods:
            s, e = _clip(s, e, lo, hi)
            if e <= s:
                continue
            by_program[name] += e - s
            if rid in tr.launch_of:
                by_span[tr.span_at(tr.launch_of[rid])] += e - s
            else:
                unattributed += e - s
        clipped = []
        for s, e, op, own in _self_times(dev["ops"], lo, hi):
            clipped.append((s, e))
            i = bisect.bisect_right(starts, s) - 1
            prog = mods[i][2] if i >= 0 and mods[i][1] >= s else "?"
            ops_time[f"{prog}/{op}"] += own
        merged = union(clipped)
        busy += sum(e - s for s, e in merged)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for gs, ge in zip(edges[0::2], edges[1::2]):
            if ge > gs:
                gaps.append((ge - gs, tr.span_at((gs + ge) / 2)))
    idle_by_span = collections.Counter()
    for dur, label in gaps:
        idle_by_span[label] += dur / n
    gaps.sort(key=lambda g: -g[0])
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy / n / 1e9,
        "n_chips": n,
        "by_span": {k: v / n / 1e9 for k, v in by_span.items()},
        "by_program": {k: v / n / 1e9 for k, v in by_program.items()},
        "unattributed_s": unattributed / n / 1e9,
        "device_ops": [[k, v / n / 1e9]
                       for k, v in ops_time.most_common(top)],
        "idle_gaps": [[label, dur / 1e9] for dur, label in gaps[:top]],
        "idle_by_span": {k: v / 1e9 for k, v in idle_by_span.items()},
    }
