"""From a profiler trace to device idle time by the serving engine's own
wall-clock spans, JAX's compiles by engine span, and device time by the
model's named scopes.

The engine opens ``engine.*`` spans on the host plane (the program's
``obs.host_span``: ``engine.admit`` and its ``.pack``/``.put``/``.launch``/
``.sync``/``.bind`` children, ``engine.decode`` and ``engine.horizon`` with
theirs).  Where ``trace_reduce`` credits an idle gap to the harness span
(``bench.*``) open at its midpoint, this credits each stretch of idle
device time to the innermost engine span open over it, by overlap; idle
time under no engine span is ``host`` (the harness's own loop between its
calls into the engine).  A trace without engine spans reads all idle time
as ``host``.

The recorded device ops carry no metadata, so ``by_scope`` maps each op to
its ``op_name`` through the compiled text of the same program
(``compile().as_text()``; the engine's ``decode_program_text()``), and
from there to a named scope (``scope_map``).
"""

from __future__ import annotations

import bisect
import collections
import re

import trace_reduce

ENGINE_PREFIX = "engine."
HOST_LABEL = trace_reduce.HOST_LABEL
# the model's named scopes (``jax.named_scope``), innermost wins
SCOPES = ("embed", "attn", "kv_write", "mlp", "norm", "lm_head")
OTHER_SCOPE = "other"
# JAX's own host spans around an XLA compile (``profiler.annotate_function``)
COMPILE_EVENTS = ("backend_compile_and_load", "backend_compile")

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+) \(")
_INSTR = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")


def scope_map(text: str) -> dict:
    """HLO instruction name -> its named scope, for every instruction of
    the compiled ``text``.  An instruction votes for the innermost of
    ``SCOPES`` in its ``op_name`` metadata, and a fusion also by every
    instruction of the computation it calls (XLA often gives a fusion the
    metadata of a root outside the scope, as the layer loop's stacking
    of a KV-cache write); the scope with most votes wins, ``OTHER_SCOPE``
    where none votes (ops XLA made, as weight casts hoisted out of the
    layer loop, carry no metadata)."""
    comps, own, calls, comp = {}, {}, {}, None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m and line.rstrip().endswith("{"):
            comp = comps.setdefault(m.group(1), [])
            continue
        m = _INSTR.match(line)
        if not m or comp is None:
            continue
        name = m.group(1)
        comp.append(name)
        op = _OP_NAME.search(line)
        parts = op.group(1).split("/") if op else []
        own[name] = next((p for p in reversed(parts) if p in SCOPES), None)
        called = _CALLS.search(line)
        if called:
            calls[name] = called.group(1)

    def votes(name, seen=()):
        out = collections.Counter()
        if own.get(name):
            out[own[name]] += 1
        callee = calls.get(name)
        if callee in comps and callee not in seen:
            for inner in comps[callee]:
                out.update(votes(inner, seen + (callee,)))
        return out

    out = {}
    for name in own:
        v = votes(name)
        out[name] = max(v, key=lambda k: (v[k], k)) if v else OTHER_SCOPE
    return out


def host_events(profile):
    """-> (engine spans, compile events) of the host plane, each a list of
    (start, end, name) in nanoseconds."""
    spans, compiles = [], []
    for plane in profile.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(ENGINE_PREFIX):
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                  ev.name))
                elif ev.name in COMPILE_EVENTS:
                    compiles.append((ev.start_ns,
                                     ev.start_ns + ev.duration_ns, ev.name))
    return sorted(spans), sorted(compiles)


def innermost(spans):
    """Nested (start, end, name) spans -> disjoint, sorted (start, end,
    name) segments, each labelled by the innermost span open over it."""
    segs, stack, cur = [], [], None

    def close_until(t):
        nonlocal cur
        while stack and stack[-1][0] <= t:
            end, name = stack.pop()
            if end > cur:
                segs.append((cur, end, name))
                cur = end

    for s, e, name in sorted(spans, key=lambda x: (x[0], -x[1])):
        if cur is None:
            cur = s
        close_until(s)
        if stack and s > cur:
            segs.append((cur, s, stack[-1][1]))
        cur = max(cur, s)
        stack.append((e, name))
    if stack:
        close_until(float("inf"))
    return segs


def overlaps(gap, segs, starts) -> dict:
    """Label -> ns of ``gap`` (start, end) covered by each label of the
    disjoint sorted ``segs``; the rest under ``HOST_LABEL``."""
    gs, ge = gap
    out = collections.Counter()
    i = max(0, bisect.bisect_right(starts, gs) - 1)
    covered = 0
    while i < len(segs) and segs[i][0] < ge:
        s, e, name = segs[i]
        d = min(e, ge) - max(s, gs)
        if d > 0:
            out[name] += d
            covered += d
        i += 1
    if ge - gs > covered:
        out[HOST_LABEL] += ge - gs - covered
    return out


def reduce(path, texts=None, top: int = 10) -> dict:
    """``reduce_profile`` of the trace file at ``path``."""
    import jax

    return reduce_profile(jax.profiler.ProfileData.from_file(str(path)),
                          texts, top)


def reduce_profile(profile, texts=None, top: int = 10) -> dict:
    """Times in seconds, each device-time figure the mean over the chips.
    ``texts``: program name (``jit_decode_step``) -> its compiled text.

    -> {"idle_by_engine_span": {engine span or "host": idle seconds},
        "idle_gaps_engine": [["<bench span>/<engine span>", seconds]] (the
            ``top`` longest gaps, each under the engine span that covers
            most of it),
        "compiles_by_span": {engine span or "host": {"n", "s"}},
        "by_scope": {program: {scope: device self seconds}} (programs
            in ``texts`` only)}"""
    tr = trace_reduce.Trace(profile)
    if not tr.devices:
        raise ValueError("no TPU device plane in the trace")
    lo, hi = tr.window()
    n = len(tr.devices)
    spans, compiles = host_events(profile)
    segs = innermost(spans)
    starts = [s for s, _, _ in segs]
    maps = {prog: scope_map(text) for prog, text in (texts or {}).items()}

    idle = collections.Counter()
    gaps = []
    by_scope = collections.defaultdict(collections.Counter)
    for dev in tr.devices.values():
        mods = sorted(dev["modules"])
        mod_starts = [m[0] for m in mods]
        clipped = []
        for s, e, op, own in trace_reduce._self_times(dev["ops"], lo, hi):
            clipped.append((s, e))
            i = bisect.bisect_right(mod_starts, s) - 1
            prog = mods[i][2] if i >= 0 and mods[i][1] >= s else None
            if prog in maps:
                by_scope[prog][maps[prog].get(op, OTHER_SCOPE)] += own
        edges = [lo] + [x for iv in trace_reduce.union(clipped)
                        for x in iv] + [hi]
        for gs, ge in zip(edges[0::2], edges[1::2]):
            if ge <= gs:
                continue
            parts = overlaps((gs, ge), segs, starts)
            idle.update(parts)
            label = max(parts, key=lambda k: (parts[k], k))
            gaps.append((ge - gs, f"{tr.span_at((gs + ge) / 2)}/{label}"))

    compiled = collections.defaultdict(lambda: {"n": 0, "s": 0.0})
    for s, e, _ in compiles:
        open_ = [(ss, name) for ss, ee, name in spans if ss <= s and e <= ee]
        label = max(open_)[1] if open_ else HOST_LABEL
        compiled[label]["n"] += 1
        compiled[label]["s"] += (e - s) / 1e9
    gaps.sort(key=lambda g: -g[0])
    return {
        "idle_by_engine_span": {k: v / n / 1e9 for k, v in idle.items()},
        "idle_gaps_engine": [[label, dur / 1e9]
                             for dur, label in gaps[:top]],
        "compiles_by_span": dict(compiled),
        "by_scope": {prog: {k: v / n / 1e9 for k, v in scopes.items()}
                     for prog, scopes in by_scope.items()},
    }
