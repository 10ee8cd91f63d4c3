"""`serve.connect`: the one serving entry point (DESIGN.md §11).

The pre-plan API exposed three divergent entry points — ``ServeEngine``,
``ContinuousEngine``, ``fabric.Router`` — each with its own pile of
per-call knobs.  Following the paper authors' follow-up argument (stop
exposing user-visible endpoints; let callers declare intent and streams,
resolve resources internally), callers now do:

    client = serve.connect(cfg, "shared_dynamic", params=params)
    client = serve.connect(cfg, Hints(latency_target_ms=80,
                                      burstiness=0.9), n_workers=8)
    client = serve.connect(cfg, SharingVector(slots=1, channels=3))

    s = client.stream()                  # ordered lane (MPIX-stream-like)
    s.submit(prompt_a); s.submit(prompt_b)
    client.submit(prompt_c)              # unordered: free concurrency
    tokens = client.run()                # {rid: [generated tokens]}

``connect`` resolves anything plan-shaped (``core.plan.as_plan``) into an
``EndpointPlan`` and the client picks the executor: a fleet of
continuous-batching workers behind the fabric router when
``plan.n_workers > 1``, a single ``ContinuousEngine`` otherwise, or the
legacy wave engine when the plan says ``executor="wave"``.  The old
classes survive as these internal executors; every knob they used to take
lives on the plan.

**Stream semantics.**  A ``Stream`` is an ordered lane: its requests
start AND finish in submission order (request *i+1* is released into the
engine only after request *i* retires), while different streams — and all
unordered submissions — run concurrently.  In fleet mode a stream
additionally carries its id as the fabric session key, so
session-affinity placement pins the lane to one channel group (the
stream → channel-group mapping); in single-engine mode the lane occupies
at most one slot of the pool's admission groups at a time (the stream →
slot-group mapping).  Ordering changes WHEN tokens are produced, never
their values.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Dict, List, Optional, Union

import jax
import numpy as np

from repro.core.adapt import Replanner, WindowStats
from repro.core.plan import EndpointPlan, Hints, SharingVector, as_plan
from repro.models.model import Model
from repro.models.params import serving_params
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import NOOP_OBS, Observability
from repro.serve.engine import ContinuousEngine, Request, ServeEngine
from repro.serve.fabric.faults import FaultPlan
from repro.serve.fabric.placement import POLICIES
from repro.serve.fabric.router import (Completion, EngineWorker,
                                       FabricCosts, FleetReport, Router)
from repro.serve.fabric.traffic import Arrival
from repro.serve.recovery import RecoveryPolicy

#: Plan fields a live ``replan`` may NOT change: they size caches,
#: compiled shapes, or the worker fleet itself — migrating them would
#: mean evicting in-flight requests, which the migration contract forbids.
STRUCTURAL_FIELDS = ("n_workers", "n_slots", "max_len", "decode_horizon",
                     "prefill_buckets", "use_ragged_kernel", "executor",
                     "page_size", "page_budget", "roles")

# fabric session keys for streams live above any plausible caller-supplied
# session id, so a stream's affinity key can never alias a user session
_STREAM_SESSION_BASE = 1 << 32


@dataclasses.dataclass
class _Pending:
    """One submitted request waiting for the next ``run()``."""

    rid: int
    prompt: np.ndarray
    max_new_tokens: int
    eos_id: Optional[int]
    sid: Optional[int]                # stream id; None = unordered
    at_ns: float                      # virtual arrival time (fleet mode)
    session: int = -1                 # affinity key for unordered requests


class Stream:
    """An ordered lane of one ``ServeClient`` (explicit, MPIX-style).

    Requests submitted to a stream complete in submission order; distinct
    streams progress concurrently.  Obtain one via ``client.stream()``.
    """

    def __init__(self, client: "ServeClient", sid: int,
                 name: Optional[str] = None):
        self.client = client
        self.sid = sid
        self.name = name or f"stream{sid}"
        self.rids: List[int] = []

    def submit(self, prompt, max_new_tokens: int = 16,
               eos_id: Optional[int] = None, at_ns: float = 0.0) -> int:
        return self.client.submit(prompt, max_new_tokens=max_new_tokens,
                                  eos_id=eos_id, stream=self, at_ns=at_ns)

    @property
    def outputs(self) -> List[Optional[List[int]]]:
        """This stream's generated tokens, in submission order (None for
        requests the client has not run yet)."""
        return [self.client.results.get(r) for r in self.rids]

    def __repr__(self):
        return f"Stream({self.name!r}, sid={self.sid}, " \
               f"requests={len(self.rids)})"


class ServeClient:
    """A connected serving session over one resolved ``EndpointPlan``.

    Build via ``serve.connect``.  ``submit`` queues work (optionally on a
    ``Stream``), ``run`` drains everything queued so far and returns
    ``{rid: [tokens]}``; ``results`` accumulates across runs.
    """

    def __init__(self, cfg, params, plan: EndpointPlan,
                 obs: Optional[Observability] = None,
                 faults: Union[FaultPlan, str, None] = None,
                 recovery: Optional[RecoveryPolicy] = None,
                 plan_repository=None, migrations=None):
        if plan.placement not in POLICIES:
            raise ValueError(f"unknown placement {plan.placement!r}; "
                             f"one of {sorted(POLICIES)}")
        self.cfg = cfg
        #: weights bound once in the compute dtype, shared by the engine
        #: or every fleet replica (which then find nothing left to cast)
        self.params, self.weight_binding = serving_params(
            params, cfg, Model(cfg).plan)
        self.plan = plan
        #: tuned-plan store (DESIGN.md §16, duck-typed
        #: ``tune.PlanRepository``): consulted by hint re-resolution in
        #: ``replan`` and handed to the adaptive controller so live
        #: transitions jump to measured frontier plans.  None = the
        #: historical analytic/hysteresis behavior, bit-identical.
        self.plan_repository = plan_repository
        #: observability bundle (DESIGN.md §14): defaults to the no-op
        #: recorder/registry; ``connect(..., obs=enabled_obs())`` records
        #: a fleet's virtual-time spans and every run's metrics (a single
        #: engine's wall-clock spans go to the JAX profiler instead)
        self.obs = obs if obs is not None else NOOP_OBS
        self.executor = plan.resolved_executor
        if (faults is not None or recovery is not None
                or migrations) and self.executor != "fleet":
            raise ValueError(
                "fault injection / crash recovery / live migration live "
                "on the fleet fabric (plan.n_workers > 1); this plan "
                f"resolved to the {self.executor!r} executor")
        #: chaos fabric (DESIGN.md §15): a FaultPlan (or its string
        #: grammar) injected into every run's router; ``recovery`` tunes
        #: detection/backoff/shedding.  Both None = today's fault-free
        #: event stream, bit-identical.
        self.faults = faults
        self.recovery = recovery
        #: scheduled decode→decode live migrations (DESIGN.md §17):
        #: (t_ns, src_worker, dst_worker) triples the router drains at
        #: their virtual times on EVERY fleet run — the source worker's
        #: live sessions leave as KV handoffs and resume on the
        #: destination mid-stream, token streams bit-identical
        self.migrations = list(migrations) if migrations else None
        self.results: Dict[int, List[int]] = {}
        #: exactly-once delivery cursor: tokens of ``results[rid]``
        #: already surfaced to the caller.  Completion replays (a retry
        #: racing its original, a duplicate splice) append only the
        #: tokens past the cursor — never double-deliver, never reorder.
        self._cursor: Dict[int, int] = {}
        #: replays that DISAGREED with already-delivered tokens
        #: (first-wins; structurally impossible under fail-stop, counted
        #: defensively)
        self.dedup_conflicts = 0
        self.report: Optional[FleetReport] = None   # last fleet report
        #: live migrations applied so far: (schedule key, vector) —
        #: virtual ns in fleet mode, engine step count in single-engine
        self.transitions: List = []
        self._pending: List[_Pending] = []
        self._requests: Dict[int, _Pending] = {}
        self._streams: List[Stream] = []
        self._next_rid = 0
        self._closed = False
        self.engine = None            # single-executor engine
        self.workers: List[EngineWorker] = []
        if self.executor == "wave":
            self.engine = ServeEngine(cfg, self.params, plan=plan)
        elif self.executor == "continuous":
            self.engine = ContinuousEngine(cfg, self.params, plan=plan,
                                           exec_group=plan.exec_group_of(0))
        if self.engine is not None:
            self.engine.weight_binding = self.weight_binding
        # fleet workers are built lazily on the first run()

    # ----- submission -----------------------------------------------------
    def stream(self, name: Optional[str] = None) -> Stream:
        """A new ordered lane.  Wave execution cannot order (one static
        wave is the level-4 extreme), so streams need a continuous or
        fleet executor."""
        if self.executor == "wave":
            raise ValueError("ordered streams need the continuous or "
                             "fleet executor; the wave engine is one "
                             "unordered static wave")
        s = Stream(self, len(self._streams), name)
        self._streams.append(s)
        return s

    def submit(self, prompt, max_new_tokens: int = 16,
               eos_id: Optional[int] = None,
               stream: Union[Stream, int, None] = None,
               at_ns: float = 0.0, session: int = -1) -> int:
        """Queue one request; -> its rid.  ``stream`` orders it behind
        the stream's earlier requests; ``at_ns`` is its virtual arrival
        time in fleet mode (ignored by the single-engine executors, which
        are closed-loop); ``session`` is a placement-affinity key for
        unordered requests (a stream already carries its own)."""
        if self._closed:
            raise RuntimeError("client is closed")
        if isinstance(stream, Stream):
            if stream.client is not self:
                raise ValueError("stream belongs to a different client")
        elif stream is not None:
            stream = self._streams[stream]
        prompt = np.asarray(prompt, np.int32)
        if prompt.ndim != 1 or prompt.size == 0:
            raise ValueError("prompt must be a non-empty 1-D token array")
        if self.executor != "wave" and len(prompt) >= self.plan.max_len:
            # the continuous engines (and fleet accounting) need the
            # prompt to fit; the wave engine instead truncates the decode
            # budget at the cache edge — a supported legacy mode
            raise ValueError(f"prompt of {len(prompt)} tokens cannot fit "
                             f"max_len={self.plan.max_len}")
        rid = self._next_rid
        self._next_rid += 1
        p = _Pending(rid=rid, prompt=prompt,
                     max_new_tokens=int(max_new_tokens), eos_id=eos_id,
                     sid=stream.sid if stream is not None else None,
                     at_ns=float(at_ns), session=int(session))
        self._pending.append(p)
        self._requests[rid] = p
        if stream is not None:
            stream.rids.append(rid)
        return rid

    def generate(self, prompts, max_new_tokens: int = 16) -> List[List[int]]:
        """Convenience: submit a batch of unordered prompts, run, and
        return their outputs in input order."""
        rids = [self.submit(p, max_new_tokens=max_new_tokens)
                for p in prompts]
        out = self.run()
        return [out[r] for r in rids]

    # ----- execution ------------------------------------------------------
    def run(self) -> Dict[int, List[int]]:
        """Serve everything queued since the last run; -> their
        ``{rid: [tokens]}`` (also merged into ``results``)."""
        if self._closed:
            raise RuntimeError("client is closed")
        batch, self._pending = self._pending, []
        if not batch:
            return {}
        if self.executor == "fleet":
            out = self._run_fleet(batch)
        elif self.executor == "wave":
            out = self._run_wave(batch)
        else:
            out = self._run_continuous(batch)
        missing = {p.rid for p in batch} - out.keys()
        if missing and self.report is not None:
            # shed / retry-exhausted requests are ACCOUNTED losses (the
            # report names them); stream successors behind a dropped
            # head return to the pending queue for the next run()
            dropped = ({rid for rid, _, _ in self.report.shed}
                       | set(self.report.failed)
                       | {p.rid for p in self._pending})
            missing -= dropped
        assert not missing, f"requests lost by the executor: {missing}"
        self.results.update(out)
        return out

    def _ingest(self, rid: int, tokens) -> List[int]:
        """Fold a completion's token list into ``results[rid]`` through
        the exactly-once cursor: the overlap with what was already
        delivered must agree (first delivery wins; a disagreement bumps
        ``dedup_conflicts`` and is dropped), and only the suffix past
        the cursor is appended.  Idempotent under replays."""
        tokens = [int(x) for x in tokens]
        got = self.results.setdefault(rid, [])
        cur = self._cursor.get(rid, len(got))
        overlap = min(cur, len(tokens))
        if tokens[:overlap] != got[:overlap]:
            self.dedup_conflicts += 1
            return got
        got.extend(tokens[cur:])
        self._cursor[rid] = len(got)
        return got

    # ----- fault-tolerance views (populated by fleet runs) ----------------
    @property
    def shed(self) -> List:
        """Requests refused before acceptance: (rid, reason, t_ns)."""
        return list(self.report.shed) if self.report is not None else []

    @property
    def failed(self) -> List[int]:
        """Requests that exhausted their retry budget."""
        return list(self.report.failed) if self.report is not None else []

    def _request(self, p: _Pending) -> Request:
        return Request(rid=p.rid, prompt=p.prompt,
                       max_new_tokens=p.max_new_tokens, eos_id=p.eos_id)

    def _split(self, batch):
        """-> (unordered pendings, {sid: deque of its pendings})."""
        unordered, streams = [], {}
        for p in batch:
            if p.sid is None:
                unordered.append(p)
            else:
                streams.setdefault(p.sid, deque()).append(p)
        return unordered, streams

    def _run_wave(self, batch) -> Dict[int, List[int]]:
        eng = self.engine
        for p in batch:
            eng.submit(self._request(p))
        rids = {p.rid for p in batch}
        eng.run()
        return {r.rid: list(r.output) for r in eng.done if r.rid in rids}

    def _run_continuous(self, batch) -> Dict[int, List[int]]:
        """Drive the single engine's external-stepping hooks, releasing
        each stream's next request only once its predecessor retires —
        per-stream FIFO over the slot pool, cross-stream concurrency.
        With ``plan.adaptive`` a ``Replanner`` samples the engine's own
        counters every window (windows sized in decode steps via the
        fabric cost model, so one knob paces both executors) and its
        proposals land through ``_apply_vector`` — the same path manual
        ``replan`` takes."""
        eng = self.engine
        unordered, streams = self._split(batch)
        inflight = {sid: None for sid in streams}
        for p in unordered:
            eng.submit(self._request(p))
        out: Dict[int, List[int]] = {}
        eng.start()
        # latency baseline per run(), exactly as ContinuousEngine.run()
        # re-baselines (start() is idempotent and keeps the first _t0)
        eng._t0 = time.perf_counter()
        adapt = self._make_replanner() if self.plan.adaptive else None
        win_steps = max(1, int(self.plan.adapt_window_ns
                               // FabricCosts().t_step_base_ns))
        # single-engine window accounting runs through the same metrics
        # fabric the fleet router uses (DESIGN.md §14): the engine
        # publishes its absolute counters, the registry window diffs
        # them — no hand-threaded stats-dict marks
        reg = (self.obs.metrics if self.obs.metrics.enabled
               else MetricsRegistry())
        eng.publish_metrics(reg, worker=0)
        win = reg.window()
        step_mark = eng.stats["decode_steps"]
        while True:
            for sid in sorted(streams):
                if inflight[sid] is None and streams[sid]:
                    p = streams[sid].popleft()
                    eng.submit(self._request(p))
                    inflight[sid] = p.rid
            if not eng.has_work:
                break
            eng.admit_waiting()
            for r in eng.step():
                out[r.rid] = list(r.output)
                sid = self._requests[r.rid].sid
                if sid is not None and inflight.get(sid) == r.rid:
                    inflight[sid] = None
            if adapt is not None and eng.stats["decode_steps"] \
                    - step_mark >= win_steps:
                step_mark = eng.stats["decode_steps"]
                eng.publish_metrics(reg, worker=0)
                d_slot = win.delta("engine.slot_steps", axis="slots",
                                   worker=0)
                d_busy = win.delta("engine.busy_slot_steps", axis="slots",
                                   worker=0)
                d_compiles = win.delta_total("engine.jit_compiles")
                win.roll()
                vec = adapt.observe(WindowStats(
                    occupancy=d_busy / d_slot if d_slot else 0.0,
                    queue_depth=float(len(eng.queue)),
                    jit_compiles=max(0, int(d_compiles)),
                    tokens=int(d_busy),
                    page_pressure=(eng.page_pool.pressure()
                                   if eng.paged else 0.0)))
                if vec is not None:
                    self._apply_vector(vec)
                    self.transitions.append((eng._step_no, vec))
        eng.publish_metrics(reg, worker=0)
        if adapt is not None and adapt.vector != self.plan.vector:
            self.plan = dataclasses.replace(self.plan, preset=None,
                                            vector=adapt.vector)
        return out

    def _build_workers(self):
        plan = self.plan

        def request_fn(arrival: Arrival) -> Request:
            return self._request(self._requests[arrival.rid])

        self.workers = [
            EngineWorker(
                w,
                ContinuousEngine(self.cfg, self.params, plan=plan,
                                 exec_group=plan.exec_group_of(w)),
                request_fn=request_fn)
            for w in range(plan.n_workers)]
        for wk in self.workers:
            wk.engine.weight_binding = self.weight_binding

    def _run_fleet(self, batch) -> Dict[int, List[int]]:
        """One router pass over fresh channels (the engines persist and
        keep their jitted state): unordered requests and stream heads
        enter at their arrival times; each completion of a stream request
        releases the stream's next via the router's ``on_complete`` hook
        — per-stream FIFO mapped onto the channel groups."""
        if not self.workers:
            self._build_workers()
        unordered, waiting = self._split(batch)

        def arrival(p: _Pending, t_ns: float) -> Arrival:
            return Arrival(rid=p.rid, t_ns=t_ns,
                           prompt_len=len(p.prompt),
                           max_new_tokens=p.max_new_tokens,
                           session=(p.session if p.sid is None
                                    else _STREAM_SESSION_BASE + p.sid))

        trace = [arrival(p, p.at_ns) for p in unordered]
        for q in waiting.values():
            head = q.popleft()
            trace.append(arrival(head, head.at_ns))
        trace.sort(key=lambda a: (a.t_ns, a.rid))

        def on_complete(c: Completion):
            # stream tokens through the exactly-once cursor as they
            # complete (the final loop below replays idempotently)
            self._ingest(c.rid, c.output)
            sid = self._requests[c.rid].sid
            if sid is None or not waiting.get(sid):
                return ()
            nxt = waiting[sid].popleft()
            return [arrival(nxt, max(nxt.at_ns, c.t_done_ns))]

        adapt = self._make_replanner() if self.plan.adaptive else None
        router = Router(self.workers, self.plan,
                        placement=self.plan.placement,
                        on_complete=on_complete, adapt=adapt,
                        adapt_window_ns=self.plan.adapt_window_ns,
                        obs=self.obs, faults=self.faults,
                        recovery=self.recovery,
                        migrations=self.migrations)
        self.report = router.run(trace)
        if adapt is not None:
            self.transitions.extend(self.report.transitions)
            if router.vector != self.plan.vector:
                # the migrated vector persists: the next run()'s router
                # (and its dispatch plan) starts where this one ended
                self.plan = dataclasses.replace(self.plan, preset=None,
                                                vector=router.vector)
        # a shed/failed stream head never releases its successors: they
        # go back on the pending queue so a later run() can retry them
        # (fault-free, the waiting queues always drain — this is inert)
        for q in waiting.values():
            self._pending.extend(q)
        return {c.rid: list(self._ingest(c.rid, c.output))
                for c in self.report.completions}

    # ----- live re-planning -----------------------------------------------
    def _make_replanner(self) -> Replanner:
        """The controller for this client's plan.  If an
        ``adapt_budget`` forces the starting vector tighter than the plan
        asked for, the clamp is applied to the live stack immediately so
        the controller and the fleet never disagree."""
        plan = self.plan
        adapt = Replanner(plan.vector, n_workers=plan.n_workers,
                          n_slots=plan.n_slots, budget=plan.adapt_budget,
                          paged=plan.paged,
                          repository=self.plan_repository)
        if adapt.vector != plan.vector:
            self._apply_vector(adapt.vector)
            self.plan = dataclasses.replace(plan, preset=None,
                                            vector=adapt.vector)
        return adapt

    def _apply_vector(self, vec: SharingVector) -> None:
        """THE client-side migration executor — manual ``replan`` and the
        automatic controller both land here.  Single-engine mode re-keys
        the live engine (slot pool in place, executable group between
        dispatches); fleet mode re-keys every persistent worker engine,
        and the channel axis re-keys when the next ``run()`` builds its
        router from the updated plan (mid-run fleet channel migration is
        ``Router.apply_vector``, this method's virtual-time twin)."""
        if self.executor == "wave":
            raise ValueError("the wave executor cannot re-plan live; "
                             "adaptive plans need continuous or fleet")
        if self.executor == "continuous":
            self.engine.regroup(
                slot_level=vec.slots, exec_group=vec.exec_group_of(0, 1),
                page_level=(vec.pages if self.engine.paged else None))
        else:
            for w, worker in enumerate(self.workers):
                worker.regroup(
                    slot_level=vec.slots,
                    exec_group=vec.exec_group_of(w, self.plan.n_workers),
                    page_level=vec.pages)

    def replan(self, spec=None, **overrides) -> EndpointPlan:
        """Manually migrate this client to a new plan WITHOUT dropping
        queued work or evicting in-flight state (DESIGN.md §12).

        ``spec`` is anything ``connect`` accepts — an ``EndpointPlan``,
        ``Hints`` (re-resolved against this client's fleet shape), a
        ``SharingVector``, a preset name, or None with field overrides.
        Only the sharing vector (and placement) may change: structural
        fields (``n_workers``, ``n_slots``, ``max_len``, horizons,
        buckets, executor) are pinned to the live deployment and raise
        ``ValueError`` if a spec tries to move them.  Returns the new
        plan.  Token values are migration-invariant — pinned bit-exactly
        by the golden-trace harness."""
        if self._closed:
            raise RuntimeError("client is closed")
        plan = self.plan
        if isinstance(spec, EndpointPlan):
            new = as_plan(spec, **overrides)
        else:
            keep = {f: getattr(plan, f) for f in STRUCTURAL_FIELDS}
            keep.update(placement=plan.placement, adaptive=plan.adaptive,
                        adapt_window_ns=plan.adapt_window_ns,
                        adapt_budget=plan.adapt_budget)
            if isinstance(spec, Hints):
                # hints resolve their own placement and budget; the live
                # plan's pre-filled values would silently override them
                if spec.session_ordering:
                    keep.pop("placement")
                if spec.footprint_budget is not None:
                    keep.pop("adapt_budget")
                keep.update(overrides)
                # hint re-resolution consults the attached tuned-plan
                # repository first, exactly like connect (DESIGN.md §16)
                new = EndpointPlan.from_hints(
                    spec, repository=self.plan_repository, **keep)
            else:
                keep.update(overrides)
                new = as_plan(spec, **keep)
        for f in STRUCTURAL_FIELDS:
            if getattr(new, f) != getattr(plan, f):
                raise ValueError(
                    f"live replan cannot change {f} "
                    f"({getattr(plan, f)!r} -> {getattr(new, f)!r}); "
                    f"connect() a fresh client for structural changes")
        if new.placement not in POLICIES:
            raise ValueError(f"unknown placement {new.placement!r}; "
                             f"one of {sorted(POLICIES)}")
        if new.paged != plan.paged:
            # the PAGES LEVEL re-keys budgets live (pure accounting),
            # but flipping the physical cache LAYOUT — contiguous <->
            # paged — resizes every cache leaf, which is structural
            raise ValueError(
                "live replan cannot switch the KV-cache layout "
                f"({'paged' if plan.paged else 'contiguous'} -> "
                f"{'paged' if new.paged else 'contiguous'}); "
                "connect() a fresh client with the paged plan instead")
        if new.vector != plan.vector:
            self._apply_vector(new.vector)
            self.transitions.append((None, new.vector))
        self.plan = new
        return new

    # ----- lifecycle ------------------------------------------------------
    def close(self):
        self._closed = True

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    def __repr__(self):
        v = self.plan.vector
        return (f"ServeClient(executor={self.executor!r}, "
                f"vector=(slots={v.slots}, channels={v.channels}, "
                f"execs={v.execs}), workers={self.plan.n_workers}, "
                f"slots={self.plan.n_slots})")


def connect(cfg, plan: Union[EndpointPlan, Hints, SharingVector, str,
                             None] = None, *,
            params=None, seed: int = 0,
            obs: Optional[Observability] = None,
            faults: Union[FaultPlan, str, None] = None,
            recovery: Optional[RecoveryPolicy] = None,
            plan_repository=None, use_repository: bool = True,
            migrations=None,
            **overrides) -> ServeClient:
    """Connect a serving session: resolve ``plan`` (an ``EndpointPlan``,
    ``Hints``, ``SharingVector``, ``Category``/preset name, or None for
    the default plan; ``overrides`` set/replace plan fields) and return a
    ``ServeClient`` over the executor the plan selects.  ``params``
    defaults to freshly initialized weights (``seed``).  ``obs`` (an
    ``obs.Observability``, e.g. ``obs.enabled_obs()``) turns on the
    flight recorder + metrics registry for every run.  ``faults`` (a
    ``FaultPlan`` or its ``"crash@4.5ms:w0,stall@2ms:w1:1ms"`` grammar)
    injects deterministic failures into every fleet run; ``recovery``
    (a ``serve.RecoveryPolicy``) tunes detection, retry backoff, and
    overload shedding — both need the fleet executor.

    ``migrations`` schedules decode→decode live migrations on every
    fleet run: ``(t_ns, src_worker, dst_worker)`` triples drained at
    their virtual times — the source's live sessions leave as KV
    handoffs and resume on the destination without dropping or
    duplicating a token (DESIGN.md §17).  ``roles="2P+2D"`` (a plan
    field / override) splits the fleet into prefill-only and
    decode-only sub-fleets with the KV handed off after each prefill.

    ``plan_repository`` (DESIGN.md §16) attaches a tuned-plan store
    (``tune.PlanRepository``): ``Hints`` resolution consults its stored
    Pareto-frontier plans before the analytic planner
    (``use_repository=False`` is the explicit escape hatch — attach the
    store for the adaptive controller but resolve analytically), and
    the adaptive controller jumps between its frontier plans instead of
    stepping one sharing axis at a time."""
    if isinstance(plan, Hints) and plan_repository is not None:
        resolved = EndpointPlan.from_hints(
            plan, repository=plan_repository,
            use_repository=use_repository, **overrides)
    else:
        resolved = as_plan(plan, **overrides)
    if params is None:
        params = Model(cfg).init(jax.random.PRNGKey(seed))
    return ServeClient(cfg, params, resolved, obs=obs, faults=faults,
                       recovery=recovery, plan_repository=plan_repository,
                       migrations=migrations)


# connect(..., adaptive=True) is the one-flag spelling of live
# re-planning: the override lands on the plan, and the client attaches a
# core.adapt.Replanner to every run (DESIGN.md §12).  Manual migration is
# client.replan(plan_or_hints); both go through the same apply path.
