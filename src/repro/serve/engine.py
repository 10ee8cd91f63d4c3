"""Serving engines: static wave batching and continuous batching.

``ServeEngine`` is the legacy wave scheduler (DESIGN.md §6.1): requests
are grouped into waves of equal prompt length, each wave prefills batched
into a shared KV cache and decodes until every member finishes — finished
slots keep decoding into a masked void, the standard static-batching
tradeoff, and nothing is admitted mid-wave.

``ContinuousEngine`` (DESIGN.md §6.2) is the paper's resource-pool idea
applied to decode slots: per-slot sequence positions (``Model.init_cache``
``per_slot`` + position-aware ``decode_step``), ragged slot lengths in one
shared cache, and slot admission/eviction so a finished request frees its
slot for a queued request mid-decode.  The admission policy is a
``SlotPool`` keyed by the ``slots`` sharing level of an
``EndpointPlan``'s ``SharingVector`` (DESIGN.md §3, §11): a
dedicated slot per request is MPI-everywhere, one shared wave is
MPI+threads, and k-way-shared slot groups are the scalable middle.

Two host-interaction batching layers sit on the continuous hot path
(DESIGN.md §10 — the serving translation of the paper's doorbell
batching and bounded-QP-set lessons):

* **Fused decode horizon** (``decode_horizon=K``): token generation runs
  on device for K steps per host sync (``Model.decode_horizon`` — argmax
  sampling, budget decrement, EOS detection, and the finished mask fused
  into one early-exiting ``lax.while_loop``), then the whole K-step
  token trace drains in a single transfer.  ``K=1`` is the per-step host
  loop, kept as the bit-exactness oracle.
* **Bucketed batched prefill** (``prefill_buckets``): every admission of
  a round pads to a shared power-of-2 length bucket and prefills as ONE
  fixed-shape batched call + one fused multi-slot cache scatter, so jit
  specializations are bounded by ``len(buckets)`` instead of one per
  distinct prompt length.  Trailing padding is bit-invisible under causal
  attention (``Model.prefill`` ``last_index``); models with recurrent
  blocks or rolling-window caches fall back to exact-length prefill.

Both engines drive the same jitted ``Model.decode_step`` the dry-run
lowers, so serving exercises exactly the production path.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from collections import defaultdict, deque
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core.plan import EndpointPlan, SharingVector
from repro.models.model import Model
from repro.models.transformer import ragged_kv_block
from repro.serve.pages import PagePool, sentinel
from repro.serve.slots import SlotPool, _coerce_level


@dataclasses.dataclass
class KVHandoff:
    """One session's portable KV state (DESIGN.md §17) — everything a
    decode worker needs to resume a stream some other worker started:
    the batch-1 contiguous cache (None for virtual SimWorkers), the
    next token to feed (decided, not yet decoded), the resident cache
    position, the remaining token budget, and the tokens already
    emitted.  ``kv_tokens``/``kv_bytes`` price the transfer on the
    fabric (``FabricCosts.t_handoff_*``).  Greedy decoding is a pure
    function of the context, so resuming from this state elsewhere is
    bit-identical to never having moved."""

    rid: int
    cache: object                      # batch-1 contiguous cache | None
    next_tok: int
    pos: int
    remaining: int
    emitted: List[int] = dataclasses.field(default_factory=list)
    eos_id: int = -1
    kv_tokens: int = 0
    kv_bytes: int = 0


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray                 # (len,) int32
    max_new_tokens: int = 16
    eos_id: Optional[int] = None
    output: Optional[list] = None      # filled by the engine
    kv: Optional[KVHandoff] = None     # imported cache: admission merges
    #                                    it instead of running a prefill


class ServeEngine:
    """Static wave batching (the MPI+threads extreme of the slot pools)."""

    def __init__(self, cfg: ArchConfig, params, *, n_slots: int = 4,
                 max_len: int = 512, plan: Optional[EndpointPlan] = None,
                 exec_group: int = 0):
        assert cfg.input_mode == "tokens" and not cfg.is_encdec, \
            "the wave engine serves decoder-only token models"
        if plan is not None:
            n_slots, max_len = plan.n_slots, plan.max_len
        self.cfg = cfg
        self.params = params
        self.plan = plan or EndpointPlan(
            vector=SharingVector(slots=4), n_slots=n_slots,
            max_len=max_len, executor="wave")
        self.n_slots = n_slots
        self.max_len = max_len
        self.queue: deque = deque()
        self.done: List[Request] = []
        self.latency: Dict[int, float] = {}      # rid -> s from run() start
        self._t0 = 0.0
        # shared executables: every wave engine (and every continuous
        # engine) of one config reuses the same jitted decode/prefill
        # instead of re-jitting per-instance lambdas (N-fold compile).
        # ``exec_group`` (the plan's execs axis) splits that sharing.
        steps = _shared_steps(cfg, False, exec_group)
        self.model = steps.model
        self._decode = steps.decode
        self._prefill = steps.prefill

    def submit(self, req: Request):
        req.output = []
        self.queue.append(req)

    def _next_wave(self) -> List[Request]:
        """Up to n_slots queued requests sharing one prompt length."""
        if not self.queue:
            return []
        by_len = defaultdict(list)
        for r in self.queue:
            by_len[len(r.prompt)].append(r)
        # largest group first (throughput)
        length = max(by_len, key=lambda l: len(by_len[l]))
        wave = by_len[length][: self.n_slots]
        taken = {id(r) for r in wave}
        self.queue = deque(r for r in self.queue if id(r) not in taken)
        return wave

    def _run_wave(self, wave: List[Request]):
        b = len(wave)
        plen = len(wave[0].prompt)
        prompts = jnp.asarray(np.stack([r.prompt for r in wave]), jnp.int32)
        cache = self.model.init_cache(b, self.max_len)
        logits, cache = self._prefill(self.params, {"tokens": prompts},
                                      cache)
        next_tok = np.asarray(jnp.argmax(logits, -1), np.int32)
        remaining = np.array([r.max_new_tokens for r in wave], np.int64)
        alive = np.ones(b, bool)
        budget = min(self.max_len - plen - 1,
                     int(max(remaining)))
        for _ in range(max(0, budget)):
            if not alive.any():
                break
            logits, cache = self._decode(self.params, cache,
                                         jnp.asarray(next_tok))
            produced = next_tok.copy()
            next_tok = np.asarray(jnp.argmax(logits, -1), np.int32)
            for i, r in enumerate(wave):
                if not alive[i]:
                    continue
                r.output.append(int(produced[i]))
                remaining[i] -= 1
                if remaining[i] <= 0 or (r.eos_id is not None
                                         and int(next_tok[i]) == r.eos_id):
                    alive[i] = False
        for i, r in enumerate(wave):
            if alive[i]:          # wave budget exhausted
                r.output.append(int(next_tok[i]))
        now = time.perf_counter() - self._t0
        for r in wave:
            self.latency[r.rid] = now
        self.done.extend(wave)

    def run(self) -> List[Request]:
        self._t0 = time.perf_counter()
        while self.queue:
            wave = self._next_wave()
            if not wave:
                break
            self._run_wave(wave)
        return self.done


@dataclasses.dataclass(frozen=True)
class SharedSteps:
    """One set of jitted executables per (config, ragged-kernel,
    exec-group) triple — every engine of one exec-sharing group reuses
    them instead of re-jitting identical lambdas per worker (N-fold
    compile otherwise).  ``exec_group`` realizes the ``execs`` axis of a
    ``core.plan.SharingVector``: level 4 keys the whole fleet to group 0
    (one compiled set, the historical behavior), level 1 gives every
    worker a private set (process-per-rank isolation at N-fold compile
    footprint, token-identical output).  jit's own shape cache bounds
    specializations: ``admit_packed`` compiles once per length bucket,
    ``horizon`` once per decode-horizon K."""

    model: Model
    decode: object            # (params, cache, tokens) -> (logits, cache)
    prefill: object           # (params, batch, cache) -> (logits, cache)
    merge: object             # scatter one batch-1 cache into a slot
    admit_packed: object      # fused padded prefill + scatter + argmax
    horizon: object           # (params, cache, state, K, max_len)
    merge_paged: object       # paged-cache variant of ``merge``
    admit_packed_paged: object  # paged-cache variant of ``admit_packed``


def _shared_steps(cfg: ArchConfig, use_ragged_kernel: bool,
                  exec_group: int = 0) -> SharedSteps:
    # normalize the default so (cfg, ragged) and (cfg, ragged, 0) hit the
    # same cache line (lru_cache keys the raw call signature)
    return _shared_steps_cached(cfg, use_ragged_kernel, exec_group)


@functools.lru_cache(maxsize=None)
def _shared_steps_cached(cfg: ArchConfig, use_ragged_kernel: bool,
                         exec_group: int) -> SharedSteps:
    model = Model(cfg)
    decode = jax.jit(
        lambda p, c, t: model.decode_step(
            p, c, tokens=t, use_ragged_kernel=use_ragged_kernel))
    prefill = jax.jit(lambda p, b, c: model.prefill(p, b, c))

    def admit_packed(p, full, state, toks, last_index, slot_ids, valid,
                     lengths, remaining, eos, has_eos, max_len):
        """One executable admits a whole round: padded batched prefill
        (fresh cache allocated in-graph, each row's logits gathered at
        its own last real token), fused multi-slot scatter into the live
        cache, argmax of the first tokens, and the per-slot decode state
        update — so admission costs one dispatch, never materializes the
        intermediate cache, and (with a fused decode horizon) never
        blocks: the state stays device-resident and the next horizon's
        trace is the only host sync."""
        logits, many = model.prefill(
            p, {"tokens": toks}, model.init_cache(toks.shape[0], max_len),
            last_index=last_index)
        has, src = _slot_mapping(slot_ids, valid, full["idx"].shape[0])
        cache = _scatter_slots(full, many, has, src, lengths)
        first = jnp.argmax(logits, -1).astype(jnp.int32)
        state = {
            "tok": jnp.where(has, first[src], state["tok"]),
            "remaining": jnp.where(has, remaining[src],
                                   state["remaining"]),
            "finished": state["finished"] & ~has,
            "eos": jnp.where(has, eos[src], state["eos"]),
            "has_eos": jnp.where(has, has_eos[src], state["has_eos"]),
        }
        return cache, state

    def admit_packed_paged(p, full, state, toks, last_index, slot_ids,
                           valid, lengths, remaining, eos, has_eos, pt,
                           max_len):
        """``admit_packed`` for the PAGED cache layout (DESIGN.md §13):
        the prefill still runs on a fresh CONTIGUOUS in-graph cache (the
        prompt is dense), then one fused page scatter lands each row's
        cache in the pages its slot owns; ``pt`` is the round's merged
        host page table, installed as the cache's new ``pt``."""
        logits, many = model.prefill(
            p, {"tokens": toks}, model.init_cache(toks.shape[0], max_len),
            last_index=last_index)
        has, src = _slot_mapping(slot_ids, valid, full["idx"].shape[0])
        cache = _scatter_slots_paged(full, many, has, src, lengths, pt,
                                     max_len)
        first = jnp.argmax(logits, -1).astype(jnp.int32)
        state = {
            "tok": jnp.where(has, first[src], state["tok"]),
            "remaining": jnp.where(has, remaining[src],
                                   state["remaining"]),
            "finished": state["finished"] & ~has,
            "eos": jnp.where(has, eos[src], state["eos"]),
            "has_eos": jnp.where(has, has_eos[src], state["has_eos"]),
        }
        return cache, state

    merge = jax.jit(_scatter_slot)
    admit_packed = jax.jit(admit_packed, static_argnums=(11,))
    merge_paged = jax.jit(_scatter_slot_paged)
    admit_packed_paged = jax.jit(admit_packed_paged, static_argnums=(12,))
    horizon = jax.jit(
        lambda p, c, s, k, ml: model.decode_horizon(
            p, c, s, horizon=k, max_len=ml,
            use_ragged_kernel=use_ragged_kernel),
        static_argnums=(3, 4))
    return SharedSteps(model=model, decode=decode, prefill=prefill,
                       merge=merge, admit_packed=admit_packed,
                       horizon=horizon, merge_paged=merge_paged,
                       admit_packed_paged=admit_packed_paged)


def _scatter_slot(full, one, slot):
    """Insert the batch-1 cache ``one`` as batch row ``slot`` of ``full``
    and pin that slot's position to the prompt length.  Prefix block
    caches carry batch at axis 0; scanned body caches at axis 1 (behind
    the leading n_periods axis)."""
    def upd(axis):
        return lambda dst, src: jax.lax.dynamic_update_slice_in_dim(
            dst, src, slot, axis=axis)

    stack = {
        "prefix": [jax.tree.map(upd(0), f, o)
                   for f, o in zip(full["stack"]["prefix"],
                                   one["stack"]["prefix"])],
        "body": [jax.tree.map(upd(1), f, o)
                 for f, o in zip(full["stack"]["body"],
                                 one["stack"]["body"])],
    }
    return {"stack": stack, "idx": full["idx"].at[slot].set(one["idx"])}


def _slot_mapping(slot_ids, valid, n_slots):
    """-> (has (n,) bool: slot receives a row; src (n,) i32: its source
    row) from a round's row-major (slot_ids, valid) assignment."""
    match = ((slot_ids[None, :] == jnp.arange(n_slots)[:, None])
             & valid[None, :])
    return match.any(axis=1), jnp.argmax(match, axis=1)


def _scatter_slots(full, many, has, src, lengths):
    """Fused multi-slot scatter: for every slot ``b`` with ``has[b]``,
    row ``src[b]`` of the batched-prefill cache ``many`` lands in slot
    ``b`` of ``full`` and that slot's position pins to
    ``lengths[src[b]]``.  One executable replaces a round's per-request
    merge chain."""
    n = full["idx"].shape[0]

    def upd(axis):
        def f(dst, s):
            g = jnp.take(s, src, axis=axis)
            shape = [1] * dst.ndim
            shape[axis] = n
            return jnp.where(has.reshape(shape), g, dst)
        return f

    stack = {
        "prefix": [jax.tree.map(upd(0), f, o)
                   for f, o in zip(full["stack"]["prefix"],
                                   many["stack"]["prefix"])],
        "body": [jax.tree.map(upd(1), f, o)
                 for f, o in zip(full["stack"]["body"],
                                 many["stack"]["body"])],
    }
    idx = jnp.where(has, jnp.take(lengths, src).astype(full["idx"].dtype),
                    full["idx"])
    return {"stack": stack, "idx": idx}


def _scatter_slot_paged(full, one, slot, pt_slot):
    """Paged variant of ``_scatter_slot``: the batch-1 contiguous prefill
    cache ``one`` lands in the pages slot ``slot`` owns (``pt_slot``,
    (max_pages,) int32 — sentinel entries scatter nowhere via
    ``mode="drop"``), its position pins, and the slot's page-table row
    installs.  Prefix leaves are (N, ps, ...) pages (scatter axis 0);
    scanned body leaves carry the n_periods axis first (axis 1)."""
    max_pages = pt_slot.shape[0]
    ids = pt_slot.astype(jnp.int32)

    def upd(axis):
        def f(dst, s):
            ps = dst.shape[axis + 1]
            tail = s.shape[axis + 2:]
            pre = s.shape[:axis]
            rows = s.reshape(pre + (max_pages, ps) + tail)
            if axis == 0:
                return dst.at[ids].set(rows, mode="drop")
            return dst.at[:, ids].set(rows, mode="drop")
        return f

    stack = {
        "prefix": [jax.tree.map(upd(0), f, o)
                   for f, o in zip(full["stack"]["prefix"],
                                   one["stack"]["prefix"])],
        "body": [jax.tree.map(upd(1), f, o)
                 for f, o in zip(full["stack"]["body"],
                                 one["stack"]["body"])],
    }
    return {"stack": stack, "idx": full["idx"].at[slot].set(one["idx"]),
            "pt": full["pt"].at[slot].set(ids)}


def _scatter_slots_paged(full, many, has, src, lengths, pt, max_len):
    """Fused multi-slot PAGED scatter: for every slot ``b`` with
    ``has[b]``, row ``src[b]`` of the batched-prefill contiguous cache
    ``many`` splits into page-size chunks and scatters into the pages
    ``pt[b]`` maps; slots without a row (and sentinel table entries)
    scatter nowhere.  ``pt`` is the round's merged host page table and
    becomes the cache's new table wholesale."""
    n = full["idx"].shape[0]
    max_pages = pt.shape[1]
    ps = max_len // max_pages
    # rows that must not land anywhere send every table entry to the
    # sentinel (one past the last physical page -> dropped)
    def flat_ids(dst_pages):
        sent = jnp.int32(dst_pages)
        return jnp.where(has[:, None], pt.astype(jnp.int32),
                         sent).reshape(n * max_pages)

    def upd(axis):
        def f(dst, s):
            tail = s.shape[axis + 2:]
            pre = s.shape[:axis]
            rows = jnp.take(s, src, axis=axis)
            rows = rows.reshape(pre + (n * max_pages, ps) + tail)
            ids = flat_ids(dst.shape[axis])
            if axis == 0:
                return dst.at[ids].set(rows, mode="drop")
            return dst.at[:, ids].set(rows, mode="drop")
        return f

    stack = {
        "prefix": [jax.tree.map(upd(0), f, o)
                   for f, o in zip(full["stack"]["prefix"],
                                   many["stack"]["prefix"])],
        "body": [jax.tree.map(upd(1), f, o)
                 for f, o in zip(full["stack"]["body"],
                                 many["stack"]["body"])],
    }
    idx = jnp.where(has, jnp.take(lengths, src).astype(full["idx"].dtype),
                    full["idx"])
    return {"stack": stack, "idx": idx, "pt": pt.astype(jnp.int32)}


def _cache_bytes(cache, tokens: int, max_len: int) -> int:
    """Bytes of KV actually resident in a batch-1 cache holding
    ``tokens`` of its ``max_len`` capacity — the size-proportional
    payload a handoff moves (the allocation is max_len-shaped; only the
    occupied prefix travels)."""
    total = 0
    for group in ("prefix", "body"):
        for leaf in jax.tree.leaves(cache["stack"][group]):
            total += leaf.size * leaf.dtype.itemsize
    return int(total * tokens / max(1, max_len))


def auto_page_size(max_len: int, target: int = 0) -> int:
    """The default KV page size when the plan says paged but not how
    big: the largest divisor of ``max_len`` not exceeding ``target``
    (auto target = ``max_len // 4`` clamped to [8, 64] — at least 4
    pages per sequence so pooling has granularity to pack, pages no
    smaller than a kernel block)."""
    if target <= 0:
        target = max(8, min(64, max_len // 4))
    for ps in range(min(target, max_len), 0, -1):
        if max_len % ps == 0:
            return ps
    return max_len


def pow2_buckets(max_len: int, lo: int = 8) -> Tuple[int, ...]:
    """Power-of-2 prompt-length buckets covering [1, max_len): the
    bounded set of prefill jit specializations (the serving analogue of
    the paper's bounded QP set — a handful of shared resources instead of
    one dedicated resource per distinct consumer)."""
    out, b = [], lo
    while b < max_len:
        out.append(b)
        b *= 2
    out.append(max_len)
    return tuple(out)


Buckets = Union[None, str, Sequence[int]]


class ContinuousEngine:
    """Continuous batching over an endpoint-style slot pool.

    One persistent ``n_slots``-row cache holds every active request at its
    own ragged length; a finished request immediately frees its slot and
    the ``SlotPool`` decides when a queued request may take it (group
    fully drained — group size 1 admits instantly).  Prompt lengths need
    not match across slots, so no wave grouping and no padding at decode.

    ``decode_horizon=K`` batches K decode steps per host sync (fused
    on-device sampling; ``K=1`` is the per-step oracle) and
    ``prefill_buckets`` batches a round's admissions into one padded
    prefill (``None`` disables; ``"pow2"``/``"auto"`` derives power-of-2
    buckets; a sequence of ints uses those lengths).  Both change WHEN
    host work happens, never token values: outputs are bit-identical
    across every (K, buckets) setting on eligible models.
    """

    def __init__(self, cfg: ArchConfig, params, *, n_slots: int = 4,
                 max_len: int = 512, category=None, slot_level: int = None,
                 pool: Optional[SlotPool] = None,
                 use_ragged_kernel: bool = False,
                 decode_horizon: int = 1,
                 prefill_buckets: Buckets = "auto",
                 plan: Optional[EndpointPlan] = None,
                 exec_group: int = 0):
        assert cfg.input_mode == "tokens" and not cfg.is_encdec, \
            "the continuous engine serves decoder-only token models"
        if category is not None:
            # deprecated path: the scalar category collapses to its slot
            # sharing level (the diagonal); _coerce_level warns
            slot_level = _coerce_level(None, category, "ContinuousEngine")
        if plan is not None:
            # the plan is authoritative for every knob it carries; the
            # engine consumes only the single-worker slice (the facade
            # hands fleet-level axes to the router / exec grouping)
            n_slots, max_len = plan.n_slots, plan.max_len
            decode_horizon = plan.decode_horizon
            prefill_buckets = plan.prefill_buckets
            use_ragged_kernel = plan.use_ragged_kernel
            slot_level = plan.vector.slots if slot_level is None \
                else slot_level
        if decode_horizon < 1:
            raise ValueError(f"decode_horizon must be >= 1, "
                             f"got {decode_horizon}")
        self.cfg = cfg
        self.params = params
        self.n_slots = n_slots
        self.max_len = max_len
        self.pool = pool or SlotPool(
            1 if slot_level is None else slot_level, n_slots)
        assert self.pool.n_slots == n_slots
        self.plan = plan or EndpointPlan(
            vector=SharingVector(slots=self.pool.level),
            n_slots=n_slots, max_len=max_len,
            decode_horizon=decode_horizon,
            prefill_buckets=prefill_buckets,
            use_ragged_kernel=use_ragged_kernel, executor="continuous")
        self.decode_horizon = decode_horizon
        self.queue: deque = deque()
        self.done: List[Request] = []
        self.latency: Dict[int, float] = {}      # rid -> s from run() start
        # deterministic schedule keys (wall-clock free): the engine's
        # token-step counter at admission/retirement, plus the order
        # requests were bound into slots — invariant across horizons
        self.admit_steps: Dict[int, int] = {}
        self.retire_steps: Dict[int, int] = {}
        self.admit_order: List[int] = []
        # decode_steps: token steps; decode_calls: jitted executables
        # dispatched; host_syncs: blocking device->host transfers;
        # busy_slot_steps / slot_steps is the pool's occupancy
        self.stats = {"decode_steps": 0, "decode_calls": 0,
                      "slot_steps": 0, "busy_slot_steps": 0,
                      "prefills": 0, "prefilled_requests": 0,
                      "host_syncs": 0, "regroups": 0}
        self.use_ragged_kernel = use_ragged_kernel
        self.exec_group = exec_group
        self._steps = _shared_steps(cfg, use_ragged_kernel, exec_group)
        self.model = self._steps.model
        self._decode = self._steps.decode
        self._prefill = self._steps.prefill
        self._merge = self._steps.merge
        # ----- paged KV cache (plan-gated; DESIGN.md §13) ----------------
        # The paged layout engages only when the plan asks for it AND the
        # model can honor it (pure attention, no rolling window, decoder-
        # only); otherwise the historical contiguous cache runs untouched
        # — a paged plan on an ineligible model quietly falls back, like
        # the auto prefill buckets do.
        self.page_pool: Optional[PagePool] = None
        self.page_size = 0
        self._pt = None                  # host page-table mirror (np)
        if plan is not None and plan.paged \
                and self.model.supports_paged_cache:
            self.page_size = plan.page_size or auto_page_size(max_len)
            self.page_pool = PagePool(
                plan.vector.pages, n_slots, max_len // self.page_size,
                total_pages=plan.page_budget)
            # page telemetry only exists on paged engines, so every
            # contiguous stats dict (and committed golden) is unchanged
            self.stats["page_deferrals"] = 0
            self.stats["page_hwm"] = 0
        elif use_ragged_kernel:
            ragged_kv_block(max_len)     # refuse a max_len it cannot take
        self.prefill_buckets = self._resolve_buckets(prefill_buckets)
        self._t0 = 0.0
        self._started = False
        self._cache = None
        self._step_no = 0
        # pre-start shape so free_slots()/admissible_slots() work before
        # start() (the cache itself is allocated lazily there)
        self._slot_req: List[Optional[Request]] = [None] * n_slots
        self._next_tok = None
        self._remaining = None
        self._pos = None
        self._eos_id = None
        self._has_eos = None
        self._dev_state = None     # device-resident state (fused mode)

    def _resolve_buckets(self, buckets: Buckets) -> Tuple[int, ...]:
        """-> the active bucket set (empty tuple = exact-length prefill).
        Auto modes quietly disable themselves on models where trailing
        padding is not exact (recurrent blocks, rolling-window caches);
        an explicit bucket list on such a model is an error."""
        auto = isinstance(buckets, str)
        if auto and buckets not in ("auto", "pow2"):
            raise ValueError(f"unknown prefill_buckets mode {buckets!r}")
        if not buckets:
            return ()
        if not self.model.supports_padded_prefill:
            if auto:
                return ()
            raise ValueError(
                f"{self.cfg.name}: bucketed prefill needs a pure-attention "
                f"stack without rolling-window caches")
        if auto:
            return pow2_buckets(self.max_len)
        out = tuple(sorted({min(int(b), self.max_len) for b in buckets}))
        if not all(b > 0 for b in out):
            raise ValueError(f"buckets must be positive, got {buckets}")
        return out

    def _bucket_of(self, length: int) -> int:
        for b in self.prefill_buckets:
            if b >= length:
                return b
        raise ValueError(f"prompt length {length} exceeds the largest "
                         f"bucket {self.prefill_buckets[-1]}")

    def submit(self, req: Request):
        req.output = []
        if len(req.prompt) >= self.max_len:
            raise ValueError(
                f"prompt of {len(req.prompt)} tokens cannot fit max_len="
                f"{self.max_len}")
        self.queue.append(req)

    # ----- slot lifecycle -------------------------------------------------
    def _bind(self, slot: int, req: Request,
              first_tok: Optional[int] = None):
        """Host bookkeeping shared by both admission paths.  ``first_tok``
        is None in fused-horizon mode: the decode state lives on device
        and the first token surfaces through the next horizon's trace."""
        self._slot_req[slot] = req
        if first_tok is not None:
            self._next_tok[slot] = first_tok
        self._remaining[slot] = req.max_new_tokens
        self._pos[slot] = len(req.prompt)
        self._eos_id[slot] = -1 if req.eos_id is None else req.eos_id
        self._has_eos[slot] = req.eos_id is not None
        self.admit_order.append(req.rid)
        self.admit_steps[req.rid] = self._step_no

    def _admit(self, cache, slot: int, req: Request):
        """Prefill ``req`` alone and scatter its cache into ``slot`` (the
        exact-length path: one jit specialization per prompt length)."""
        prompt = jnp.asarray(np.asarray(req.prompt)[None], jnp.int32)
        one = self.model.init_cache(1, self.max_len)
        logits, one = self._prefill(self.params, {"tokens": prompt}, one)
        if self.page_pool is not None:
            # the batch-1 prefill is contiguous (prompts are dense); the
            # page scatter splits it into the slot's pages
            cache = self._steps.merge_paged(
                cache, one, jnp.asarray(slot, jnp.int32),
                jnp.asarray(self._pt[slot]))
        else:
            cache = self._merge(cache, one, jnp.asarray(slot, jnp.int32))
        first = int(jnp.argmax(logits, -1)[0])
        self._bind(slot, req, first)
        if self._dev_state is not None:
            s = self._dev_state
            self._dev_state = {
                "tok": s["tok"].at[slot].set(first),
                "remaining": s["remaining"].at[slot].set(
                    req.max_new_tokens),
                "finished": s["finished"].at[slot].set(False),
                "eos": s["eos"].at[slot].set(self._eos_id[slot]),
                "has_eos": s["has_eos"].at[slot].set(
                    bool(self._has_eos[slot])),
            }
        self.stats["prefills"] += 1
        self.stats["prefilled_requests"] += 1
        self.stats["host_syncs"] += 1
        return cache

    def _host_state(self):
        """Decode state assembled from the host mirrors (horizon-1 mode,
        where the mirrors are authoritative)."""
        return {
            "tok": jnp.asarray(self._next_tok),
            "remaining": jnp.asarray(self._remaining),
            "finished": jnp.asarray(
                np.array([r is None for r in self._slot_req])),
            "eos": jnp.asarray(self._eos_id),
            "has_eos": jnp.asarray(self._has_eos),
        }

    def _admit_batch(self, cache, batch: List[Tuple[int, Request]]):
        """Admit a whole round at once: every prompt pads to the round's
        length bucket, ONE fixed-(n_slots)-row batched prefill runs, and
        one fused scatter + state update lands every row in its slot.
        Row and length padding are bit-invisible (independent batch rows;
        causal attention), so outputs match the exact-length path while
        jit specializations stay bounded by ``len(prefill_buckets)``.
        In fused-horizon mode the round is fire-and-forget (no sync)."""
        n = self.n_slots
        bucket = self._bucket_of(max(len(r.prompt) for _, r in batch))
        toks = np.zeros((n, bucket), np.int32)
        last = np.zeros((n,), np.int32)
        slot_ids = np.zeros((n,), np.int32)
        valid = np.zeros((n,), bool)
        lengths = np.zeros((n,), np.int32)
        remaining = np.zeros((n,), np.int32)
        eos = np.full((n,), -1, np.int32)
        has_eos = np.zeros((n,), bool)
        for j, (slot, req) in enumerate(batch):
            ln = len(req.prompt)
            toks[j, :ln] = req.prompt
            last[j] = ln - 1
            slot_ids[j] = slot
            valid[j] = True
            lengths[j] = ln
            remaining[j] = req.max_new_tokens
            eos[j] = -1 if req.eos_id is None else req.eos_id
            has_eos[j] = req.eos_id is not None
        fused = self._dev_state is not None
        state = self._dev_state if fused else self._host_state()
        if self.page_pool is not None:
            cache, state = self._steps.admit_packed_paged(
                self.params, cache, state, jnp.asarray(toks),
                jnp.asarray(last), jnp.asarray(slot_ids),
                jnp.asarray(valid), jnp.asarray(lengths),
                jnp.asarray(remaining), jnp.asarray(eos),
                jnp.asarray(has_eos), jnp.asarray(self._pt), self.max_len)
        else:
            cache, state = self._steps.admit_packed(
                self.params, cache, state, jnp.asarray(toks),
                jnp.asarray(last), jnp.asarray(slot_ids),
                jnp.asarray(valid), jnp.asarray(lengths),
                jnp.asarray(remaining), jnp.asarray(eos),
                jnp.asarray(has_eos), self.max_len)
        if fused:
            self._dev_state = state
            for slot, req in batch:
                self._bind(slot, req)
        else:
            first = np.asarray(state["tok"])              # one sync
            for j, (slot, req) in enumerate(batch):
                self._bind(slot, req, int(first[slot_ids[j]]))
            self.stats["host_syncs"] += 1
        self.stats["prefills"] += 1
        self.stats["prefilled_requests"] += len(batch)
        return cache

    # ----- prefill/decode disaggregation (DESIGN.md §17) -----------------
    def prefill_only(self, req: Request) -> KVHandoff:
        """Prefill-role service: run the batch-1 exact-length prefill
        and return the session's portable KV payload instead of binding
        a decode slot — the prefill worker's whole contribution.  Exact-
        length batch-1 prefill is bit-identical to the bucketed
        admission path (padding is bit-invisible under causal
        attention), so decoding this payload elsewhere reproduces the
        co-located token stream exactly."""
        req.output = []
        if len(req.prompt) >= self.max_len:
            raise ValueError(
                f"prompt of {len(req.prompt)} tokens cannot fit max_len="
                f"{self.max_len}")
        prompt = jnp.asarray(np.asarray(req.prompt)[None], jnp.int32)
        one = self.model.init_cache(1, self.max_len)
        logits, one = self._prefill(self.params, {"tokens": prompt}, one)
        first = int(jnp.argmax(logits, -1)[0])
        self.stats["prefills"] += 1
        self.stats["prefilled_requests"] += 1
        self.stats["host_syncs"] += 1
        pos = len(req.prompt)
        return KVHandoff(
            rid=req.rid, cache=one, next_tok=first, pos=pos,
            remaining=max(1, req.max_new_tokens), emitted=[],
            eos_id=-1 if req.eos_id is None else req.eos_id,
            kv_tokens=pos, kv_bytes=_cache_bytes(one, pos, self.max_len))

    def _admit_handoff(self, cache, slot: int, req: Request):
        """Land an imported KV payload in ``slot``: the session's cache
        merges exactly where a prefill's would have, then the slot
        resumes at the imported position / budget / next token.  No
        forward pass runs here — that is the whole point."""
        h = req.kv
        if self.page_pool is not None:
            cache = self._steps.merge_paged(
                cache, h.cache, jnp.asarray(slot, jnp.int32),
                jnp.asarray(self._pt[slot]))
        else:
            cache = self._merge(cache, h.cache,
                                jnp.asarray(slot, jnp.int32))
        req.output = list(h.emitted)
        self._bind(slot, req, h.next_tok)
        # _bind assumed a fresh prefill; the payload is authoritative
        # for where the session actually stands
        self._pos[slot] = h.pos
        self._remaining[slot] = h.remaining
        if self._dev_state is not None:
            s = self._dev_state
            self._dev_state = {
                "tok": s["tok"].at[slot].set(h.next_tok),
                "remaining": s["remaining"].at[slot].set(h.remaining),
                "finished": s["finished"].at[slot].set(False),
                "eos": s["eos"].at[slot].set(self._eos_id[slot]),
                "has_eos": s["has_eos"].at[slot].set(
                    bool(self._has_eos[slot])),
            }
        return cache

    def export_session(self, slot: int) -> KVHandoff:
        """Strip the live session in ``slot`` into a portable KV payload
        (live decode→decode migration): its cache rows leave as a
        batch-1 CONTIGUOUS cache — sliced out of the slot cache, or
        gathered page-by-page on the paged layout — and the slot frees
        exactly as an evacuation would (pages returned, device rows
        drained, nothing retired)."""
        req = self._slot_req[slot]
        assert req is not None, f"slot {slot} holds no session"
        if self._dev_state is not None:
            # fused mode: tok/remaining are device-resident; this export
            # is the one host sync the migration costs
            tok = int(jax.device_get(self._dev_state["tok"][slot]))
            rem = int(jax.device_get(self._dev_state["remaining"][slot]))
            self.stats["host_syncs"] += 1
        else:
            tok = int(self._next_tok[slot])
            rem = int(self._remaining[slot])
        pos = int(self._pos[slot])
        if self.page_pool is not None:
            # gather the slot's pages into contiguous order; sentinel
            # entries clamp to the last physical page — garbage rows,
            # but they sit beyond ``pos`` where attention never reads
            ids = jnp.asarray(
                np.minimum(self._pt[slot],
                           self.page_pool.total_pages - 1), jnp.int32)

            def gather(axis):
                def f(leaf):
                    pages = jnp.take(leaf, ids, axis=axis)
                    pre = pages.shape[:axis]
                    tail = pages.shape[axis + 2:]
                    return pages.reshape(pre + (1, self.max_len) + tail)
                return f

            stack = {
                "prefix": [jax.tree.map(gather(0), f)
                           for f in self._cache["stack"]["prefix"]],
                "body": [jax.tree.map(gather(1), f)
                         for f in self._cache["stack"]["body"]],
            }
        else:
            def take(axis):
                return lambda leaf: jax.lax.dynamic_slice_in_dim(
                    leaf, slot, 1, axis=axis)

            stack = {
                "prefix": [jax.tree.map(take(0), f)
                           for f in self._cache["stack"]["prefix"]],
                "body": [jax.tree.map(take(1), f)
                         for f in self._cache["stack"]["body"]],
            }
        # scalar idx, matching ``init_cache(1, …)`` (only per_slot caches
        # carry a vector idx) — ``_scatter_slot`` sets it into one row
        one = {"stack": stack, "idx": self._cache["idx"][slot]}
        # free the slot like an evacuation: no retirement, no latency
        self._slot_req[slot] = None
        self._remaining[slot] = 0
        if self.page_pool is not None:
            self.page_pool.free(slot)
            self._pt[slot] = sentinel(self.page_pool.total_pages)
            self._cache["pt"] = self._cache["pt"].at[slot].set(
                jnp.asarray(self._pt[slot]))
        if self._dev_state is not None:
            self._dev_state = {
                **self._dev_state,
                "finished": self._dev_state["finished"].at[slot].set(True),
                "remaining": self._dev_state["remaining"].at[slot].set(0),
            }
        return KVHandoff(
            rid=req.rid, cache=one, next_tok=tok, pos=pos, remaining=rem,
            emitted=list(req.output or []),
            eos_id=-1 if req.eos_id is None else req.eos_id,
            kv_tokens=pos, kv_bytes=_cache_bytes(one, pos, self.max_len))

    def export_sessions(self) -> List[KVHandoff]:
        """Every live slot leaves as a KV payload (slot order — the
        deterministic migration drain); the engine's own admission
        queue stays put: it holds no KV yet."""
        return [self.export_session(slot)
                for slot, req in enumerate(self._slot_req)
                if req is not None]

    def publish_metrics(self, registry, worker: int = 0) -> None:
        """Publish this engine's absolute counters into an
        ``obs.MetricsRegistry`` (DESIGN.md §14) under a ``worker`` label.
        ``set_total`` is idempotent, so any cadence is safe; the engine
        keeps its ``stats`` dict authoritative and the registry mirrors
        it — consumers (adaptive windows, ``--metrics-out``, the fleet
        report) read the registry instead of threading stats dicts."""
        for name, axis in (("decode_steps", "execs"),
                           ("decode_calls", "execs"),
                           ("host_syncs", "execs"),
                           ("prefills", "execs"),
                           ("prefilled_requests", "execs"),
                           ("slot_steps", "slots"),
                           ("busy_slot_steps", "slots"),
                           ("regroups", "slots")):
            registry.counter(f"engine.{name}", axis=axis,
                             worker=worker).set_total(self.stats[name])
        registry.counter("engine.jit_compiles", axis="execs",
                         group=self.exec_group,
                         worker=worker).set_total(self.compile_count())
        registry.gauge("engine.queue_depth", axis="channels",
                       worker=worker).set(len(self.queue))
        if self.page_pool is not None:
            self.page_pool.publish_metrics(registry, axis="pages",
                                           worker=worker)

    def compile_count(self) -> int:
        """Jitted specializations materialized so far across this
        engine's executable set (jit's own per-shape cache sizes — the
        counter the horizon tests and serve bench already read).  The
        adaptive controller diffs this per window: fresh compiles are
        the execs axis' contention signal.  0 when the running jax
        lacks the probe."""
        total = 0
        for fn in (self._steps.decode, self._steps.prefill,
                   self._steps.merge, self._steps.admit_packed,
                   self._steps.merge_paged,
                   self._steps.admit_packed_paged, self._steps.horizon):
            probe = getattr(fn, "_cache_size", None)
            if probe is not None:
                total += probe()
        return total

    def decode_program_text(self) -> str:
        """Compiled text of the decode executable this engine dispatches
        (the fused horizon when ``decode_horizon > 1``, else the single
        step), lowered for its live cache — where a kernel on the decode
        path shows up as a custom call."""
        self.start()
        if self.decode_horizon > 1:
            lowered = self._steps.horizon.lower(
                self.params, self._cache, self._dev_state,
                self.decode_horizon, self.max_len)
        else:
            lowered = self._decode.lower(self.params, self._cache,
                                         jnp.asarray(self._next_tok))
        return lowered.compile().as_text()

    def regroup(self, slot_level: Optional[int] = None,
                exec_group: Optional[int] = None,
                page_level: Optional[int] = None) -> bool:
        """Live migration (DESIGN.md §12): re-key the slot pool and/or
        the shared-executable group WITHOUT dropping queued or in-flight
        requests; -> True when anything changed.

        Slot regrouping is pure admission policy (``SlotPool.regroup``):
        occupied slots keep decoding, the new group structure gates only
        future admissions.  Exec regrouping swaps ``_shared_steps``
        between jitted calls — the step that is executing when the swap
        lands was dispatched from the OLD executable set and finishes on
        it; the next dispatch keys into the new group, compiling lazily
        if that group has never run this shape.  Neither path touches
        the cache or the decode state, so token values are invariant
        (the golden-trace harness pins this bit-exactly).
        """
        changed = False
        if slot_level is not None and int(slot_level) != self.pool.level:
            self.pool.regroup(slot_level)
            changed = True
        if page_level is not None:
            if self.page_pool is None:
                if int(page_level) != 1:
                    raise ValueError(
                        "cannot regroup pages on a contiguous-layout "
                        "engine: the physical cache layout is structural "
                        "— connect with a paged plan (vector.pages > 1 "
                        "or page_size) first")
            elif int(page_level) != self.page_pool.level:
                # pure budget re-keying: every live page mapping
                # survives (PagePool.regroup), tokens are invariant
                self.page_pool.regroup(int(page_level))
                changed = True
        if exec_group is not None and int(exec_group) != self.exec_group:
            self.exec_group = int(exec_group)
            steps = _shared_steps(self.cfg, self.use_ragged_kernel,
                                  self.exec_group)
            self._steps = steps
            self._decode = steps.decode
            self._prefill = steps.prefill
            self._merge = steps.merge
            changed = True
        if changed:
            self.stats["regroups"] += 1
            # keep the engine's plan truthful for the axis it owns: a
            # migrated engine matches no named preset, and the slots
            # level tracks the pool.  The execs LEVEL is fleet-relative
            # (``exec_group`` is a group id — level 2 at 8 workers and
            # level 4 at 2 workers both key group 0), so the facade's
            # plan, not the engine's, is authoritative for that axis;
            # ``self.exec_group`` records what this engine actually runs.
            self.plan = dataclasses.replace(
                self.plan, preset=None,
                vector=dataclasses.replace(
                    self.plan.vector, slots=self.pool.level,
                    pages=(self.page_pool.level
                           if self.page_pool is not None
                           else self.plan.vector.pages)))
        return changed

    def _retire(self, slot: int):
        req = self._slot_req[slot]
        self.latency[req.rid] = time.perf_counter() - self._t0
        self.retire_steps[req.rid] = self._step_no
        self.done.append(req)
        self._slot_req[slot] = None
        if self.page_pool is not None:
            # return the pages AND sentinel the slot's device table row:
            # a drained slot still rides the batched decode (horizon-1
            # mode) and must not write into pages a new tenant now owns
            self.page_pool.free(slot)
            self._pt[slot] = sentinel(self.page_pool.total_pages)
            self._cache["pt"] = self._cache["pt"].at[slot].set(
                jnp.asarray(self._pt[slot]))

    def evacuate(self) -> Tuple[List[Request], List[Request]]:
        """Fail-stop teardown (the chaos fabric, DESIGN.md §15): pop
        every resident request — live decode slots and the still-queued
        backlog — WITHOUT retiring them: no ``done``/``latency`` entry,
        because the work did not finish here.  Pages go back to the
        pool and page-table rows are sentineled (conservation: a dead
        worker leaks nothing), fused-mode device rows are marked
        drained, and the engine stays steppable — the recovery layer
        re-admits the evacuees on surviving workers.

        -> ``(live, queued)``: live requests carry their emitted prefix
        in ``output``; queued ones never started (``emitted == 0``)."""
        live: List[Request] = []
        evac_slots: List[int] = []
        for slot, req in enumerate(self._slot_req):
            if req is None:
                continue
            live.append(req)
            evac_slots.append(slot)
            self._slot_req[slot] = None
            self._remaining[slot] = 0
            if self.page_pool is not None:
                self.page_pool.free(slot)
                self._pt[slot] = sentinel(self.page_pool.total_pages)
                self._cache["pt"] = self._cache["pt"].at[slot].set(
                    jnp.asarray(self._pt[slot]))
        if evac_slots and self.decode_horizon > 1:
            idx = jnp.asarray(np.asarray(evac_slots, np.int32))
            self._dev_state["finished"] = \
                self._dev_state["finished"].at[idx].set(True)
            self._dev_state["remaining"] = \
                self._dev_state["remaining"].at[idx].set(0)
        queued = list(self.queue)
        self.queue.clear()
        return live, queued

    # ----- external stepping ---------------------------------------------
    # The serving fabric (serve/fabric/) drives workers in virtual time, so
    # the engine's lifecycle is exposed as start / admit_waiting / step and
    # run() is just the single-worker loop over them.

    def start(self):
        """Allocate the persistent slot cache and reset per-slot state.
        Idempotent: calling twice without run/step in between is a no-op."""
        if self._started:
            return
        b = self.n_slots
        self._t0 = time.perf_counter()
        if self.page_pool is not None:
            # shared physical pages + per-slot page tables; every table
            # starts all-sentinel (no page mapped anywhere)
            self._cache = self.model.init_cache(
                b, self.max_len, per_slot=True, page_size=self.page_size,
                n_pages=self.page_pool.total_pages)
            self._pt = np.full(
                (b, self.max_len // self.page_size),
                sentinel(self.page_pool.total_pages), np.int32)
        else:
            self._cache = self.model.init_cache(b, self.max_len,
                                                per_slot=True)
        self._slot_req = [None] * b
        self._next_tok = np.zeros(b, np.int32)
        self._remaining = np.zeros(b, np.int32)
        self._pos = np.zeros(b, np.int64)
        self._eos_id = np.full(b, -1, np.int32)
        self._has_eos = np.zeros(b, bool)
        if self.decode_horizon > 1:
            # fused mode: the decode state lives on device between
            # horizons; every slot starts drained
            self._dev_state = {
                "tok": jnp.zeros(b, jnp.int32),
                "remaining": jnp.zeros(b, jnp.int32),
                "finished": jnp.ones(b, bool),
                "eos": jnp.full(b, -1, jnp.int32),
                "has_eos": jnp.zeros(b, bool),
            }
        self._started = True

    @property
    def paged(self) -> bool:
        """Whether this engine runs the paged KV-cache layout (the plan
        asked AND the model supports it)."""
        return self.page_pool is not None

    @property
    def n_active(self) -> int:
        return sum(r is not None for r in self._slot_req)

    @property
    def has_work(self) -> bool:
        return bool(self.queue) or self.n_active > 0

    def free_slots(self) -> List[int]:
        """Slots the pool could admit to regardless of the wait queue —
        the fabric's capacity probe (`serve.fabric.EngineWorker`)."""
        occupied = [r is not None for r in self._slot_req]
        return self.pool.admissible(occupied)

    def admissible_slots(self) -> List[int]:
        """Slots the pool would admit to right now, bounded by the wait
        queue (empty queue -> [] without scanning the groups)."""
        occupied = [r is not None for r in self._slot_req]
        return self.pool.admissible(occupied, queue_len=len(self.queue))

    def admit_waiting(self) -> int:
        """Admit queued requests into every admissible slot; -> count.
        Starts the engine if the caller has not (start() is idempotent).
        With buckets active the whole round admits as one batched
        prefill; prompts longer than the largest bucket fall back to the
        exact-length path."""
        self.start()
        batch: List[Tuple[int, Request]] = []
        for slot in self.admissible_slots():
            if not self.queue:
                break
            if self.page_pool is not None:
                # reserve the request's full worst-case page span up
                # front (prompt + budget, capped at max_len) so decode
                # never allocates mid-stream — safe under fused horizons.
                # A dry pool DEFERS in FIFO order: the head request waits
                # rather than being overtaken (pool state untouched).
                req = self.queue[0]
                # a KV import's span is keyed by the RESIDENT cache
                # (possibly mid-decode), not the raw prompt
                base = (req.kv.pos if req.kv is not None
                        else len(req.prompt))
                span = min(base + req.max_new_tokens, self.max_len)
                need = max(1, -(-span // self.page_size))
                if self.page_pool.alloc(slot, need) is None:
                    break
                self._pt[slot] = self.page_pool.table(slot)
            batch.append((slot, self.queue.popleft()))
        if self.page_pool is not None:
            self.stats["page_deferrals"] = self.page_pool.deferrals
            self.stats["page_hwm"] = self.page_pool.hwm
        if not batch:
            return 0
        kv_batch = [(s, r) for s, r in batch if r.kv is not None]
        batch = [(s, r) for s, r in batch if r.kv is None]
        for slot, req in kv_batch:      # cache merge, no forward pass
            self._cache = self._admit_handoff(self._cache, slot, req)
        if self.prefill_buckets:
            cap = self.prefill_buckets[-1]
            fit = [(s, r) for s, r in batch if len(r.prompt) <= cap]
            if fit:
                self._cache = self._admit_batch(self._cache, fit)
            for slot, req in batch:
                if len(req.prompt) > cap:
                    self._cache = self._admit(self._cache, slot, req)
        else:
            for slot, req in batch:
                self._cache = self._admit(self._cache, slot, req)
        return len(batch) + len(kv_batch)

    def step(self) -> List[Request]:
        """Decode ``decode_horizon`` steps over every live slot; ->
        requests retired (possibly admitted this very call: a request
        whose budget is one token frees its slot again immediately).
        Horizon 1 is the per-step host loop — the oracle the fused path
        is tested bit-identical against."""
        if self.decode_horizon > 1:
            return self._step_fused()
        active = [i for i, r in enumerate(self._slot_req) if r is not None]
        if not active:
            return []
        logits, self._cache = self._decode(self.params, self._cache,
                                           jnp.asarray(self._next_tok))
        self.stats["decode_steps"] += 1
        self.stats["decode_calls"] += 1
        self.stats["host_syncs"] += 1
        self.stats["slot_steps"] += self.n_slots
        self.stats["busy_slot_steps"] += len(active)
        self._step_no += 1
        produced = self._next_tok.copy()
        # np.array (copy): admission writes the prefill token in-place
        nxt = np.array(jnp.argmax(logits, -1), np.int32)
        self._pos += 1       # every row's cache index advanced
        retired: List[Request] = []
        for i in active:
            r = self._slot_req[i]
            r.output.append(int(produced[i]))
            self._remaining[i] -= 1
            finished = (self._remaining[i] <= 0
                        or (r.eos_id is not None
                            and int(nxt[i]) == r.eos_id))
            if not finished and self._pos[i] >= self.max_len - 1:
                r.output.append(int(nxt[i]))   # budget exhausted
                finished = True
            if finished:
                self._retire(i)
                retired.append(r)
        self._next_tok = nxt
        return retired

    def _step_fused(self) -> List[Request]:
        """One fused horizon: K decode steps on device, one host drain.
        The carry state never leaves the device — the trace transfer is
        the horizon's single host sync (the batched doorbell)."""
        if self.n_active == 0:
            return []
        k = self.decode_horizon
        self._cache, self._dev_state, trace = self._steps.horizon(
            self.params, self._cache, self._dev_state, k, self.max_len)
        # ONE blocking transfer drains the whole K-step token trace
        trace = jax.device_get(trace)
        # the horizon exits early once every slot drains, so the executed
        # step count comes from the trace, not from K
        executed = int(trace["live"].any(axis=1).sum())
        self.stats["decode_steps"] += executed
        self.stats["decode_calls"] += 1
        self.stats["host_syncs"] += 1
        self.stats["slot_steps"] += executed * self.n_slots
        retired: List[Request] = []
        for s in range(k):
            row_live = trace["live"][s]
            if not row_live.any():
                break     # liveness is monotone within a horizon
            self._step_no += 1
            self.stats["busy_slot_steps"] += int(row_live.sum())
            for i in np.nonzero(row_live)[0]:
                r = self._slot_req[i]
                r.output.append(int(trace["tok"][s, i]))
                if trace["bonus"][s, i]:
                    r.output.append(int(trace["bonus_tok"][s, i]))
                if trace["retired"][s, i]:
                    self._retire(i)
                    retired.append(r)
        self._pos += executed    # every row's cache index advanced as one
        return retired

    # ----- main loop ------------------------------------------------------
    def run(self) -> List[Request]:
        self.start()
        self._t0 = time.perf_counter()   # latency baseline per run(), not
        while self.has_work:             # per start() (which is idempotent)
            self.admit_waiting()
            if not self.step():       # no live slot: queue drained mid-check
                if self.n_active == 0:
                    break
        return self.done

    @property
    def occupancy(self) -> float:
        """Fraction of slot-steps that decoded a live request."""
        if not self.stats["slot_steps"]:
            return 0.0
        return self.stats["busy_slot_steps"] / self.stats["slot_steps"]
