"""Serving launcher over the `serve.connect` facade (DESIGN.md §11).

The plan is declared either as a preset / explicit sharing vector or as
hints the planner resolves:

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --smoke \
      --plan shared_dynamic --requests 8 --prompt-len 16 --max-new 12

  # off-diagonal: dedicated decode slots, 4-way-shared dispatch queues
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --smoke \
      --plan slots=1,channels=3 --workers 4 --traffic bursty

  # intent instead of resources: the planner resolves the vector
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --smoke \
      --hint latency_target_ms=80 --hint burstiness=0.9 --workers 4

The pre-plan flags (--engine/--category/--workers/--slots/...) keep
working: they translate to the equivalent preset `EndpointPlan`
(--category warns: it is the deprecated diagonal spelling).
"""

from __future__ import annotations

import argparse
import dataclasses
import time
import warnings

import jax
import numpy as np

from repro.configs import ARCHS, get_config, get_smoke_config
from repro.core.endpoints import Category
from repro.core.plan import EndpointPlan, Hints, SharingVector
from repro.launch.compile_cache import use_compile_cache
from repro.obs import enabled_obs
from repro.serve import connect
from repro.serve.fabric import TRAFFIC_SHAPES, bursty_trace, phased_trace, \
    poisson_trace, session_trace
from repro.serve.fabric.faults import _parse_time_ns
from repro.serve.fabric.placement import POLICIES
from repro.serve.recovery import RecoveryPolicy


def parse_migrations(items):
    """--migrate TIME:wSRC:wDST (repeatable) -> [(t_ns, src, dst)].
    Times use the fault grammar's units ('600us', '1.2ms', bare ns)."""
    out = []
    for item in items:
        try:
            t, src, dst = item.split(":")
            if not (src.startswith("w") and dst.startswith("w")):
                raise ValueError("workers spell as wN")
            out.append((_parse_time_ns(t), int(src[1:]), int(dst[1:])))
        except ValueError as e:
            raise ValueError(
                f"--migrate wants 'TIME:wSRC:wDST' (e.g. '600us:w2:w3'); "
                f"got {item!r}: {e}") from None
    return out


def make_trace(args):
    """Traffic for fleet mode honoring the request-shape flags: prompts
    drawn from --prompt-len (or the {1/2, 1, 2}x mix), budgets up to
    --max-new."""
    p = args.prompt_len
    prompt_lens = (max(1, p // 2), p, 2 * p) if args.mixed_lengths else (p,)
    new_tokens = (max(1, args.max_new // 2), args.max_new)
    if args.traffic == "poisson":
        return poisson_trace(args.requests, prompt_lens=prompt_lens,
                             new_tokens=new_tokens, seed=args.seed)
    if args.traffic == "bursty":
        return bursty_trace(args.requests, prompt_lens=prompt_lens,
                            new_tokens=new_tokens, seed=args.seed)
    if args.traffic == "phased":
        return phased_trace(max(1, args.requests // 3),
                            prompt_lens=prompt_lens,
                            new_tokens=new_tokens, seed=args.seed)[0]
    return session_trace(max(1, args.requests // 4), 4,
                         prompt_lens=prompt_lens, new_tokens=new_tokens,
                         seed=args.seed)


def parse_buckets(spec: str):
    """--prefill-buckets: 'auto'/'pow2' derive power-of-2 buckets,
    'none'/'off' disable (exact-length prefill), else a comma list of
    lengths, e.g. '8,16,32'."""
    if spec in ("auto", "pow2"):
        return spec
    if spec in ("none", "off"):
        return None
    return tuple(int(tok) for tok in spec.split(",") if tok.strip())


def parse_vector(spec: str) -> SharingVector:
    """--plan as an explicit vector: 'slots=1,channels=3[,execs=4]'."""
    fields = {}
    for tok in spec.split(","):
        k, _, v = tok.partition("=")
        fields[k.strip()] = int(v)
    return SharingVector(**fields)


_HINT_TYPES = {"latency_target_ms": float, "burstiness": float,
               "footprint_budget": float, "memory_budget": float,
               "session_ordering": lambda v: v.lower() in ("1", "true",
                                                           "yes", "on"),
               "compile_isolation": lambda v: v.lower() in ("1", "true",
                                                            "yes", "on")}


def parse_hints(items) -> Hints:
    """--hint k=v (repeatable) -> Hints."""
    fields = {}
    for item in items:
        k, _, v = item.partition("=")
        if k not in _HINT_TYPES:
            raise ValueError(f"unknown hint {k!r}; one of "
                             f"{sorted(_HINT_TYPES)}")
        fields[k] = _HINT_TYPES[k](v)
    return Hints(**fields)


def build_plan(args, ap) -> EndpointPlan:
    """Resolve the flag surface — new (--plan/--hint) or legacy
    (--engine/--category) — into ONE EndpointPlan."""
    # getattr defaults: programmatic callers hand-build Namespaces that
    # may predate the adaptive flags
    adaptive = getattr(args, "adaptive", False)
    knobs = dict(n_workers=args.workers, n_slots=args.slots,
                 max_len=args.max_len, decode_horizon=args.decode_horizon,
                 prefill_buckets=parse_buckets(args.prefill_buckets),
                 use_ragged_kernel=args.ragged_kernel,
                 adaptive=adaptive,
                 adapt_window_ns=getattr(args, "adapt_window",
                                         250.0) * 1e3)
    if getattr(args, "roles", None):
        knobs["roles"] = args.roles
    pages = getattr(args, "pages", 1) or 1
    page_size = getattr(args, "page_size", 0) or 0
    if pages < 1 or pages > 4:
        ap.error("--pages must be a sharing level in 1..4")
    if page_size:
        knobs["page_size"] = page_size
    if getattr(args, "page_budget", None) is not None:
        knobs["page_budget"] = args.page_budget

    def done(plan: EndpointPlan) -> EndpointPlan:
        """Land --pages on whichever vector the flag surface resolved
        (presets and legacy flags predate the pages axis)."""
        if pages > 1:
            if plan.vector.pages not in (1, pages):
                ap.error(f"--pages {pages} conflicts with the plan's "
                         f"pages level {plan.vector.pages}")
            plan = dataclasses.replace(
                plan, vector=dataclasses.replace(plan.vector,
                                                 pages=pages))
        return plan
    if args.placement is not None:
        # only an explicit flag pins placement — hints may resolve their
        # own (session_ordering -> session_affinity)
        knobs["placement"] = args.placement
    if args.plan and args.hint:
        ap.error("--plan and --hint are exclusive: a plan IS resolved "
                 "hints")
    if (args.plan or args.hint) and args.category:
        ap.error("--category conflicts with --plan/--hint; the preset "
                 "spelling is --plan " + args.category)
    if (args.plan or args.hint) and args.engine is not None:
        ap.error(f"--engine {args.engine} conflicts with --plan/--hint "
                 f"(a plan resolves its own executor)")
    if args.engine == "wave" and adaptive:
        # the IMPLICIT wave default silently upgrades to continuous
        # under --adaptive, but an explicit engine choice must not be
        # silently dropped
        ap.error("--engine wave cannot re-plan live; drop --adaptive or "
                 "use the continuous engine")
    if args.plan:
        if args.plan in (c.value for c in Category):
            return done(EndpointPlan.from_preset(args.plan, **knobs))
        try:
            return done(EndpointPlan(vector=parse_vector(args.plan),
                                     **knobs))
        except (TypeError, ValueError) as e:
            ap.error(f"--plan must be a preset "
                     f"({', '.join(c.value for c in Category)}) or "
                     f"'slots=..,channels=..[,execs=..,pages=..]': {e}")
    if args.hint:
        try:
            return done(EndpointPlan.from_hints(parse_hints(args.hint),
                                                **knobs))
        except ValueError as e:
            ap.error(str(e))
    # ----- legacy flag translation ---------------------------------------
    category = Category.MPI_EVERYWHERE
    if args.category is not None:
        warnings.warn(
            "--category is deprecated and now means the DIAGONAL preset: "
            "the level applies to slots, channels, AND executables (the "
            "pre-plan fleet shared only the dispatch queues — that "
            "spelling is --plan slots=1,channels=N).  Use --plan "
            "<preset|slots=..,channels=..> or --hint k=v",
            DeprecationWarning, stacklevel=2)
        category = Category(args.category)
    executor = "auto"
    if args.workers == 1 and (args.engine or "wave") == "wave" \
            and not adaptive and pages == 1 and not page_size:
        # the historical single-engine default (a wave engine cannot
        # re-plan live or page its cache, so --adaptive and the page
        # flags keep the continuous executor)
        executor = "wave"
        knobs.update(decode_horizon=1, prefill_buckets="auto")
    if args.category is None and args.workers > 1:
        # the bare legacy fleet (no category asked for) keeps the
        # pre-plan sharing structure: dedicated slots and queues but ONE
        # shared compiled set — the full level-1 diagonal would silently
        # compile a private executable set per worker (N-fold jit cost
        # the old fleet never paid); only an explicit --category opts
        # into the diagonal (and warns above)
        return done(EndpointPlan(
            vector=SharingVector(slots=1, channels=1, execs=4),
            executor=executor, **knobs))
    return done(EndpointPlan.from_category(category, executor=executor,
                                           **knobs))


def run_fleet(cfg, client, args) -> None:
    trace = make_trace(args)
    for a in trace:
        rng = np.random.default_rng(a.rid)
        client.submit(rng.integers(1, cfg.vocab,
                                   size=a.prompt_len).astype(np.int32),
                      max_new_tokens=a.max_new_tokens, at_ns=a.t_ns,
                      session=a.session)
    t0 = time.time()
    client.run()
    dt = time.time() - t0
    rep = client.report
    v = client.plan.vector
    u = rep.endpoint_usage
    preset = f" preset={client.plan.preset}" if client.plan.preset else ""
    print(f"fleet: {rep.n_workers} workers, vector=(slots={v.slots}, "
          f"channels={v.channels}, execs={v.execs}){preset}, "
          f"placement={rep.placement}, traffic={args.traffic}")
    print(f"  {rep.n_completed}/{rep.n_arrivals} requests, "
          f"{rep.total_new_tokens} tokens in {rep.makespan_ns / 1e6:.2f} "
          f"virtual ms ({rep.tok_per_s:,.0f} tok/s; host {dt:.2f}s)")
    print(f"  p50={rep.latency_percentile(0.5) / 1e6:.2f}ms "
          f"p99={rep.latency_percentile(0.99) / 1e6:.2f}ms "
          f"occupancy={rep.occupancy:.2f} fairness={rep.fairness:.3f} "
          f"lock_wait={rep.lock_wait_ns:.0f}ns")
    foot = client.plan.footprint()
    print(f"  footprint: plan={client.plan.footprint_score() * 100:.1f}% "
          f"({'/'.join(foot)} "
          f"{'/'.join(f'{x * 100:.0f}%' for x in foot.values())}), "
          f"endpoint uuars={u['uuars'] * 100:.1f}% "
          f"memory={u['memory'] * 100:.1f}%")
    if rep.roles is not None or rep.handoffs or rep.migrations:
        topo = (f"{rep.roles[0]}P+{rep.roles[1]}D"
                if rep.roles is not None else "co-located")
        print(f"  disagg: {topo}, {rep.handoffs} KV handoffs "
              f"({rep.kv_tokens_moved} tokens, "
              f"{rep.kv_bytes_moved:,} bytes), "
              f"{rep.migrations} live migrations")
    if rep.page_hwm_frac is not None:
        print(f"  pages: peak {rep.page_hwm_frac * 100:.1f}% of the "
              f"dedicated reservation, {rep.page_deferrals} deferrals")
    if rep.faults_injected or rep.detections or rep.retries or rep.shed:
        worst = (max(rep.recovery_latency_ns) / 1e6
                 if rep.recovery_latency_ns else 0.0)
        print(f"  chaos: {rep.faults_injected} faults, "
              f"{rep.detections} detections (worst {worst:.2f}ms), "
              f"{rep.retries} retries, {len(rep.recovered)} recovered, "
              f"{len(rep.failed)} failed, {rep.n_shed} shed, "
              f"{rep.duplicate_completions} duplicate completions")
    if client.plan.adaptive:
        path = " -> ".join(
            f"{vec.label}@{t / 1e6:.2f}ms"
            for t, vec in rep.transitions) or "none"
        print(f"  adaptive: {rep.n_windows} windows, "
              f"{len(rep.transitions)} migrations ({path}), "
              f"mean footprint {rep.mean_footprint * 100:.1f}%")
    for c in rep.completions[:4]:
        print(f"  req {c.rid} (worker {c.worker}): {c.output}")


def run_single(cfg, client, args) -> None:
    rng = np.random.default_rng(args.seed)
    for i in range(args.requests):
        plen = args.prompt_len
        if args.mixed_lengths:
            plen = int(rng.choice([max(1, plen // 2), plen, 2 * plen]))
        client.submit(rng.integers(1, cfg.vocab,
                                   size=plen).astype(np.int32),
                      max_new_tokens=args.max_new)
    t0 = time.time()
    out = client.run()
    dt = time.time() - t0
    engine = client.engine
    n_tok = sum(len(toks) for toks in out.values())
    lat = sorted(engine.latency.values())
    p50 = lat[len(lat) // 2] if lat else 0.0
    print(f"served {len(out)} requests, {n_tok} tokens in {dt:.2f}s "
          f"({n_tok / dt:.1f} tok/s, executor={client.executor}, "
          f"p50 latency {p50:.2f}s)")
    if client.executor == "continuous":
        syncs = engine.stats["host_syncs"] / max(1, n_tok)
        print(f"slot pool: level {engine.pool.level} "
              f"(group size {engine.pool.group_size}), "
              f"occupancy {engine.occupancy:.2f}, "
              f"{engine.stats['decode_steps']} decode steps in "
              f"{engine.stats['decode_calls']} calls "
              f"(horizon {engine.decode_horizon}), "
              f"{engine.stats['prefills']} prefills for "
              f"{engine.stats['prefilled_requests']} requests "
              f"(buckets {list(engine.prefill_buckets) or 'off'}), "
              f"{syncs:.2f} host syncs/token")
        if engine.paged:
            pool = engine.page_pool
            print(f"page pool: level {pool.level} "
                  f"(page size {engine.page_size}, "
                  f"{pool.total_pages} pages), "
                  f"hwm {pool.hwm} ({pool.hwm / pool.total_pages:.0%}), "
                  f"{pool.deferrals} deferrals")
        if client.plan.adaptive:
            path = " -> ".join(
                f"{vec.label}@step{step}"
                for step, vec in client.transitions) or "none"
            print(f"adaptive: {engine.stats['regroups']} regroups "
                  f"({path}); final vector {client.plan.vector.label}")
    for rid in sorted(out)[:4]:
        print(f"  req {rid}: {out[rid]}")


def main(argv=None):
    """Serve the requests the flags describe; -> the connected client,
    whose ``results`` hold every completed request's tokens."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b", choices=list(ARCHS))
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--plan", default=None,
                    help="endpoint plan: a preset (one of "
                         f"{[c.value for c in Category]}) or an explicit "
                         "vector 'slots=1,channels=3[,execs=4]'")
    ap.add_argument("--hint", action="append", default=[],
                    metavar="K=V",
                    help="intent for the planner (repeatable): "
                         "latency_target_ms=, burstiness=, "
                         "session_ordering=, footprint_budget=, "
                         "compile_isolation=")
    ap.add_argument("--engine", default=None,
                    choices=("wave", "continuous"),
                    help="[legacy] single-engine scheduler (default "
                         "wave); a fleet (--workers > 1) is always "
                         "continuous")
    ap.add_argument("--category", default=None,
                    choices=[c.value for c in Category],
                    help="[deprecated] diagonal sharing preset; use "
                         "--plan")
    ap.add_argument("--workers", type=int, default=1,
                    help="> 1 serves through the fabric router with this "
                         "many continuous-engine workers")
    ap.add_argument("--placement", default=None,
                    choices=sorted(POLICIES),
                    help="dispatch placement policy (default round_robin; "
                         "left unset, hints may resolve their own)")
    ap.add_argument("--traffic", default="bursty",
                    choices=sorted(TRAFFIC_SHAPES))
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--mixed-lengths", action="store_true",
                    help="draw prompt lengths from {1/2, 1, 2}x prompt-len")
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--ragged-kernel", action="store_true",
                    help="decode attention through the Pallas ragged "
                         "kernel (interpret mode off-TPU)")
    ap.add_argument("--decode-horizon", type=int, default=1,
                    help="fused decode steps per host sync (continuous "
                         "engine; 1 = per-step host loop, the oracle)")
    ap.add_argument("--pages", type=int, default=1,
                    help="KV page-pool sharing level 1..4 (DESIGN.md "
                         "§13): 1 = dedicated per-slot reservation (the "
                         "contiguous-equivalent default), 4 = one "
                         "worker-wide pool; > 1 engages the paged cache "
                         "layout")
    ap.add_argument("--page-size", type=int, default=0,
                    help="tokens per KV page (0 = auto: the largest "
                         "divisor of max-len <= 64); setting it also "
                         "engages the paged layout")
    ap.add_argument("--page-budget", type=int, default=None,
                    help="total pool pages per worker (default: the "
                         "dedicated reservation slots x max-len / "
                         "page-size)")
    ap.add_argument("--prefill-buckets", default="auto",
                    help="admission prefill length buckets: 'auto'/'pow2' "
                         "(power-of-2 set), 'none' (exact-length), or a "
                         "comma list like '8,16,32'")
    ap.add_argument("--adaptive", action="store_true",
                    help="live re-planning (DESIGN.md §12): a Replanner "
                         "samples per-resource telemetry every window "
                         "and migrates the SharingVector under shifting "
                         "traffic")
    ap.add_argument("--adapt-window", type=float, default=250.0,
                    metavar="US",
                    help="adaptation window in virtual microseconds "
                         "(fleet mode; the single engine converts it to "
                         "decode steps via the fabric cost model)")
    ap.add_argument("--roles", default=None, metavar="SPEC",
                    help="prefill/decode disaggregation (DESIGN.md §17): "
                         "'2P+2D' splits the fleet into 2 prefill-only + "
                         "2 decode-only workers (must sum to --workers); "
                         "finished prefills hand their KV to a decode "
                         "worker over the fabric")
    ap.add_argument("--migrate", action="append", default=[],
                    metavar="TIME:wSRC:wDST",
                    help="decode→decode live migration (repeatable): at "
                         "TIME (fault-grammar units, e.g. '600us') the "
                         "source worker's live sessions move to the "
                         "destination as KV handoffs, token streams "
                         "bit-identical (fleet mode only)")
    ap.add_argument("--faults", default=None, metavar="SPEC",
                    help="chaos fabric (DESIGN.md §15): deterministic "
                         "fault plan, comma-separated "
                         "'kind@time:target[:duration[:frac]]' — kinds "
                         "crash/stall/chan_stall/page_pressure, e.g. "
                         "'crash@4.5ms:w0,stall@2.2ms:w1:1ms' (fleet "
                         "mode only)")
    ap.add_argument("--heartbeat-us", type=float, default=None,
                    help="failure-detector probe cadence in virtual us "
                         "(default 100)")
    ap.add_argument("--deadline-us", type=float, default=None,
                    help="heartbeat silence that declares a worker dead, "
                         "virtual us (default 400; must exceed the "
                         "largest healthy step)")
    ap.add_argument("--shed-capacity", type=int, default=None,
                    help="max outstanding requests before the router "
                         "sheds new arrivals, lowest priority first "
                         "(default 0 = unlimited)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="a fleet (--workers > 1) writes a "
                         "Chrome/Perfetto trace-event JSON of the run in "
                         "virtual time (open at https://ui.perfetto.dev); "
                         "a single engine writes a JAX profiler trace "
                         "directory at PATH, its wall-clock engine spans "
                         "beside the device's programs (DESIGN.md §14)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the unified metrics registry "
                         "(counters/gauges/quantile sketches keyed by "
                         "resource axis/group/worker) as JSON")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if args.workers > 1 and args.engine == "wave":
        ap.error("--workers > 1 serves through continuous-engine workers; "
                 "--engine wave only applies to a single engine")
    if args.workers == 1 and (args.engine or "wave") == "wave" \
            and not (args.plan or args.hint or args.adaptive
                     or args.pages > 1 or args.page_size
                     or args.page_budget is not None):
        if args.decode_horizon != 1:
            ap.error("--decode-horizon applies to the continuous engine")
        if parse_buckets(args.prefill_buckets) not in ("auto", "pow2",
                                                       None):
            # 'auto' (the default) and 'none' are both no-ops for the
            # wave engine; only an explicit bucket list is a misuse
            ap.error("--prefill-buckets applies to the continuous engine")
    pmax = args.prompt_len * (2 if args.mixed_lengths else 1)
    if args.workers > 1 and pmax + args.max_new >= args.max_len:
        # fleet accounting needs every request to fit; the single-engine
        # path instead truncates at the cache budget (a supported mode)
        ap.error(f"longest prompt ({pmax}) + max-new ({args.max_new}) "
                 f"must fit max-len ({args.max_len}) in fleet mode")
    ft_knobs = (args.heartbeat_us, args.deadline_us, args.shed_capacity)
    if (args.faults or any(k is not None for k in ft_knobs)) \
            and args.workers <= 1:
        ap.error("--faults and the recovery knobs need a fleet "
                 "(--workers > 1)")
    if (args.roles or args.migrate) and args.workers <= 1:
        ap.error("--roles and --migrate need a fleet (--workers > 1)")
    try:
        migrations = parse_migrations(args.migrate) or None
    except ValueError as e:
        ap.error(str(e))
    recovery = None
    if args.faults or any(k is not None for k in ft_knobs):
        kw = {}
        if args.heartbeat_us is not None:
            kw["heartbeat_ns"] = args.heartbeat_us * 1e3
        if args.deadline_us is not None:
            kw["deadline_ns"] = args.deadline_us * 1e3
        if args.shed_capacity is not None:
            kw["shed_capacity"] = args.shed_capacity
        recovery = RecoveryPolicy(**kw)
    plan = build_plan(args, ap)
    use_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    obs = enabled_obs() if (args.trace_out or args.metrics_out) else None
    client = connect(cfg, plan, seed=args.seed, obs=obs,
                     faults=args.faults, recovery=recovery,
                     migrations=migrations)
    if plan.n_workers > 1:
        run_fleet(cfg, client, args)
        if args.trace_out:
            obs.recorder.dump(args.trace_out)
            print(f"trace: {len(obs.recorder.events)} events -> "
                  f"{args.trace_out} (open at https://ui.perfetto.dev)")
    elif args.trace_out:
        with jax.profiler.trace(args.trace_out):
            run_single(cfg, client, args)
        print(f"trace: JAX profiler trace -> {args.trace_out} (read with "
              f"jax.profiler.ProfileData or TensorBoard's profile plugin)")
    else:
        run_single(cfg, client, args)
    if args.metrics_out:
        obs.metrics.dump(args.metrics_out)
        print(f"metrics: {len(obs.metrics.names())} series -> "
              f"{args.metrics_out}")
    return client


if __name__ == "__main__":
    main()
