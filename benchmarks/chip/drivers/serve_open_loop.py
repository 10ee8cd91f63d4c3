"""Open-loop serving on one chip through ``serve.connect``.

Set-up makes the weights on the device from the seed in one jitted call,
connects a client with the cell's deployment facts (``n_slots``,
``max_len``) and the program's defaults for everything else, and warms
every admission shape the traffic can use.  The window then drives
``client.engine``'s hooks (``submit``, ``admit_waiting``, ``step``) on the
wall clock from one thread: requests are submitted when they fall due,
whether or not earlier ones finished, and each request's tokens are seen
in ``Request.output`` after every hook returns.

After the window the program's state is freed and the reference
(``reference/decoder.py``) runs once over a sample of the finished
requests, prompt and served tokens: ``logit_gap_max`` is the widest gap
by which a served token's logit lies below the reference's best.  Every
served token is greedy, so a sound program reads rounding only.
"""

from __future__ import annotations

import dataclasses
import gc
import shutil
import tempfile
import time
from typing import Optional

import numpy as np

import harness
import loadgen
import opcount
import trace_reduce
from reference import decoder

# configuration-file keys -> the program's ArchConfig fields
ARCH_FIELDS = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
               "num_attention_heads": "n_heads",
               "num_key_value_heads": "n_kv_heads",
               "intermediate_size": "d_ff", "vocab_size": "vocab",
               "rope_theta": "rope_theta",
               "tie_word_embeddings": "tie_embeddings"}


@dataclasses.dataclass
class Track:
    """One scheduled request and what the host saw of it."""

    due: loadgen.Due
    req: object = None
    t_submit: Optional[float] = None
    t_admit: Optional[float] = None
    t_first: Optional[float] = None
    t_last: Optional[float] = None
    t_done: Optional[float] = None
    seen: int = 0


def program_config(cfg: dict):
    """The program's ``ArchConfig`` for a configuration file: the
    registered architecture with every size the file states."""
    from repro.configs import get_config

    arch = get_config(cfg["program_arch"])
    sizes = {f: cfg[k] for k, f in ARCH_FIELDS.items()}
    sizes["d_head"] = decoder.dims(cfg)["dh"]
    sizes["qkv_bias"] = decoder.has_qkv_bias(cfg)
    return dataclasses.replace(arch, **sizes)


def program_params(cfg: dict, model, w: dict) -> dict:
    """The reference layout's weights rearranged into the program's
    parameter tree (checked leaf by leaf against its shapes)."""
    import jax

    m = decoder.dims(cfg)
    n, d, hq, hkv, dh = m["n_layers"], m["d"], m["hq"], m["hkv"], m["dh"]
    attn = {"wq": w["wq"].reshape(n, d, hq, dh),
            "wk": w["wk"].reshape(n, d, hkv, dh),
            "wv": w["wv"].reshape(n, d, hkv, dh),
            "wo": w["wo"].reshape(n, hq, dh, d)}
    if "bq" in w:
        attn.update(bq=w["bq"].reshape(n, hq, dh),
                    bk=w["bk"].reshape(n, hkv, dh),
                    bv=w["bv"].reshape(n, hkv, dh))
    block = {"norm1": {"scale": w["ln1"]}, "attn": attn,
             "norm2": {"scale": w["ln2"]},
             "ffn": {"w_gate": w["w_gate"], "w_up": w["w_up"],
                     "w_down": w["w_down"]}}
    embed = {"tok": w["embed"]}
    if "lm_head" in w:
        embed["head"] = w["lm_head"]
    tree = {"decoder": {"prefix": [], "body": [block]},
            "final_norm": {"scale": w["final_norm"]}, "embed": embed}
    want = model.abstract_params()
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       tree)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise RuntimeError("the program's parameter tree is not the layout "
                           "this driver fills")
    return tree


def warm_lengths(spec: dict, buckets) -> list:
    """Prompt lengths that reach every admission shape the traffic can
    use: one per prefill bucket of the engine that a length in the
    traffic's range pads to; with no buckets (exact-length prefill), every
    multiple of 16 in the range and both ends."""
    lo, hi = int(spec["min"]), int(spec["max"])
    if not buckets:
        return sorted({lo, hi} | set(range(-(-lo // 16) * 16, hi + 1, 16)))
    out, prev = set(), 0
    for b in sorted(buckets):
        if prev < hi and b >= lo:
            out.add(min(b, hi))
        prev = b
    if hi > max(buckets):          # longer prompts prefill at exact length
        out |= set(range(max(buckets) + 1, hi + 1))
    return sorted(out)


def run(r) -> harness.Outcome:
    import jax

    from repro import serve
    from repro.models.model import Model
    from repro.serve.engine import Request

    clock = time.perf_counter
    cfg, traffic = r.config, r.traffic
    dep = traffic["deployment"]
    counter = harness.CompileCounter(jax)

    def span(what):       # host spans the trace reduction labels work by
        return jax.profiler.TraceAnnotation(trace_reduce.SPAN_PREFIX + what)
    arch = program_config(cfg)
    model = Model(arch)
    key = decoder.seed_key(r.seed)
    params = jax.jit(lambda k: program_params(
        cfg, model, decoder.init_weights(cfg, k)))(key)
    client = serve.connect(arch, params=params, n_slots=dep["n_slots"],
                           max_len=dep["max_len"])
    eng = client.engine

    # warm every admission shape, one round per length, then drain
    warm_rng = np.random.default_rng([r.seed, 1])
    for i, length in enumerate(warm_lengths(traffic["prompt_len"],
                                            eng.prefill_buckets)):
        eng.submit(Request(rid=-1 - i, max_new_tokens=2, prompt=warm_rng
                           .integers(0, cfg["vocab_size"], length,
                                     dtype=np.int32)))
        eng.admit_waiting()
        eng.step()
    while eng.has_work:
        eng.admit_waiting()
        eng.step()

    sched = loadgen.schedule(traffic, r.seed, r.seconds, cfg["vocab_size"])
    tracks = [Track(due=d) for d in sched]
    preroll = float(traffic["preroll_s"])
    t0 = clock()
    setup_s = t0 - r.t_start
    t_open, t_end = t0 + preroll, t0 + preroll + r.seconds
    trace_end = t_open + float(traffic.get("trace_seconds", r.seconds))
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if r.trace else None
    tracing = window_span = None
    marks = {}
    compiles_at_open = None
    acc = {"live_rows": 0, "context_sum": 0, "prefill_ops": 0.0}
    kv_in_window = 0            # cache positions the window's tokens read
    steps_at_open = None
    nxt = 0
    active = {}
    tokens_in_window = 0

    def take_counters():
        return {**acc, "decode_steps": eng.stats["decode_steps"]}

    while True:
        now = clock()
        if compiles_at_open is None and now >= t_open:
            compiles_at_open = counter.mark()
            steps_at_open = eng.stats["decode_steps"]
        if r.trace:
            if tracing is None and now >= t_open:
                harness.start_trace(jax, trace_dir)
                window_span = jax.profiler.TraceAnnotation(
                    trace_reduce.WINDOW_SPAN)
                window_span.__enter__()
                tracing = True
                marks["start"] = take_counters()
            elif tracing and now >= trace_end:
                window_span.__exit__(None, None, None)
                marks["end"] = take_counters()
                jax.profiler.stop_trace()
                tracing = False
        if now >= t_end:
            break
        while nxt < len(tracks) and t0 + tracks[nxt].due.t <= now:
            tr = tracks[nxt]
            tr.req = Request(rid=tr.due.idx, prompt=tr.due.prompt,
                             max_new_tokens=tr.due.max_new_tokens)
            eng.submit(tr.req)
            tr.t_submit = now
            active[tr.due.idx] = tr
            nxt += 1
        if eng.queue:
            waiting = {q.rid for q in eng.queue}
            t_admit = clock()
            with span("admit"):
                eng.admit_waiting()
            left = {q.rid for q in eng.queue}
            for rid in waiting - left:
                tr = active[rid]
                tr.t_admit = t_admit
                acc["prefill_ops"] += opcount.prefill_ops(
                    cfg, len(tr.due.prompt))
        if eng.n_active:
            with span("step"):
                eng.step()
        elif nxt < len(tracks):
            with span("wait"):
                time.sleep(max(0.0, min(t_end, t0 + tracks[nxt].due.t)
                               - clock()))
            continue
        else:
            with span("wait"):
                time.sleep(max(0.0, t_end - clock()))
            continue
        t = clock()
        for rid in list(active):
            tr = active[rid]
            k = len(tr.req.output)
            if k <= tr.seen:
                continue
            plen = len(tr.due.prompt)
            context = sum(plen + j + 1 for j in range(tr.seen, k))
            acc["live_rows"] += k - tr.seen
            acc["context_sum"] += context
            if t >= t_open:
                tokens_in_window += k - tr.seen
                kv_in_window += context
            if tr.t_first is None:
                tr.t_first = t
            tr.t_last, tr.seen = t, k
            if k >= tr.due.max_new_tokens:
                tr.t_done = t
                del active[rid]
    t_close = clock()
    if tracing:
        window_span.__exit__(None, None, None)
        marks["end"] = take_counters()
        jax.profiler.stop_trace()
    compiles = tuple(b - a for a, b in zip(compiles_at_open, counter.mark()))
    memory_peak = harness.memory_peak_bytes(r.devices)
    stats = dict(eng.stats)

    # ----- end-to-end numbers: every request due in the window ----------
    window = [tr for tr in tracks if tr.due.t >= preroll]
    ttft = [((tr.t_first or t_close) - (t0 + tr.due.t)) for tr in window]
    tpot = [(tr.t_last - tr.t_first) / (tr.seen - 1)
            for tr in window if tr.seen >= 2]
    queue_wait = [((tr.t_admit or t_close) - (t0 + tr.due.t))
                  for tr in window]
    lag = [tr.t_submit - (t0 + tr.due.t) for tr in window
           if tr.t_submit is not None]
    done = [tr for tr in tracks if tr.t_done is not None]
    steps_in_window = stats["decode_steps"] - (steps_at_open or 0)
    kv_filled = kv_in_window / max(1, steps_in_window * dep["n_slots"]
                                   * dep["max_len"])
    e2e = {"setup_s": setup_s,
           "output_tok_per_s": tokens_in_window / (t_close - t_open),
           "ttft_p95_ms": 1e3 * harness.quantile(ttft, 0.95)}
    if tpot:
        e2e["tpot_p95_ms"] = 1e3 * harness.quantile(tpot, 0.95)
    notes = [
        f"schedule: {len(tracks)} requests, {len(window)} due in the "
        f"{t_close - t_open:.3f} s window after a {preroll} s pre-roll; "
        f"rate {traffic['arrivals']['rate_per_s']}/s",
        f"requests due in the window: {len(window)}, admitted "
        f"{sum(tr.t_admit is not None for tr in window)}, with a first "
        f"token {sum(tr.t_first is not None for tr in window)}, finished "
        f"{sum(tr.t_done is not None for tr in window)}, censored at the "
        f"close (no first token) "
        f"{sum(tr.t_first is None for tr in window)}",
        f"output tokens received in the window: {tokens_in_window}",
        f"time to first token (due to first token in the output, ms): "
        f"p50 {1e3 * harness.quantile(ttft, 0.5)}, p95 "
        f"{1e3 * harness.quantile(ttft, 0.95)}; queue wait (due to the "
        f"admission round, ms): p50 {1e3 * harness.quantile(queue_wait, 0.5)}"
        f", p95 {1e3 * harness.quantile(queue_wait, 0.95)}",
        f"KV cache filled in the window (cache positions the live rows "
        f"read over decode steps x n_slots x max_len): {100 * kv_filled}%; "
        f"live rows per decode step: "
        f"{tokens_in_window / max(1, steps_in_window)}",
        f"compiles inside the window: {compiles[0]} traced, {compiles[1]} "
        f"compiled (there should be none)",
        f"generator lag (submit - due, s): p50 "
        f"{harness.quantile(lag, 0.5) if lag else 0.0}, p95 "
        f"{harness.quantile(lag, 0.95) if lag else 0.0}, max "
        f"{max(lag) if lag else 0.0}",
        f"engine counters at the close: {stats}",
        f"memory_peak_bytes: {memory_peak}",
    ]
    readings = {"queued_at_close": len(eng.queue),
                "active_at_close": eng.n_active,
                "queue_wait_p95_ms": 1e3 * harness.quantile(queue_wait, 0.95),
                "kv_filled": kv_filled}
    if r.trace:
        readings["counters"] = {k: marks["end"][k] - marks["start"][k]
                                for k in marks["start"]}

    # ----- correctness: the reference over a sample of finished ---------
    sample = sample_finished(done, r.seed, int(traffic["check"]
                                               ["sample_requests"]))
    records = [(np.asarray(tr.due.prompt), list(tr.req.output))
               for tr in sample]
    del client, eng, params, tracks, active, done, sample
    gc.collect()
    gaps = check_served(cfg, key, records, dep["max_len"], r.control)
    if r.control:
        gaps, readings["control_gaps"] = gaps
    limit = float(traffic["check"]["limits"]["logit_gap_max"])
    widest = max(gaps) if gaps else float("inf")
    failed = sum(g > limit for g in gaps)
    notes.append(f"checked {len(records)} finished requests, "
                 f"{sum(len(o) for _, o in records)} served tokens; widest "
                 f"logit gap per request {gaps}")
    if r.trace:
        readings["trace"] = trace_reduce_dir(trace_dir, r.keep_trace)
    return harness.Outcome(
        correct=bool(records) and widest <= limit,
        attempted=len(window), failed=failed, end_to_end=e2e,
        memory_peak_bytes=memory_peak,
        compared={"logit_gap_max": (widest, limit)}, notes=notes,
        readings=readings)


def sample_finished(done: list, seed: int, n: int) -> list:
    """The longest finished request and ``n - 1`` others drawn from the
    seed."""
    if not done:
        return []
    longest = max(done, key=lambda tr: (tr.due.max_new_tokens, -tr.due.idx))
    rest = [tr for tr in done if tr is not longest]
    rng = np.random.default_rng([seed, 2])
    pick = rng.choice(len(rest), min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def check_served(cfg: dict, key, records, max_len: int,
                 control: bool = False):
    """Widest gap per request (and with ``control`` the control's too):
    the reference's weights drawn again from the seed, each prompt with
    its served tokens padded to ``max_len``."""
    import jax

    w = jax.jit(lambda k: decoder.init_weights(cfg, k))(key)
    check = decoder.compiled_check(decoder.cfg_key(cfg), control)
    served, ctl = [], []
    for prompt, out in records:
        toks = np.zeros(max_len, np.int32)
        seq = np.concatenate([prompt, np.asarray(out, np.int32)])
        toks[:len(seq)] = seq
        got = jax.device_get(check(w, toks))
        lo, hi = len(prompt) - 1, len(prompt) - 1 + len(out)
        served.append(float(np.max(got["served"][lo:hi])))
        if control:
            ctl.append(float(np.max(got["control"][lo:hi])))
    return (served, ctl) if control else served


def trace_reduce_dir(trace_dir: str, keep: str = "") -> dict:
    try:
        path = harness.trace_file(trace_dir)
        if keep:
            shutil.copy(path, keep)
        return trace_reduce.reduce(path)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
