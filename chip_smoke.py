"""Bring-up check on a TPU: the serving main path at published widths.

  python chip_smoke.py             # one chip: serve qwen2-0.5b, check kernels
  python chip_smoke.py --chips 4   # four chips: data-parallel training only

One chip: qwen2-0.5b at its published widths (random weights from a seed)
serves a few requests through the launcher (``repro.launch.serve``,
``serve.connect``, ``ContinuousEngine``) in three plan variants: jnp
decode attention, the ragged Pallas decode kernel, and the paged one.
Then both decode kernels run compiled on the chip against their jnp
oracles.  Four chips: smollm-360m at published widths takes a few
data-parallel training steps under three endpoint categories on a
4-device ``data`` mesh, against the same steps on one device.

Everything runs in this one process: a chip belongs to one process.  The
script exits non-zero, with no result line, unless JAX finds a TPU and
every phase passes.  The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
These are bring-up checks, not a benchmark: the times it prints include
compilation and are not measurements of serving speed.
"""

from __future__ import annotations

import argparse
import importlib.metadata
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent

ARCH, SLOTS, MAX_LEN, PAGE_SIZE, SEED = "qwen2-0.5b", 8, 512, 64, 0
SERVE_REQUESTS, SERVE_MAX_NEW = 8, 32
# 8 slots of 512 tokens, K=8 fused decode steps per host sync; prompts of
# 32, 64 or 128 tokens all pad to the one prefill bucket, so each variant
# compiles one prefill and one decode program
SERVE_ARGS = ("--arch", ARCH, "--engine", "continuous",
              "--decode-horizon", "8", "--slots", str(SLOTS),
              "--max-len", str(MAX_LEN),
              "--requests", str(SERVE_REQUESTS), "--prompt-len", "64",
              "--mixed-lengths", "--max-new", str(SERVE_MAX_NEW),
              "--prefill-buckets", "128", "--seed", str(SEED))
VARIANTS = {"jnp": (), "ragged": ("--ragged-kernel",),
            "paged": ("--pages", "4", "--page-size", str(PAGE_SIZE),
                      "--ragged-kernel")}

# Kernel against oracle: both read the same bf16 cache, accumulate in f32
# and round the (B, 1, Hq, dh) result to bf16.  Attention outputs here stay
# below 2 in magnitude, where one bf16 ulp is at most 2**-7 = 7.8e-3; the
# two sides sum in different orders and may round to neighbouring values,
# so allow two ulps and a margin.
KERNEL_TOL = 2e-2

TRAIN_ARCH, TRAIN_CHIPS = "smollm-360m", 4
TRAIN_STEPS, TRAIN_BATCH, TRAIN_SEQ = 4, 8, 512
TRAIN_CATEGORIES = ("mpi_everywhere", "2x_dynamic", "mpi_threads")
# Loss agreement across categories and against one device, relative to the
# loss: the programs differ only in how gradients are bucketed for the
# all-reduce and in per-device batch (2 rows against 8), so bf16 activation
# rounding (2**-8 relative per element) enters in a different order and
# averages over the 4096 tokens of the global batch.  Sound runs on a v5e
# 2x2 read 3.3e-6 (mpi_everywhere, 2x_dynamic) and 6.6e-6 (mpi_threads);
# one step moves the loss by about 2e-3 relative, so a wrong or dropped
# gradient slice shows well above this limit.
LOSS_RTOL = 1e-4

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def check(ok: bool, what: str) -> None:
    if not ok:
        sys.exit(f"chip_smoke: FAILED: {what}")


class CompileClock:
    """Sums the backend compile seconds JAX reports for this process."""

    def __init__(self, jax):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, seconds, **_):
        if event == COMPILE_EVENT:
            self.seconds += seconds


def device_phase(jax, chips: int) -> dict:
    devices = jax.devices()
    d = devices[0]
    print(f"device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devices)}")
    print(f"versions: jax {jax.__version__}, "
          f"libtpu {importlib.metadata.version('libtpu')}")
    check(d.platform == "tpu",
          f"JAX found no TPU (platform {d.platform!r}); this script "
          f"checks the chip and has no CPU fallback")
    check(len(devices) >= chips,
          f"--chips {chips} needs {chips} devices, JAX found {len(devices)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def serve_phase(clock) -> None:
    from repro.launch import serve as launcher

    n_requests, max_new = SERVE_REQUESTS, SERVE_MAX_NEW
    streams = {}
    for name, flags in VARIANTS.items():
        c0 = clock.seconds
        t0 = time.perf_counter()
        client = launcher.main(list(SERVE_ARGS) + list(flags))
        wall = time.perf_counter() - t0        # token lists are on the host
        eng = client.engine
        check(eng.use_ragged_kernel == ("--ragged-kernel" in flags)
              and eng.paged == ("--pages" in flags),
              f"serve[{name}]: the engine did not take the variant's plan")
        out = client.results
        done = sum(len(toks) == max_new for toks in out.values())
        n_tok = sum(len(toks) for toks in out.values())
        print(f"serve[{name}]: {done}/{n_requests} requests complete, "
              f"{n_tok} tokens, {wall} s wall (init, compile and serve), "
              f"{clock.seconds - c0} s backend compile")
        check(len(out) == n_requests and done == n_requests,
              f"serve[{name}]: {done}/{n_requests} requests complete")
        streams[name] = out
        if name == "ragged":
            text = eng.decode_program_text()
            print(f"serve[{name}]: tpu_custom_call in the compiled decode "
                  f"program: {'tpu_custom_call' in text}")
            check("tpu_custom_call" in text,
                  "the ragged kernel is not in the compiled decode program")
    ref = streams["jnp"]
    same = sum(all(s[rid] == ref[rid] for s in streams.values())
               for rid in ref)
    print(f"serve: {same}/{len(ref)} token streams identical across "
          f"{', '.join(streams)}")


def kernel_phase() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.kernels.flash_attention.ops import (
        flash_decode_attention, paged_flash_decode_attention)
    from repro.models.attention import (attention_decode,
                                        attention_decode_paged)
    from repro.models.transformer import ragged_kv_block

    cfg = get_config(ARCH)
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(jax.random.PRNGKey(SEED), 3)
    q = jax.random.normal(ks[0], (SLOTS, 1, hq, dh), jnp.bfloat16)
    k = jax.random.normal(ks[1], (SLOTS, MAX_LEN, hkv, dh), jnp.bfloat16)
    v = jax.random.normal(ks[2], (SLOTS, MAX_LEN, hkv, dh), jnp.bfloat16)
    rng = np.random.default_rng(SEED)
    # ragged lengths from a fresh slot (one position) to a full cache
    idx = rng.integers(0, MAX_LEN, SLOTS).astype(np.int32)
    idx[0], idx[-1] = 0, MAX_LEN - 1
    idx = jnp.asarray(idx)

    # the same logical cache as pages behind a shuffled page table:
    # logical page j of the flattened (slot, page) order sits in
    # physical page perm[j]
    n_pages = SLOTS * MAX_LEN // PAGE_SIZE
    perm = rng.permutation(n_pages)
    table = jnp.asarray(perm.reshape(SLOTS, MAX_LEN // PAGE_SIZE),
                        jnp.int32)
    inv = np.argsort(perm)
    kp = k.reshape(n_pages, PAGE_SIZE, hkv, dh)[inv]
    vp = v.reshape(n_pages, PAGE_SIZE, hkv, dh)[inv]

    with jax.default_matmul_precision("highest"):
        ref = attention_decode(q, k, v, idx)
        ref_p = attention_decode_paged(q, kp, vp, table, idx,
                                       page_size=PAGE_SIZE, max_len=MAX_LEN)
    out = flash_decode_attention(q, k, v, idx,
                                 kv_block=ragged_kv_block(MAX_LEN),
                                 interpret=False)
    out_p = paged_flash_decode_attention(q, kp, vp, table, idx,
                                         interpret=False)

    def err(a, b):
        return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                     - b.astype(jnp.float32))))

    for name, e in (("flash_decode_attention vs attention_decode",
                     err(out, ref)),
                    ("paged_flash_decode_attention vs "
                     "attention_decode_paged", err(out_p, ref_p))):
        print(f"kernel: {name} at {ARCH} widths "
              f"({SLOTS}x{MAX_LEN}, {hq}/{hkv} heads, d_head {dh}): "
              f"max abs err {e} (tol {KERNEL_TOL})")
        check(e <= KERNEL_TOL, f"{name}: max abs err {e} > {KERNEL_TOL}")


def train_phase() -> None:
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import get_config
    from repro.core.endpoints import Category
    from repro.data.pipeline import SyntheticLMData
    from repro.launch.hlo_analysis import analyze
    from repro.launch.mesh import make_mesh
    from repro.launch.steps import make_ddp_train_step
    from repro.models.model import Model
    from repro.optim.adamw import AdamW, cosine_schedule

    cfg = get_config(TRAIN_ARCH)
    model = Model(cfg)
    data = SyntheticLMData(vocab=cfg.vocab, seq_len=TRAIN_SEQ,
                           global_batch=TRAIN_BATCH, seed=SEED)
    batches = [data.batch_at(s) for s in range(TRAIN_STEPS)]

    def run(devices, category):
        mesh = make_mesh((len(devices),), ("data",), devices=devices)
        opt = AdamW(learning_rate=cosine_schedule(3e-4, 1, TRAIN_STEPS))
        step, _ = make_ddp_train_step(model, opt, mesh, category=category)
        replicated = NamedSharding(mesh, P())
        rows = NamedSharding(mesh, P("data"))
        params = jax.device_put(model.init(jax.random.PRNGKey(SEED)),
                                replicated)
        opt_state = jax.device_put(opt.init(params), replicated)

        def put(b):
            return {name: jax.device_put(np.asarray(a), rows)
                    for name, a in b.items()}

        first = put(batches[0])
        compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
            params, opt_state, first, ()).compile()
        all_reduce = analyze(compiled.as_text()).collective_counts.get(
            "all-reduce", 0)
        shard_devices = {s.device for s in first["tokens"].addressable_shards}
        losses = []
        for b in [first] + [put(b) for b in batches[1:]]:
            params, opt_state, metrics, _ = compiled(params, opt_state, b,
                                                     ())
            losses.append(float(metrics["loss"]))
        return losses, shard_devices, all_reduce

    devices = jax.devices()[:TRAIN_CHIPS]
    base, _, _ = run(devices[:1], Category.MPI_EVERYWHERE)
    print(f"train[1 device]: {cfg.name}, batch {TRAIN_BATCH}x{TRAIN_SEQ}, "
          f"losses {base}")
    for name in TRAIN_CATEGORIES:
        losses, devs, all_reduce = run(devices, Category(name))
        diff = max(abs(a - b) / abs(b) for a, b in zip(losses, base))
        print(f"train[{name}]: batch shards on {len(devs)} distinct "
              f"devices, {all_reduce} all-reduces in the compiled step, "
              f"losses {losses}, max relative difference to 1 device "
              f"{diff} (tol {LOSS_RTOL})")
        check(len(devs) == TRAIN_CHIPS,
              f"train[{name}]: batch on {len(devs)} devices, "
              f"not {TRAIN_CHIPS}")
        check(all_reduce >= 1, f"train[{name}]: no all-reduce compiled")
        check(diff <= LOSS_RTOL,
              f"train[{name}]: losses differ from one device by {diff}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: serving and kernel phases; 4: only the "
                         "data-parallel training phase on four chips")
    args = ap.parse_args(argv)
    check((ROOT / "src" / "repro").is_dir(),
          f"no src/repro beside {ROOT / 'chip_smoke.py'}; run it from a "
          f"checkout of the repository")
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    from repro.launch.compile_cache import use_compile_cache

    print(f"compile cache: {use_compile_cache()}")
    device = device_phase(jax, args.chips)
    clock = CompileClock(jax)
    if args.chips == 4:
        train_phase()
    else:
        serve_phase(clock)
        kernel_phase()
    print(f"backend compile total: {clock.seconds} s")
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
