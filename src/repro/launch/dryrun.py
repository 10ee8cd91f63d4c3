import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this proves the sharding config is coherent at production
scale (256-chip single pod, 512-chip 2-pod mesh) and records the per-device
memory analysis, HLO FLOPs/bytes, and the collective schedule for the
roofline (EXPERIMENTS.md §Dry-run / §Roofline).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --all
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-0.5b \
      --shape train_4k --mesh single
"""

import argparse
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, get_config
from repro.launch.hlo_analysis import analyze
from repro.launch.mesh import make_production_mesh
from repro.launch.shapes import (SHAPES, batch_specs, cache_specs,
                                 cell_applicable, decode_token_specs)
from repro.launch.sharding import (RULE_PRESETS, make_shard_fn,
                                   shard_struct)
from repro.launch.steps import (make_decode_step, make_prefill_step,
                                make_train_step)
from repro.models.model import Model
from repro.optim.adamw import AdamW

FSDP_THRESHOLD = 5e9      # params above this use fsdp_tp rules
ACT_RESIDUAL_TARGET = 4 * 2 ** 30   # aim <= ~4 GiB of layer-input residuals


def auto_accum(cfg, cell, mesh, rules=None) -> int:
    """Gradient-accumulation factor: bound per-device activation residuals
    (n_layers x B_dev x S x d_model bf16) to ~4 GiB.  Sequence-parallel
    rule sets already divide residuals by the model-axis size."""
    if cell.kind != "train":
        return 1
    from repro.launch.mesh import data_axes, mesh_axis_size
    from repro.models.transformer import _remat_group
    dp = mesh_axis_size(mesh, data_axes(mesh))
    bdev = max(1, cell.batch // dp)
    n_layers = cfg.n_layers + cfg.n_enc_layers    # enc-dec counts both
    g = _remat_group(n_layers)
    eff_layers = n_layers // g + g if g > 1 else n_layers
    if cfg.is_encdec:
        eff_layers *= 3       # cross-attention K/V + encoder memory
    # x2: XLA CPU keeps an fp32 copy of the saved bf16 stack (hoisted
    # convert); budget for it
    resid = 2 * eff_layers * bdev * cell.seq * cfg.d_model * 2
    if cfg.moe is not None:
        resid *= 2     # dispatch/combine intermediates scale with tokens
    if rules and rules.get("seq"):
        resid /= mesh.shape.get("model", 1)
    accum = 1
    while (resid / accum > ACT_RESIDUAL_TARGET and accum * 2 <= bdev
           and bdev % (accum * 2) == 0):
        accum *= 2
    return accum


def rules_for(model: Model, preset: str = "auto"):
    if preset == "auto":
        preset = "fsdp_tp" if model.n_params() > FSDP_THRESHOLD else "tp"
    return RULE_PRESETS[preset](), preset


def _opt_specs(model: Model, mesh, rules, params_sds, preset: str = "",
               master_fp32: bool = False):
    opt = AdamW(master_fp32=master_fp32)
    abstract = jax.eval_shape(opt.init, params_sds)
    axes = model.param_axes()
    opt_rules = rules
    if preset == "tp_zero1" or master_fp32:
        # ZeRO-1: moments (and fp32 master) sharded over data even though
        # the live params are not
        from repro.launch.sharding import fsdp_tp_rules
        opt_rules = fsdp_tp_rules()
    out = {"mu": shard_struct(opt_rules, mesh, abstract["mu"], axes),
           "nu": shard_struct(opt_rules, mesh, abstract["nu"], axes),
           "count": jax.ShapeDtypeStruct(
               (), jnp.int32, sharding=jax.sharding.NamedSharding(
                   mesh, jax.sharding.PartitionSpec()))}
    if master_fp32:
        out["master"] = shard_struct(opt_rules, mesh, abstract["master"],
                                     axes)
    return opt, out


def lower_cell(arch: str, shape_name: str, mesh, rules_preset: str = "auto",
               accum_override: int = 0, cast_params_once: bool = False,
               params_bf16: bool = False):
    """-> (lowered, compiled, record) for one cell."""
    cfg = get_config(arch)
    cell = SHAPES[shape_name]
    model = Model(cfg)
    rules, preset = rules_for(model, rules_preset)
    shard_fn = make_shard_fn(rules, mesh)
    params_sds = shard_struct(rules, mesh, model.abstract_params(),
                              model.param_axes())
    if params_bf16:
        # mixed precision: live params bf16, fp32 master in opt state
        params_sds = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16,
                                           sharding=s.sharding)
            if s.dtype == jnp.float32 and len(s.shape) >= 2 else s,
            params_sds)

    accum = accum_override or auto_accum(cfg, cell, mesh, rules)
    with jax.set_mesh(mesh):
        if cell.kind == "train":
            opt, opt_sds = _opt_specs(model, mesh, rules, params_sds,
                                      preset, master_fp32=params_bf16)
            step = make_train_step(model, opt, shard_fn=shard_fn,
                                   accum_steps=accum,
                                   cast_params_once=cast_params_once)
            args = (params_sds, opt_sds,
                    batch_specs(cfg, cell, mesh, rules))
            jitted = jax.jit(step, donate_argnums=(0, 1))
        elif cell.kind == "prefill":
            step = make_prefill_step(model, shard_fn=shard_fn)
            args = (params_sds, batch_specs(cfg, cell, mesh, rules),
                    cache_specs(model, cell, mesh))
            jitted = jax.jit(step, donate_argnums=(2,))
        else:  # decode
            step = make_decode_step(model, shard_fn=shard_fn)
            tok = decode_token_specs(cfg, cell, mesh)
            args = (params_sds, cache_specs(model, cell, mesh),
                    next(iter(tok.values())))
            jitted = jax.jit(step, donate_argnums=(1,))

        t0 = time.time()
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = analyze(compiled.as_text())
    n_chips = mesh.devices.size
    record = {
        "arch": arch, "shape": shape_name, "kind": cell.kind,
        "mesh": dict(zip(mesh.axis_names,
                         [mesh.shape[a] for a in mesh.axis_names])),
        "n_chips": n_chips,
        "rules": preset,
        "accum_steps": accum,
        "n_params": model.n_params(),
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_live_bytes": (mem.argument_size_in_bytes
                                + mem.output_size_in_bytes
                                + mem.temp_size_in_bytes
                                - mem.alias_size_in_bytes),
        },
        # loop-aware walker (trip counts multiplied through while bodies)
        "cost": {"flops_per_device": hlo.flops,
                 "bytes_per_device": hlo.bytes_accessed},
        # XLA's own cost_analysis, which counts loop bodies ONCE — kept for
        # reference / cross-check only
        "cost_xla_loop_unaware": {
            "flops_per_device": cost.get("flops", 0.0),
            "bytes_per_device": cost.get("bytes accessed", 0.0)},
        "collectives": {
            "counts": hlo.collective_counts,
            "bytes": hlo.collective_bytes,
            "total_bytes": hlo.collective_total_bytes,
            "total_count": hlo.collective_total_count},
    }
    return lowered, compiled, record


def run_cells(archs, shapes, meshes, out_dir: str,
              rules_preset: str = "auto", verbose: bool = True):
    os.makedirs(out_dir, exist_ok=True)
    results = []
    for mesh_name in meshes:
        mesh = make_production_mesh(multi_pod=(mesh_name == "multi"))
        for arch in archs:
            cfg = get_config(arch)
            for shape_name in shapes:
                cell = SHAPES[shape_name]
                ok, reason = cell_applicable(cfg, cell)
                tag = f"{arch}|{shape_name}|{mesh_name}"
                out_path = os.path.join(
                    out_dir, f"{arch}_{shape_name}_{mesh_name}.json")
                if not ok:
                    rec = {"arch": arch, "shape": shape_name,
                           "mesh_name": mesh_name, "status": "skipped",
                           "reason": reason}
                    json.dump(rec, open(out_path, "w"), indent=1)
                    results.append(rec)
                    if verbose:
                        print(f"[skip] {tag}: {reason}", flush=True)
                    continue
                try:
                    _, compiled, rec = lower_cell(arch, shape_name, mesh,
                                                  rules_preset)
                    rec["status"] = "ok"
                    rec["mesh_name"] = mesh_name
                    if verbose:
                        m = rec["memory"]
                        print(f"[ ok ] {tag}: compile={rec['compile_s']}s "
                              f"args={m['argument_bytes']/2**30:.2f}GiB "
                              f"temp={m['temp_bytes']/2**30:.2f}GiB "
                              f"flops/dev={rec['cost']['flops_per_device']:.3e} "
                              f"coll={rec['collectives']['total_count']}ops/"
                              f"{rec['collectives']['total_bytes']/2**20:.1f}MiB",
                              flush=True)
                    del compiled
                except Exception as e:      # noqa: BLE001 — record and move on
                    rec = {"arch": arch, "shape": shape_name,
                           "mesh_name": mesh_name, "status": "failed",
                           "error": f"{type(e).__name__}: {e}",
                           "traceback": traceback.format_exc()[-4000:]}
                    if verbose:
                        print(f"[FAIL] {tag}: {rec['error']}", flush=True)
                json.dump(rec, open(out_path, "w"), indent=1)
                results.append(rec)
    summary = {
        "total": len(results),
        "ok": sum(r.get("status") == "ok" for r in results),
        "skipped": sum(r.get("status") == "skipped" for r in results),
        "failed": sum(r.get("status") == "failed" for r in results),
    }
    json.dump({"summary": summary, "cells": results},
              open(os.path.join(out_dir, "summary.json"), "w"), indent=1)
    print("SUMMARY:", summary, flush=True)
    return results


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=list(ARCHS))
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--rules", default="auto",
                    choices=["auto", "tp", "fsdp_tp"])
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args(argv)

    archs = list(ARCHS) if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) \
        else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    results = run_cells(archs, shapes, meshes, args.out, args.rules)
    if any(r.get("status") == "failed" for r in results):
        sys.exit(1)


if __name__ == "__main__":
    main()
