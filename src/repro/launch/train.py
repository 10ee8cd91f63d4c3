"""Training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m \
      --smoke --steps 100 --batch 8 --seq 256 --mode ddp \
      --endpoint 2x_dynamic

``--mode ddp`` runs the shard_map data-parallel step whose gradient sync is
scheduled by the scalable-endpoints engine (--endpoint picks the category);
``--mode jit`` runs the auto-SPMD step used by the dry-run.  On this CPU
container use --smoke configs; full configs are exercised via
``repro.launch.dryrun``.
"""

from __future__ import annotations

import argparse

import jax

from repro.configs import ARCHS, get_config, get_smoke_config
from repro.core.endpoints import Category
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_mesh
from repro.train.loop import TrainConfig, Trainer


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m", choices=list(ARCHS))
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--mode", default="jit", choices=["jit", "ddp"])
    ap.add_argument("--endpoint", default="2x_dynamic",
                    choices=[c.value for c in Category])
    ap.add_argument("--ckpt-dir", default="checkpoints")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--metrics", default="metrics.jsonl")
    args = ap.parse_args(argv)

    use_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    mesh = None
    if args.mode == "ddp":
        n = len(jax.devices())
        mesh = make_mesh((n,), ("data",))
    tc = TrainConfig(
        seq_len=args.seq, global_batch=args.batch, n_steps=args.steps,
        peak_lr=args.lr, checkpoint_dir=args.ckpt_dir,
        checkpoint_every=args.ckpt_every, mode=args.mode,
        endpoint_category=Category(args.endpoint), mesh=mesh)
    trainer = Trainer(cfg, tc)
    logs = trainer.train()
    trainer.save_metrics(args.metrics)
    print(f"final: {logs[-1]}")


if __name__ == "__main__":
    main()
