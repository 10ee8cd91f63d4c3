"""The paper's 5-point stencil (Section VII) in the TPU domain: a real
shard_map halo exchange over a device mesh, with the halo traffic scheduled
per scalable-endpoint category and costed by the alpha-beta ICI model.

This script re-execs itself with 8 forced host devices (safe: examples run
as their own process).

  PYTHONPATH=src python examples/stencil_endpoints.py
"""

import os

if "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax                                    # noqa: E402
import jax.numpy as jnp                       # noqa: E402
from jax.sharding import PartitionSpec as P   # noqa: E402

from repro.comm.costs import estimate_sync_time     # noqa: E402
from repro.core.channels import plan_for            # noqa: E402
from repro.core.endpoints import Category           # noqa: E402
from repro.launch.mesh import make_mesh             # noqa: E402

GRID = 512
STEPS = 5


def main():
    n = len(jax.devices())
    mesh = make_mesh((n,), ("ranks",))

    def stencil_step(tile):
        # tile: (rows/n, cols) per rank; halo via collective_permute —
        # exactly the per-rank neighbor messages of the paper's Fig. 13
        up = jax.lax.ppermute(tile[-1:], "ranks",
                              [(i, (i + 1) % n) for i in range(n)])
        down = jax.lax.ppermute(tile[:1], "ranks",
                                [(i, (i - 1) % n) for i in range(n)])
        padded = jnp.concatenate([up, tile, down], axis=0)
        lap = (padded[:-2] + padded[2:]
               + jnp.roll(tile, 1, 1) + jnp.roll(tile, -1, 1) - 4 * tile)
        return tile + 0.1 * lap

    @jax.jit
    def run(grid):
        def body(g, _):
            return stencil_step(g), None
        out, _ = jax.lax.scan(body, grid, None, length=STEPS)
        return out

    sharded = jax.shard_map(run, mesh=mesh, in_specs=P("ranks"),
                            out_specs=P("ranks"))
    grid = jax.random.normal(jax.random.PRNGKey(0), (GRID, GRID))
    out = jax.jit(sharded)(grid)
    print(f"stencil on {n} ranks, grid {GRID}^2, {STEPS} steps: "
          f"sum={float(jnp.sum(out)):.3f}")
    hlo = jax.jit(sharded).lower(grid).compile().as_text()
    import re
    n_perm = len(re.findall(r"= \S+ collective-permute", hlo))
    print(f"collective-permutes in HLO: {n_perm} "
          f"(2 per step = the paper's 2 halo messages per rank)")

    # endpoint-category cost of the halo exchange per step
    halo_bytes = GRID * 4 * 2               # two rows
    print("\nhalo-exchange scheduling per endpoint category "
          "(alpha-beta ICI model):")
    for cat in Category:
        plan = plan_for(cat, lanes=n)
        cost = estimate_sync_time([halo_bytes] * n, plan, axis_size=n)
        print(f"  {cat.value:16s} est={cost.seconds * 1e6:8.2f}us  "
              f"channels={plan.n_buckets(n)}")


if __name__ == "__main__":
    main()
