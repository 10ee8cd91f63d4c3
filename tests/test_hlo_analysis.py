"""Loop-aware HLO walker: exact trip-count handling, dot flops, collective
parsing (multi-device case in a subprocess)."""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro.launch.hlo_analysis import analyze


def test_scan_trip_count_exact():
    def scanned(x, ws):
        def body(c, w):
            return (c @ w).astype(jnp.float32), None
        out, _ = jax.lax.scan(body, x, ws)
        return jnp.sum(out)

    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    ws = jax.ShapeDtypeStruct((10, 128, 128), jnp.float32)
    c = analyze(jax.jit(scanned).lower(x, ws).compile().as_text())
    assert c.flops == 2 * 128 ** 3 * 10


def test_nested_scans_multiply():
    def inner(x, ws):
        def body(c, w):
            return (c @ w).astype(jnp.float32), None
        return jax.lax.scan(body, x, ws)[0]

    def outer(x, ws):
        def body(c, _):
            return inner(c, ws), None
        return jnp.sum(jax.lax.scan(body, x, None, length=3)[0])

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    ws = jax.ShapeDtypeStruct((5, 64, 64), jnp.float32)
    c = analyze(jax.jit(outer).lower(x, ws).compile().as_text())
    assert c.flops == 2 * 64 ** 3 * 5 * 3


def test_unrolled_matmuls_counted():
    def f(a, b):
        return a @ b @ b
    a = jax.ShapeDtypeStruct((32, 32), jnp.float32)
    c = analyze(jax.jit(f).lower(a, a).compile().as_text())
    assert c.flops == 2 * 2 * 32 ** 3


def test_xla_cost_analysis_loop_unaware_documented():
    """The reason the walker exists: XLA's own cost_analysis counts scan
    bodies once."""
    def scanned(x, ws):
        def body(c, w):
            return (c @ w).astype(jnp.float32), None
        return jnp.sum(jax.lax.scan(body, x, ws)[0])

    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    ws = jax.ShapeDtypeStruct((10, 128, 128), jnp.float32)
    compiled = jax.jit(scanned).lower(x, ws).compile()
    xla_flops = compiled.cost_analysis()["flops"]
    assert xla_flops < 2 * 128 ** 3 * 10 / 2      # body counted ~once


SUBPROCESS_COLLECTIVES = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_mesh
    from repro.launch.hlo_analysis import analyze

    mesh = make_mesh((8,), ("data",))
    def f(x):
        return jax.lax.psum(x * 2, "data")
    sf = jax.shard_map(f, mesh=mesh, in_specs=P("data"), out_specs=P())
    x = jax.ShapeDtypeStruct((8, 1024), jnp.float32)
    c = analyze(jax.jit(sf).lower(x).compile().as_text())
    assert c.collective_counts.get("all-reduce", 0) >= 1, c.collective_counts
    # per-device shard is (1, 1024) f32 = 4096 bytes
    assert c.collective_bytes["all-reduce"] >= 4096, c.collective_bytes
    # scan-wrapped psum multiplies
    def g(x):
        def body(c_, xi):
            return c_ + jax.lax.psum(xi[0], "data"), None
        out, _ = jax.lax.scan(body, jnp.zeros((1024,)), x)
        return out
    sg = jax.shard_map(g, mesh=mesh, in_specs=P(None, "data"),
                       out_specs=P())
    x2 = jax.ShapeDtypeStruct((6, 8, 1024), jnp.float32)
    c2 = analyze(jax.jit(sg).lower(x2).compile().as_text())
    assert c2.collective_counts.get("all-reduce", 0) >= 6, \\
        c2.collective_counts
    print("OK")
""")


@pytest.mark.slow
def test_collectives_parsed_with_trip_counts():
    res = subprocess.run([sys.executable, "-c", SUBPROCESS_COLLECTIVES],
                         capture_output=True, text=True, cwd=".",
                         env={**os.environ, "JAX_PLATFORMS": "cpu"},
                         timeout=300)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "OK" in res.stdout
