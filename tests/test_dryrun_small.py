"""Dry-run machinery end-to-end on a reduced host-device mesh (subprocess
sets XLA_FLAGS before importing jax; the production 512-device sweep runs
via `python -m repro.launch.dryrun --all`)."""

import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    import sys
    sys.path.insert(0, "src")
    import json, tempfile
    from repro.launch.dryrun import lower_cell, run_cells
    from repro.launch.mesh import make_production_mesh

    # one real cell on the single-pod mesh
    mesh = make_production_mesh()
    _, compiled, rec = lower_cell("qwen2-0.5b", "decode_32k", mesh)
    assert rec["cost"]["flops_per_device"] > 0
    assert rec["memory"]["argument_bytes"] > 0
    assert rec["collectives"]["total_count"] > 0
    assert rec["n_chips"] == 256

    # multi-pod mesh proves the pod axis shards
    mesh2 = make_production_mesh(multi_pod=True)
    _, compiled2, rec2 = lower_cell("qwen2-0.5b", "decode_32k", mesh2)
    assert rec2["n_chips"] == 512
    # per-device argument bytes shrink when the batch also shards over pod
    assert rec2["memory"]["argument_bytes"] <= rec["memory"]["argument_bytes"]

    # skip logic
    with tempfile.TemporaryDirectory() as d:
        res = run_cells(["smollm-360m"], ["long_500k"], ["single"], d)
        assert res[0]["status"] == "skipped"
    print("OK")
""")


@pytest.mark.slow
def test_dryrun_cell_and_multipod():
    res = subprocess.run([sys.executable, "-c", SCRIPT],
                         capture_output=True, text=True, cwd=".",
                         env={**os.environ, "JAX_PLATFORMS": "cpu"},
                         timeout=560)
    assert res.returncode == 0, res.stdout[-2000:] + res.stderr[-4000:]
    assert "OK" in res.stdout
