"""Every cell resolves to its files; a run refuses without the chip or
without the program."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import run
from conftest import HERE, ROOT

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves(cell):
    spec = run.resolve(cell)
    assert spec["driver"].is_file()
    assert spec["traffic"]["traffic"] == spec["cell"]["traffic"]
    names = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    assert spec["per_layer"], "every cell reports a per-layer metric"
    for m in spec["per_layer"]:
        assert (HERE / "metrics" / f"{m['name']}.py").is_file()
        assert m["moves"] in names


def test_contract_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for c in BENCH["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert c["file"].startswith(BENCH["paths"][0] + "/")
    layers = {}
    for m in BENCH["per_layer"]:
        assert set(m["workloads"]) <= set(CELLS)
        layers.setdefault(m["layer"], m["layer"])


def _run_cli(cwd, env_extra):
    env = dict(os.environ, **env_extra)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(proc):
    lines = proc.stdout.strip().splitlines()
    return proc.returncode != 0 and not (lines and lines[-1].startswith("{"))


def test_refuses_without_a_tpu():
    proc = _run_cli(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert _no_result(proc), proc.stdout[-2000:]
    assert "no TPU" in proc.stderr


def test_refuses_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run_cli(tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert _no_result(proc), proc.stdout[-2000:]
    assert "no program" in proc.stderr
