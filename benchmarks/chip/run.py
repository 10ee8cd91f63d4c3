"""Run one cell of the chip benchmark once.

  python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
      --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in the root ``BENCHMARK.json``.  It
resolves to ``configs/<config>.json`` (the model's sizes),
``traffic/<cell>.json`` (the deployment, the traffic mix, the limits of
the correctness check), the driver that traffic file names
(``drivers/<driver>.py``), and one reader per per-layer metric
(``metrics/<metric>.py``).  Adding a cell, a mix, a driver or a metric
adds files and entries; nothing here names one.

With ``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of part of
the window.  The run needs the TPU chips the cell asks for: with fewer,
or none, it exits non-zero and prints no result.  The last line of
standard output is the result, one JSON object; the numbers the
correctness check compared, each beside its limit, are also the last
lines of standard error.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]


class Refused(SystemExit):
    """The run cannot produce a result; exits non-zero."""

    def __init__(self, why: str):
        super().__init__(f"run.py: {why}")


@dataclasses.dataclass
class Run:
    """What a driver is given."""

    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    t_start: float
    devices: list
    peaks: dict
    control: bool = False      # also read the control (calibrate.py)
    keep_trace: str = ""       # copy the raw trace here (calibrate.py)


def load_module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def resolve(cell_name: str, root: pathlib.Path = ROOT) -> dict:
    """The cell's entry, configuration, traffic file, driver and metric
    readers; refuses a cell that does not resolve."""
    bench_path = root / "BENCHMARK.json"
    if not bench_path.is_file():
        raise Refused(f"no {bench_path}")
    bench = json.loads(bench_path.read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if cell_name not in cells:
        raise Refused(f"no cell {cell_name!r}; cells: {sorted(cells)}")
    cell = cells[cell_name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[cell["config"]]["file"]).read_text())
    traffic = json.loads((HERE / "traffic" / f"{cell_name}.json")
                         .read_text())
    if traffic["traffic"] != cell["traffic"]:
        raise Refused(f"traffic/{cell_name}.json is mix "
                      f"{traffic['traffic']!r}, the cell names "
                      f"{cell['traffic']!r}")
    driver = HERE / "drivers" / f"{traffic['driver']}.py"
    if not driver.is_file():
        raise Refused(f"no driver {driver}")

    def mine(metric):
        return cell_name in metric.get("workloads", [cell_name])

    end_to_end = [m for m in bench["end_to_end"] if mine(m)]
    per_layer = [m for m in bench["per_layer"] if mine(m)]
    for m in per_layer:
        if not (HERE / "metrics" / f"{m['name']}.py").is_file():
            raise Refused(f"no reader metrics/{m['name']}.py")
    return {"cell": cell, "config": config, "traffic": traffic,
            "driver": driver, "end_to_end": end_to_end,
            "per_layer": per_layer}


def use_checkout_cache(jax) -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, so that only the first run of a cell there compiles; every
    program is kept, however quickly it compiled, and none is evicted (a
    size limit set in the environment turns on JAX's eviction, whose
    bookkeeping failed every write on the chip's host)."""
    path = str(ROOT / ".jax_cache")
    os.makedirs(path, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def chips(jax, n: int) -> list:
    """The first ``n`` TPU chips, or refusal."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise Refused(f"JAX found no TPU (platform "
                      f"{devices[0].platform!r}); the benchmark measures "
                      f"the chip and has no fallback")
    if len(devices) < n:
        raise Refused(f"the cell needs {n} chips, JAX found {len(devices)}")
    return devices[:n]


def per_layer_metrics(spec: dict, readings: dict, peaks: dict) -> dict:
    """Each per-layer metric its reader finds something to read for."""
    out = {}
    for m in spec["per_layer"]:
        reader = load_module(HERE / "metrics" / f"{m['name']}.py")
        value = reader.read(readings, spec["config"], peaks)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv=None) -> dict:
    """One run; -> the result printed as the last line."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = resolve(args.workload)
    if not (ROOT / "src" / "repro").is_dir():
        raise Refused(f"no program under {ROOT / 'src' / 'repro'}")
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(HERE))
    import jax

    import opcount

    cache = use_checkout_cache(jax)
    devices = chips(jax, int(spec["cell"]["chips"]))
    kind = devices[0].device_kind
    peaks = opcount.peaks(kind)
    driver = load_module(spec["driver"])
    outcome = driver.run(Run(
        cell=spec["cell"], config=spec["config"], traffic=spec["traffic"],
        seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        t_start=T_START, devices=devices, peaks=peaks))

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": outcome.memory_peak_bytes}
    result = {"correct": outcome.correct, "attempted": outcome.attempted,
              "failed": outcome.failed}
    if args.trace:
        tr = outcome.readings["trace"]
        device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        result["metrics"] = per_layer_metrics(spec, outcome.readings, peaks)
        result["device"] = device
        result["breakdown"] = {"device_ops": tr["device_ops"],
                               "idle_gaps": tr["idle_gaps"]}
    else:
        missing = [m["name"] for m in spec["end_to_end"]
                   if m["name"] not in outcome.end_to_end]
        if missing:
            outcome.correct = result["correct"] = False
            outcome.notes.append(f"end-to-end metrics not measured: "
                                 f"{missing}")
        result["metrics"] = {
            m["name"]: {"value": outcome.end_to_end[m["name"]],
                        "unit": m["unit"]}
            for m in spec["end_to_end"] if m["name"] in outcome.end_to_end}
        result["device"] = device
    result["compared"] = {
        name: {"value": value if math.isfinite(value) else None,
               "limit": limit}
        for name, (value, limit) in outcome.compared.items()}
    print(f"compile cache: {cache}")
    for line in outcome.notes:
        print(line)
    if args.trace:
        print(f"trace: {json.dumps(outcome.readings['trace'])}")
    for name, (value, limit) in outcome.compared.items():
        print(f"{name} {value} limit {limit}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return result


if __name__ == "__main__":
    main()
