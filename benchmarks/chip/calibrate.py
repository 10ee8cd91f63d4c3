"""Readings for the limits and the rate of a serving cell, in one process.

  python3 benchmarks/chip/calibrate.py --workload <cell> --seeds 12 \\
      --seconds 20 [--rates 4,5,6] [--control] [--out <file.json>]

For each rate (default: the cell's own) and each of ``--seeds`` seeds it
drives one run of the cell's driver, as ``run.py`` does, and records the
end-to-end numbers, the backlog at the close, the correctness numbers
and, with ``--control``, the same check with the control (the reference
at float8) in the program's place.  The lower reading of a limit is the
largest a sound program gives over the seeds, the upper the smallest the
control gives; the knee is the highest rate whose backlog stays flat.
The benchmark's own runs never run this.
"""

import argparse
import json
import sys
import time

import run


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=7_000_000_001)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", default="")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--out", default="")
    ap.add_argument("--trace-sample", default="",
                    help="also make one traced run with a short trace and "
                         "copy the raw trace to this path")
    args = ap.parse_args(argv)
    spec = run.resolve(args.workload)
    sys.path.insert(0, str(run.ROOT / "src"))
    sys.path.insert(0, str(run.HERE))
    import jax

    import opcount

    run.use_checkout_cache(jax)
    devices = run.chips(jax, int(spec["cell"]["chips"]))
    peaks = opcount.peaks(devices[0].device_kind)
    driver = run.load_module(spec["driver"])
    rates = [float(x) for x in args.rates.split(",") if x] or [None]
    rows = []
    for rate in rates:
        traffic = dict(spec["traffic"])
        if rate is not None:
            traffic["arrivals"] = {**traffic["arrivals"], "rate_per_s": rate}
        for i in range(args.seeds):
            seed = args.first_seed + 1000 * len(rows)
            out = driver.run(run.Run(
                cell=spec["cell"], config=spec["config"], traffic=traffic,
                seed=seed, seconds=args.seconds, trace=False,
                t_start=time.perf_counter(), devices=devices, peaks=peaks,
                control=args.control))
            row = {"rate": traffic["arrivals"]["rate_per_s"], "seed": seed,
                   "correct": out.correct, "end_to_end": out.end_to_end,
                   "compared": out.compared,
                   "control_gaps": out.readings.get("control_gaps"),
                   "queued_at_close": out.readings["queued_at_close"],
                   "active_at_close": out.readings["active_at_close"],
                   "queue_wait_p95_ms": out.readings["queue_wait_p95_ms"],
                   "kv_filled": out.readings["kv_filled"],
                   "memory_peak_bytes": out.memory_peak_bytes,
                   "notes": out.notes}
            rows.append(row)
            print(json.dumps({k: v for k, v in row.items() if k != "notes"}),
                  flush=True)
    if args.trace_sample:
        traffic = {**spec["traffic"], "trace_seconds": 0.25}
        out = driver.run(run.Run(
            cell=spec["cell"], config=spec["config"], traffic=traffic,
            seed=args.first_seed - 1, seconds=5.0, trace=True,
            t_start=time.perf_counter(), devices=devices, peaks=peaks,
            keep_trace=args.trace_sample))
        print(json.dumps(out.readings["trace"]), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
