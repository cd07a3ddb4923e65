#!/usr/bin/env python3
"""Find a serving cell's knee: its window at several fixed rates, one set-up.

    python3 chipbench/sweep.py --workload kron16.serve_poisson \
        --rates 40,55,70 --seconds 10 --seed 5

For each rate (requests per second) one open-loop window, and one JSON
line: the latency median and 95th percentile, the 95th percentile of the
first and of the second half of the requests, the rate at which answers
came back, how late the generator ran and the mean batch width.  Above the
knee the queue grows all through the window: answers come back slower than
requests are sent, and the second half waits longer than the first.  The knee, once
found, is written into the traffic file as a number; the benchmark's own
runs never run this.
"""
import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from chipbench import harness  # noqa: E402


def sweep(bench: dict, cell: str, rates, seconds: float, seed: int, *,
          root=harness.HERE, out=print) -> None:
    """One window of ``cell`` at each rate in ``rates``, after one set-up."""
    run = harness.prepare(bench, cell, seed, root)
    drv = harness.driver(run.traffic["driver"], root)
    harness.make_matrix(run)
    drv.setup(run, seconds)
    for rate in rates:
        run.traffic["arrivals"]["rate_per_s"] = rate
        drv.schedule(run, seconds)
        t0 = time.perf_counter()
        w = drv.window(run, seconds)
        lat, due = w["latency_s"], run.state["due"]
        half = lat.size // 2
        out(json.dumps({
            "rate_per_s": rate, "requests": int(lat.size), "failed": w["failed"],
            "completed_per_s": float(lat.size / np.max(due + lat)),
            "latency_p50_ms": float(np.percentile(lat, 50)) * 1e3,
            "latency_p95_ms": w["latency_p95_ms"],
            "p95_first_half_ms": float(np.percentile(lat[:half], 95)) * 1e3,
            "p95_second_half_ms": float(np.percentile(lat[half:], 95)) * 1e3,
            "gen_lag_p95_ms": w["gen_lag_p95_ms"], "batch_k_mean": w["batch_k_mean"],
            "wall_s": time.perf_counter() - t0,
        }))


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated, requests/s")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    bench = json.loads(harness.BENCHMARK.read_text())
    sys.path.insert(0, str(harness.CHECKOUT / "src"))
    harness.use_compile_cache()
    cell = {c["name"]: c for c in bench["workloads"]}[args.workload]
    why = harness.chips_ok(cell)
    if why is not None:
        print(f"chipbench: {why}", file=sys.stderr)
        return 1
    sweep(bench, args.workload, [float(r) for r in args.rates.split(",")],
          args.seconds, args.seed, out=lambda line: print(line, flush=True))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
