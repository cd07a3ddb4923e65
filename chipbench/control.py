#!/usr/bin/env python3
"""Readings for a cell's correctness limit: the program's and the control's.

    python3 chipbench/control.py --workload kron16.serve_poisson \
        --seeds 11,12,13 --seconds 5

In one process, for each seed: the cell's set-up and a short window at the
cell's own load, the check's numbers of what the program produced, and the
same check with the control's answer in the program's place (the driver's
``CONTROL``): the reference with its values and vectors in bfloat16, the
step below the float32 the configurations state.
The limit lies between the largest program reading (over a dozen seeds or
more) and the smallest control reading.  One JSON line per seed, then a
summary line.  The benchmark's own runs never run this.
"""
import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from chipbench import harness  # noqa: E402


def readings(bench: dict, cell: str, seeds, seconds: float, *, root=harness.HERE,
             compiles=None, out=print) -> dict:
    """Program and control readings of ``cell`` on each seed."""
    compiles = compiles or harness.Compiles()
    program, control, control_correct = [], [], []
    for seed in seeds:
        run = harness.prepare(bench, cell, seed, root)
        res = harness.execute(bench, run, seconds=seconds, trace=False,
                              t_start=time.perf_counter(), compiles=compiles, root=root)
        drv = harness.driver(run.traffic["driver"], root)
        compared = drv.check(run, answer=drv.CONTROL)
        ctrl = {n: v for n, v, _ in compared}
        program.append(res["limits"]["max_rel_err"]["value"])
        control.append(ctrl["max_rel_err"])
        control_correct.append(all(v <= lim for _, v, lim in compared))
        out(json.dumps({"seed": seed, "program": res["limits"], "control": ctrl,
                        "control_correct": control_correct[-1],
                        "correct": res["correct"], "attempted": res["attempted"],
                        "metrics": res["metrics"]}))
        del run, res
        gc.collect()
    summary = {"workload": cell, "seeds": len(program), "lower": max(program),
               "upper": min(control), "control_ever_correct": any(control_correct)}
    out(json.dumps(summary))
    return summary


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    bench = json.loads(harness.BENCHMARK.read_text())
    sys.path.insert(0, str(harness.CHECKOUT / "src"))
    harness.use_compile_cache()
    compiles = harness.Compiles().listen()
    cell = {c["name"]: c for c in bench["workloads"]}[args.workload]
    why = harness.chips_ok(cell)
    if why is not None:
        print(f"chipbench: {why}", file=sys.stderr)
        return 1
    readings(bench, args.workload, [int(s) for s in args.seeds.split(",")],
             args.seconds, compiles=compiles, out=lambda s: print(s, flush=True))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
