"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  ``--full`` sweeps the whole
scaled Table-I suite (slower); the default subset covers every structural
family.  ``--only`` takes a comma-separated subset of bench names;
``--json PATH`` additionally writes the structured per-bench records
(name, config, median/p50/p99 µs) that ``benchmarks.compare`` gates CI
regressions against.
"""
from __future__ import annotations

import argparse
import json
import sys
import traceback
from pathlib import Path


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument(
        "--only",
        default=None,
        help="comma list: stddev,preprocess,spmv,spmm,combine,memtraffic,"
        "schedule,roofline,solvers,traffic,gnn,gnn_train,obs",
    )
    ap.add_argument(
        "--json",
        default=None,
        metavar="PATH",
        help="write structured per-bench records (median/p50/p99 µs) to PATH",
    )
    ap.add_argument(
        "--trace",
        default=None,
        metavar="PATH",
        help="enable repro.obs for the run and write a Chrome-trace JSON "
        "(load in Perfetto / chrome://tracing) of the benchmark's spans",
    )
    args = ap.parse_args()

    from repro import obs
    from repro.runtime import use_compile_cache

    use_compile_cache(Path(__file__).resolve().parent.parent)

    from . import (
        bench_combine,
        bench_gnn,
        bench_gnn_train,
        bench_memtraffic,
        bench_obs,
        bench_preprocess,
        bench_roofline,
        bench_schedule,
        bench_solvers,
        bench_spmm,
        bench_spmv,
        bench_stddev,
        bench_traffic,
        common,
    )

    benches = {
        "stddev": bench_stddev.main,        # Fig. 6
        "preprocess": bench_preprocess.main,  # Fig. 7
        "spmv": bench_spmv.main,            # Figs. 8/10
        "spmm": bench_spmm.main,            # one-pass kernel grid (beyond-paper)
        "combine": bench_combine.main,      # Fig. 9
        "memtraffic": bench_memtraffic.main,  # Table II
        "schedule": bench_schedule.main,    # §III-C
        "roofline": bench_roofline.main,    # EXPERIMENTS §Roofline
        "solvers": bench_solvers.main,      # workload level (beyond-paper)
        "traffic": bench_traffic.main,      # serving engine (beyond-paper)
        "gnn": bench_gnn.main,              # graph aggregation (beyond-paper)
        "gnn_train": bench_gnn_train.main,  # differentiable fwd+bwd step
        "obs": bench_obs.main,              # instrumentation overhead guard
    }
    if args.only:
        selected = [s.strip() for s in args.only.split(",") if s.strip()]
        unknown = [s for s in selected if s not in benches]
        if unknown:
            ap.error(
                f"unknown bench name(s) {', '.join(unknown)} — "
                f"choose from: {', '.join(benches)}"
            )
    else:
        selected = list(benches)
    if args.trace:
        obs.enable()
    print("name,us_per_call,derived")
    ok = True
    for name in selected:
        try:
            benches[name](full=args.full)
        except Exception:
            ok = False
            print(f"{name},0,ERROR", file=sys.stderr)
            traceback.print_exc()
    if args.trace:
        obs.write_trace(args.trace)
        print(f"wrote Chrome trace to {args.trace}", file=sys.stderr)
    if args.json:
        payload = {
            "schema": 1,
            "full": args.full,
            "selected": selected,
            "benches": common.RESULTS,
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
        print(f"wrote {len(common.RESULTS)} records to {args.json}", file=sys.stderr)
    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    main()
