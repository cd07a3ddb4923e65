"""Render EXPERIMENTS.md §Roofline/§Dry-run tables from the sweep JSONs.

    PYTHONPATH=src python -m repro.analysis.report [--dir experiments/dryrun]

``--obs PATH`` switches modes: re-render the text dashboard from a
``repro.obs.dump()`` snapshot instead (counters, histograms, span
aggregates, amortized-preprocess ledger)::

    PYTHONPATH=src python -m repro.analysis.report --obs obs.json

``--requests PATH`` renders the slowest-N request waterfall from a
snapshot's request log: per-request queue wait, dispatch and time in
flight, and the queue-wait vs compute-share decomposition, trace ids
included so rows join to flight dumps and histogram exemplars (``--top``
bounds N)::

    PYTHONPATH=src python -m repro.analysis.report --requests obs.json --top 10

``--explain MATRIX`` renders the per-matrix explain report — partition
quality (tile occupancy, competitive ratio, hash-group cohesion),
autotune decision provenance, the served requests' dispatch / in-flight
split and the imbalance verdict — from the ``--obs`` snapshot (default
``serve_obs.json``, the artifact ``examples/serve_spmv.py`` leaves)::

    PYTHONPATH=src python -m repro.analysis.report --explain circuit --obs serve_obs.json

``--diff A B`` compares two obs dumps (or two ``benchmarks.run --json``
artifacts) and prints the ranked culprit table
(:mod:`repro.analysis.diff`)::

    PYTHONPATH=src python -m repro.analysis.report --diff before.json after.json
"""
from __future__ import annotations

import argparse
import json
from pathlib import Path

ARCH_ORDER = [
    "llava-next-34b", "olmo-1b", "mistral-nemo-12b", "internlm2-20b",
    "nemotron-4-340b", "granite-moe-1b-a400m", "deepseek-v2-lite-16b",
    "mamba2-370m", "jamba-1.5-large-398b", "seamless-m4t-large-v2",
]
SHAPE_ORDER = ["train_4k", "prefill_32k", "decode_32k", "long_500k"]


def load(dirpath: Path):
    recs = {}
    for p in dirpath.glob("*.json"):
        r = json.loads(p.read_text())
        recs[(r["arch"], r["shape"], r["mesh"])] = r
    return recs


def fmt_s(x: float) -> str:
    if x >= 1:
        return f"{x:.2f}s"
    return f"{x*1e3:.1f}ms"


def roofline_table(recs) -> str:
    lines = [
        "| arch | shape | compute | memory | collective | bound | t_bound | useful/HLO | fits |",
        "|---|---|---|---|---|---|---|---|---|",
    ]
    for arch in ARCH_ORDER:
        for shape in SHAPE_ORDER:
            r = recs.get((arch, shape, "single"))
            if r is None:
                lines.append(f"| {arch} | {shape} | — | — | — | — | — | — | MISSING |")
                continue
            if r["status"] == "skipped":
                lines.append(f"| {arch} | {shape} | — | — | — | — | — | — | skip (full-attn @500k) |")
                continue
            if r["status"] != "ok" or "roofline" not in r:
                lines.append(f"| {arch} | {shape} | — | — | — | — | — | — | {r['status']} |")
                continue
            rf = r["roofline"]
            tb = max(rf["t_compute_s"], rf["t_memory_s"], rf["t_collective_s"])
            ratio = r.get("model_flops_ratio") or 0
            lines.append(
                f"| {arch} | {shape} | {fmt_s(rf['t_compute_s'])} | "
                f"{fmt_s(rf['t_memory_s'])} | {fmt_s(rf['t_collective_s'])} | "
                f"{rf['bottleneck']} | {fmt_s(tb)} | {ratio:.2f} | "
                f"{'✓' if r['fits_hbm'] else 'OVER'} |"
            )
    return "\n".join(lines)


def dryrun_table(recs) -> str:
    lines = [
        "| arch | shape | mesh | status | peak GiB | fits | compile s | n_micro |",
        "|---|---|---|---|---|---|---|---|",
    ]
    for arch in ARCH_ORDER:
        for shape in SHAPE_ORDER:
            for mesh in ("single", "multipod"):
                r = recs.get((arch, shape, mesh))
                if r is None:
                    lines.append(f"| {arch} | {shape} | {mesh} | MISSING | | | | |")
                    continue
                if r["status"] != "ok":
                    lines.append(
                        f"| {arch} | {shape} | {mesh} | {r['status']} | | | | |"
                    )
                    continue
                m = r["memory"]
                lines.append(
                    f"| {arch} | {shape} | {mesh} | ok | "
                    f"{m['peak_estimate_bytes']/2**30:.2f} | "
                    f"{'✓' if r['fits_hbm'] else 'OVER'} | {r['compile_s']} | "
                    f"{r.get('n_microbatch', '')} |"
                )
    return "\n".join(lines)


def fleet_stats(recs) -> str:
    ok = [r for r in recs.values() if r["status"] == "ok"]
    skipped = [r for r in recs.values() if r["status"] == "skipped"]
    err = [r for r in recs.values() if r["status"] == "error"]
    fits = [r for r in ok if r.get("fits_hbm")]
    return (
        f"cells: {len(recs)} recorded — {len(ok)} compiled ok "
        f"({len(fits)} fit in 16 GB/chip), {len(skipped)} skipped per "
        f"assignment sheet, {len(err)} errors"
    )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default="experiments/dryrun")
    ap.add_argument(
        "--obs",
        default=None,
        metavar="PATH",
        help="render the dashboard from a repro.obs.dump() snapshot instead",
    )
    ap.add_argument(
        "--requests",
        default=None,
        metavar="PATH",
        help="render the slowest-N request waterfall (queue wait, dispatch, "
        "in flight, compute share, trace ids) from a repro.obs.dump() snapshot",
    )
    ap.add_argument(
        "--top",
        type=int,
        default=20,
        help="how many requests the --requests waterfall shows (default 20)",
    )
    ap.add_argument(
        "--explain",
        default=None,
        metavar="MATRIX",
        help="render the per-matrix explain report (partition quality, "
        "autotune provenance, dispatch / in-flight split, imbalance "
        "verdict) from the --obs snapshot (default serve_obs.json)",
    )
    ap.add_argument(
        "--diff",
        nargs=2,
        default=None,
        metavar=("A", "B"),
        help="differential comparison of two obs dumps or two "
        "benchmarks.run --json artifacts (ranked culprit table)",
    )
    args = ap.parse_args()
    if args.diff:
        from repro.analysis.diff import diff_artifacts, load_artifact, render_text

        a, b = args.diff
        print(
            render_text(diff_artifacts(load_artifact(a), load_artifact(b)), top=args.top),
            end="",
        )
        return
    if args.explain:
        from repro.obs.planview import explain_report

        snapshot = json.loads(Path(args.obs or "serve_obs.json").read_text())
        print(explain_report(snapshot, args.explain), end="")
        return
    if args.requests:
        from repro.obs.requesttrace import waterfall

        snapshot = json.loads(Path(args.requests).read_text())
        print(waterfall(snapshot, n=args.top))
        return
    if args.obs:
        from repro.obs.report import render

        print(render(json.loads(Path(args.obs).read_text())))
        return
    recs = load(Path(args.dir))
    print("## §Roofline (single-pod 16×16, per-device per-step seconds)\n")
    print(roofline_table(recs))
    print("\n## §Dry-run matrix\n")
    print(fleet_stats(recs) + "\n")
    print(dryrun_table(recs))


if __name__ == "__main__":
    main()
