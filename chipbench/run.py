#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 chipbench/run.py --workload kron16.serve_poisson --seed 7 \
        --seconds 20 --trace 0

The cell is an entry of ``BENCHMARK.json``'s ``workloads``; its
configuration, traffic mix and per-layer metrics are the files named after
them under ``chipbench/`` (see ``harness.py``).  The last stdout line is one
JSON object (``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
``breakdown`` when traced, and ``limits`` last).  Without a TPU, or with
fewer chips than the cell asks for, the run exits 1 and prints no result.
"""
import time

T_START = time.perf_counter()  # set-up is timed from here, before JAX loads

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from chipbench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], t_start=T_START))
