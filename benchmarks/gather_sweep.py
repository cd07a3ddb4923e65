"""Per-tile time of the fused SpMM's two tile bodies at every k bucket.

Runs on a TPU only: the lane gather (``hbp_spmv._fused``) and the row
gather (``hbp_spmv._fused_rows``) over the same staged tiles of the
benchmark's ``kron16`` configuration (its pinned geometry), one sum-monoid
launch set per k in ``K_BUCKETS``, and prints one JSON line per (body, k):
the median of ``--reps`` timed calls, microseconds per tile, and the row
body's largest relative difference from the lane body.  ``K_ROW`` is the
narrowest bucket at which the row body is the faster one.  A last line
times the row body with every tile's width set to 0: the launches' fixed
per-step cost, without the slot loop.

    PYTHONPATH=src python -m benchmarks.gather_sweep [--reps 5] [--out PATH]
"""
from __future__ import annotations

import argparse
import functools
import importlib
import json
import statistics
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent.parent


def _timed(fn, args, reps):
    import jax

    jax.block_until_ready(fn(*args))  # compile and warm
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return statistics.median(times), jax.block_until_ready(fn(*args))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", default=None, help="also write the lines to this file")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    if jax.default_backend() != "tpu":
        print("gather_sweep: no TPU; the sweep measures the chip only", file=sys.stderr)
        return 1
    sys.path.insert(0, str(ROOT))
    from chipbench import matrices
    from repro.core import PartitionConfig, build_tiles
    from repro.core.formats import CSRMatrix
    from repro.kernels import ops

    K = importlib.import_module("repro.kernels.hbp_spmv")
    config = json.loads((ROOT / "chipbench" / "configs" / "kron16.json").read_text())
    c = matrices.make(config, args.seed)
    tiles = build_tiles(CSRMatrix(c.indptr, c.indices, c.data, c.shape),
                        PartitionConfig(**config["partition"]))
    dt = ops.device_tiles(tiles)
    T, col_block = tiles.n_tiles, tiles.cfg.col_block
    kw = dict(n_rowgroups=tiles.n_rowgroups, combine="sum", interpret=False,
              name="hbp_spmm_fused")
    lane = jax.jit(functools.partial(K._fused, **kw))
    row = jax.jit(functools.partial(K._fused_rows, **kw))
    rng = np.random.default_rng(args.seed)
    lines = []

    def emit(**rec):
        rec.update(device=jax.devices()[0].device_kind, tiles=T)
        lines.append(json.dumps(rec))
        print(lines[-1], flush=True)

    head = (dt.rowgroup, dt.colblock, dt.first)
    for k in ops.K_BUCKETS:
        x = ops.blocked_matrix(jnp.asarray(rng.standard_normal((c.shape[1], k), np.float32)),
                               col_block)
        t_lane, y_lane = _timed(lane, (*head, dt.data, dt.cols, x), args.reps)
        t_row, y_row = _timed(row, (*head, dt.width, dt.data, dt.cols, x), args.reps)
        y_lane, y_row = np.asarray(y_lane)[..., :k], np.asarray(y_row)[..., :k]
        diff = float(np.abs(y_row - y_lane).max() / max(np.abs(y_lane).max(), 1e-30))
        for body, t in (("lane", t_lane), ("row", t_row)):
            emit(body=body, k=k, call_ms=t * 1e3, us_per_tile=t / T * 1e6,
                 chosen=K.gather_body(k, x.shape[0] * col_block) == body,
                 row_vs_lane_rel=diff)
    t0, _ = _timed(row, (*head, jnp.zeros_like(dt.width), dt.data, dt.cols, x), args.reps)
    emit(body="row_width0", k=k, call_ms=t0 * 1e3, us_per_tile=t0 / T * 1e6,
         slots=int(tiles.cfg.group * np.asarray(dt.width).sum()))
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text("\n".join(lines) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
