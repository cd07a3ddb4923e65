"""Per-call time of the fused SpMM's two tile bodies at every k bucket and
every block size ``B`` (tiles a grid step carries).

Runs on a TPU only: the lane gather (``hbp_spmv._fused``) and the row
gather (``hbp_spmv._fused_rows``) over the same staged tiles of the
benchmark's ``kron16`` configuration (its pinned geometry), one sum-monoid
launch set per (body, k, B), and prints one JSON line each: the median of
``--reps`` timed calls, microseconds per tile, the body's visits per tile
next to them, and the largest error against a float64 CSR product,
relative to the largest ``|A||x|``.  Lane lines count 128-column chunk
gathers (``chunk_visits_per_tile``: every chunk of the segment for every
RHS column) and the share of them that reach a chunk holding a live slot
(``hit_chunk_visits_per_tile``); row lines count X row loads
(``slot_visits_per_tile``: ``group`` rows times the tile's live width, for
every k-tile).
``K_ROW`` is the narrowest bucket at which the row body is the faster
one; ``BLOCK_LANE`` and ``BLOCK_ROW`` are the fastest ``B`` of each body.
Lane lines also run at ``B`` = ``BLOCK_LANE`` with every count of tiles
a loop iteration computes in ``INTERLEAVES`` (``interleave``; the others
run ``INTERLEAVE_LANE``, its fastest).
Last, for each ``B``, the row body with every tile's width set to 0: the
launches' fixed cost without the slot loop.

    PYTHONPATH=src python -m benchmarks.gather_sweep [--reps 3] [--out PATH]
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
BLOCKS = {"lane": (1, 32, 64, 128, 256, 512), "row": (1, 4, 8, 16, 32)}
INTERLEAVES = (1, 2, 3, 4)


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
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", default=None, help="also write the lines to this file")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    if jax.default_backend() != "tpu":
        print("gather_sweep: no TPU; the sweep measures the chip only", file=sys.stderr)
        return 1
    sys.path.insert(0, str(ROOT))
    import scipy.sparse as sp

    from chipbench import matrices
    from repro.core import PartitionConfig, build_tiles
    from repro.core.formats import CSRMatrix
    from repro.kernels import ops, ref

    K = importlib.import_module("repro.kernels.hbp_spmv")
    config = json.loads((ROOT / "chipbench" / "configs" / "kron16.json").read_text())
    c = matrices.make(config, args.seed)
    tiles = build_tiles(CSRMatrix(c.indptr, c.indices, c.data, c.shape),
                        PartitionConfig(**config["partition"]))
    csr = sp.csr_matrix((c.data.astype(np.float64), c.indices, c.indptr), shape=c.shape)
    dt = ops.device_tiles(tiles)
    T, col_block, n_rows = tiles.n_tiles, tiles.cfg.col_block, c.shape[0]
    kw = dict(n_rowgroups=tiles.n_rowgroups, combine="sum", interpret=False,
              name="hbp_spmm_fused")
    bodies = {
        "lane": lambda b, u: jax.jit(functools.partial(K._fused, block=b, interleave=u,
                                                       **kw)),
        "row": lambda b, u: jax.jit(functools.partial(K._fused_rows, block=b, **kw)),
    }
    runs = [(body, b, K.INTERLEAVE_LANE) for body in bodies for b in BLOCKS[body]]
    runs += [("lane", K.BLOCK_LANE, u) for u in INTERLEAVES if u != K.INTERLEAVE_LANE]
    rng = np.random.default_rng(args.seed)
    lines = []
    n_chunks = -(-col_block // K.LANE_TILE)
    live = np.nonzero(tiles.data)
    hit_chunks = np.unique(live[0] * n_chunks + tiles.cols[live] // K.LANE_TILE).size / T
    slots = tiles.cfg.group * float(np.asarray(dt.width).mean())

    def visits(body, k):
        kp = K._padded_k(k)
        if body == "row":
            return dict(slot_visits_per_tile=slots * K._k_grid(kp)[1])
        return dict(chunk_visits_per_tile=n_chunks * kp,
                    hit_chunk_visits_per_tile=hit_chunks * kp)

    def emit(**rec):
        rec.update(device=jax.devices()[0].device_kind, tiles=T)
        lines.append(json.dumps(rec))
        print(lines[-1], flush=True)

    head = (dt.rowgroup, dt.colblock)
    for k in ops.K_BUCKETS:
        xh = rng.standard_normal((c.shape[1], k), np.float32)
        want, scale = csr @ xh, abs(csr) @ np.abs(xh)
        x = ops.blocked_matrix(jnp.asarray(xh), col_block)
        for body, b, u in runs:
            width = (dt.width,) if body == "row" else ()
            t, y = _timed(bodies[body](b, u), (*head, *width, dt.data, dt.cols, x), args.reps)
            y = np.asarray(ref.unpermute(y[..., :k], dt.perm, n_rows))
            err = float((np.abs(y - want) / np.maximum(scale, 1e-30)).max())
            lane = dict(interleave=u) if body == "lane" else {}
            emit(body=body, k=k, block=b, **lane, call_ms=t * 1e3, us_per_tile=t / T * 1e6,
                 **visits(body, k),
                 chosen=K.gather_body(k, x.shape[0] * col_block) == body
                 and K.tiles_per_step(k, x.shape[0] * col_block, col_block) == b
                 and u == K.INTERLEAVE_LANE,
                 rel_err=err)
    x = ops.blocked_matrix(jnp.ones((c.shape[1], 8), jnp.float32), col_block)
    for b in BLOCKS["row"]:
        t0, _ = _timed(bodies["row"](b, None),
                       (*head, jnp.zeros_like(dt.width), dt.data, dt.cols, x), args.reps)
        emit(body="row_width0", k=8, block=b, call_ms=t0 * 1e3, us_per_tile=t0 / T * 1e6,
             slots=int(tiles.cfg.group * np.asarray(dt.width).sum()))
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text("\n".join(lines) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
