"""One-pass kernel grid smoke: 2D k-tiled SpMM + paired-payload argmax.

The two numbers the CI regression gate watches (with bench_preprocess):

* ``spmm/serve_k256`` — a wide-feature SpMM launch on the serving path
  (``strategy="stable"``), the steady-state cost every GNN layer and
  coalesced request block pays;
* ``spmm/argmax_onepass`` — the max-aggregation forward with winner
  tracking.  Its derived column also reports the structural tile-stream
  traversal count of the paired-payload recovery (counted by
  ``ref.count_traversals``) and asserts it stays ≤ 1.

``--full`` sweeps the three kernel strategies (the fused kernel in
interpret mode, on a smaller matrix — interpret timings are correctness
smoke, not performance).
"""
from __future__ import annotations

import numpy as np

from repro.core import PartitionConfig, build_tiles
from repro.core.matrices import rmat
from repro.kernels import ops, ref

from .common import emit, timeit

K_WIDE = 256


def _setup(n: int, nnz_target: int, cfg: PartitionConfig, k: int, seed: int = 0):
    csr = rmat(n, nnz_target, seed=seed)
    tiles = build_tiles(csr, cfg)
    dt = ops.device_tiles(tiles)
    x = np.random.default_rng(seed).standard_normal((csr.n_cols, k)).astype(np.float32)
    meta = dict(
        n_rowgroups=tiles.n_rowgroups,
        n_rows=tiles.shape[0],
        col_block=cfg.col_block,
    )
    return csr, tiles, dt, x, meta


def _traversal_count(dt, x, col_block, n_rowgroups):
    """Structural tile-stream traversals of the argmax (eager reference)."""
    import jax.numpy as jnp

    xb = ops.blocked_matrix(jnp.asarray(x[:, :8]), col_block)
    with ref.count_traversals() as one:
        ref.hbp_spmm_hashed_argmax_onepass(
            dt.rowgroup, dt.colblock, dt.data, dt.cols, xb, n_rowgroups=n_rowgroups
        )
    return one[0]


def main(full: bool = False) -> None:
    cfg = PartitionConfig(row_block=256, col_block=512, group=8, lane=16)
    csr, tiles, dt, x, meta = _setup(1 << 11, 16_000, cfg, K_WIDE)
    nnz = csr.nnz

    # --- the serving-path SpMM number the regression gate tracks
    t = timeit(
        lambda: ops.hbp_spmm(dt, x, strategy="stable", **meta),
        repeats=9, warmup=2,
    )
    emit(
        "spmm/serve_k256",
        t,
        f"stable k={K_WIDE} {2 * nnz * K_WIDE / t / 1e9:.2f}Gmul/s",
        config={"n": csr.n_rows, "nnz": nnz, "k": K_WIDE, "strategy": "stable"},
    )

    # --- paired-payload argmax
    one = _traversal_count(dt, x, cfg.col_block, tiles.n_rowgroups)
    assert one <= 1, f"one-pass argmax traversed the tile stream {one}x"
    k_arg = 8
    t = timeit(lambda: ops.hbp_spmm_argmax(dt, x[:, :k_arg], **meta), repeats=9, warmup=2)
    emit(
        "spmm/argmax_onepass",
        t,
        f"traversals={one}",
        config={"n": csr.n_rows, "nnz": nnz, "k": k_arg},
    )

    if full:
        # the three kernel strategies on a small matrix (the fused kernel
        # in interpret mode: correctness smoke, timings not comparable)
        cfg_s = PartitionConfig(row_block=64, col_block=128, group=8, lane=16)
        csr_s, tiles_s, dt_s, x_s, meta_s = _setup(1 << 8, 2_000, cfg_s, K_WIDE, seed=1)
        for strategy in ("fused", "reference", "stable"):
            interpret = strategy == "fused"
            t = timeit(
                lambda s=strategy: ops.hbp_spmm(
                    dt_s, x_s, strategy=s, interpret=True, **meta_s
                ),
                repeats=3, warmup=1,
            )
            emit(
                f"spmm/strategy_{strategy}_k256",
                t,
                "interpret-mode smoke" if interpret else "",
                config={"n": csr_s.n_rows, "k": K_WIDE, "strategy": strategy},
            )


if __name__ == "__main__":
    main()
