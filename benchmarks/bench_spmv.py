"""Figs. 8/10: SpMV throughput — HBP vs CSR vs plain 2D-partitioning.

Two views are reported per matrix:

* **measured** — wall time of the jitted XLA implementations on the host
  CPU (HBP tiles run the jnp oracle of the Pallas kernel; interpret-mode
  Pallas timing is meaningless).  GFLOPS = 2·nnz / t, the paper's metric.
* **projected v5e** — analytic HBM-traffic model of each format divided by
  819 GB/s: the bandwidth-bound throughput the format's byte footprint
  permits on the target hardware (SpMV is memory-bound, so bytes/nnz is
  the controlling quantity; padding waste shows up directly here).
  CSR's per-nnz random x read is charged one 64 B transaction — the
  effect the paper's Table II measures directly (0.15% mem-busy,
  2.85 GB/s effective CSR throughput vs 145 GB/s for HBP's staged
  streams).  HBP staging is modelled for the fused-combine kernel, which
  re-stages x per row-group/col-block run.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import PartitionConfig, build_tiles, csr_spmv_jnp, tuned_partition_config
from repro.kernels import device_tiles
from repro.kernels.ops import blocked_vector
from repro.kernels.ref import hbp_spmv_hashed_ref, unpermute

from .common import emit, load_suite, timeit

HBM_BW = 819e9  # v5e B/s


def _projected_tpu_gflops(nnz: int, bytes_moved: float) -> float:
    t = bytes_moved / HBM_BW
    return 2 * nnz / t / 1e9


def main(full: bool = False) -> None:
    cfg = PartitionConfig()  # the paper's 512 x 4096
    for name, csr in load_suite(full).items():
        x = np.random.default_rng(1).standard_normal(csr.n_cols).astype(np.float32)
        xj = jnp.asarray(x)
        nnz = csr.nnz

        # --- CSR baseline (Algorithm 1 as segment-sum)
        indptr = jnp.asarray(csr.indptr)
        indices = jnp.asarray(csr.indices)
        data = jnp.asarray(csr.data.astype(np.float32))
        csr_fn = jax.jit(lambda v: csr_spmv_jnp(indptr, indices, data, v, csr.n_rows))
        t_csr = timeit(lambda: csr_fn(xj).block_until_ready())

        # --- HBP (hash), plain 2D (no reordering), tuned-geometry HBP
        results = {}
        tuned_cfg = tuned_partition_config(csr)
        for method, label, tcfg in (
            ("hash", "hbp", cfg),
            ("none", "2d", cfg),
            ("hash", "hbp-tuned", tuned_cfg),
        ):
            tiles = build_tiles(csr, tcfg, method=method)
            dt = device_tiles(tiles)
            xb = blocked_vector(xj, cfg.col_block)
            nrg, nrows = tiles.n_rowgroups, csr.n_rows

            def run(dt=dt, xb=xb, nrg=nrg, nrows=nrows):
                y = hbp_spmv_hashed_ref(dt.rowgroup, dt.colblock, dt.data, dt.cols, xb, n_rowgroups=nrg)
                return unpermute(y, dt.perm, nrows)

            jrun = jax.jit(run)
            t = timeit(lambda: jrun().block_until_ready())
            # v5e traffic model: tiles stream (data f32 + cols i32), x
            # staged per colblock run, y written once
            tile_bytes = tiles.n_tiles * tcfg.group * tcfg.lane * 8
            switches = int(np.count_nonzero(np.diff(tiles.colblock)) + 1)
            y_bytes = tiles.padded_rows() * 4
            results[label] = (t, tile_bytes + switches * tcfg.col_block * 4 + y_bytes)

        # data+col streams + one 64B transaction per random x read + ptr+y
        csr_bytes = nnz * 12 + nnz * 64 + csr.n_rows * 12

        def g(t):
            return 2 * nnz / t / 1e9
        t_hbp, hbp_bytes = results["hbp"]
        t_2d, d2_bytes = results["2d"]
        t_tuned, tuned_bytes = results["hbp-tuned"]
        emit(
            f"spmv/{name}",
            t_hbp,
            f"measured GFLOPS csr={g(t_csr):.2f} 2d={g(t_2d):.2f} hbp={g(t_hbp):.2f} "
            f"tuned={g(t_tuned):.2f} "
            f"speedup_vs_csr={t_csr/t_hbp:.2f}x speedup_vs_2d={t_2d/t_hbp:.2f}x | "
            f"projected-v5e GFLOPS csr={_projected_tpu_gflops(nnz, csr_bytes):.1f} "
            f"2d={_projected_tpu_gflops(nnz, d2_bytes):.1f} "
            f"hbp={_projected_tpu_gflops(nnz, hbp_bytes):.1f} "
            f"tuned={_projected_tpu_gflops(nnz, tuned_bytes):.1f} (beyond-paper)",
        )


if __name__ == "__main__":
    main()
