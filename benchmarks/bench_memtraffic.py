"""Table II analogue: memory-traffic character of CSR vs HBP.

The paper measures Mem-Busy / throughput with Nsight; without hardware
counters we report the analytic byte footprint and access pattern of each
format: bytes moved per nonzero, contiguity (fraction of bytes in
streaming reads), and the x-vector reuse factor from 2D partitioning.
"""
from __future__ import annotations

import numpy as np

from repro.core import PartitionConfig, build_tiles, tuned_partition_config

from .common import emit, load_suite


def main(full: bool = False) -> None:
    cfg = PartitionConfig()
    for name, csr in load_suite(full).items():
        nnz = csr.nnz
        # CSR: data+col per nnz (stream) + one random x read per nnz
        # (charged a 64B DRAM transaction — the paper's Table II effect)
        csr_stream = nnz * 12 + csr.n_rows * 12
        csr_random = nnz * 64
        def fmt(tiles):
            tile_stream = tiles.n_tiles * tiles.cfg.group * tiles.cfg.lane * 8
            switches = int(np.count_nonzero(np.diff(tiles.colblock)) + 1)
            y_bytes = tiles.padded_rows() * 4
            fused = tile_stream + switches * tiles.cfg.col_block * 4 + y_bytes
            return fused, tiles.nnz_utilization()

        hbp_total, util = fmt(build_tiles(csr, cfg, method="hash"))
        tuned_total, tuned_util = fmt(
            build_tiles(csr, tuned_partition_config(csr), method="hash")
        )
        csr_total = csr_stream + csr_random
        emit(
            f"memtraffic/{name}",
            0.0,
            f"csr_bytes/nnz={csr_total/nnz:.1f} (random_frac={csr_random/csr_total:.2f}) "
            f"hbp_bytes/nnz={hbp_total/nnz:.1f} (util={util:.2f}) "
            f"hbp-tuned_bytes/nnz={tuned_total/nnz:.1f} (util={tuned_util:.2f}, beyond-paper)",
        )


if __name__ == "__main__":
    main()
