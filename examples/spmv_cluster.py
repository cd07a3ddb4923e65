"""Distributed SpMV across a device mesh (deliverable b, cluster scale).

    PYTHONPATH=src python examples/spmv_cluster.py

Maps the paper's fixed/competitive block scheduling onto a (small, CPU)
device mesh via shard_map: "grid" placement = locality-first (x segments
never move), "balanced" = LPT competitive replay.  On the 512-chip
production mesh the same code path shards over the full "data" axis.
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np

import jax

from repro.core import PartitionConfig
from repro.core.distributed import build_sharded_spmv
from repro.core.matrices import rmat


def main() -> None:
    # one "data" axis over every device this process sees (8 virtual CPU
    # devices by default; the chips of a TPU host when run there)
    mesh = jax.make_mesh((len(jax.devices()),), ("data",))
    A = rmat(1 << 13, 200_000, seed=0)
    x = np.random.default_rng(0).standard_normal(A.n_cols).astype(np.float32)
    y_ref = A.matvec(x)

    for mode in ("balanced", "grid"):
        sh = build_sharded_spmv(
            A, mesh, cfg=PartitionConfig(row_block=256, col_block=1024), mode=mode
        )
        y = np.asarray(sh.matvec(jax.numpy.asarray(x)))
        err = np.abs(y - y_ref).max() / (np.abs(y_ref).max() + 1e-12)
        imbalance = sh.loads.max() / max(sh.loads.mean(), 1e-9)
        print(
            f"mode={mode:9s} rel_err={err:.2e} tiles/worker imbalance="
            f"{imbalance:.2f} (loads {sh.loads.astype(int).tolist()})"
        )
        assert err < 1e-4
    print("OK")


if __name__ == "__main__":
    main()
