"""Distributed SpMV: the paper's block scheduling at cluster scale.

The tile stream is split across the devices of one mesh axis; the combine
part becomes a collective.  Two placements mirror the paper's
fixed/competitive split:

* ``grid``     — locality-first (the *fixed* part writ large): tiles follow
  their column block (block ``b`` lives on device ``b mod n``), so each
  device gathers from its own x segments only.
* ``balanced`` — the *competitive* part: tiles are LPT-assigned to devices
  by count regardless of position (deterministic replay of the paper's
  ticket-lock).  Better makespan on power-law matrices.

Either way x is replicated and the per-device partial outputs reduce with
one ``psum`` over the axis.

Implementation: ``jax.shard_map`` over the mesh; per-device tile lists are
padded to equal length with null tiles (rowgroup -1 → accumulated into a
scratch row), so every device runs the same program — the SPMD analogue of
the paper's equal-length fixed quota.  The stacked shards are placed with
``NamedSharding(mesh, P(axis))``, one slice per device.  The per-device
body is a jnp gather-multiply, not the Pallas kernel.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Literal

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .formats import CSRMatrix
from .partition import PartitionConfig
from .schedule import lpt_schedule
from .tile import HBPTiles, build_tiles

__all__ = ["ShardedSpmv", "build_sharded_spmv"]


def _pad_tiles(arrs, n_pad, rowgroup_fill=-1):
    data, cols, rowgroup, colblock = arrs
    G, LANE = data.shape[1], data.shape[2]
    return (
        np.concatenate([data, np.zeros((n_pad, G, LANE), data.dtype)]),
        np.concatenate([cols, np.zeros((n_pad, G, LANE), cols.dtype)]),
        np.concatenate([rowgroup, np.full(n_pad, rowgroup_fill, rowgroup.dtype)]),
        np.concatenate([colblock, np.zeros(n_pad, colblock.dtype)]),
    )


@dataclasses.dataclass
class ShardedSpmv:
    """Device-placed tile shards + the sharded matvec."""

    mesh: Mesh
    mode: str
    axis: str
    tiles: HBPTiles
    # stacked per-device tiles [n_dev, T_max, ...], sharded over ``axis``
    data: jax.Array
    cols: jax.Array
    rowgroup: jax.Array
    colblock: jax.Array
    perm: jax.Array
    n_rows: int
    loads: np.ndarray

    def matvec(self, x: jax.Array) -> jax.Array:
        cfg = self.tiles.cfg
        return _sharded_matvec(
            self.data, self.cols, self.rowgroup, self.colblock, self.perm,
            jnp.asarray(x, jnp.float32),
            mesh=self.mesh, axis=self.axis, n_rowgroups=self.tiles.n_rowgroups,
            col_block=cfg.col_block, n_rows=self.n_rows,
        )


@functools.partial(
    jax.jit, static_argnames=("mesh", "axis", "n_rowgroups", "col_block", "n_rows")
)
def _sharded_matvec(data, cols, rowgroup, colblock, perm, x, *, mesh, axis,
                    n_rowgroups, col_block, n_rows):
    n_cb = -(-x.shape[0] // col_block)
    xb = jnp.pad(x, (0, n_cb * col_block - x.shape[0])).reshape(n_cb, col_block)

    def local(data, cols, rowgroup, colblock, xb):
        # data: [1, T, G, L] local shard; xb: [n_cb, col_block] replicated
        segs = xb[colblock[0]]  # [T, col_block]
        T, G, L = data.shape[1:]
        gathered = jnp.take_along_axis(
            segs[:, None, :], cols[0].reshape(T, 1, G * L), axis=2
        ).reshape(T, G, L)
        contrib = jnp.sum(data[0] * gathered, axis=2)  # [T, G]
        seg_ids = jnp.where(rowgroup[0] < 0, n_rowgroups, rowgroup[0])
        y_part = jax.ops.segment_sum(contrib, seg_ids, num_segments=n_rowgroups + 1)
        # combine part: one collective over the worker axis; the null-tile
        # scratch row is dropped
        return jax.lax.psum(y_part[:n_rowgroups], axis)

    sharded = P(axis)
    y_hashed = jax.shard_map(
        local, mesh=mesh, in_specs=(sharded,) * 4 + (P(),), out_specs=P(),
    )(data, cols, rowgroup, colblock, xb)
    out = jnp.zeros(perm.shape[0], y_hashed.dtype).at[perm].set(y_hashed.reshape(-1))
    return out[:n_rows]


def build_sharded_spmv(
    csr: CSRMatrix,
    mesh: Mesh,
    *,
    cfg: PartitionConfig | None = None,
    mode: Literal["grid", "balanced"] = "balanced",
    axis: str = "data",
) -> ShardedSpmv:
    cfg = cfg or PartitionConfig()
    tiles = build_tiles(csr, cfg, method="hash")
    n_workers = mesh.shape[axis]

    if mode == "balanced":
        # competitive placement: LPT over tiles, balanced by count
        costs = np.ones(tiles.n_tiles)
        sched = lpt_schedule(costs, n_workers)
        assign = sched.assignment
    else:
        # locality placement: tiles follow their column block (x reuse)
        assign = [[] for _ in range(n_workers)]
        for t in range(tiles.n_tiles):
            assign[int(tiles.colblock[t]) % n_workers].append(t)

    t_max = max((len(a) for a in assign), default=1)
    per_dev = []
    loads = np.zeros(n_workers)
    for w in range(n_workers):
        ids = np.asarray(assign[w], dtype=np.int64)
        loads[w] = ids.size
        arrs = (
            tiles.data[ids],
            tiles.cols[ids],
            tiles.rowgroup[ids],
            tiles.colblock[ids],
        )
        per_dev.append(_pad_tiles(arrs, t_max - ids.size))
    # each device receives only its own slice of the stacked shards
    sharded = NamedSharding(mesh, P(axis))
    data, cols, rowgroup, colblock = (
        jax.device_put(np.stack([d[i] for d in per_dev]), sharded) for i in range(4)
    )

    return ShardedSpmv(
        mesh=mesh,
        mode=mode,
        axis=axis,
        tiles=tiles,
        data=data,
        cols=cols,
        rowgroup=rowgroup,
        colblock=colblock,
        perm=jax.device_put(tiles.perm.astype(np.int32), NamedSharding(mesh, P())),
        n_rows=csr.n_rows,
        loads=loads,
    )
