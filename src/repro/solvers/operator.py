"""The operator abstraction every solver dispatches through.

A :class:`LinearOperator` is a *traceable* ``y = A @ x``: its ``matvec`` /
``matmat`` closures hold only jnp arrays (device-resident tile formats,
CSR arrays, dense matrices), so a solver loop built on it stays inside one
``jax.lax.while_loop`` — no host round-trips per iteration.

:func:`aslinearoperator` adapts every container in the library:

* :class:`~repro.core.tile.HBPTiles` — the production path: the HBP kernel
  path the backend resolves (the fused Pallas kernels on TPU; SpMV for
  single vectors, the multi-RHS SpMM kernel for ``[n, k]`` blocks).  The
  host tiles are staged to the device ONCE at operator construction;
  solver iterations touch only :class:`DeviceTiles`.
* :class:`~repro.core.formats.CSRMatrix` — the segment-sum CSR baseline
  (Algorithm 1) for apples-to-apples workload benchmarks.
* dense ``np.ndarray`` / ``jax.Array`` — ``jnp.dot``, the oracle solvers
  are validated against.
"""
from __future__ import annotations

from typing import Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.formats import CSRMatrix
from repro.core.spmv import csr_spmm_jnp, csr_spmv_jnp
from repro.core.tile import HBPTiles

__all__ = ["LinearOperator", "aslinearoperator"]


class LinearOperator:
    """Matrix-free ``A``: a shape plus traceable matvec/matmat closures.

    ``matmat`` defaults to column-at-a-time matvec; format-aware adapters
    (HBP tiles) override it with the one-launch SpMM kernel.
    """

    # HBP operators: the (DeviceTiles, keywords) every launch passes to the
    # repro.kernels.ops wrappers, e.g. for ops.lower_launch
    launch_args: tuple | None = None

    def __init__(
        self,
        shape: Tuple[int, int],
        matvec: Callable[[jax.Array], jax.Array],
        matmat: Callable[[jax.Array], jax.Array] | None = None,
        dtype=jnp.float32,
    ):
        self.shape = tuple(shape)
        self.dtype = dtype
        self._matvec = matvec
        self._matmat = matmat

    def matvec(self, x: jax.Array) -> jax.Array:
        """``A @ x`` for a single vector ``x: [n]``."""
        return self._matvec(x)

    def matmat(self, x: jax.Array) -> jax.Array:
        """``A @ X`` for a block of right-hand sides ``X: [n, k]``."""
        if self._matmat is not None:
            return self._matmat(x)
        return jnp.stack([self._matvec(x[:, j]) for j in range(x.shape[1])], axis=1)

    def __call__(self, x: jax.Array) -> jax.Array:
        """Shape-polymorphic apply: [n] -> matvec, [n, k] -> matmat."""
        return self.matvec(x) if x.ndim == 1 else self.matmat(x)

    def __matmul__(self, x):
        return self(x)


def _from_hbp_tiles(
    tiles: HBPTiles, *, strategy: str | None = None, interpret: bool | None = None
) -> LinearOperator:
    from repro.kernels import ops

    dt = ops.device_tiles(tiles)  # staged once; iterations reuse it
    meta = dict(
        n_rowgroups=tiles.n_rowgroups,
        n_rows=tiles.shape[0],
        col_block=tiles.cfg.col_block,
        strategy=strategy or ops.default_strategy(),
        interpret=interpret,
    )
    op = LinearOperator(
        tiles.shape,
        matvec=lambda x: ops.hbp_spmv(dt, x, **meta),
        matmat=lambda x: ops.hbp_spmm(dt, x, **meta),
    )
    op.launch_args = (dt, meta)
    return op


def _from_csr(csr: CSRMatrix) -> LinearOperator:
    indptr = jnp.asarray(csr.indptr)
    indices = jnp.asarray(csr.indices)
    data = jnp.asarray(csr.data, jnp.float32)
    n_rows = csr.n_rows
    return LinearOperator(
        csr.shape,
        matvec=lambda x: csr_spmv_jnp(indptr, indices, data, x, n_rows),
        matmat=lambda x: csr_spmm_jnp(indptr, indices, data, x, n_rows),
    )


def _from_dense(a) -> LinearOperator:
    aj = jnp.asarray(a, jnp.float32)
    return LinearOperator(aj.shape, matvec=lambda x: aj @ x, matmat=lambda x: aj @ x)


def aslinearoperator(
    A, *, strategy: str | None = None, interpret: bool | None = None
) -> LinearOperator:
    """Adapt any supported container to a :class:`LinearOperator`.

    ``strategy`` / ``interpret`` configure the HBP kernel path and apply
    only to :class:`HBPTiles` inputs; ``strategy=None`` resolves it per
    backend (:func:`repro.kernels.ops.default_strategy`).
    """
    if isinstance(A, LinearOperator):
        return A
    if isinstance(A, HBPTiles):
        return _from_hbp_tiles(A, strategy=strategy, interpret=interpret)
    if isinstance(A, CSRMatrix):
        return _from_csr(A)
    if isinstance(A, (np.ndarray, jax.Array)):
        if A.ndim != 2:
            raise ValueError(f"dense operator must be 2-D, got shape {A.shape}")
        return _from_dense(A)
    raise TypeError(f"cannot build a LinearOperator from {type(A)!r}")
