"""The plain float64 reference the benchmark's ``correct`` compares with.

The arithmetic is a copy of ``chip_smoke.py``'s ``reference`` and
``rel_err``: a product is judged per row against the magnitude it sums,

    err = max_i |y_i - y^_i| / (|A| |x|)_i,

so a row whose terms cancel is not held to a tighter bound than its
terms allow.  One change: rows whose terms sum to less than ``FLOOR`` are
judged against ``FLOOR`` (``chip_smoke.py`` uses float32's smallest normal
number), since a chain of normalised products drives some entries below
what float32 holds.  Nothing here imports the program: the matrix, the PageRank
transition matrix and the iteration are computed from the benchmark's own
CSR arrays in scipy.

``*_bf16`` are the controls: the same computation with the matrix values
and the vectors rounded to bfloat16 and accumulated in float32, the step
down from the float32 the configurations state.  A control must read as
not correct.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np
import scipy.sparse

from .matrices import Csr

__all__ = ["product", "product_bf16", "rel_err", "pagerank", "pagerank_bf16",
           "transition"]


def _scipy(csr: Csr, data: np.ndarray) -> scipy.sparse.csr_matrix:
    return scipy.sparse.csr_matrix((data, csr.indices, csr.indptr), shape=csr.shape)


def bf16(a) -> np.ndarray:
    """``a`` rounded to bfloat16, held as float32."""
    return np.asarray(a, np.float32).astype(ml_dtypes.bfloat16).astype(np.float32)


def product(csr: Csr, x: np.ndarray):
    """``(A @ x, |A| @ |x|)`` in float64 (``x``: ``[n]`` or ``[n, k]``)."""
    x64 = np.asarray(x, np.float64)
    data = csr.data.astype(np.float64)
    return _scipy(csr, data) @ x64, _scipy(csr, np.abs(data)) @ np.abs(x64)


def product_bf16(csr: Csr, x: np.ndarray) -> np.ndarray:
    """The control: ``A @ x`` from bfloat16 values, float32 sums."""
    return _scipy(csr, bf16(csr.data)) @ bf16(x)


# below float32's smallest normal number the device flushes to zero, so a
# row whose terms sum to less than this is judged in absolute terms: a
# flushed row then reads at most about float32's epsilon
FLOOR = np.finfo(np.float32).tiny / np.finfo(np.float32).eps


def rel_err(y, y_ref: np.ndarray, scale: np.ndarray) -> float:
    """``max_i |y_i - y^_i| / max(scale_i, FLOOR)``; inf for a wrong shape or
    a non-finite ``y``."""
    y = np.asarray(y, np.float64)
    if y.shape != y_ref.shape or not np.isfinite(y).all():
        return float("inf")
    if y.size == 0:
        return 0.0
    return float(np.max(np.abs(y - y_ref) / np.maximum(scale, FLOOR)))


def transition(csr: Csr):
    """Column-stochastic PageRank matrix ``M`` (scipy, float64) and the
    dangling-row indicator: edge weights ``|a_ij|`` over the row's out-weight,
    transposed so that ``p' = M p`` moves rank along edges."""
    w = _scipy(csr, np.abs(csr.data.astype(np.float64)))
    out = np.asarray(w.sum(axis=1)).ravel()
    dangling = (out == 0).astype(np.float64)
    inv = np.where(out > 0, 1.0 / np.where(out > 0, out, 1.0), 0.0)
    return (scipy.sparse.diags(inv) @ w).T.tocsr(), dangling


def pagerank(csr: Csr, iterations: int, damping: float):
    """``iterations`` PageRank steps from the uniform vector in float64.

    Returns ``(p, scale)``: the last iterate and ``|M| |p_prev| + |p|``, the
    magnitude the last step sums, for :func:`rel_err`.
    """
    M, dangling = transition(csr)
    n = M.shape[0]
    v = np.full(n, 1.0 / n)
    p = prev = v
    for _ in range(iterations):
        prev = p
        p = damping * (M @ prev + (dangling @ prev) * v) + (1 - damping) * v
    return p, M @ np.abs(prev) + np.abs(p)


def pagerank_bf16(csr: Csr, iterations: int, damping: float) -> np.ndarray:
    """The control: the same steps with ``M`` and each iterate in bfloat16,
    float32 sums."""
    M, dangling = transition(csr)
    M = M.astype(np.float32)
    M.data = bf16(M.data)
    n = M.shape[0]
    v = np.full(n, 1.0 / n, np.float32)
    d = dangling.astype(np.float32)
    p = bf16(v)
    for _ in range(iterations):
        p = bf16(np.float32(damping) * (M @ p + (d @ p) * v)
                 + np.float32(1 - damping) * v)
    return p
