"""The benchmark's matrices, made from a configuration and a seed.

``rmat`` is the Graph500 Kronecker generator (R-MAT with initiator
``a, b, c``): edges are drawn one level of the recursion at a time, self
loops and repeated edges are dropped, and edges are drawn until the graph
holds ``edges`` distinct undirected edges, the count of the published
file.  With ``scramble`` the vertex labels are then permuted at random, as
Graph500's generator does.  The matrix is symmetric: ``(i, j)`` and
``(j, i)`` carry the same value.  The edge drawing follows ``rmat`` in
``repro.core.matrices``, kept here so that no change to the program can
move the benchmark's inputs.

What is drawn comes from two seeds:

* the sparsity pattern from the configuration's fixed ``pattern_seed``:
  the pattern is the deployment, as one SuiteSparse file is, so every run
  admits the same tiles and does the same work;
* the stored values from the run's ``--seed``, so each seed checks the
  program on other numbers.

Values are float32, the precision the program stores.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np
import scipy.sparse

__all__ = ["Csr", "make", "values_rng"]


class Csr(NamedTuple):
    """A CSR matrix as plain arrays: sorted column ids per row, f32 values."""

    indptr: np.ndarray  # int64[n_rows + 1]
    indices: np.ndarray  # int64[nnz]
    data: np.ndarray  # float32[nnz]
    shape: tuple

    @property
    def nnz(self) -> int:
        return int(self.indices.size)


def values_rng(seed: int, stream: int) -> np.random.Generator:
    """The run's generator for one use (``stream``): values, inputs, arrivals."""
    return np.random.default_rng([int(seed), int(stream)])


def _rmat_draw(rng: np.random.Generator, m: int, *, scale: int, a: float, b: float,
               c: float):
    """``m`` R-MAT edges (rows, cols)."""
    rows = np.zeros(m, dtype=np.int64)
    cols = np.zeros(m, dtype=np.int64)
    for level in range(scale):
        r = rng.random(m)
        go_down = r >= a + b
        go_right = ((r >= a) & (r < a + b)) | (r >= a + b + c)
        rows |= go_down.astype(np.int64) << level
        cols |= go_right.astype(np.int64) << level
    return rows, cols


def rmat_edges(gen: dict):
    """The ``edges`` distinct undirected edges ``(lo, hi)``, ``lo < hi``, in
    the order first drawn, with labels scrambled where asked."""
    n = 1 << gen["scale"]
    want = gen["edges"]
    rng = np.random.default_rng(gen["pattern_seed"])
    keys = np.empty(0, np.int64)
    batch = max(want + want // 2, 1024)  # repeats and loops drop about a quarter
    while True:
        r, c = _rmat_draw(rng, batch, scale=gen["scale"], a=gen["a"], b=gen["b"],
                          c=gen["c"])
        batch = max(want // 8, 1024)
        keep = r != c
        keys = np.concatenate([keys, np.minimum(r, c)[keep] * n + np.maximum(r, c)[keep]])
        distinct, first = np.unique(keys, return_index=True)
        if distinct.size >= want:
            break
    keys = keys[np.sort(first)[:want]]
    lo, hi = keys // n, keys % n
    if gen.get("scramble"):
        perm = np.random.default_rng([gen["pattern_seed"], 1]).permutation(n)
        lo, hi = perm[lo], perm[hi]
    return lo, hi, n


def _rmat(gen: dict, seed: int):
    lo, hi, n = rmat_edges(gen)
    vals = values_rng(seed, 0).standard_normal(lo.size)
    return (np.concatenate([lo, hi]), np.concatenate([hi, lo]),
            np.concatenate([vals, vals]), (n, n))


_GENERATORS = {"rmat": _rmat}


def make(config: dict, seed: int) -> Csr:
    """The configuration's matrix with values from ``seed``."""
    gen = config["generator"]
    try:
        build = _GENERATORS[gen["kind"]]
    except KeyError:
        raise ValueError(f"unknown generator {gen['kind']!r}") from None
    rows, cols, vals, shape = build(gen, seed)
    a = scipy.sparse.coo_matrix((vals, (rows, cols)), shape=shape).tocsr()
    a.sum_duplicates()
    a.sort_indices()
    return Csr(a.indptr.astype(np.int64), a.indices.astype(np.int64),
               a.data.astype(np.float32), tuple(shape))
