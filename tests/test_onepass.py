"""One-launch kernel grid acceptance: 2D k-tiled SpMM + paired-payload argmax.

Two contracts are pinned here:

* one SpMM launch at any width (the 2D (k-tile, tile-block) Pallas grid,
  the reference's single trace, the stable path's <=128-lane chains)
  agrees with the dense oracle at every k-bucket boundary —
  k ∈ {1, 127, 128, 129, 256} — on every kernel path, *bitwise* under the
  max monoid, and the stable path's bits never depend on the width;
* the one-pass paired-payload argmax returns the dense oracle's triples,
  including the tie-to-lowest-column and empty-row (idx = -1, coeff = 0,
  y = 0, no gradient) conventions, while traversing the tile stream once.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import PartitionConfig, build_tiles, csr_from_dense
from repro.kernels import ops, ref
from repro.kernels import hbp_spmm, hbp_spmv

K_BOUNDARIES = [1, 127, 128, 129, 256]
STRATEGIES = ["fused", "fused_streamed", "reference", "stable"]
CFG = PartitionConfig(row_block=32, col_block=64, group=8, lane=8)


@pytest.fixture(scope="module")
def problem():
    rng = np.random.default_rng(11)
    dense = (rng.standard_normal((70, 90)) * (rng.random((70, 90)) < 0.12)).astype(
        np.float32
    )
    dense[5] = 0.0  # empty rows inside occupied groups
    dense[13] = 0.0
    dense[64] = 0.0
    csr = csr_from_dense(dense)
    return dense, csr, build_tiles(csr, CFG)


def _tied_problem():
    """A matrix + features engineered to produce many tied maxima."""
    rng = np.random.default_rng(3)
    dense = np.zeros((40, 48), np.float32)
    mask = rng.random((40, 48)) < 0.3
    dense[mask] = 1.0  # every stored entry identical -> ties everywhere
    dense[::7] = 0.0  # plus empty rows
    X = np.repeat(rng.standard_normal((48 // 4, 3)).astype(np.float32), 4, axis=0)
    csr = csr_from_dense(dense)
    return dense, csr, build_tiles(csr, CFG), X


def _argmax_oracle(dense, X):
    """Dense (y, idx, coeff) with ties to the lowest column, empty -> -1/0."""
    n, k = dense.shape[0], X.shape[1]
    y = np.zeros((n, k), np.float32)
    idx = np.full((n, k), -1, np.int32)
    coeff = np.zeros((n, k), np.float32)
    for i in range(n):
        nz = np.nonzero(dense[i])[0]
        if not nz.size:
            continue
        prod = dense[i, nz, None] * X[nz]  # [nnz_i, k]
        best = prod.max(axis=0)
        y[i] = best
        for c in range(k):
            winners = nz[prod[:, c] == best[c]]
            idx[i, c] = winners.min()
            coeff[i, c] = dense[i, idx[i, c]]
    return y, idx, coeff


def _max_oracle(dense, X):
    """Dense max-monoid product over stored entries; empty rows -> 0."""
    return _argmax_oracle(dense, X)[0]


# --- one launch vs dense, at every k-bucket boundary -----------------------


@pytest.mark.parametrize("k", K_BOUNDARIES)
@pytest.mark.parametrize("strategy", STRATEGIES)
def test_grid_matches_loop_and_dense_at_k_boundaries(problem, k, strategy, rng,
                                                     kernel_strategy):
    """One launch at any width — fused, reference and stable alike —
    against ``dense @ X``; under ``"stable"`` each column's bits are those
    of its own width-1 launch."""
    dense, csr, tiles = problem
    strategy = kernel_strategy(strategy)
    X = rng.standard_normal((90, k)).astype(np.float32)
    Y = np.asarray(hbp_spmm(tiles, X, strategy=strategy, interpret=True))
    np.testing.assert_allclose(Y, dense @ X, rtol=1e-4, atol=1e-4)
    if strategy == "stable":
        # the serving contract: bits never depend on the launch width
        for j in {0, k // 2, k - 1}:
            yj = np.asarray(hbp_spmv(tiles, X[:, j], strategy="stable"))
            assert np.array_equal(Y[:, j], yj), j


@pytest.mark.parametrize("k", K_BOUNDARIES)
@pytest.mark.parametrize("strategy", STRATEGIES)
def test_grid_matches_loop_max_combine_bitwise(problem, k, strategy, rng, kernel_strategy):
    """max is reassociation-free: one launch matches the dense max oracle
    bitwise on EVERY kernel path, and empty rows stay exactly 0."""
    dense, csr, tiles = problem
    strategy = kernel_strategy(strategy)
    X = rng.standard_normal((90, k)).astype(np.float32)
    Y = np.asarray(hbp_spmm(tiles, X, strategy=strategy, combine="max", interpret=True))
    np.testing.assert_array_equal(Y, _max_oracle(dense, X))
    empty = np.asarray(csr.row_nnz() == 0)
    assert (Y[empty] == 0).all()


def test_stable_bits_invariant_across_k_tiling_and_width(problem, rng):
    """A column's bits must match the width-1 launch at every
    serving-visible width: the engine pads requests to bucket widths
    (``hbp_spmm_bucketed``), so that entry is where the bitwise guarantee
    lives — including k boundaries 127/129 that pad across a chunk edge."""
    dense, csr, tiles = problem
    X = rng.standard_normal((90, 256)).astype(np.float32)
    singles = {
        j: np.asarray(hbp_spmv(tiles, X[:, j], strategy="stable"))
        for j in (0, 126, 127, 128, 129, 255)
    }
    for width in (127, 128, 129, 256):
        Y = np.asarray(ops.hbp_spmm_bucketed(tiles, X[:, :width], strategy="stable"))
        assert Y.shape[1] == width
        for j, yj in singles.items():
            if j < width:
                assert np.array_equal(Y[:, j], yj), (width, j)


# --- one-pass argmax vs the dense oracle -----------------------------------


@pytest.mark.parametrize("k", [1, 5, 127, 129])
def test_argmax_onepass_equals_threepass_and_oracle(problem, k, rng):
    """The argmax triple is the dense oracle's, and its ``y`` is the
    max-combine SpMM's bits on every kernel path."""
    dense, csr, tiles = problem
    X = rng.standard_normal((90, k)).astype(np.float32)
    one = tuple(np.asarray(a) for a in ops.hbp_spmm_argmax(tiles, X))
    for got, w in zip(one, _argmax_oracle(dense, X)):
        np.testing.assert_array_equal(got, w)
    for strategy in ("fused", "reference", "stable"):
        Ym = np.asarray(
            hbp_spmm(tiles, X, strategy=strategy, combine="max", interpret=True)
        )
        np.testing.assert_array_equal(one[0], Ym)


def test_argmax_tie_breaks_to_lowest_column_onepass():
    dense, csr, tiles, X = _tied_problem()
    y1, i1, c1 = (np.asarray(a) for a in ops.hbp_spmm_argmax(tiles, X))
    yo, io, co = _argmax_oracle(dense, X)
    np.testing.assert_array_equal(i1, io)  # ties -> lowest column, always
    np.testing.assert_array_equal(y1, yo)
    np.testing.assert_array_equal(c1, co)


def test_argmax_empty_rows_convention(problem, rng):
    dense, csr, tiles = problem
    X = rng.standard_normal((90, 4)).astype(np.float32)
    empty = np.asarray(csr.row_nnz() == 0)
    assert empty.any()
    y, idx, coeff = (np.asarray(a) for a in ops.hbp_spmm_argmax(tiles, X))
    assert (y[empty] == 0).all()
    assert (idx[empty] == -1).all()
    assert (coeff[empty] == 0).all()


def test_onepass_traverses_tile_stream_once(problem, rng):
    """The paired payload walks the tile stream once."""
    _, _, tiles = problem
    dt = ops.device_tiles(tiles)
    xb = ops.blocked_matrix(jnp.asarray(rng.standard_normal((90, 4)), jnp.float32), 64)
    with ref.count_traversals() as one:
        ref.hbp_spmm_hashed_argmax_onepass(
            dt.rowgroup, dt.colblock, dt.data, dt.cols, xb,
            n_rowgroups=tiles.n_rowgroups,
        )
    assert one[0] == 1
