"""Pallas HBP SpMV kernels vs the jnp oracle and the dense matmul.

Sweeps shapes/dtypes per the deliverable: every kernel path (the fused
kernel with X resident and with X streamed a segment a step, and the jnp
reference) must agree with the dense oracle in interpret mode.
"""
import numpy as np
import pytest
pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st


from repro.core import PartitionConfig, build_tiles, csr_from_dense
from repro.kernels import hbp_spmv


CASES = [
    (64, 64, 0.3, "hash"),
    (100, 120, 0.1, "hash"),
    (300, 500, 0.03, "hash"),
    (257, 130, 0.02, "none"),
    (64, 300, 0.15, "sort2d"),
]


@pytest.mark.parametrize("m,k,density,method", CASES)
@pytest.mark.parametrize("strategy", ["fused", "fused_streamed", "reference"])
def test_hbp_spmv_strategies_match_dense(m, k, density, method, strategy, rng,
                                         kernel_strategy):
    strategy = kernel_strategy(strategy)
    dense = (rng.standard_normal((m, k)) * (rng.random((m, k)) < density)).astype(
        np.float32
    )
    csr = csr_from_dense(dense)
    cfg = PartitionConfig(row_block=64, col_block=128, group=8, lane=32)
    tiles = build_tiles(csr, cfg, method=method)
    x = rng.standard_normal(k).astype(np.float32)
    y = np.asarray(hbp_spmv(tiles, x, strategy=strategy, interpret=True))
    y_ref = dense @ x
    np.testing.assert_allclose(y, y_ref, rtol=1e-4, atol=1e-4)


@given(
    st.integers(8, 120),
    st.integers(8, 200),
    st.floats(0.01, 0.4),
    st.integers(0, 10),
    st.sampled_from([(4, 8), (8, 16), (8, 128)]),
)
@settings(max_examples=20, deadline=None)
def test_hbp_fused_property(m, k, density, seed, geom):
    rng = np.random.default_rng(seed)
    dense = (rng.standard_normal((m, k)) * (rng.random((m, k)) < density)).astype(
        np.float32
    )
    csr = csr_from_dense(dense)
    group, lane = geom
    cfg = PartitionConfig(row_block=4 * group, col_block=2 * lane, group=group, lane=lane)
    tiles = build_tiles(csr, cfg, method="hash")
    x = rng.standard_normal(k).astype(np.float32)
    y = np.asarray(hbp_spmv(tiles, x, strategy="fused", interpret=True))
    np.testing.assert_allclose(y, dense @ x, rtol=1e-4, atol=1e-4)


def test_tile_format_invariants(rng):
    dense = (rng.standard_normal((96, 160) ) * (rng.random((96, 160)) < 0.1)).astype(np.float32)
    csr = csr_from_dense(dense)
    cfg = PartitionConfig(row_block=32, col_block=32, group=8, lane=8)
    tiles = build_tiles(csr, cfg)
    # grid order: rowgroups non-decreasing; first flags mark run starts
    assert (np.diff(tiles.rowgroup) >= 0).all()
    starts = np.flatnonzero(tiles.first)
    assert starts[0] == 0
    assert np.array_equal(np.unique(tiles.rowgroup[starts]), np.unique(tiles.rowgroup))
    # perm is a permutation of padded rows
    assert sorted(tiles.perm.tolist()) == list(range(tiles.perm.size))
    # every nonzero is represented exactly once
    assert np.count_nonzero(tiles.data) == csr.nnz


def test_tuned_geometry_matches_and_reduces_bytes(rng):
    """Beyond-paper adaptive tile geometry: same results, fewer tile bytes
    on sparse-row matrices (EXPERIMENTS.md §Perf phase 2)."""
    from repro.core import tuned_partition_config
    from repro.core.matrices import circuit

    A = circuit(6000, seed=5)
    x = rng.standard_normal(A.n_cols).astype(np.float32)
    y_ref = A.matvec(x)
    base = build_tiles(A, PartitionConfig())
    tuned = build_tiles(A, tuned_partition_config(A))
    for tiles in (base, tuned):
        y = np.asarray(hbp_spmv(tiles, x, strategy="fused", interpret=True))
        np.testing.assert_allclose(y, y_ref, rtol=1e-4, atol=1e-4)
    assert tuned.data.size < base.data.size  # less padding streamed
    assert tuned.nnz_utilization() > base.nnz_utilization()


def test_empty_matrix():
    dense = np.zeros((32, 32), np.float32)
    csr = csr_from_dense(dense)
    tiles = build_tiles(csr, PartitionConfig(row_block=16, col_block=16, group=4, lane=4))
    y = np.asarray(hbp_spmv(tiles, np.ones(32, np.float32), strategy="fused", interpret=True))
    assert y.shape == (32,) and (y == 0).all()
