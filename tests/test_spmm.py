"""Multi-RHS SpMM kernel vs the dense oracle, k independent SpMV calls,
and the end-to-end cross-implementation equivalence sweep.

The equivalence sweep runs every structural family of the scaled Table-I
suite through all three implementation layers — the faithful GPU-semantics
reference (Algorithm 3), the XLA CSR baseline (Algorithm 1), and the
Pallas tile path in ``interpret=True`` — and requires them to agree.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import (
    PartitionConfig,
    build_hbp,
    build_tiles,
    csr_from_dense,
    csr_spmv_jnp,
    hbp_spmv_reference,
    spmm,
    spmv,
)
from repro.core.matrices import banded_fem, circuit, dense_block, rmat, uniform_random
from repro.kernels import hbp_spmm, hbp_spmv


CASES = [
    (64, 64, 0.3, 1),
    (100, 120, 0.1, 4),
    (300, 500, 0.03, 8),
    (257, 130, 0.02, 3),
]


@pytest.mark.parametrize("m,k,density,nrhs", CASES)
@pytest.mark.parametrize("strategy", ["fused", "partials", "reference"])
def test_hbp_spmm_strategies_match_dense(m, k, density, nrhs, strategy, rng):
    dense = (rng.standard_normal((m, k)) * (rng.random((m, k)) < density)).astype(
        np.float32
    )
    csr = csr_from_dense(dense)
    cfg = PartitionConfig(row_block=64, col_block=128, group=8, lane=32)
    tiles = build_tiles(csr, cfg)
    X = rng.standard_normal((k, nrhs)).astype(np.float32)
    Y = np.asarray(hbp_spmm(tiles, X, strategy=strategy, interpret=True))
    np.testing.assert_allclose(Y, dense @ X, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("strategy", ["fused", "partials"])
def test_spmm_equals_k_spmv_calls(strategy, rng):
    """The acceptance property: one SpMM launch == k independent SpMV
    launches, column for column."""
    dense = (rng.standard_normal((150, 220)) * (rng.random((150, 220)) < 0.07)).astype(
        np.float32
    )
    tiles = build_tiles(
        csr_from_dense(dense), PartitionConfig(row_block=64, col_block=64, group=8, lane=16)
    )
    X = rng.standard_normal((220, 6)).astype(np.float32)
    Y = np.asarray(hbp_spmm(tiles, X, strategy=strategy, interpret=True))
    for j in range(X.shape[1]):
        yj = np.asarray(hbp_spmv(tiles, X[:, j], strategy=strategy, interpret=True))
        np.testing.assert_allclose(Y[:, j], yj, rtol=1e-5, atol=1e-5)


def test_spmv_routes_2d_rhs_to_spmm(rng):
    dense = (rng.standard_normal((80, 90)) * (rng.random((80, 90)) < 0.15)).astype(
        np.float32
    )
    csr = csr_from_dense(dense)
    tiles = build_tiles(csr, PartitionConfig(row_block=32, col_block=32, group=8, lane=8))
    X = rng.standard_normal((90, 5)).astype(np.float32)
    np.testing.assert_allclose(
        np.asarray(spmv(tiles, X, backend="jnp")), dense @ X, rtol=1e-4, atol=1e-4
    )
    np.testing.assert_allclose(
        np.asarray(spmm(csr, X, backend="jnp")), dense @ X, rtol=1e-4, atol=1e-4
    )
    np.testing.assert_allclose(spmm(csr, X), dense @ X, rtol=1e-4, atol=1e-4)


def test_spmm_k1_column_vector_keeps_shape(rng):
    """Regression: an [n, 1] RHS takes the SpMM path on every container and
    comes back as [n, 1] — never silently squeezed to [n]."""
    dense = (rng.standard_normal((60, 70)) * (rng.random((60, 70)) < 0.15)).astype(
        np.float32
    )
    csr = csr_from_dense(dense)
    tiles = build_tiles(csr, PartitionConfig(row_block=32, col_block=32, group=8, lane=8))
    hbp = build_hbp(csr, PartitionConfig(row_block=32, col_block=32, group=8, lane=8), warp=8)
    x = rng.standard_normal((70, 1)).astype(np.float32)
    want = dense @ x
    for A in (csr, tiles, hbp):
        for fn in (spmv, spmm):
            y = np.asarray(fn(A, x))
            assert y.shape == (60, 1), f"{type(A).__name__}/{fn.__name__}: {y.shape}"
            np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-4)
    # jnp backends too
    assert np.asarray(spmm(csr, x, backend="jnp")).shape == (60, 1)
    assert np.asarray(spmv(tiles, x, backend="jnp")).shape == (60, 1)


def test_spmv_dispatches_nested_list_by_true_rank(rng):
    """A 2-D input without an .ndim attribute (nested list) must still
    route to the SpMM path instead of falling through to 1-D spmv."""
    dense = (rng.standard_normal((40, 30)) * (rng.random((40, 30)) < 0.2)).astype(
        np.float32
    )
    csr = csr_from_dense(dense)
    x = rng.standard_normal((30, 1)).astype(np.float32)
    y = np.asarray(spmv(csr, x.tolist()))
    assert y.shape == (40, 1)
    np.testing.assert_allclose(y, dense @ x, rtol=1e-4, atol=1e-4)


def test_spmm_spmv_reject_wrong_rank(rng):
    csr = csr_from_dense(np.eye(8, dtype=np.float32))
    with pytest.raises(ValueError, match="spmm expects"):
        spmm(csr, np.ones(8, np.float32))
    with pytest.raises(ValueError, match="spmv expects"):
        spmv(csr, np.ones((8, 1, 1), np.float32))


def test_spmm_empty_matrix():
    tiles = build_tiles(
        csr_from_dense(np.zeros((32, 32), np.float32)),
        PartitionConfig(row_block=16, col_block=16, group=4, lane=4),
    )
    Y = np.asarray(hbp_spmm(tiles, np.ones((32, 3), np.float32), interpret=True))
    assert Y.shape == (32, 3) and (Y == 0).all()


# --- lane-tiled k loop: feature widths beyond one 128-lane tile -----------


def _max_oracle(dense: np.ndarray, X: np.ndarray) -> np.ndarray:
    """max_j(a_ij * x_jk) over stored entries; empty rows -> 0."""
    out = np.zeros((dense.shape[0], X.shape[1]), np.float32)
    for i in range(dense.shape[0]):
        nz = np.nonzero(dense[i])[0]
        if nz.size:
            out[i] = (dense[i, nz, None] * X[nz]).max(axis=0)
    return out


@pytest.mark.parametrize("k", [130, 256])
@pytest.mark.parametrize("strategy", ["fused", "partials", "reference", "stable"])
def test_lane_tiled_wide_k_matches_dense(k, strategy, rng):
    """k > LANE_TILE tiles over sequential <=128-lane chunks inside
    _hbp_spmm_device instead of spilling the lane dimension."""
    from repro.kernels.ops import LANE_TILE

    assert k > LANE_TILE
    dense = (rng.standard_normal((70, 90)) * (rng.random((70, 90)) < 0.12)).astype(
        np.float32
    )
    tiles = build_tiles(
        csr_from_dense(dense), PartitionConfig(row_block=32, col_block=64, group=8, lane=8)
    )
    X = rng.standard_normal((90, k)).astype(np.float32)
    Y = np.asarray(hbp_spmm(tiles, X, strategy=strategy, interpret=True))
    np.testing.assert_allclose(Y, dense @ X, rtol=1e-4, atol=1e-4)


def test_stable_strategy_invariant_across_lane_tiles(rng):
    """A column's bits must not depend on the launch width even when the
    width crosses the LANE_TILE boundary — the serving guarantee extended
    to GNN feature blocks."""
    dense = (rng.standard_normal((60, 80)) * (rng.random((60, 80)) < 0.15)).astype(
        np.float32
    )
    tiles = build_tiles(
        csr_from_dense(dense), PartitionConfig(row_block=32, col_block=32, group=8, lane=8)
    )
    X = rng.standard_normal((80, 200)).astype(np.float32)
    Y_wide = np.asarray(hbp_spmm(tiles, X, strategy="stable"))
    for j in (0, 127, 128, 199):  # columns straddling the chunk boundary
        yj = np.asarray(hbp_spmv(tiles, X[:, j], strategy="stable"))
        assert np.array_equal(Y_wide[:, j], yj), f"column {j}"
    Y_narrow = np.asarray(hbp_spmm(tiles, X[:, :130], strategy="stable"))
    assert np.array_equal(Y_narrow, Y_wide[:, :130])


# --- max-monoid combine (GNN max aggregation) ------------------------------


@pytest.mark.parametrize("k", [1, 5, 16, 256])
@pytest.mark.parametrize("strategy", ["fused", "partials", "reference", "stable"])
def test_hbp_spmm_max_matches_oracle(k, strategy, rng):
    dense = (rng.standard_normal((60, 70)) * (rng.random((60, 70)) < 0.12)).astype(
        np.float32
    )
    dense[7] = 0.0  # empty rows inside occupied groups
    dense[31] = 0.0
    tiles = build_tiles(
        csr_from_dense(dense), PartitionConfig(row_block=32, col_block=32, group=8, lane=8)
    )
    X = rng.standard_normal((70, k)).astype(np.float32)
    Y = np.asarray(
        hbp_spmm(tiles, X, strategy=strategy, combine="max", interpret=True)
    )
    # max is exact arithmetic (no reassociation error): exact equality
    np.testing.assert_array_equal(Y, _max_oracle(dense, X))


@pytest.mark.parametrize("strategy", ["fused", "partials", "stable"])
def test_max_identity_never_leaks_on_empty_rows(strategy, rng):
    """Satellite acceptance: with all-negative features, empty rows must
    yield exactly 0 — the -inf identity of the max monoid (and the 0 of a
    padded slot's product) must never surface."""
    dense = np.zeros((48, 50), np.float32)
    keep = rng.random((48, 50)) < 0.1
    keep[::5] = False  # every 5th row fully empty
    # positive weights: every stored product of a negative feature is
    # negative, so a leaked 0 (padded slot) or -inf (identity) would show
    dense[keep] = (0.1 + rng.random(int(keep.sum()))).astype(np.float32)
    csr = csr_from_dense(dense)
    tiles = build_tiles(csr, PartitionConfig(row_block=16, col_block=32, group=4, lane=4))
    X = -1.0 - rng.random((50, 6)).astype(np.float32)  # strictly negative
    Y = np.asarray(hbp_spmm(tiles, X, strategy=strategy, combine="max", interpret=True))
    assert np.isfinite(Y).all()
    empty = np.asarray(csr.row_nnz() == 0)
    assert (Y[empty] == 0).all(), "empty rows must be exactly 0"
    # non-empty rows of an all-negative product really are negative — the
    # padded slots' 0 product did not win the max
    np.testing.assert_array_equal(Y, _max_oracle(dense, X))
    assert (Y[~empty] < 0).all()


@pytest.mark.parametrize("k", [1, 6, 256])
@pytest.mark.parametrize("combine", ["sum", "max"])
def test_fused_row_groups_without_tiles_are_zero(combine, k, rng):
    """Row groups no tile visits come back exactly 0: the fused kernels'
    output starts at the monoid identity, which no launch overwrites."""
    dense = np.zeros((64, 40), np.float32)
    dense[::4] = rng.standard_normal((16, 40)) * (rng.random((16, 40)) < 0.5)
    tiles = build_tiles(
        csr_from_dense(dense), PartitionConfig(row_block=32, col_block=32, group=4, lane=4)
    )
    assert len(np.unique(tiles.rowgroup)) < tiles.n_rowgroups  # the hash clusters them
    X = -1.0 - rng.random((40, k)).astype(np.float32)
    if combine == "sum" and k == 1:
        Y = np.asarray(hbp_spmv(tiles, X[:, 0], strategy="fused", interpret=True))[:, None]
    else:
        Y = np.asarray(hbp_spmm(tiles, X, strategy="fused", combine=combine, interpret=True))
    empty = ~dense.any(axis=1)
    assert (Y[empty] == 0).all()
    expect = dense @ X if combine == "sum" else _max_oracle(dense, X)
    np.testing.assert_allclose(Y, expect, rtol=1e-5, atol=1e-5)


def test_max_combine_empty_matrix_is_zero():
    tiles = build_tiles(
        csr_from_dense(np.zeros((16, 16), np.float32)),
        PartitionConfig(row_block=8, col_block=8, group=4, lane=4),
    )
    Y = np.asarray(
        hbp_spmm(tiles, np.ones((16, 3), np.float32), combine="max", interpret=True)
    )
    assert Y.shape == (16, 3) and (Y == 0).all()


def test_unknown_combine_rejected(rng):
    tiles = build_tiles(
        csr_from_dense(np.eye(8, dtype=np.float32)),
        PartitionConfig(row_block=8, col_block=8, group=4, lane=4),
    )
    with pytest.raises(ValueError, match="combine"):
        hbp_spmm(tiles, np.ones((8, 2), np.float32), combine="min", interpret=True)


# --- end-to-end equivalence across the scaled Table-I structural families ---

FAMILIES = {
    "rmat": lambda: rmat(1 << 9, 3000, seed=4),
    "circuit": lambda: circuit(700, seed=1, n_dense_rows=3, dense_row_frac=0.02),
    "banded_fem": lambda: banded_fem(600, seed=3, band=4, fill=0.9),
    "dense_block": lambda: dense_block(512, seed=8, block=48, n_blocks=3, background=4.0),
    "uniform": lambda: uniform_random(400, 0.01, seed=0),
}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_end_to_end_equivalence(family):
    """hbp_spmv_reference (Algorithm 3) vs csr_spmv_jnp (Algorithm 1) vs
    the Pallas interpret path, on every suite generator family."""
    csr = FAMILIES[family]()
    x = np.random.default_rng(7).standard_normal(csr.n_cols).astype(np.float32)

    y_csr_np = csr.matvec(x)
    y_csr_jnp = np.asarray(
        csr_spmv_jnp(
            jnp.asarray(csr.indptr),
            jnp.asarray(csr.indices),
            jnp.asarray(csr.data.astype(np.float32)),
            jnp.asarray(x),
            csr.n_rows,
        )
    )
    cfg = PartitionConfig(row_block=128, col_block=256, group=8, lane=16)
    hbp = build_hbp(csr, cfg, warp=8, method="hash")
    y_hbp_ref = hbp_spmv_reference(hbp, x.astype(np.float64))
    tiles = build_tiles(csr, cfg, method="hash")
    y_pallas = np.asarray(spmv(tiles, x, backend="pallas", interpret=True))

    scale = np.abs(y_csr_np).max() + 1e-12
    np.testing.assert_allclose(y_csr_jnp / scale, y_csr_np / scale, atol=2e-6)
    np.testing.assert_allclose(y_hbp_ref / scale, y_csr_np / scale, atol=2e-6)
    np.testing.assert_allclose(y_pallas / scale, y_csr_np / scale, atol=2e-6)


@pytest.mark.parametrize("k", [1, 3, 256])
@pytest.mark.parametrize("combine", ["sum", "max"])
@pytest.mark.parametrize("strategy", ["fused", "partials"])
def test_launches_split_into_tile_chunks_match_dense(strategy, combine, k, rng, monkeypatch):
    """A tile stream longer than one launch's SMEM budget runs as several
    launches over one output buffer; row-group runs that straddle a launch
    boundary continue where the previous launch left off."""
    import importlib

    import jax

    kernels = importlib.import_module("repro.kernels.hbp_spmv")
    monkeypatch.setattr(kernels, "TILE_CHUNK", 5)
    # fused grid steps of 5 tiles: each launch is one step over several row groups
    monkeypatch.setattr(kernels, "BLOCK_LANE", 5)
    monkeypatch.setattr(kernels, "BLOCK_ROW", 5)
    jax.clear_caches()  # the wrappers are jitted: retrace under the small chunk
    # long rows: row groups span many tiles, so runs straddle chunks
    dense = (rng.standard_normal((40, 120)) * (rng.random((40, 120)) < 0.4)).astype(
        np.float32
    )
    tiles = build_tiles(
        csr_from_dense(dense), PartitionConfig(row_block=16, col_block=64, group=8, lane=4)
    )
    assert tiles.n_tiles > 5 * 4 and (tiles.first[5::5] == 0).any()
    X = rng.standard_normal((120, k)).astype(np.float32)
    if combine == "sum" and k == 1:
        Y = np.asarray(hbp_spmv(tiles, X[:, 0], strategy=strategy, interpret=True))[:, None]
    else:
        Y = np.asarray(
            hbp_spmm(tiles, X, strategy=strategy, combine=combine, interpret=True)
        )
    expect = dense @ X if combine == "sum" else _max_oracle(dense, X)
    jax.clear_caches()
    np.testing.assert_allclose(Y, expect, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("split", [False, True])
@pytest.mark.parametrize("k,combine", [(1, "sum"), (1, "max"), (16, "sum"), (16, "max")])
def test_staged_padding_tiles_change_nothing(k, combine, split, monkeypatch):
    """``build_tiles(bucket_tiles=True)`` pads the tile stream with empty
    tiles up to ``staged_tile_count``: they continue the last row group and
    add nothing, on the lane gather (k=1) and the row gather (k=16); with
    ``split`` the padding is a launch of its own, which carries the last
    row group on."""
    import importlib

    import jax

    from repro.core.tile import staged_tile_count
    from repro.kernels import ops

    kernels = importlib.import_module("repro.kernels.hbp_spmv")
    rng = np.random.default_rng(0)
    dense = (rng.standard_normal((48, 256)) * (rng.random((48, 256)) < 0.3)).astype(
        np.float32
    )
    cfg = PartitionConfig(row_block=16, col_block=64, group=8, lane=4)
    plain = build_tiles(csr_from_dense(dense), cfg)
    tiles = build_tiles(csr_from_dense(dense), cfg, bucket_tiles=True)
    T, n = plain.n_tiles, staged_tile_count(plain.n_tiles)
    assert n > T and tiles.n_tiles == n and tiles.first[T] == 0
    for field in ("data", "cols", "rowgroup", "colblock", "first"):
        np.testing.assert_array_equal(getattr(tiles, field)[:T], getattr(plain, field))
    assert not tiles.data[T:].any() and not tiles.first[T:].any()
    assert (tiles.rowgroup[T:] == plain.rowgroup[-1]).all()
    dt = ops.device_tiles(tiles)
    assert (np.asarray(dt.width)[T:] == 0).all()
    if split:
        monkeypatch.setattr(kernels, "TILE_CHUNK", T)
        monkeypatch.setattr(kernels, "BLOCK_LANE", T)
        monkeypatch.setattr(kernels, "BLOCK_ROW", T)
    jax.clear_caches()  # the wrappers are jitted: retrace under the chunk
    X = rng.standard_normal((256, k)).astype(np.float32)
    meta = dict(n_rowgroups=tiles.n_rowgroups, n_rows=48, col_block=64, interpret=True)
    if combine == "sum" and k == 1:
        Y = np.asarray(ops.hbp_spmv(dt, X[:, 0], **meta))[:, None]
    else:
        Y = np.asarray(ops.hbp_spmm(dt, X, combine=combine, **meta))
    jax.clear_caches()
    expect = dense @ X if combine == "sum" else _max_oracle(dense, X)
    np.testing.assert_allclose(Y, expect, rtol=1e-4, atol=1e-4)


# --- the fused SpMM's row gather -------------------------------------------


def _row_gather_problem(rng, lane):
    """A matrix with every kind of row the row gather meets: scattered rows,
    rows of one entry (row groups whose tiles are one slot wide), a row
    longer than a tile (full-width tiles) and empty rows."""
    m, n = 64, 300
    dense = np.zeros((m, n), np.float32)
    dense[:16] = rng.standard_normal((16, n)) * (rng.random((16, n)) < 0.05)
    dense[16:32, rng.integers(0, n, 16)] = np.diag(rng.standard_normal(16))
    dense[32, :200] = rng.standard_normal(200)
    dense[33:40] = rng.standard_normal((7, n)) * (rng.random((7, n)) < 0.2)
    tiles = build_tiles(
        csr_from_dense(dense), PartitionConfig(row_block=64, col_block=128, group=8, lane=lane)
    )
    return dense, tiles


ROW_GATHER_CASES = [
    (lane, k, combine, False)
    for lane in (8, 128) for k in (8, 16, 128, 256) for combine in ("sum", "max")
] + [(8, 16, "sum", True), (8, 256, "max", True), (128, 128, "sum", True), (128, 8, "max", True)]


@pytest.mark.parametrize("lane,k,combine,split", ROW_GATHER_CASES)
def test_row_gather_matches_reference(lane, k, combine, split, rng, monkeypatch):
    """The row body against ``ref.py``: rows of no entry come back as the
    monoid identity from the kernel (0, or -inf under max) and 0 once
    assembled; tiles one slot wide and full width; with ``split``, launches
    short enough that a row group's run crosses a launch boundary."""
    import importlib

    from repro.kernels import ops, ref

    kernels = importlib.import_module("repro.kernels.hbp_spmv")
    dense, tiles = _row_gather_problem(rng, lane)
    dt = ops.device_tiles(tiles)
    width = np.asarray(dt.width)
    assert width.min() == 1 and width.max() == lane
    fn = kernels.hbp_spmm_fused if combine == "sum" else kernels.hbp_spmm_fused_max
    if split:
        # the first launch ends inside a run: its last tile's group goes on
        monkeypatch.setattr(kernels, "TILE_CHUNK", int(np.flatnonzero(tiles.first == 0)[-1]))
        monkeypatch.setattr(kernels, "BLOCK_ROW", 1)  # launches of exactly TILE_CHUNK tiles
        fn.clear_cache()  # the wrapper is jitted: retrace under the small chunk
    X = rng.standard_normal((dense.shape[1], k)).astype(np.float32)
    xb = ops.blocked_matrix(jnp.asarray(X), tiles.cfg.col_block)
    assert kernels.gather_body(k, xb.shape[0] * xb.shape[1]) == "row"
    hashed = fn(dt.rowgroup, dt.colblock, dt.data, dt.cols, xb, dt.width,
                n_rowgroups=tiles.n_rowgroups, interpret=True)
    empty = ~dense.any(axis=1)
    # hashed slots of empty rows and of padding (perm maps slots to rows)
    empty_slots = ~np.isin(np.asarray(dt.perm), np.flatnonzero(~empty))
    identity = 0.0 if combine == "sum" else -np.inf
    assert (np.asarray(hashed).reshape(-1, k)[empty_slots] == identity).all()
    Y = np.asarray(ref.unpermute(jnp.where(jnp.isneginf(hashed), 0.0, hashed), dt.perm,
                                 dense.shape[0]))
    assert (Y[empty] == 0).all()
    want = np.asarray(hbp_spmm(tiles, X, strategy="reference", combine=combine))
    if combine == "max":
        np.testing.assert_array_equal(Y, want)  # products are the same, max is exact
    else:
        np.testing.assert_allclose(Y, want, rtol=1e-5, atol=1e-5)


BLOCKED_CASES = [
    (body, k, combine, split)
    for body in ("lane", "row") for k in (1, 4, 8, 16, 128, 256)
    for combine in ("sum", "max") for split in (False, True)
]


@pytest.mark.parametrize("body,k,combine,split", BLOCKED_CASES)
def test_blocked_grid_matches_reference(body, k, combine, split, rng, monkeypatch):
    """Either tile body on the blocked grid, at any k, against ``ref.py``.
    The 47 tiles are no whole number of blocks; a block spans several row
    groups, and one row group's run of 27 tiles spans several blocks (or,
    under the lane gather, one short block holds all 47); two row groups
    have no tile, so they keep the identity.  With ``split``:
    blocks of 3, launches of 12 tiles and a window of 2 row groups, so runs
    cross launch boundaries and windows: a launch that did not continue
    from what the previous one wrote back would fail here."""
    import importlib

    from repro.kernels import ops, ref

    kernels = importlib.import_module("repro.kernels.hbp_spmv")
    dense, tiles = _row_gather_problem(rng, 8)
    dt = ops.device_tiles(tiles)
    block = 3 if split else {"lane": kernels.BLOCK_LANE, "row": kernels.BLOCK_ROW}[body]
    assert tiles.n_tiles % block != 0
    if block < tiles.n_tiles:  # else one short block holds every tile
        runs = np.diff(np.append(np.flatnonzero(tiles.first), tiles.n_tiles))
        assert (runs > block).any()  # a run spans blocks
    if split:
        monkeypatch.setattr(kernels, "TILE_CHUNK", 12)
        monkeypatch.setattr(kernels, "WINDOW_BYTES", 2 * 8 * kernels.LANE_TILE * 4)
        assert kernels._window(8, tiles.n_rowgroups) == 2
        assert (tiles.first[12::12] == 0).any()  # a run crosses a launch boundary
    X = rng.standard_normal((dense.shape[1], k)).astype(np.float32)
    xb = ops.blocked_matrix(jnp.asarray(X), tiles.cfg.col_block)
    kw = dict(n_rowgroups=tiles.n_rowgroups, combine=combine, interpret=True,
              name="hbp_spmm_fused", block=block)
    if body == "lane":
        hashed = kernels._fused(dt.rowgroup, dt.colblock, dt.data, dt.cols, xb, **kw)
    else:
        hashed = kernels._fused_rows(dt.rowgroup, dt.colblock, dt.width, dt.data, dt.cols,
                                     xb, **kw)
    hashed = np.asarray(hashed[..., :k])
    empty_slots = ~np.isin(np.asarray(dt.perm), np.flatnonzero(dense.any(axis=1)))
    identity = 0.0 if combine == "sum" else -np.inf
    assert (hashed.reshape(-1, k)[empty_slots] == identity).all()
    Y = np.asarray(ref.unpermute(jnp.where(jnp.isneginf(hashed), 0.0, hashed), dt.perm,
                                 dense.shape[0]))
    want = np.asarray(hbp_spmm(tiles, X, strategy="reference", combine=combine))
    if combine == "max":
        np.testing.assert_array_equal(Y, want)
    else:
        np.testing.assert_allclose(Y, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("k,x_rows,body", [
    (1, 65_536, "lane"),
    (4, 65_536, "lane"),  # below K_ROW: the lane gather is the cheaper body
    (8, 65_536, "row"),
    (128, 65_536, "row"),
    (256, 65_536, "row"),
    (8, 196_608, "row"),  # X of 96 MiB: exactly the budget
    (8, 196_616, "lane"),  # one sublane group over it
    (128, 327_680, "lane"),
])
def test_gather_body_choice(k, x_rows, body):
    import importlib

    kernels = importlib.import_module("repro.kernels.hbp_spmv")
    assert kernels.gather_body(k, x_rows) == body


@pytest.mark.parametrize("op,strategy,k,gather,block", [
    ("spmm", "fused", 8, "row", "BLOCK_ROW"),
    ("spmm", "fused", 4, "lane", "BLOCK_LANE"),
    ("spmv", "fused", 1, "lane", "BLOCK_LANE"),
    ("spmm", "partials", 8, "lane", 1),
    ("spmm", "stable", 8, "none", 0),
])
def test_launch_counter_labels_gather(op, strategy, k, gather, block, rng):
    """``kernels.launches`` names the tile body each launch runs and the
    tiles each of its grid steps carries."""
    import importlib

    from repro import obs

    if isinstance(block, str):
        block = getattr(importlib.import_module("repro.kernels.hbp_spmv"), block)
        assert block > 1

    dense = (rng.standard_normal((40, 50)) * (rng.random((40, 50)) < 0.2)).astype(np.float32)
    tiles = build_tiles(
        csr_from_dense(dense), PartitionConfig(row_block=64, col_block=128, lane=16)
    )
    X = rng.standard_normal((50, k)).astype(np.float32)
    obs.reset()
    obs.enable()
    try:
        if op == "spmv":
            hbp_spmv(tiles, X[:, 0], strategy=strategy, interpret=True)
        else:
            hbp_spmm(tiles, X, strategy=strategy, interpret=True)
        assert obs.registry().value(
            "kernels.launches", op=op, strategy=strategy, k_tiling="grid", combine="sum",
            gather=gather, block=block,
        ) == 1
    finally:
        obs.disable()
        obs.reset()
