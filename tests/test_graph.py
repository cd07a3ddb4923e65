"""Graph workload subsystem: construction, aggregation monoids, GNN layers.

Acceptance (ISSUE 3): GCN/GraphSAGE forward on a 10k-node synthetic
power-law graph matches a dense-oracle reference — sum/mean to fp32
tolerance, max exactly — for feature dims 16 through 256, with k = 256
exercising the lane-tiled kernel path rather than a fallback.
"""
import numpy as np
import pytest

import jax

from repro.core import PartitionConfig, build_tiles
from repro.core.formats import CSRMatrix
from repro.graph import (
    add_self_loops,
    aggregate,
    degrees,
    gcn_forward,
    graph_from_edges,
    init_gcn,
    init_sage,
    make_aggregator,
    normalize_adjacency,
    plan_aggregator,
    power_law_graph,
    rmat_graph,
    sage_forward,
)


# --- numpy oracles (CSR-based: the 10k acceptance graph has no dense form) --


def _sum_oracle(csr: CSRMatrix, X: np.ndarray) -> np.ndarray:
    rows = np.repeat(np.arange(csr.n_rows), csr.row_nnz())
    out = np.zeros((csr.n_rows, X.shape[1]), np.float64)
    np.add.at(out, rows, csr.data[:, None] * X[csr.indices])
    return out


def _mean_oracle(csr: CSRMatrix, X: np.ndarray) -> np.ndarray:
    return _sum_oracle(csr, X) / np.maximum(csr.row_nnz(), 1)[:, None]


def _max_oracle(csr: CSRMatrix, X: np.ndarray) -> np.ndarray:
    rows = np.repeat(np.arange(csr.n_rows), csr.row_nnz())
    live = csr.data != 0
    out = np.full((csr.n_rows, X.shape[1]), -np.inf, np.float32)
    np.maximum.at(
        out, rows[live], (csr.data[live, None] * X[csr.indices[live]]).astype(np.float32)
    )
    out[np.isneginf(out).all(axis=1)] = 0.0
    out[np.isneginf(out)] = 0.0
    return out


# --- construction ----------------------------------------------------------


def test_graph_from_edges_directed_dedup():
    A = graph_from_edges([0, 1, 2, 2], [1, 2, 0, 0], n_nodes=4)
    D = A.to_dense()
    # row = destination, col = source; the repeated (2 -> 0) edge is one edge
    want = np.zeros((4, 4))
    want[1, 0] = want[2, 1] = want[0, 2] = 1
    np.testing.assert_array_equal(D, want)


def test_graph_from_edges_symmetric_and_self_loops():
    A = graph_from_edges([0, 1], [1, 2], n_nodes=3, symmetric=True, self_loops=True)
    D = A.to_dense()
    assert (D == D.T).all()
    np.testing.assert_array_equal(np.diagonal(D), np.ones(3))
    # idempotent self-loops: renormalizing never doubles the diagonal
    np.testing.assert_array_equal(add_self_loops(A).to_dense(), D)


def test_graph_from_edges_weighted_sums_duplicates():
    A = graph_from_edges([0, 0], [1, 1], n_nodes=2, weights=[2.0, 3.0])
    assert A.to_dense()[1, 0] == 5.0


def test_graph_from_edges_validation():
    with pytest.raises(ValueError, match="length mismatch"):
        graph_from_edges([0, 1], [1])
    with pytest.raises(ValueError, match="outside"):
        graph_from_edges([0], [5], n_nodes=3)


@pytest.mark.parametrize("kind", ["sym", "row"])
def test_normalize_adjacency_vs_dense(kind, rng):
    G = power_law_graph(300, 6.0, seed=1)
    D = G.to_dense()
    deg = D.sum(axis=1)
    with np.errstate(divide="ignore", invalid="ignore"):
        if kind == "sym":
            s = np.where(deg > 0, 1.0 / np.sqrt(deg), 0.0)
            want = s[:, None] * D * s[None, :]
        else:
            want = np.where(deg > 0, 1.0 / deg, 0.0)[:, None] * D
    np.testing.assert_allclose(normalize_adjacency(G, kind).to_dense(), want, atol=1e-6)
    # row-stochastic: every non-empty row sums to 1
    if kind == "row":
        rows = normalize_adjacency(G, kind).to_dense().sum(axis=1)
        np.testing.assert_allclose(rows[deg > 0], 1.0, atol=1e-5)


def test_normalize_none_and_unknown():
    G = rmat_graph(64, 4.0, seed=0)
    np.testing.assert_array_equal(normalize_adjacency(G, "none").to_dense(), G.to_dense())
    with pytest.raises(ValueError, match="normalization"):
        normalize_adjacency(G, "colwise")


def test_power_law_graph_exact_n_and_skew():
    G = power_law_graph(1000, 8.0, seed=3)
    assert G.shape == (1000, 1000)
    d = degrees(G)
    # power-law skew: the hub dwarfs the median — the load-imbalance
    # profile the nonlinear hash targets
    assert d.max() > 10 * max(np.median(d), 1)
    assert (G.to_dense() == G.to_dense().T).all()


def test_rmat_graph_is_binary():
    G = rmat_graph(128, 4.0, seed=1)
    assert set(np.unique(G.data)) <= {1.0}


# --- aggregation operators -------------------------------------------------


@pytest.mark.parametrize("op", ["sum", "mean", "max"])
@pytest.mark.parametrize("strategy", ["fused", "fused_streamed", "reference", "stable"])
def test_aggregate_matches_oracle_small(op, strategy, rng, kernel_strategy):
    strategy = kernel_strategy(strategy)
    G = power_law_graph(200, 5.0, seed=2)
    X = rng.standard_normal((200, 12)).astype(np.float32)
    tiles = build_tiles(G, PartitionConfig(row_block=64, col_block=64, group=8, lane=8))
    Y = np.asarray(
        aggregate(tiles, X, op=op, degree=degrees(G), strategy=strategy, interpret=True)
    )
    oracle = {"sum": _sum_oracle, "mean": _mean_oracle, "max": _max_oracle}[op](G, X)
    if op == "max":
        np.testing.assert_array_equal(Y, oracle)
    else:
        np.testing.assert_allclose(Y, oracle, rtol=1e-4, atol=1e-4)


def test_aggregate_isolated_nodes_are_zero(rng):
    """Nodes with no in-neighbors aggregate to 0 under every op — the max
    monoid's -inf identity must not leak (satellite acceptance)."""
    # nodes 3 and 7 have no incoming edges
    src = [0, 1, 2, 4, 5]
    dst = [1, 2, 0, 5, 6]
    G = graph_from_edges(src, dst, n_nodes=8)
    X = -1.0 - rng.random((8, 4)).astype(np.float32)  # strictly negative
    tiles = build_tiles(G, PartitionConfig(row_block=8, col_block=8, group=4, lane=4))
    iso = np.asarray(degrees(G) == 0)
    assert iso.sum() >= 2
    for op in ("sum", "mean", "max"):
        Y = np.asarray(aggregate(tiles, X, op=op, degree=degrees(G), interpret=True))
        assert np.isfinite(Y).all()
        assert (Y[iso] == 0).all(), op


def test_aggregate_validation(rng):
    G = rmat_graph(32, 2.0, seed=0)
    tiles = build_tiles(G, PartitionConfig(row_block=32, col_block=32, group=8, lane=8))
    X = rng.standard_normal((32, 2)).astype(np.float32)
    with pytest.raises(ValueError, match="unknown aggregation"):
        aggregate(tiles, X, op="median")
    with pytest.raises(ValueError, match="degree"):
        aggregate(tiles, X, op="mean")
    with pytest.raises(ValueError, match="degree"):
        make_aggregator(tiles, op="mean")


def test_make_aggregator_mean_accepts_device_degree(rng):
    """degree may arrive as a jax array (e.g. computed on device by a
    training loop) — no host np.asarray round-trip in the closure build."""
    import jax.numpy as jnp

    G = power_law_graph(120, 4.0, seed=9)
    X = rng.standard_normal((120, 6)).astype(np.float32)
    deg_dev = jnp.asarray(degrees(G))
    agg = make_aggregator(G, op="mean", degree=deg_dev)
    np.testing.assert_allclose(
        np.asarray(agg(X)), _mean_oracle(G, X), rtol=1e-4, atol=1e-4
    )
    tiles = build_tiles(G, PartitionConfig(row_block=64, col_block=64, group=8, lane=8))
    Y = aggregate(tiles, X, op="mean", degree=deg_dev, interpret=True)
    np.testing.assert_allclose(np.asarray(Y), _mean_oracle(G, X), rtol=1e-4, atol=1e-4)


def test_make_aggregator_closure_is_jittable(rng):
    G = power_law_graph(150, 4.0, seed=4)
    agg = make_aggregator(G, op="mean")
    X = rng.standard_normal((150, 8)).astype(np.float32)
    Y = np.asarray(jax.jit(agg)(X))
    np.testing.assert_allclose(Y, _mean_oracle(G, X), rtol=1e-4, atol=1e-4)


# --- serving-plan wiring ---------------------------------------------------


def test_plan_aggregator_through_registry(tmp_path, rng):
    from repro.serving import MatrixRegistry

    G = power_law_graph(250, 5.0, seed=6)
    reg = MatrixRegistry(cache_dir=tmp_path / "cache", search=False)
    plan = reg.admit(G, "graph")
    X = rng.standard_normal((250, 10)).astype(np.float32)
    np.testing.assert_allclose(
        np.asarray(plan_aggregator(plan, op="sum")(X)), _sum_oracle(G, X),
        rtol=1e-4, atol=1e-4,
    )
    np.testing.assert_allclose(
        np.asarray(plan_aggregator(plan, op="mean")(X)), _mean_oracle(G, X),
        rtol=1e-4, atol=1e-4,
    )
    np.testing.assert_array_equal(
        np.asarray(plan_aggregator(plan, op="max")(X)), _max_oracle(G, X)
    )
    # re-admission of the same content reuses the resident plan
    assert reg.admit(G) is plan
    with pytest.raises(ValueError, match="unknown aggregation"):
        plan.aggregate(X, op="median")


def test_gcn_forward_over_plan_aggregator(tmp_path, rng):
    from repro.serving import MatrixRegistry

    G = power_law_graph(180, 5.0, seed=7)
    A_hat = normalize_adjacency(add_self_loops(G), "sym")
    reg = MatrixRegistry(cache_dir=tmp_path / "cache", search=False)
    plan = reg.admit(A_hat, "gcn-adj")
    X = rng.standard_normal((180, 16)).astype(np.float32)
    params = init_gcn(jax.random.PRNGKey(0), [16, 8, 3])
    out = np.asarray(gcn_forward(plan_aggregator(plan), params, X))
    D = A_hat.to_dense()
    h = np.maximum(D @ (X @ np.asarray(params[0].W)) + np.asarray(params[0].b), 0)
    want = D @ (h @ np.asarray(params[1].W)) + np.asarray(params[1].b)
    np.testing.assert_allclose(out, want, rtol=2e-4, atol=2e-4)


# --- acceptance: 10k-node power-law graph, k = 16 .. 256 -------------------


@pytest.fixture(scope="module")
def big_graph():
    return power_law_graph(10_000, 6.0, seed=42)


def _gcn_oracle(csr, params, X):
    h = X.astype(np.float64)
    for i, p in enumerate(params):
        h = _sum_oracle(csr, h @ np.asarray(p.W, np.float64)) + np.asarray(p.b)
        if i < len(params) - 1:
            h = np.maximum(h, 0)
    return h


def _sage_oracle(csr, params, X, op):
    agg = {"mean": _mean_oracle, "max": _max_oracle}[op]
    h = X.astype(np.float64)
    for i, p in enumerate(params):
        h = (
            h @ np.asarray(p.W_self, np.float64)
            + agg(csr, h) @ np.asarray(p.W_neigh, np.float64)
            + np.asarray(p.b)
        )
        if i < len(params) - 1:
            h = np.maximum(h, 0)
    return h


@pytest.mark.parametrize("k", [16, 64, 128, 256])
def test_gcn_forward_10k_power_law(big_graph, k, rng):
    from repro.kernels.ops import LANE_TILE, bucket_k

    if k == 256:  # the lane-tiled path, not a fallback: two full lane tiles
        assert k > LANE_TILE and bucket_k(k) == 256
    A_hat = normalize_adjacency(add_self_loops(big_graph), "sym")
    agg = make_aggregator(A_hat, op="sum")
    params = init_gcn(jax.random.PRNGKey(k), [k, 32, 8])
    X = rng.standard_normal((10_000, k)).astype(np.float32)
    out = np.asarray(gcn_forward(agg, params, X))
    want = _gcn_oracle(A_hat, params, X)
    scale = np.abs(want).max() + 1e-12
    np.testing.assert_allclose(out / scale, want / scale, atol=5e-6)


@pytest.mark.parametrize("k", [16, 64, 128, 256])
@pytest.mark.parametrize("op", ["mean", "max"])
def test_sage_forward_10k_power_law(big_graph, k, op, rng):
    """GraphSAGE aggregates at the RAW feature width: k = 256 drives the
    lane-tiled k loop through a full two-layer forward."""
    agg = make_aggregator(big_graph, op=op)
    params = init_sage(jax.random.PRNGKey(100 + k), [k, 32, 8])
    X = rng.standard_normal((10_000, k)).astype(np.float32)
    out = np.asarray(sage_forward(agg, params, X))
    want = _sage_oracle(big_graph, params, X, op)
    scale = np.abs(want).max() + 1e-12
    np.testing.assert_allclose(out / scale, want / scale, atol=5e-6)


@pytest.mark.parametrize("k", [16, 256])
def test_max_aggregation_10k_is_exact(big_graph, k, rng):
    """The monoid path is reassociation-free: raw max aggregation over the
    10k graph is bit-exact against the numpy oracle, including at the
    lane-tiled width."""
    agg = make_aggregator(big_graph, op="max")
    X = rng.standard_normal((10_000, k)).astype(np.float32)
    np.testing.assert_array_equal(np.asarray(agg(X)), _max_oracle(big_graph, X))