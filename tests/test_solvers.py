"""Iterative solvers vs dense numpy references.

Covers the acceptance criteria: CG and power iteration on every scaled
Table-I structural family through the HBP Pallas path (``interpret=True``
on CPU), matching ``np.linalg.solve`` / ``np.linalg.eigvalsh`` to 1e-5,
with multi-RHS solves validated against per-column runs.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import PartitionConfig, build_tiles, csr_from_dense
from repro.core.matrices import banded_fem, circuit, dense_block, rmat
from repro.solvers import (
    LinearOperator,
    aslinearoperator,
    bicgstab,
    block_jacobi,
    cg,
    chebyshev,
    estimate_spectrum,
    hash_group_blocks,
    jacobi,
    pagerank,
    power_iteration,
    transition_matrix,
)

CFG = PartitionConfig(row_block=64, col_block=128, group=8, lane=16)

# SPD analogues of the suite's structural families: S = A A^T / n + I keeps
# each family's sparsity signature while guaranteeing a well-conditioned
# symmetric positive definite system with a dense-solve reference.
FAMILIES = {
    "rmat": lambda: rmat(1 << 7, 900, seed=4),
    "circuit": lambda: circuit(128, seed=1, n_dense_rows=2, dense_row_frac=0.05),
    "banded_fem": lambda: banded_fem(128, seed=3, band=4, fill=0.9),
    "dense_block": lambda: dense_block(128, seed=8, block=24, n_blocks=2, background=3.0),
}


def hbp_op(tiles):
    """The HBP operator on the fused Pallas kernels (interpreted off-TPU)."""
    return aslinearoperator(tiles, strategy="fused", interpret=True)


def spd_family(name):
    A = FAMILIES[name]().to_dense().astype(np.float64)
    n = A.shape[0]
    return (A @ A.T / n + np.eye(n)).astype(np.float32)


@pytest.fixture(scope="module")
def spd64():
    rng = np.random.default_rng(0)
    G = rng.standard_normal((64, 64)).astype(np.float32) * (rng.random((64, 64)) < 0.3)
    return (G @ G.T / 64 + 2 * np.eye(64, dtype=np.float32)).astype(np.float32)


# --- operator abstraction -------------------------------------------------


def test_operator_adapts_every_container(spd64, rng):
    x = rng.standard_normal(64).astype(np.float32)
    X = rng.standard_normal((64, 3)).astype(np.float32)
    csr = csr_from_dense(spd64)
    tiles = build_tiles(csr, PartitionConfig(row_block=32, col_block=32, group=8, lane=8))
    y_ref = spd64 @ x
    Y_ref = spd64 @ X
    for container in (spd64, csr, tiles):
        op = aslinearoperator(container, strategy="fused", interpret=True)
        np.testing.assert_allclose(np.asarray(op(x)), y_ref, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(op(X)), Y_ref, rtol=1e-4, atol=1e-4)
    # matvec-only operators synthesize matmat column by column
    op = LinearOperator(spd64.shape, matvec=lambda v: jnp.asarray(spd64) @ v)
    np.testing.assert_allclose(np.asarray(op.matmat(jnp.asarray(X))), Y_ref, rtol=1e-4, atol=1e-4)


def test_operator_rejects_unknown():
    with pytest.raises(TypeError):
        aslinearoperator("not a matrix")
    with pytest.raises(ValueError):
        aslinearoperator(np.ones(3, np.float32))


# --- CG -------------------------------------------------------------------


def test_cg_dense_matches_np_solve(spd64, rng):
    b = rng.standard_normal(64).astype(np.float32)
    res = cg(spd64, b, tol=1e-7, maxiter=500)
    x_ref = np.linalg.solve(spd64.astype(np.float64), b)
    assert bool(res.converged)
    np.testing.assert_allclose(np.asarray(res.x), x_ref, rtol=1e-5, atol=1e-5)
    # history: finite prefix ends at the final residual, NaN beyond
    hist = np.asarray(res.history)
    k = int(res.iterations)
    assert np.isfinite(hist[: k + 1]).all()
    assert np.isnan(hist[k + 1 :]).all()
    np.testing.assert_allclose(hist[k], float(res.residual), rtol=1e-6)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_cg_converges_on_suite_families_hbp(family, rng):
    """Acceptance: CG through the HBP Pallas path on every family."""
    S = spd_family(family)
    tiles = build_tiles(csr_from_dense(S), CFG)
    b = rng.standard_normal(S.shape[0]).astype(np.float32)
    res = cg(hbp_op(tiles), b, tol=1e-7, maxiter=800)
    x_ref = np.linalg.solve(S.astype(np.float64), b)
    assert bool(res.converged)
    err = np.abs(np.asarray(res.x) - x_ref).max() / np.abs(x_ref).max()
    assert err < 1e-5


def test_cg_multirhs_matches_columnwise(spd64, rng):
    """Blocked-RHS CG (one SpMM per iteration) == k independent solves."""
    tiles = build_tiles(csr_from_dense(spd64), PartitionConfig(row_block=32, col_block=32, group=8, lane=8))
    op = hbp_op(tiles)
    B = rng.standard_normal((64, 4)).astype(np.float32)
    res = cg(op, B, tol=1e-7, maxiter=500)
    assert bool(res.converged)
    X_ref = np.linalg.solve(spd64.astype(np.float64), B)
    np.testing.assert_allclose(np.asarray(res.x), X_ref, rtol=1e-4, atol=1e-5)
    for j in range(4):
        single = cg(op, B[:, j], tol=1e-7, maxiter=500)
        np.testing.assert_allclose(np.asarray(res.x)[:, j], np.asarray(single.x), atol=1e-5)


def test_cg_is_jittable(spd64, rng):
    op = aslinearoperator(spd64)
    solve = jax.jit(lambda b: cg(op, b, tol=1e-7, maxiter=500).x)
    b = rng.standard_normal(64).astype(np.float32)
    np.testing.assert_allclose(
        np.asarray(solve(b)), np.linalg.solve(spd64.astype(np.float64), b), atol=1e-5
    )


# --- BiCGSTAB -------------------------------------------------------------


def test_bicgstab_nonsymmetric_matches_np_solve(rng):
    n = 64
    G = rng.standard_normal((n, n)).astype(np.float32) * (rng.random((n, n)) < 0.3)
    N = (G + 8 * np.eye(n, dtype=np.float32)).astype(np.float32)
    b = rng.standard_normal(n).astype(np.float32)
    res = bicgstab(N, b, tol=1e-8, maxiter=1000)
    assert bool(res.converged)
    np.testing.assert_allclose(
        np.asarray(res.x), np.linalg.solve(N.astype(np.float64), b), rtol=1e-4, atol=1e-5
    )


def test_bicgstab_hbp_path_multirhs(rng):
    n = 128
    A = circuit(n, seed=2, n_dense_rows=2, dense_row_frac=0.05).to_dense().astype(np.float32)
    N = (A + (np.abs(A).sum(axis=1).max() + 1) * np.eye(n, dtype=np.float32)).astype(np.float32)
    tiles = build_tiles(csr_from_dense(N), CFG)
    B = rng.standard_normal((n, 3)).astype(np.float32)
    res = bicgstab(hbp_op(tiles), B, tol=1e-7, maxiter=1000)
    assert bool(res.converged)
    X_ref = np.linalg.solve(N.astype(np.float64), B)
    err = np.abs(np.asarray(res.x) - X_ref).max() / np.abs(X_ref).max()
    assert err < 1e-5


# --- Jacobi preconditioning -----------------------------------------------


def badly_scaled_spd(n, rng):
    """SPD with a diagonal spanning 4 decades: S A S for A ~ I."""
    R = rng.standard_normal((n, n)) * 0.02
    A = np.eye(n) + R @ R.T
    s = 10.0 ** rng.uniform(-2, 2, n)
    S = (A * s).T * s
    return ((S + S.T) / 2).astype(np.float32)


def test_csr_diagonal_sums_duplicates():
    """diagonal() must match matvec semantics: duplicate entries sum."""
    from repro.core import COOMatrix, csr_from_coo

    coo = COOMatrix([0, 0, 1], [0, 0, 2], [1.0, 2.0, 5.0], (3, 3))
    csr = csr_from_coo(coo, sum_duplicates=False)
    e0 = np.zeros(3)
    e0[0] = 1.0
    assert csr.matvec(e0)[0] == 3.0
    np.testing.assert_allclose(csr.diagonal(), [3.0, 0.0, 0.0])
    # rectangular: diagonal length is min(shape)
    wide = csr_from_coo(COOMatrix([0, 1], [0, 1], [4.0, 6.0], (2, 5)))
    np.testing.assert_allclose(wide.diagonal(), [4.0, 6.0])


def test_jacobi_accepts_csr_dense_and_diag(rng):
    A = badly_scaled_spd(32, rng)
    x = rng.standard_normal(32).astype(np.float32)
    want = (x / np.diagonal(A)).astype(np.float32)
    for M in (jacobi(csr_from_dense(A)), jacobi(A), jacobi(np.diagonal(A))):
        np.testing.assert_allclose(np.asarray(M(x)), want, rtol=1e-6)
        np.testing.assert_allclose(
            np.asarray(M(np.stack([x, 2 * x], axis=1)))[:, 1], 2 * want, rtol=1e-6
        )
    # zero diagonal entries fall back to identity scale
    M0 = jacobi(np.array([2.0, 0.0, 4.0], np.float32))
    np.testing.assert_allclose(
        np.asarray(M0(np.ones(3, np.float32))), [0.5, 1.0, 0.25], rtol=1e-6
    )
    with pytest.raises(ValueError):
        jacobi(np.ones((2, 2, 2), np.float32))


def test_jacobi_cg_converges_in_fewer_iterations(rng):
    """The ROADMAP acceptance: Jacobi-preconditioned CG needs fewer
    iterations than plain CG on a badly diagonal-scaled SPD system."""
    A = badly_scaled_spd(128, rng)
    csr = csr_from_dense(A)
    b = rng.standard_normal(128).astype(np.float32)
    plain = cg(csr, b, tol=1e-6, maxiter=600)
    pcg = cg(csr, b, tol=1e-6, maxiter=600, M=jacobi(csr))
    assert bool(pcg.converged)
    assert int(pcg.iterations) < int(plain.iterations)
    x_ref = np.linalg.solve(A.astype(np.float64), b)
    err = np.abs(np.asarray(pcg.x) - x_ref).max() / np.abs(x_ref).max()
    assert err < 1e-4


def test_jacobi_cg_through_hbp_plan_diagonal(rng):
    """Preconditioned CG with the diagonal captured at tile-build time —
    the serving-registry composition (plan.diag -> jacobi -> M=)."""
    A = badly_scaled_spd(96, rng)
    csr = csr_from_dense(A)
    tiles = build_tiles(csr, CFG)
    b = rng.standard_normal(96).astype(np.float32)
    res = cg(hbp_op(tiles), b, tol=1e-6, maxiter=600, M=jacobi(csr.diagonal()))
    assert bool(res.converged)
    x_ref = np.linalg.solve(A.astype(np.float64), b)
    assert np.abs(np.asarray(res.x) - x_ref).max() / np.abs(x_ref).max() < 1e-4


def block_diag_dominant_spd(n, bs, rng, coupling=0.05):
    """SPD matrix with strong [bs, bs] diagonal blocks + weak off-block
    coupling — the regime where block-Jacobi beats point Jacobi."""
    A = np.zeros((n, n))
    for lo in range(0, n, bs):
        B = rng.standard_normal((bs, bs))
        A[lo : lo + bs, lo : lo + bs] = B @ B.T + bs * np.eye(bs)
    R = rng.standard_normal((n, n)) * coupling
    return (A + R @ R.T).astype(np.float32)


def test_block_jacobi_exact_on_block_diagonal(rng):
    """On a purely block-diagonal matrix the preconditioner IS the inverse."""
    n, bs = 64, 8
    A = block_diag_dominant_spd(n, bs, rng, coupling=0.0)
    M = block_jacobi(csr_from_dense(A), block_size=bs)
    x = rng.standard_normal(n).astype(np.float32)
    want = np.linalg.solve(A.astype(np.float64), x)
    np.testing.assert_allclose(np.asarray(M(x)), want, rtol=1e-4, atol=1e-5)
    # blocked RHS goes through the batched einsum path
    X = rng.standard_normal((n, 3)).astype(np.float32)
    np.testing.assert_allclose(
        np.asarray(M(X)), np.linalg.solve(A.astype(np.float64), X), rtol=1e-4, atol=1e-5
    )


def test_block_jacobi_cg_beats_point_jacobi(rng):
    """The ROADMAP acceptance: on a block-diagonal-dominant system,
    block-Jacobi PCG needs fewer iterations than point-Jacobi PCG."""
    n, bs = 128, 8
    A = block_diag_dominant_spd(n, bs, rng)
    csr = csr_from_dense(A)
    b = rng.standard_normal(n).astype(np.float32)
    point = cg(csr, b, tol=1e-8, maxiter=400, M=jacobi(csr))
    block = cg(csr, b, tol=1e-8, maxiter=400, M=block_jacobi(csr, block_size=bs))
    assert bool(block.converged)
    assert int(block.iterations) < int(point.iterations)
    x_ref = np.linalg.solve(A.astype(np.float64), b)
    assert np.abs(np.asarray(block.x) - x_ref).max() / np.abs(x_ref).max() < 1e-4


def test_block_jacobi_hash_group_partition(rng):
    """The tile-format composition: one dense [group, group] inverse per
    hash group, partition straight from HBPTiles."""
    n = 128
    A = block_diag_dominant_spd(n, 8, rng)
    csr = csr_from_dense(A)
    tiles = build_tiles(csr, CFG)
    blocks = hash_group_blocks(tiles)
    # a true partition of the row space at hash-group granularity
    flat = np.concatenate(blocks)
    assert np.array_equal(np.sort(flat), np.arange(n))
    assert all(len(b) <= tiles.cfg.group for b in blocks)
    res = cg(hbp_op(tiles), rng.standard_normal(n).astype(np.float32), tol=1e-8,
             maxiter=400, M=block_jacobi(csr, blocks=blocks))
    assert bool(res.converged)


def test_block_jacobi_partial_cover_and_validation(rng):
    n = 32
    A = block_diag_dominant_spd(n, 8, rng, coupling=0.0)
    csr = csr_from_dense(A)
    # rows outside the listed blocks fall back to point Jacobi
    M = block_jacobi(csr, blocks=[np.arange(0, 8), np.arange(16, 24)])
    x = np.ones(n, np.float32)
    y = np.asarray(M(x))
    np.testing.assert_allclose(
        y[:8], np.linalg.solve(A[:8, :8].astype(np.float64), x[:8]), rtol=1e-4
    )
    np.testing.assert_allclose(y[8:16], x[8:16] / np.diagonal(A)[8:16], rtol=1e-5)
    with pytest.raises(ValueError, match="disjoint"):
        block_jacobi(csr, blocks=[np.arange(0, 8), np.arange(4, 12)])
    with pytest.raises(ValueError, match="outside"):
        block_jacobi(csr, blocks=[np.array([40])])
    with pytest.raises(TypeError, match="CSR"):
        block_jacobi(build_tiles(csr, CFG))


def test_jacobi_bicgstab_converges_in_fewer_iterations(rng):
    n = 128
    G = np.eye(n) + rng.standard_normal((n, n)) * 0.01
    s = 10.0 ** rng.uniform(-2, 2, n)
    N = ((G * s).T * s).astype(np.float32)
    b = rng.standard_normal(n).astype(np.float32)
    plain = bicgstab(csr_from_dense(N), b, tol=1e-6, maxiter=800)
    pre = bicgstab(csr_from_dense(N), b, tol=1e-6, maxiter=800, M=jacobi(csr_from_dense(N)))
    assert bool(pre.converged)
    assert int(pre.iterations) < int(plain.iterations)
    x_ref = np.linalg.solve(N.astype(np.float64), b)
    assert np.abs(np.asarray(pre.x) - x_ref).max() / np.abs(x_ref).max() < 1e-4


# --- Chebyshev ------------------------------------------------------------


def test_chebyshev_with_exact_bounds(spd64, rng):
    ev = np.linalg.eigvalsh(spd64.astype(np.float64))
    b = rng.standard_normal(64).astype(np.float32)
    res = chebyshev(spd64, b, lam_min=float(ev[0]), lam_max=float(ev[-1]), tol=1e-7, maxiter=3000)
    assert bool(res.converged)
    np.testing.assert_allclose(
        np.asarray(res.x), np.linalg.solve(spd64.astype(np.float64), b), rtol=1e-4, atol=1e-5
    )


def test_chebyshev_estimated_bounds_smooths(spd64, rng):
    """With power-iteration bounds the residual must strictly decrease —
    the smoothing-pass contract (fixed degree, tol=0)."""
    lam_min, lam_max = estimate_spectrum(spd64, maxiter=50)
    b = rng.standard_normal(64).astype(np.float32)
    res = chebyshev(spd64, b, lam_min=lam_min, lam_max=lam_max, tol=0.0, maxiter=30)
    hist = np.asarray(res.history)
    assert int(res.iterations) == 30
    assert hist[30] < 1e-2 * hist[0]


def test_chebyshev_rejects_bad_bounds(spd64):
    with pytest.raises(ValueError):
        chebyshev(spd64, np.ones(64, np.float32), lam_min=2.0, lam_max=1.0)


# --- power iteration / PageRank ------------------------------------------


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_power_iteration_on_suite_families_hbp(family):
    """Acceptance: power iteration through the HBP Pallas path matches the
    dense dominant eigenvalue to 1e-5 on every family."""
    S = spd_family(family)
    tiles = build_tiles(csr_from_dense(S), CFG)
    res = power_iteration(hbp_op(tiles), tol=1e-6, maxiter=3000)
    lam_ref = float(np.linalg.eigvalsh(S.astype(np.float64))[-1])
    assert bool(res.converged)
    assert abs(float(res.eigenvalue) - lam_ref) / lam_ref < 1e-5
    # eigenvector residual: ||S v - lam v|| small relative to lam
    v = np.asarray(res.eigenvector)
    assert np.linalg.norm(S @ v - float(res.eigenvalue) * v) < 1e-4 * lam_ref


def test_pagerank_matches_dense_reference(rng):
    n = 96
    A = (rng.random((n, n)) < 0.08).astype(np.float32)
    np.fill_diagonal(A, 0)
    M, dang = transition_matrix(csr_from_dense(A))
    res = pagerank(M, damping=0.85, dangling=dang, tol=1e-10, maxiter=500)
    p = np.asarray(res.x)
    assert bool(res.converged)
    np.testing.assert_allclose(p.sum(), 1.0, atol=1e-5)
    Md = M.to_dense().astype(np.float64)
    v = np.full(n, 1.0 / n)
    q = v.copy()
    for _ in range(2000):
        q_new = 0.85 * (Md @ q + (dang.astype(np.float64) @ q) * v) + 0.15 * v
        done = np.abs(q_new - q).sum() < 1e-14 * n
        q = q_new
        if done:
            break
    np.testing.assert_allclose(p, q, atol=1e-6)


def test_pagerank_multi_personalization_spmm(rng):
    """k personalization vectors in one run (SpMM path) == k single runs."""
    adj = rmat(1 << 7, 600, seed=9, symmetric=False)
    M, dang = transition_matrix(adj)
    op = hbp_op(build_tiles(M, CFG))
    n = adj.n_rows
    P = rng.random((n, 3)).astype(np.float32) + 0.01
    multi = pagerank(op, personalization=P, dangling=dang, tol=1e-10, maxiter=300)
    assert bool(multi.converged)
    pm = np.asarray(multi.x)
    np.testing.assert_allclose(pm.sum(axis=0), np.ones(3), atol=1e-5)
    for j in range(3):
        single = pagerank(op, personalization=P[:, j], dangling=dang, tol=1e-10, maxiter=300)
        np.testing.assert_allclose(pm[:, j], np.asarray(single.x), atol=1e-6)


# --- convergence telemetry (record_history) --------------------------------


@pytest.mark.parametrize("solver_kwargs", [
    (cg, {}),
    (bicgstab, {}),
], ids=["cg", "bicgstab"])
def test_record_history_false_single_slot_same_solution(solver_kwargs, spd64, rng):
    solver, kw = solver_kwargs
    b = rng.standard_normal(64).astype(np.float32)
    full = solver(spd64, b, tol=1e-7, maxiter=500, **kw)
    lean = solver(spd64, b, tol=1e-7, maxiter=500, record_history=False, **kw)
    assert np.asarray(lean.history).shape == (1,)  # initial norm only
    assert np.asarray(full.history).shape == (501,)
    # the iteration itself is untouched: same trajectory, same exit
    assert int(lean.iterations) == int(full.iterations)
    np.testing.assert_array_equal(np.asarray(lean.x), np.asarray(full.x))
    np.testing.assert_allclose(
        np.asarray(lean.history)[0], np.asarray(full.history)[0], rtol=1e-6
    )


def test_chebyshev_record_history_false(spd64, rng):
    lo, hi = estimate_spectrum(spd64)
    b = rng.standard_normal(64).astype(np.float32)
    full = chebyshev(spd64, b, lam_min=lo, lam_max=hi, tol=0.0, maxiter=30)
    lean = chebyshev(
        spd64, b, lam_min=lo, lam_max=hi, tol=0.0, maxiter=30, record_history=False
    )
    assert np.asarray(lean.history).shape == (1,)
    np.testing.assert_array_equal(np.asarray(lean.x), np.asarray(full.x))


def test_cg_history_is_monotone_ish(spd64, rng):
    """The recorded residual stream behaves like CG on an SPD system:
    overall decay by orders of magnitude, no sustained growth.  (CG's
    2-norm residual is not strictly monotone, so assert a loose envelope:
    each residual stays under 10x the running minimum.)"""
    b = rng.standard_normal(64).astype(np.float32)
    res = cg(spd64, b, tol=1e-8, maxiter=500)
    hist = np.asarray(res.history)[: int(res.iterations) + 1]
    assert hist[-1] < 1e-6 * hist[0]  # decayed hard
    running_min = np.minimum.accumulate(hist)
    assert np.all(hist <= 10.0 * np.maximum(running_min, 1e-30))


def test_record_history_streams_to_obs(spd64, rng):
    """With obs enabled the carried history surfaces as a metric stream;
    record_history=False keeps the stream silent."""
    from repro import obs

    b = rng.standard_normal(64).astype(np.float32)
    obs.reset()
    obs.enable()
    try:
        res = cg(spd64, b, tol=1e-7, maxiter=500)
        cg(spd64, b, tol=1e-7, maxiter=500, record_history=False)
        streams = obs.registry().find("solver.cg.residual")
        assert len(streams) == 1  # only the recording run emitted
        (s,) = streams
        assert len(s.points) == int(res.iterations) + 1
        vals = np.asarray(s.values)
        assert vals[-1] < vals[0]
    finally:
        obs.disable()
        obs.reset()
