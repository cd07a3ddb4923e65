"""Differentiable HBP aggregation: custom VJPs that stay on the kernel path.

``jax.grad`` through a plain aggregation closure would trace *into* the
SpMM implementation and transpose whatever it finds there — a thicket of
gathers and segment sums, or a Pallas kernel it cannot differentiate at
all.  But the backward pass of sparse aggregation has a closed form that
is itself an HBP SpMM:

* ``sum``:  ``y = A @ x``            → ``x̄ = Aᵀ @ ȳ``
* ``mean``: ``y = (A @ x) / d``      → ``x̄ = Aᵀ @ (ȳ / d)``
* ``max``:  ``y[i,c] = max_j a_ij x[j,c]`` → route ``ȳ[i,c]`` to the
  winning neighbor: ``x̄[j*,c] += a_{i,j*} ȳ[i,c]``

so training reuses the paper's admit-once/multiply-many economics: A and
Aᵀ are each one cheap hash-based preprocessing pass (:func:`hbp_transpose`
builds the pair together), and every backward step is one more launch of
the same kernels the forward uses.

Two wrapper flavors are exposed via ``mode``:

* ``"vjp"`` (default, the training path) — a dual :func:`jax.custom_vjp`
  pair: the backward of ``A @ x`` *is* the ``Aᵀ`` SpMM launch, and the
  backward of that backward is the ``A`` launch again, so reverse-mode
  works to any order.  Forward-mode (``jax.jvp``) is not supported on
  ``custom_vjp`` functions by JAX itself.
* ``"jvp"`` — a :func:`jax.custom_jvp` wrapper whose tangent is a second
  ``A`` SpMM launch (exact, since the op is linear).  Forward-mode is
  first-class; reverse-mode is derived by transposing that tangent
  launch's trace — correct, but the cotangent program is the transposed
  gather/segment graph rather than the resident ``Aᵀ`` tile stream.

``max`` uses :func:`jax.custom_jvp` with argmax routing under both modes
(its forward saves the winning-neighbor indices via the one-pass
paired-payload argmax SpMM of :func:`repro.kernels.ops.hbp_spmm_argmax` —
value, index and coefficient advance together through a single
tile-stream traversal; JAX transposes the tangent's gather into exactly
the argmax-routed cotangent scatter), so it supports forward and reverse
mode alike.
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.formats import CSRMatrix
from repro.core.tile import HBPTiles, build_tiles, tuned_partition_config

from . import ops

__all__ = [
    "PairedTiles",
    "hbp_transpose",
    "linear_spmm_vjp",
    "linear_spmm_jvp",
    "argmax_spmm_diff",
    "diff_aggregator",
    "device_diff_aggregator",
    "mean_divisor",
    "needs_transpose",
]

DIFF_MODES = ("vjp", "jvp")


class PairedTiles(NamedTuple):
    """HBP tile formats of a matrix and its transpose, built together.

    The pair is what the differentiable ops consume: ``tiles`` serves the
    forward launches, ``tiles_T`` the cotangent launches.  Geometry is
    tuned per side — A's row-nnz profile and Aᵀ's (A's column profile)
    generally differ, so each side gets its own partition config.
    ``tiles_T`` may be ``None`` for ops that never launch Aᵀ (see
    :func:`needs_transpose`).
    """

    tiles: HBPTiles
    tiles_T: Optional[HBPTiles]


def hbp_transpose(
    csr: CSRMatrix,
    cfg=None,
    cfg_T=None,
    *,
    method: str = "hash",
) -> PairedTiles:
    """Host-side CSR transpose + paired tile build: ``(tiles, tiles_T)``.

    One preprocessing pass per side — the transpose itself is a stable
    counting sort (:meth:`~repro.core.formats.CSRMatrix.transpose`), and
    each side's tile geometry is tuned from its own nnz profile unless
    pinned by ``cfg``/``cfg_T``.  For serving-registry residency (content
    hashing links A ↔ Aᵀ so re-admission of either is free) use
    :meth:`repro.serving.registry.MatrixRegistry.admit_pair` instead.
    """
    csr_T = csr.transpose()
    tiles = build_tiles(csr, cfg or tuned_partition_config(csr), method=method)
    tiles_T = build_tiles(csr_T, cfg_T or tuned_partition_config(csr_T), method=method)
    return PairedTiles(tiles, tiles_T)


def linear_spmm_vjp(
    apply_A: Callable[[jax.Array], jax.Array],
    apply_AT: Callable[[jax.Array], jax.Array],
) -> Callable[[jax.Array], jax.Array]:
    """Wrap a linear map and its transpose as a dual ``custom_vjp`` pair.

    ``grad`` of the result launches ``apply_AT`` on the cotangent, and
    ``grad`` of *that* launches ``apply_A`` again — reverse-mode composes
    to any order without ever tracing inside either implementation.
    """

    @jax.custom_vjp
    def f(x):
        return apply_A(x)

    @jax.custom_vjp
    def fT(g):
        return apply_AT(g)

    f.defvjp(lambda x: (apply_A(x), None), lambda _, g: (fT(g),))
    fT.defvjp(lambda g: (apply_AT(g), None), lambda _, v: (f(v),))
    return f


def linear_spmm_jvp(
    apply_A: Callable[[jax.Array], jax.Array],
) -> Callable[[jax.Array], jax.Array]:
    """Wrap a linear map as a ``custom_jvp``: tangent = a second launch.

    Forward-mode is exact and never differentiates the implementation;
    reverse-mode transposes the tangent launch's trace (correct, but not
    the resident-Aᵀ path — prefer :func:`linear_spmm_vjp` for training).
    """

    @jax.custom_jvp
    def f(x):
        return apply_A(x)

    @f.defjvp
    def _jvp(primals, tangents):
        (x,), (t,) = primals, tangents
        return apply_A(x), apply_A(t)

    return f


def argmax_spmm_diff(
    dt: ops.DeviceTiles,
    *,
    n_rowgroups: int,
    n_rows: int,
    col_block: int,
) -> Callable[[jax.Array], jax.Array]:
    """Differentiable max-aggregation over staged tiles.

    Forward runs the one-pass argmax SpMM (max value + winning-neighbor
    index + winning coefficient carried through a single tile-stream
    traversal); the tangent gathers ``coeff * t[idx]`` and JAX's
    transpose of that gather is the argmax-routed cotangent scatter.
    Ties route to the lowest winning column; rows with no live entry get
    zero output and pass no gradient.
    """
    meta = dict(n_rowgroups=n_rowgroups, n_rows=n_rows, col_block=col_block)

    @jax.custom_jvp
    def f(x):
        y, _, _ = ops.hbp_spmm_argmax(dt, x, **meta)
        return y

    @f.defjvp
    def _jvp(primals, tangents):
        (x,), (t,) = primals, tangents
        y, idx, coeff = ops.hbp_spmm_argmax(dt, x, **meta)
        picked = jnp.take_along_axis(t, jnp.maximum(idx, 0), axis=0)
        return y, jnp.where(idx >= 0, coeff * picked, 0.0)

    return f


def mean_divisor(degree, n_rows: int) -> jax.Array:
    """[n, 1] clamped in-degree: mean over an empty neighborhood is 0.

    The single home of the clamp convention — the graph aggregators and
    the serving registry delegate here, so mean forward and mean backward
    can never disagree about empty rows.  Accepts numpy or jax input
    without a device -> host round trip.
    """
    d = jnp.asarray(degree, jnp.float32).reshape(n_rows, 1)
    return jnp.maximum(d, 1.0)


def device_diff_aggregator(
    dt: ops.DeviceTiles,
    dt_T: Optional[ops.DeviceTiles],
    meta: dict,
    meta_T: Optional[dict],
    *,
    op: str = "sum",
    degree=None,
    mode: str = "vjp",
) -> Callable[[jax.Array], jax.Array]:
    """Differentiable aggregation closure over already-staged tiles.

    ``meta``/``meta_T`` are the keyword dicts :func:`repro.kernels.ops.
    hbp_spmm` needs beyond the tiles (``n_rowgroups``, ``n_rows``,
    ``col_block``, ``strategy``, ``interpret``).  ``dt_T`` may be ``None``
    for ``op="max"`` (its backward is a scatter, not a transpose SpMM)
    and for ``mode="jvp"``.  This is the layer
    :meth:`~repro.serving.registry.MatrixPlan.diff_aggregator` and
    :func:`repro.graph.aggregate.make_diff_aggregator` both sit on.
    """
    if mode not in DIFF_MODES:
        raise ValueError(f"unknown mode {mode!r} (expected one of {DIFF_MODES})")
    if op == "max":
        return argmax_spmm_diff(
            dt,
            n_rowgroups=meta["n_rowgroups"],
            n_rows=meta["n_rows"],
            col_block=meta["col_block"],
        )
    if op not in ("sum", "mean"):
        raise ValueError(f"unknown aggregation {op!r} (expected sum, mean or max)")

    div = None
    if op == "mean":
        if degree is None:
            raise ValueError("op='mean' needs the degree vector (degrees(adj))")
        div = mean_divisor(degree, meta["n_rows"])

    def apply_A(x):
        y = ops.hbp_spmm(dt, x, **meta)
        return y / div if div is not None else y

    if mode == "jvp":
        return linear_spmm_jvp(apply_A)
    if dt_T is None or meta_T is None:
        raise ValueError("mode='vjp' needs the transpose tiles (build with hbp_transpose)")

    def apply_AT(g):
        g = g / div if div is not None else g
        return ops.hbp_spmm(dt_T, g, **meta_T)

    return linear_spmm_vjp(apply_A, apply_AT)


def needs_transpose(op: str, mode: str) -> bool:
    """Whether the differentiable op launches the Aᵀ tiles at all: only
    the linear ops' ``"vjp"`` backward does — max routes a scatter and
    the ``"jvp"`` flavor re-launches A, so neither pays for a transpose
    build or residency."""
    return mode == "vjp" and op in ("sum", "mean")


def diff_aggregator(
    pair: PairedTiles,
    *,
    op: str = "sum",
    degree=None,
    strategy: str = "stable",
    interpret: bool | None = None,
    mode: str = "vjp",
) -> Callable[[jax.Array], jax.Array]:
    """Stage a :class:`PairedTiles` and return a differentiable aggregator.

    The graph-level entry with CSR handling and degree defaulting is
    :func:`repro.graph.aggregate.make_diff_aggregator`; this layer works
    directly on the tile pair (e.g. prebuilt by :func:`hbp_transpose`).
    When the op never launches Aᵀ (see :func:`needs_transpose`) the
    transpose side is not staged — ``pair.tiles_T`` may then be ``None``.
    """
    tiles, tiles_T = pair

    def _meta(t: HBPTiles) -> dict:
        return dict(
            n_rowgroups=t.n_rowgroups,
            n_rows=t.shape[0],
            col_block=t.cfg.col_block,
            strategy=strategy,
            interpret=interpret,
        )

    stage_t = needs_transpose(op, mode) and tiles_T is not None
    return device_diff_aggregator(
        ops.device_tiles(tiles),
        ops.device_tiles(tiles_T) if stage_t else None,
        _meta(tiles),
        _meta(tiles_T) if stage_t else None,
        op=op,
        degree=degree,
        mode=mode,
    )
