"""Pure-jnp oracles for the Pallas kernels.

Every kernel in this package is validated against these references with
``numpy.testing.assert_allclose`` across shape/dtype/sparsity sweeps
(tests/test_kernels.py); the references themselves are validated against
the dense matmul and the faithful GPU-semantics implementation
(:func:`repro.core.hbp.hbp_spmv_reference`).
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

__all__ = [
    "tile_contrib_ref",
    "hbp_spmv_hashed_ref",
    "tile_contrib_spmm_ref",
    "hbp_spmm_hashed_ref",
    "tile_contrib_spmm_stable",
    "hbp_spmm_hashed_stable",
    "tile_contrib_spmm_max",
    "hbp_spmm_hashed_max",
    "hbp_spmm_hashed_argmax_onepass",
    "count_traversals",
    "unpermute",
]

# Tile-stream traversal accounting.  A "traversal" is one walk over the
# packed (data, cols) stream with its x gathers — the dominant HBM traffic
# of every kernel in this package, and what the one-pass argmax keeps to
# 1.  Each lane-loop body below bumps the counter once per *trace*, so
# callers measuring it must invoke these references directly (eagerly or
# via a fresh trace), not through a cached jit.
_TRAVERSALS = [0]


def _traverse() -> None:
    _TRAVERSALS[0] += 1


@contextlib.contextmanager
def count_traversals():
    """Context manager yielding a 1-element list that, on exit, holds the
    number of tile-stream traversals traced inside the block."""
    start = _TRAVERSALS[0]
    box = [0]
    try:
        yield box
    finally:
        box[0] = _TRAVERSALS[0] - start


def tile_contrib_ref(
    colblock: jax.Array,  # i32[T]
    data: jax.Array,  # f32[T, group, lane]
    cols: jax.Array,  # i32[T, group, lane]
    x_blocked: jax.Array,  # f32[n_col_blocks, col_block]
) -> jax.Array:
    """Per-tile partial results [T, group] — oracle of the SpMV part."""
    _traverse()
    segs = x_blocked[colblock]  # [T, col_block]
    T, group, lane = data.shape
    gathered = jnp.take_along_axis(
        segs[:, None, :], cols.reshape(T, 1, group * lane), axis=2
    ).reshape(T, group, lane)
    return jnp.sum(data * gathered, axis=2)


def hbp_spmv_hashed_ref(
    rowgroup: jax.Array,
    colblock: jax.Array,
    data: jax.Array,
    cols: jax.Array,
    x_blocked: jax.Array,
    *,
    n_rowgroups: int,
) -> jax.Array:
    """Full SpMV + combine oracle, output in hashed row order
    [n_rowgroups, group]."""
    contrib = tile_contrib_ref(colblock, data, cols, x_blocked)
    return jax.ops.segment_sum(contrib, rowgroup, num_segments=n_rowgroups)


def tile_contrib_spmm_ref(
    colblock: jax.Array,  # i32[T]
    data: jax.Array,  # f32[T, group, lane]
    cols: jax.Array,  # i32[T, group, lane]
    x_blocked: jax.Array,  # f32[n_col_blocks, col_block, k]
) -> jax.Array:
    """Per-tile partial blocks [T, group, k] — oracle of the SpMM part."""
    _traverse()
    segs = x_blocked[colblock]  # [T, col_block, k]
    gathered = jax.vmap(lambda s, c: s[c])(segs, cols)  # [T, group, lane, k]
    return jnp.einsum("tgl,tglk->tgk", data, gathered)


def hbp_spmm_hashed_ref(
    rowgroup: jax.Array,
    colblock: jax.Array,
    data: jax.Array,
    cols: jax.Array,
    x_blocked: jax.Array,
    *,
    n_rowgroups: int,
) -> jax.Array:
    """Full multi-RHS SpMM + combine oracle, output in hashed row order
    [n_rowgroups, group, k]."""
    contrib = tile_contrib_spmm_ref(colblock, data, cols, x_blocked)
    return jax.ops.segment_sum(contrib, rowgroup, num_segments=n_rowgroups)


def tile_contrib_spmm_stable(
    colblock: jax.Array,  # i32[T]
    data: jax.Array,  # f32[T, group, lane]
    cols: jax.Array,  # i32[T, group, lane]
    x_blocked: jax.Array,  # f32[n_col_blocks, col_block, k]
) -> jax.Array:
    """Batch-width-invariant SpMM contributions [T, group, k].

    Numerically equivalent to :func:`tile_contrib_spmm_ref`, but the lane
    reduction is an explicitly ordered chain of elementwise adds (unrolled
    over the static lane dimension) instead of a fused contraction.  XLA
    cannot reassociate elementwise adds, so a column's bit pattern is
    independent of how many RHS columns share the launch — the guarantee
    the serving engine's k-bucketed micro-batching relies on: coalescing a
    request with arbitrary co-traffic, or padding its bucket with zero
    columns, never changes its result.  (The einsum oracle and the
    interpret-mode kernels are ~1 ulp width-dependent at small k.)

    The gather is flat and per lane: each step touches only the [T, group]
    slots it multiplies, never a [T, col_block, k] segment expansion nor a
    [T, group, lane, k] product — the largest temporary is [T, group, k],
    which is what keeps this path's k-scaling near the ideal tile-stream
    amortization (the einsum oracle loses it to the blown-up intermediates).
    """
    _traverse()
    n_cb, col_block, k = x_blocked.shape
    x_flat = x_blocked.reshape(n_cb * col_block, k)
    base = colblock[:, None] * col_block  # [T, 1] offset of each tile's segment
    acc = data[:, :, 0, None] * x_flat[base + cols[:, :, 0]]
    for lane in range(1, data.shape[2]):
        acc = acc + data[:, :, lane, None] * x_flat[base + cols[:, :, lane]]
    return acc


def hbp_spmm_hashed_stable(
    rowgroup: jax.Array,
    colblock: jax.Array,
    data: jax.Array,
    cols: jax.Array,
    x_blocked: jax.Array,
    *,
    n_rowgroups: int,
) -> jax.Array:
    """Full batch-width-invariant SpMM + combine, hashed row order
    [n_rowgroups, group, k]."""
    contrib = tile_contrib_spmm_stable(colblock, data, cols, x_blocked)
    return jax.ops.segment_sum(contrib, rowgroup, num_segments=n_rowgroups)


def tile_contrib_spmm_max(
    colblock: jax.Array,  # i32[T]
    data: jax.Array,  # f32[T, group, lane]
    cols: jax.Array,  # i32[T, group, lane]
    x_blocked: jax.Array,  # f32[n_col_blocks, col_block, k]
) -> jax.Array:
    """Max-monoid SpMM contributions [T, group, k]: per-tile
    ``max_j(a_ij * x_jk)`` over the tile's lanes.

    The max semiring backs GNN max-aggregation (``repro.graph``): the
    combine is ``maximum`` instead of ``+``, whose identity is ``-inf`` —
    so padded tile slots must be *masked out*, not multiplied through
    (``0 * x = 0`` would beat every all-negative row).  A slot is live iff
    its stored value is nonzero; explicitly stored zeros are treated as
    absent entries, consistent with sparse semantics where only the stored
    pattern participates.  Rows with no live slots come out ``-inf`` here;
    the caller maps the identity back to 0 *after* the row-group combine
    (see ``ops._hbp_spmm_device``) so it never leaks into outputs.

    Like the stable sum path, the lane reduction is an unrolled chain —
    ``maximum`` is exactly associative and commutative on floats, so this
    one form serves as reference, stable, and oracle at once (bit-exact
    under any batch width by construction).
    """
    _traverse()
    n_cb, col_block, k = x_blocked.shape
    x_flat = x_blocked.reshape(n_cb * col_block, k)
    base = colblock[:, None] * col_block  # [T, 1] offset of each tile's segment
    neg = jnp.float32(-jnp.inf)

    def lane_term(lane):
        d = data[:, :, lane, None]  # [T, group, 1]
        prod = d * x_flat[base + cols[:, :, lane]]
        return jnp.where(d != 0, prod, neg)

    acc = lane_term(0)
    for lane in range(1, data.shape[2]):
        acc = jnp.maximum(acc, lane_term(lane))
    return acc


def hbp_spmm_hashed_max(
    rowgroup: jax.Array,
    colblock: jax.Array,
    data: jax.Array,
    cols: jax.Array,
    x_blocked: jax.Array,
    *,
    n_rowgroups: int,
) -> jax.Array:
    """Max-monoid SpMM + combine, hashed row order [n_rowgroups, group, k].

    Row groups with no tiles (and all-padding slots) are ``-inf`` — the
    monoid identity, for the caller to mask."""
    contrib = tile_contrib_spmm_max(colblock, data, cols, x_blocked)
    return jax.ops.segment_max(contrib, rowgroup, num_segments=n_rowgroups)


def hbp_spmm_hashed_argmax_onepass(
    rowgroup: jax.Array,
    colblock: jax.Array,
    data: jax.Array,
    cols: jax.Array,
    x_blocked: jax.Array,
    *,
    n_rowgroups: int,
):
    """Max-monoid SpMM that also reports *which* stored entry won.

    Returns ``(y, idx, coeff)`` in hashed row order, each
    ``[n_rowgroups, group, k]``:

    * ``y`` — the max-SpMM values, bitwise those of
      :func:`hbp_spmm_hashed_max` (the value chain is the exact
      ``maximum`` sequence of :func:`tile_contrib_spmm_max`; ``-inf`` for
      rows with no live entry);
    * ``idx`` — the *global column id* of the winning stored entry
      (``-1`` where the row has none), ties broken to the lowest column;
    * ``coeff`` — the winning entry's stored value (0 where the row has
      none).

    It walks the tile stream ONCE: each lane step advances a paired
    ``(value, index, coefficient)`` payload through the max combine, where
    a lane term displaces the accumulator iff its value is strictly
    greater or equal-with-lower-column.  The per-tile payloads are then
    reduced across each row group with segment ops over the already-
    materialized ``[T, group, k]`` contributions — no further x gathers or
    data reads.
    """
    _traverse()
    n_cb, col_block, k = x_blocked.shape
    x_flat = x_blocked.reshape(n_cb * col_block, k)
    base = colblock[:, None] * col_block  # [T, 1]
    int_max = jnp.iinfo(jnp.int32).max

    def lane_term(lane):
        d = data[:, :, lane, None]  # [T, group, 1]
        gcol = (base + cols[:, :, lane])[..., None].astype(jnp.int32)
        prod = d * x_flat[base + cols[:, :, lane]]  # [T, group, k]
        live = d != 0
        v = jnp.where(live, prod, -jnp.inf)
        # dead slots carry the int32 max sentinel so the lowest-column
        # tie-break can never select them
        i = jnp.broadcast_to(jnp.where(live, gcol, int_max), v.shape)
        c = jnp.broadcast_to(jnp.where(live, d, 0.0), v.shape)
        return v, i, c

    acc_v, acc_i, acc_c = lane_term(0)
    for lane in range(1, data.shape[2]):
        v, i, c = lane_term(lane)
        take = (v > acc_v) | ((v == acc_v) & (i < acc_i))
        # the value chain stays the literal maximum() sequence of the
        # max-monoid path, so y is bitwise-identical to hashed_max
        acc_v = jnp.maximum(acc_v, v)
        acc_i = jnp.where(take, i, acc_i)
        acc_c = jnp.where(take, c, acc_c)

    # row-group combine of the per-tile payloads (contribution arrays
    # only — the tile stream is not touched again)
    y = jax.ops.segment_max(acc_v, rowgroup, num_segments=n_rowgroups)
    attain = acc_v == y[rowgroup]  # a tile's winner attains the row max
    idx_min = jax.ops.segment_min(
        jnp.where(attain, acc_i, int_max), rowgroup, num_segments=n_rowgroups
    )
    live = idx_min < int_max  # also False for never-visited row groups
    idx = jnp.where(live, idx_min, -1)
    hit = attain & (acc_i == idx[rowgroup])
    coeff = jax.ops.segment_max(
        jnp.where(hit, acc_c, -jnp.inf), rowgroup, num_segments=n_rowgroups
    )
    coeff = jnp.where(live, coeff, 0.0)
    return y, idx, coeff


def unpermute(y_hashed: jax.Array, perm: jax.Array, n_rows: int) -> jax.Array:
    """Undo the hash reordering: slot s computed original row ``perm[s]``.

    ``y_hashed`` is [n_rowgroups, group] (SpMV) or [n_rowgroups, group, k]
    (SpMM); ``perm`` maps slots (flattened hashed order) to original row
    ids over the padded row space.  Trailing RHS dims ride along.
    """
    flat = y_hashed.reshape((-1,) + y_hashed.shape[2:])
    padded = jnp.zeros((perm.shape[0],) + flat.shape[1:], dtype=y_hashed.dtype)
    # perm is a genuine permutation: declaring uniqueness lets XLA drop the
    # collision handling and makes the scatter linearly transposable (the
    # jvp-mode autodiff wrappers rely on this)
    padded = padded.at[perm].set(flat, unique_indices=True)
    return padded[:n_rows]
