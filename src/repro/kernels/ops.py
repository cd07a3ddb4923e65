"""Jitted public wrappers around the Pallas SpMV kernels.

``hbp_spmv`` is the production entry point: it stages the host-side tile
format to the device once (:func:`device_tiles`), pads the dense vector
into column-block segments, launches the requested kernel strategy and
undoes the hash permutation.
"""
from __future__ import annotations

import functools
from typing import Literal, NamedTuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.tile import HBPTiles

from . import hbp_spmv as _k
from . import ref as _ref

__all__ = [
    "DeviceTiles",
    "device_tiles",
    "hbp_spmv",
    "hbp_spmm",
    "hbp_spmm_argmax",
    "hbp_spmm_bucketed",
    "bucket_k",
    "K_BUCKETS",
    "LANE_TILE",
    "blocked_vector",
    "blocked_matrix",
    "default_strategy",
    "resolve_interpret",
    "lower_launch",
]

# RHS-width buckets of the k-padded SpMM entry.  ``_hbp_spmm_device`` is
# jitted with k baked into the trace, so an unconstrained request mix would
# compile one kernel per distinct k; padding to the next bucket bounds the
# compile count at len(K_BUCKETS) per matrix geometry.  The top bucket is
# one full lane tile (128): beyond it ``bucket_k`` rounds up to multiples
# of 128, each served as one k-tile of the 2D-grid launch — so GNN feature
# widths (256, 512, ...) add at most one partially padded k-tile, never an
# unbounded compile set.
K_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128)

# Widest RHS block one kernel grid step carries (defined with the kernels;
# re-exported here for the serving/bucketing layers).  Wider k runs the 2D
# k-tiled grid.
LANE_TILE = _k.LANE_TILE


class DeviceTiles(NamedTuple):
    """Device-resident HBP tile format (a pytree of jnp arrays)."""

    rowgroup: jax.Array  # i32[T]
    colblock: jax.Array  # i32[T]
    data: jax.Array  # f32[T, group, lane]
    cols: jax.Array  # i32[T, group, lane]
    perm: jax.Array  # i32[padded_rows] = i32[n_rowgroups * group]
    width: jax.Array  # i32[T]: the row gather's loop bound (kernels.tile_widths)


_tile_widths = jax.jit(_k.tile_widths)


def device_tiles(tiles: HBPTiles) -> DeviceTiles:
    """Stage the host tiles; each tile's live width is reduced on the
    device from the staged values, once per plan."""
    data = jnp.asarray(tiles.data, jnp.float32)
    return DeviceTiles(
        rowgroup=jnp.asarray(tiles.rowgroup, jnp.int32),
        colblock=jnp.asarray(tiles.colblock, jnp.int32),
        data=data,
        cols=jnp.asarray(tiles.cols, jnp.int32),
        perm=jnp.asarray(tiles.perm, jnp.int32),
        width=_tile_widths(data),
    )


def blocked_vector(x: jax.Array, col_block: int) -> jax.Array:
    """Pad x to a multiple of ``col_block`` and reshape into segments."""
    n = x.shape[0]
    n_blocks = -(-n // col_block)
    pad = n_blocks * col_block - n
    return jnp.pad(x, (0, pad)).reshape(n_blocks, col_block)


def blocked_matrix(x: jax.Array, col_block: int) -> jax.Array:
    """Pad an [n, k] RHS block to a multiple of ``col_block`` rows and
    reshape into [n_blocks, col_block, k] segments (k in the lane dim)."""
    n, k = x.shape
    n_blocks = -(-n // col_block)
    pad = n_blocks * col_block - n
    return jnp.pad(x, ((0, pad), (0, 0))).reshape(n_blocks, col_block, k)


def default_strategy() -> str:
    """The kernel path for this backend, as the registry, the GNN trainer
    and the solver operator resolve it: the fused Pallas kernel on TPU,
    the batch-width-invariant ``"stable"`` jnp path elsewhere (off-TPU the
    Pallas kernels only run interpreted)."""
    return "fused" if jax.default_backend() == "tpu" else "stable"


def resolve_interpret(interpret: bool | None) -> bool:
    """The caller's choice, else: Pallas TPU kernels execute natively on
    TPU and are never interpreted there unless the caller asks; elsewhere
    interpret mode is their only way to run (bit-accurate, Python-evaluated)."""
    return jax.default_backend() != "tpu" if interpret is None else interpret


def _gather(op: str, strategy: str, k: int, x_rows: int) -> str:
    """The tile body a launch runs: the fused SpMM's choice
    (:func:`repro.kernels.hbp_spmv.gather_body`), the lane gather on the
    fused SpMV, ``"none"`` on the jnp strategies."""
    if strategy != "fused":
        return "none"
    return _k.gather_body(k, x_rows) if op == "spmm" else "lane"


def _block(op: str, strategy: str, k: int, x_rows: int, col_block: int) -> int:
    """Tiles one grid step of the launch carries: the fused kernels'
    (:func:`repro.kernels.hbp_spmv.tiles_per_step`), 0 on the jnp
    strategies."""
    if strategy != "fused":
        return 0
    return _k.tiles_per_step(k if op == "spmm" else 1, x_rows, col_block)


def _record_launch(
    k: int, *, op: str, strategy: str, combine: str = "sum", x_rows: int = 0,
    col_block: int = 1,
) -> None:
    """Gated launch accounting: one bump per *Python-level* launch.

    Calls traced inside an outer ``jit`` (e.g. the solver ``while_loop``
    body) are counted once per trace, not once per device execution — the
    counters see what Python dispatches, which is the honest observable
    from this layer.  ``x_rows`` (the blocked RHS's rows) and
    ``col_block`` feed the ``gather`` and ``block`` labels.
    """
    if not obs.enabled():
        return
    obs.counter(
        "kernels.launches", op=op, strategy=strategy, combine=combine,
        gather=_gather(op, strategy, k, x_rows),
        block=_block(op, strategy, k, x_rows, col_block),
    ).inc()
    obs.histogram("kernels.launch_k").observe(k)


@functools.partial(
    jax.jit, static_argnames=("n_rowgroups", "n_rows", "strategy", "interpret")
)
def _hbp_spmv_device(
    dt: DeviceTiles,
    x_blocked: jax.Array,
    *,
    n_rowgroups: int,
    n_rows: int,
    strategy: str,
    interpret: bool,
) -> jax.Array:
    if dt.data.shape[0] == 0:  # empty matrix: no tiles, y == 0
        return jnp.zeros((n_rows,), jnp.float32)
    if strategy == "fused":
        y_hashed = _k.hbp_spmv_fused(
            dt.rowgroup, dt.colblock, dt.data, dt.cols, x_blocked,
            n_rowgroups=n_rowgroups, interpret=interpret,
        )
    elif strategy == "reference":
        y_hashed = _ref.hbp_spmv_hashed_ref(
            dt.rowgroup, dt.colblock, dt.data, dt.cols, x_blocked,
            n_rowgroups=n_rowgroups,
        )
    elif strategy == "stable":
        # the k=1 column of the batch-width-invariant SpMM, so a vector
        # served alone gets the same bits as any batched launch of it
        y_hashed = _ref.hbp_spmm_hashed_stable(
            dt.rowgroup, dt.colblock, dt.data, dt.cols, x_blocked[..., None],
            n_rowgroups=n_rowgroups,
        )[..., 0]
    else:
        raise ValueError(f"unknown strategy {strategy!r}")
    return _ref.unpermute(y_hashed, dt.perm, n_rows)


def _spmm_hashed_chunk(
    dt: DeviceTiles,
    x_blocked: jax.Array,  # f32[n_blocks, col_block, k]
    *,
    n_rowgroups: int,
    strategy: str,
    combine: str,
    interpret: bool,
) -> jax.Array:
    """One SpMM launch on the selected strategy, output in hashed row order.

    Every strategy takes any k (the Pallas wrappers pad it to their own
    k-tiles and slice it back).  Under ``combine="max"`` empty rows carry
    the monoid identity ``-inf`` here; the caller maps it to 0 once, after
    assembly."""
    if combine == "max":
        if strategy == "fused":
            return _k.hbp_spmm_fused_max(
                dt.rowgroup, dt.colblock, dt.data, dt.cols, x_blocked, dt.width,
                n_rowgroups=n_rowgroups, interpret=interpret,
            )
        if strategy in ("reference", "stable"):
            # maximum is exactly associative/commutative: the unrolled lane
            # chain is reference, stable and batch-width-invariant at once
            return _ref.hbp_spmm_hashed_max(
                dt.rowgroup, dt.colblock, dt.data, dt.cols, x_blocked,
                n_rowgroups=n_rowgroups,
            )
        raise ValueError(f"unknown strategy {strategy!r}")
    if combine != "sum":
        raise ValueError(f"unknown combine {combine!r} (expected 'sum' or 'max')")
    if strategy == "fused":
        return _k.hbp_spmm_fused(
            dt.rowgroup, dt.colblock, dt.data, dt.cols, x_blocked, dt.width,
            n_rowgroups=n_rowgroups, interpret=interpret,
        )
    if strategy == "reference":
        return _ref.hbp_spmm_hashed_ref(
            dt.rowgroup, dt.colblock, dt.data, dt.cols, x_blocked,
            n_rowgroups=n_rowgroups,
        )
    if strategy == "stable":
        return _ref.hbp_spmm_hashed_stable(
            dt.rowgroup, dt.colblock, dt.data, dt.cols, x_blocked,
            n_rowgroups=n_rowgroups,
        )
    raise ValueError(f"unknown strategy {strategy!r}")


@functools.partial(
    jax.jit, static_argnames=("n_rowgroups", "n_rows", "strategy", "interpret", "combine")
)
def _hbp_spmm_device(
    dt: DeviceTiles,
    x_blocked: jax.Array,  # f32[n_blocks, col_block, k]
    *,
    n_rowgroups: int,
    n_rows: int,
    strategy: str,
    interpret: bool,
    combine: str = "sum",
) -> jax.Array:
    """Hashed SpMM + unpermute, at any RHS width ``k``.

    ``"fused"`` and ``"reference"`` run one launch at any k: the Pallas
    wrappers pad k to whole lane tiles and run the kernels' 2D k-tiled
    grid, and the reference einsum covers the full width in one trace.
    ``"stable"`` keeps <= LANE_TILE-wide lane chains beyond one lane tile:
    its contract is bitwise batch-width invariance, which XLA upholds only
    across launch widths that share codegen (a single wide trace moves the
    tail columns by ~1 ulp; tests/test_onepass.py pins this).
    """
    k = x_blocked.shape[-1]
    if dt.data.shape[0] == 0:  # empty matrix: no tiles, Y == identity-mapped 0
        return jnp.zeros((n_rows, k), jnp.float32)
    chunk = functools.partial(
        _spmm_hashed_chunk, dt, n_rowgroups=n_rowgroups, strategy=strategy,
        combine=combine, interpret=interpret,
    )
    if k <= LANE_TILE or strategy != "stable":
        y_hashed = chunk(x_blocked)
    else:
        y_hashed = jnp.concatenate(
            [chunk(x_blocked[..., lo : lo + LANE_TILE]) for lo in range(0, k, LANE_TILE)],
            axis=-1,
        )
    if combine == "max":
        # rows with no live entry hold the monoid identity; outputs are 0
        # there (the aggregation convention for isolated graph nodes)
        y_hashed = jnp.where(jnp.isneginf(y_hashed), 0.0, y_hashed)
    return _ref.unpermute(y_hashed, dt.perm, n_rows)


def _resolve(tiles, x, n_rowgroups, n_rows, col_block):
    if isinstance(tiles, HBPTiles):
        if x.shape[0] != tiles.shape[1]:
            # jnp gathers clamp out-of-range block ids, so a wrong-sized x
            # would silently return garbage instead of erroring
            raise ValueError(
                f"x has {x.shape[0]} rows but the matrix has {tiles.shape[1]} columns"
            )
        return device_tiles(tiles), (tiles.n_rowgroups, tiles.shape[0], tiles.cfg.col_block)
    if None in (n_rowgroups, n_rows, col_block):
        raise ValueError("DeviceTiles input requires explicit metadata")
    return tiles, (n_rowgroups, n_rows, col_block)


def hbp_spmv(
    tiles: HBPTiles | DeviceTiles,
    x: jax.Array,
    *,
    strategy: Literal["fused", "reference", "stable"] = "fused",
    interpret: bool | None = None,
    n_rowgroups: int | None = None,
    n_rows: int | None = None,
    col_block: int | None = None,
) -> jax.Array:
    """HBP SpMV: ``y = A @ x`` with A in HBP tile format."""
    x = jnp.asarray(x, jnp.float32)
    dt, (n_rowgroups, n_rows, col_block) = _resolve(tiles, x, n_rowgroups, n_rows, col_block)
    x_blocked = blocked_vector(x, col_block)
    _record_launch(1, op="spmv", strategy=strategy, x_rows=x_blocked.size,
                   col_block=col_block)
    entry, kw = _entry(
        x_blocked, n_rowgroups=n_rowgroups, n_rows=n_rows, strategy=strategy,
        interpret=interpret,
    )
    return entry(dt, x_blocked, **kw)


def _entry(x_blocked, *, n_rowgroups, n_rows, strategy, interpret, combine="sum"):
    """The jitted entry a wrapper call dispatches, and its static arguments:
    ``_hbp_spmv_device`` for a blocked vector, ``_hbp_spmm_device`` for a
    blocked ``[.., k]`` matrix."""
    kw = dict(n_rowgroups=n_rowgroups, n_rows=n_rows, strategy=strategy,
              interpret=resolve_interpret(interpret))
    if len(x_blocked.shape) == 2:
        return _hbp_spmv_device, kw
    return _hbp_spmm_device, dict(kw, combine=combine)


def lower_launch(dt: DeviceTiles, x, *, col_block: int, **meta):
    """Lower, without running, the jitted entry that ``hbp_spmv(dt, x,
    **meta)`` (vector ``x``) or ``hbp_spmm(dt, x, **meta)`` (``[n, k]``
    block) dispatches.  ``x`` may be a ``jax.ShapeDtypeStruct``; the
    lowered text shows whether the launch holds the Pallas kernel
    (``tpu_custom_call``)."""
    block = blocked_vector if len(x.shape) == 1 else blocked_matrix
    x_blocked = jax.eval_shape(functools.partial(block, col_block=col_block), x)
    entry, kw = _entry(x_blocked, **meta)
    return entry.lower(dt, x_blocked, **kw)


def bucket_k(k: int, buckets: tuple = K_BUCKETS) -> int:
    """Smallest bucket width >= k; beyond the top bucket, the next
    *multiple* of it.

    A request is never clamped down to the top bucket: k = 300 over the
    default buckets pads up to 384 (three 128-wide lane tiles), and
    ``hbp_spmm_bucketed`` slices the real columns back out — the 2D k-tiled
    grid in ``_hbp_spmm_device`` serves every 128-wide k-tile in one
    tile-stream pass.  Rounding to top-bucket multiples keeps the compile count
    bounded (one trace per multiple actually seen) while supporting
    arbitrary feature widths.
    """
    if k < 1:
        raise ValueError(f"k must be >= 1, got {k}")
    if not buckets:
        raise ValueError("buckets must be non-empty")
    for b in buckets:
        if k <= b:
            return int(b)
    top = buckets[-1]
    return -(-k // top) * top


def hbp_spmm_bucketed(
    tiles: HBPTiles | DeviceTiles,
    x: jax.Array,  # [n_cols, k]
    *,
    buckets: tuple = K_BUCKETS,
    **kwargs,
) -> jax.Array:
    """k-padded SpMM: pad the RHS block to the next bucket width, launch
    :func:`hbp_spmm`, slice the real columns back out.

    The padded columns are zero, contribute nothing, and are dropped
    before returning.  Under ``strategy="stable"`` the surviving columns
    are bitwise identical to the unpadded launch (the lane reduction is
    launch-width-invariant); the other strategies agree numerically but
    may differ by ~1 ulp when the bucket changes the launch width.  This
    is the entry the serving micro-batcher routes coalesced request
    blocks through.

    Zero-padding is also safe under ``combine="max"``: padded columns are
    sliced off before returning, and a padded *column* cannot influence a
    real one (the lane reduction never mixes k slots).
    """
    x = jnp.asarray(x, jnp.float32)
    k = x.shape[1]
    kb = bucket_k(k, buckets)
    if kb != k:
        x = jnp.pad(x, ((0, 0), (0, kb - k)))
    return hbp_spmm(tiles, x, **kwargs)[:, :k]


@functools.partial(jax.jit, static_argnames=("n_rowgroups", "n_rows"))
def _hbp_spmm_argmax_device(
    dt: DeviceTiles,
    x_blocked: jax.Array,  # f32[n_blocks, col_block, k]
    *,
    n_rowgroups: int,
    n_rows: int,
):
    k = x_blocked.shape[-1]
    if dt.data.shape[0] == 0:  # no tiles: every row is empty
        return (
            jnp.zeros((n_rows, k), jnp.float32),
            jnp.full((n_rows, k), -1, jnp.int32),
            jnp.zeros((n_rows, k), jnp.float32),
        )
    y_h, idx_h, coeff_h = _ref.hbp_spmm_hashed_argmax_onepass(
        dt.rowgroup, dt.colblock, dt.data, dt.cols, x_blocked,
        n_rowgroups=n_rowgroups,
    )
    y_h = jnp.where(jnp.isneginf(y_h), 0.0, y_h)  # empty rows aggregate to 0
    return (
        _ref.unpermute(y_h, dt.perm, n_rows),
        _ref.unpermute(idx_h, dt.perm, n_rows),
        _ref.unpermute(coeff_h, dt.perm, n_rows),
    )


def hbp_spmm_argmax(
    tiles: HBPTiles | DeviceTiles,
    x: jax.Array,  # [n_cols, k]
    *,
    n_rowgroups: int | None = None,
    n_rows: int | None = None,
    col_block: int | None = None,
):
    """Max-monoid SpMM with winner tracking: ``(y, idx, coeff)``.

    ``y`` matches ``hbp_spmm(..., combine="max")`` exactly; ``idx[i, c]``
    is the global source column whose stored entry attained the max (ties
    to the lowest column, ``-1`` for rows with no live entry) and
    ``coeff[i, c]`` that entry's value.  This is the forward pass of the
    differentiable max-aggregation (:mod:`repro.kernels.autodiff`): the
    VJP scatters ``coeff * cotangent`` back to row ``idx`` of the input.
    The reduction runs on the monoid-exact jnp path (the same lane chain
    as ``strategy="stable"``), so values are bitwise identical across
    batch widths and strategies.  A paired (value, index, coefficient)
    payload goes through a single tile-stream traversal
    (:func:`repro.kernels.ref.hbp_spmm_hashed_argmax_onepass`).
    """
    x = jnp.asarray(x, jnp.float32)
    dt, (n_rowgroups, n_rows, col_block) = _resolve(tiles, x, n_rowgroups, n_rows, col_block)
    _record_launch(x.shape[1], op="spmm_argmax", strategy="stable", combine="max")
    x_blocked = blocked_matrix(x, col_block)
    return _hbp_spmm_argmax_device(dt, x_blocked, n_rowgroups=n_rowgroups, n_rows=n_rows)


def hbp_spmm(
    tiles: HBPTiles | DeviceTiles,
    x: jax.Array,  # [n_cols, k]
    *,
    strategy: Literal["fused", "reference", "stable"] = "fused",
    combine: Literal["sum", "max"] = "sum",
    interpret: bool | None = None,
    n_rowgroups: int | None = None,
    n_rows: int | None = None,
    col_block: int | None = None,
) -> jax.Array:
    """HBP multi-RHS SpMM: ``Y = A (x) X`` with A in HBP tile format.

    One grid step serves up to :data:`LANE_TILE` columns of X; wider
    blocks run one launch of the fused kernel's 2D k-tiled grid
    (k-tile-major: each k-tile re-reads the tile stream) or the
    ``"reference"`` jnp path's single full-width traversal; the
    ``"stable"`` path runs <= LANE_TILE-wide lane chains.

    ``combine`` selects the reduction monoid: ``"sum"`` is the standard
    SpMM; ``"max"`` computes ``Y[i, c] = max_j A[i, j] * X[j, c]`` over
    A's *stored* entries (rows with none yield 0) — the max-aggregation
    semiring of GNN message passing (:mod:`repro.graph`).
    """
    x = jnp.asarray(x, jnp.float32)
    dt, (n_rowgroups, n_rows, col_block) = _resolve(tiles, x, n_rowgroups, n_rows, col_block)
    x_blocked = blocked_matrix(x, col_block)
    _record_launch(
        x.shape[1], op="spmm", strategy=strategy, combine=combine,
        x_rows=x_blocked.shape[0] * col_block, col_block=col_block,
    )
    entry, kw = _entry(
        x_blocked, n_rowgroups=n_rowgroups, n_rows=n_rows, strategy=strategy,
        interpret=interpret, combine=combine,
    )
    return entry(dt, x_blocked, **kw)
