"""Pallas TPU kernels for HBP SpMV / SpMM.

Two kernel strategies, both consuming the tile format of
:mod:`repro.core.tile`:

* **fused combine** (:func:`hbp_spmv_fused`, :func:`hbp_spmm_fused`,
  :func:`hbp_spmm_fused_max`) — beyond-paper.  The grid walks tiles sorted
  by (row-group, col-block); consecutive tiles of the same row group
  accumulate into the same output block, so the "combine part" of Fig. 1
  disappears into the SpMV pass.  On the GPU the paper tried this fusion
  and found atomics too expensive (Discussion section); the TPU's
  sequential grid gives it for free.

* **two-phase partials** (:func:`hbp_spmv_partials`,
  :func:`hbp_spmm_partials`, :func:`hbp_spmm_partials_max`) — faithful to
  the paper's SpMV-part/combine-part split: each tile writes its own
  partial block; the combine is a separate segment reduction in XLA
  (``ops.hbp_spmv(..., strategy="partials")``).

All six entry points share the lane gather body (:func:`_tile_columns`)
under two launch geometries; SpMV is the ``k = 1`` case of SpMM.  The two
fused SpMM entry points also have a second body, the row gather
(:func:`_row_kernel`), chosen per launch by :func:`gather_body`.  Each entry
point passes its own name to ``pallas_call`` (``name=``, the entry point's
name), so a profile tells the six kernels apart.

**Layouts.**  Every block obeys the TPU's rule for the last two block
dimensions (divisible by (8, 128) or equal to the array's):

* tile stream ``data``/``cols``: ``[T, group, lane]``, block
  ``(1, group, lane)`` — one tile per grid step; in VMEM under the lane
  gather, in SMEM (slot values and column ids as scalars) under the row
  gather;
* x under the lane gather: segments ``[n_col_blocks, k, seg]``
  (:func:`_segments`), RHS columns on sublanes and the segment's columns on
  lanes, ``seg`` = ``col_block`` rounded up to whole 128-lane chunks; block
  ``(1, k_tile, seg)``, index ``colblock[t]``;
* x under the row gather: ``[n_col_blocks * col_block, k]``, matrix columns
  on sublanes and RHS columns on lanes; block ``(n_col_blocks * col_block,
  k_tile)``, index ``(0, j)``: the whole column range of k-tile ``j``;
* output: ``[rows, group, k]``, block ``(1, group, k_tile)``.

Under the lane gather the x block's index depends only on ``colblock[t]``,
so Pallas skips the copy while consecutive tiles stay in one column block
(the VMEM analogue of the paper's shared-memory vector-segment reuse).
Power-law matrices switch column block at almost every step, so the
segment is re-fetched nearly every step there; the row gather's X changes
only at k-tile boundaries (the fused grid is k-tile-major) and is copied
once per launch and k-tile.

**Gather.**  Two bodies, one result:

* *lane gather* (:func:`_tile_columns`).  Mosaic lowers a vector gather
  only as a lane permutation inside one ``[rows, 128]`` operand whose index
  array has the same shape (``jnp.take_along_axis(..., axis=1)``).  The
  body widens the tile's column ids to 128 lanes (a masked store into a
  VMEM scratch when ``lane < 128``), splits each id into a 128-lane chunk
  and an offset, gathers every chunk of the segment row, and keeps the
  value of the chunk the id points into: ``seg / 128`` gathers over every
  slot, live or not, per RHS column per tile.  Its cost grows with k.
* *row gather* (:func:`_row_kernel`).  For each slot ``s < width[t]``
  (:func:`tile_widths`, staged once with the plan) each of the ``group``
  rows reads one X row, ``x[colblock[t] * col_block + cols[g, s], :]``: a
  dynamic one-sublane load holding all ``k_tile`` RHS columns, scaled by
  the slot's value and folded into that row's accumulator.  It visits only
  the tile's live width, once for all ``k_tile`` columns, so its cost does
  not grow with k up to one lane tile.

*Choice* (:func:`gather_body`): the row gather runs when the k-tile is at
least :data:`K_ROW` wide and one k-tile of X fits :data:`ROW_X_BUDGET`
whole; otherwise the lane gather.  ``K_ROW`` = 8 comes from a chip sweep
of both bodies over ``K_BUCKETS`` on ``kron16``'s tiles
(``benchmarks/gather_sweep.py``; PERF.md): the lane gather is faster at
k ≤ 4, the row gather from k = 8.  SpMV and the partials kernels always
run the lane gather.

**VMEM per grid step** (group 8, lane 128, col_block 4096).  Lane gather:
data and cols 4 KiB each, the id scratch 4 KiB, the x segment ``k_tile ×
16 KiB`` (2 MiB at ``k_tile`` = 128), the output block ``32 × k_tile`` B —
double-buffered, inside the default scoped-VMEM limit.  Row gather: the
resident X, ``n_col_blocks * col_block × 128 lanes × 4 B`` whatever
``k_tile`` (32 MiB for 65,536 columns), held in one buffer
(``pl.Buffered(1)``), plus the output windows; the launch raises
``vmem_limit_bytes`` to the X tile plus :data:`ROW_VMEM_SLACK`.  data and
cols sit in SMEM, 4 KiB each a step, beside the four scalar-prefetch arrays
(4 × 128 KiB at :data:`TILE_CHUNK` tiles).  The compile tests
(``tests/test_tpu_compile.py``) check both against a described v5e.

**2D k-tiled grid.**  One grid step carries at most :data:`LANE_TILE` RHS
columns; wider blocks are padded to a LANE_TILE multiple and run a 2D grid
in one launch.  The two families tile k differently, because Pallas TPU
only preserves an output block across *consecutive* grid steps:

* **partials** — grid ``(T, k // LANE_TILE)``, tile-major.  Every step
  writes its own output block ``(t, j)``; the (data, cols) block maps
  depend only on ``t``, so each tile is fetched ONCE — the stream is read
  once in total.
* **fused** — grid ``(k // LANE_TILE, T)``, k-tile-major.  The fused
  combine accumulates into output block ``(rg[t], j)``, which is only
  well-defined while revisits are consecutive — so ``t`` is innermost and
  each k-tile re-reads the stream.

Kernels are validated against ``ref.py`` in ``interpret=True`` mode on CPU
and compiled for a described v5e by ``tests/test_tpu_compile.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "LANE_TILE",
    "K_ROW",
    "gather_body",
    "tile_widths",
    "hbp_spmv_fused",
    "hbp_spmv_partials",
    "hbp_spmm_fused",
    "hbp_spmm_partials",
    "hbp_spmm_fused_max",
    "hbp_spmm_partials_max",
]

# Lanes of one VREG: the gather's chunk width and the widest RHS block one
# grid step carries.  Wider k runs the 2D k-tiled grid.
LANE_TILE = 128
_SUBLANES = 8  # rows of one VREG: RHS columns are read 8 at a time
# Most tiles one launch carries (see _chunks): 3 (lane gather) or 4 (row
# gather) x 4 B x 32768 = 384 or 512 KiB of scalar prefetch, inside the
# v5e's 1 MiB of SMEM.
TILE_CHUNK = 32768
# Narrowest k-tile the fused SpMM runs under the row gather: below it the
# lane gather, whose cost grows with k, is the cheaper body (on a v5e over
# kron16's tiles: lane 43.8 / 66.2 ms a call at k = 4 / 8, row 56.3 ms at
# any k; PERF.md).
K_ROW = 8
# Most VMEM a resident X k-tile may take under the row gather (the v5e has
# 128 MiB); wider X keeps the lane gather.  The launch asks for the X tile
# plus ROW_VMEM_SLACK for its windows and Mosaic's internal scratch.
ROW_X_BUDGET = 96 * 2**20
ROW_VMEM_SLACK = 16 * 2**20


def _padded_k(k: int) -> int:
    """RHS width the kernels run: whole 8-row groups beyond one group,
    whole lane tiles beyond one lane tile (padded columns are zero)."""
    if k <= _SUBLANES:
        return k
    if k <= LANE_TILE:
        return -(-k // _SUBLANES) * _SUBLANES
    return -(-k // LANE_TILE) * LANE_TILE


def _segments(x_blocked: jax.Array) -> jax.Array:
    """Kernel layout of the staged RHS: ``[n_col_blocks, k, seg]``.

    Takes :func:`repro.kernels.ops.blocked_vector` (``[n_cb, col_block]``)
    or ``blocked_matrix`` (``[n_cb, col_block, k]``) output, puts the RHS
    columns on sublanes and the segment on lanes, and zero-pads the
    segment to whole lane chunks and k to :func:`_padded_k`."""
    x = x_blocked[:, :, None] if x_blocked.ndim == 2 else x_blocked
    _, col_block, k = x.shape
    x = jnp.pad(x, ((0, 0), (0, -col_block % LANE_TILE), (0, _padded_k(k) - k)))
    return jnp.swapaxes(x, 1, 2)


def _tile_columns(data_ref, cols_ref, x_ref, idx_ref, *, combine: str):
    """One tile against ``k_tile`` RHS columns: ``[group, k_tile]`` with
    ``out[g, j] = reduce_l data[g, l] * x[j, cols[g, l]]``.

    ``combine="sum"`` reduces with ``+``; ``"max"`` with ``maximum`` over
    the live slots (stored value != 0), ``-inf`` where a row has none."""
    group, lane = data_ref.shape[1], data_ref.shape[2]
    k_tile, seg = x_ref.shape[1], x_ref.shape[2]
    if lane > LANE_TILE:
        raise ValueError(f"lane = {lane} exceeds one lane tile ({LANE_TILE})")
    cols = cols_ref[0]
    if lane < LANE_TILE:
        # the gather needs 128-lane ids; lanes >= `lane` hold junk that the
        # mask below keeps in range and the [:, :lane] slice drops
        idx_ref[:, :lane] = cols
        cols = idx_ref[...]
    chunk = lax.shift_right_logical(cols, LANE_TILE.bit_length() - 1)
    offset = cols & (LANE_TILE - 1)
    data = data_ref[0]
    col_id = lax.broadcasted_iota(jnp.int32, (group, k_tile), 1)
    rows = min(k_tile, _SUBLANES)

    def row_group(base, out):
        gathered = [jnp.zeros(cols.shape, jnp.float32)] * rows
        for c in range(seg // LANE_TILE):
            block = x_ref[0, pl.ds(base, rows), pl.ds(c * LANE_TILE, LANE_TILE)]
            hit = chunk == c
            for r in range(rows):
                src = jnp.broadcast_to(block[r : r + 1], cols.shape)
                picked = jnp.take_along_axis(src, offset, axis=1)
                gathered[r] = jnp.where(hit, picked, gathered[r])
        for r in range(rows):
            prod = data * gathered[r][:, :lane]
            if combine == "sum":
                col = jnp.sum(prod, axis=1, keepdims=True)
            else:
                col = jnp.max(jnp.where(data != 0, prod, -jnp.inf), axis=1, keepdims=True)
            out = jnp.where(col_id == base + r, col, out)
        return out

    out = jnp.zeros((group, k_tile), jnp.float32)
    if k_tile == rows:
        return row_group(0, out)
    return lax.fori_loop(
        0, k_tile // rows, lambda i, o: row_group(pl.multiple_of(i * rows, rows), o), out
    )


def _accumulate(t, first_ref, y_prev_ref, y_ref, tile_part, *, combine):
    """y[rowgroup[t]] (+|max)= tile_part(), the tile body's ``[group,
    k_tile]`` block.  t is the LAST grid dim: the accumulation revisits its
    output block, and Pallas TPU preserves an output block only across
    consecutive grid steps."""

    @pl.when(first_ref[t] == 1)
    def _init():
        identity = 0.0 if combine == "sum" else -jnp.inf
        y_ref[...] = jnp.full(y_ref.shape, identity, jnp.float32)

    @pl.when(jnp.logical_and(t == 0, first_ref[0] == 0))
    def _carry():
        # this launch starts inside a row group's run that the previous
        # launch began: continue from what that launch wrote back
        y_ref[...] = y_prev_ref[...]

    part = tile_part()
    if combine == "sum":
        y_ref[0] += part
    else:
        y_ref[0] = jnp.maximum(y_ref[0], part)


def _fused_kernel(rowgroup_ref, colblock_ref, first_ref, data_ref, cols_ref, x_ref,
                  y_prev_ref, y_ref, idx_ref, *, combine):
    """The lane gather body: one tile against its x segment."""
    _accumulate(pl.program_id(1), first_ref, y_prev_ref, y_ref,
                lambda: _tile_columns(data_ref, cols_ref, x_ref, idx_ref, combine=combine),
                combine=combine)


def _row_kernel(rowgroup_ref, colblock_ref, first_ref, width_ref, data_ref, cols_ref,
                x_ref, y_prev_ref, y_ref, *, combine, col_block):
    """The row gather body: for each live slot ``s < width[t]`` of the
    tile, row ``g`` reads one X row (all ``k_tile`` RHS columns on its
    lanes) from the VMEM-resident X and folds ``data[g, s] * row`` into
    its own accumulator.  Padded slots of shorter rows hold data 0 and
    column 0: they add 0, and under max the ``data != 0`` rule drops them."""
    t = pl.program_id(1)
    group, kt = data_ref.shape[1], x_ref.shape[1]
    base = colblock_ref[t] * col_block
    identity = 0.0 if combine == "sum" else -jnp.inf

    def slot(s, acc):
        out = []
        for g in range(group):  # unrolled: independent accumulator chains
            a = data_ref[0, g, s]
            prod = a * x_ref[pl.ds(base + cols_ref[0, g, s], 1), :]
            if combine == "sum":
                out.append(acc[g] + prod)
            else:
                out.append(jnp.maximum(acc[g], jnp.where(a != 0, prod, -jnp.inf)))
        return tuple(out)

    def tile_part():
        init = (jnp.full((1, kt), identity, jnp.float32),) * group
        return jnp.concatenate(lax.fori_loop(0, width_ref[t], slot, init), axis=0)

    _accumulate(t, first_ref, y_prev_ref, y_ref, tile_part, combine=combine)


def _partials_kernel(colblock_ref, data_ref, cols_ref, x_ref, y_prev_ref, y_ref,
                     idx_ref, *, combine):
    """One grid step = one tile: emit the tile's own partial block."""
    y_ref[0] = _tile_columns(data_ref, cols_ref, x_ref, idx_ref, combine=combine)


def _k_grid(k: int):
    """(k_tile, n_k_tiles) of a launch over a :func:`_padded_k` width."""
    if k <= LANE_TILE:
        return k, 1
    return LANE_TILE, k // LANE_TILE


def _chunks(T: int):
    """(first tile, tiles) of each launch: the per-tile scalars a launch
    prefetches live in SMEM (1 MiB on v5e), so a launch carries at most
    :data:`TILE_CHUNK` tiles.  Launches share one output buffer
    (``input_output_aliases``): blocks a launch never visits keep what the
    earlier ones wrote."""
    return [(lo, min(TILE_CHUNK, T - lo)) for lo in range(0, T, TILE_CHUNK)]


def tile_widths(data: jax.Array) -> jax.Array:
    """``i32[T]``: one past each tile's last live slot (stored value != 0)
    over its rows — the row gather's loop bound.  Rows are packed to the
    left of their tile, so this is the longest row's live count."""
    pos = jnp.arange(1, data.shape[2] + 1, dtype=jnp.int32)
    return jnp.max(jnp.where(data != 0, pos, 0), axis=(1, 2))


def _resident_x_bytes(x_rows: int) -> int:
    """VMEM of one k-tile of X held whole: ``[x_rows, k_tile]`` f32, rows
    padded to whole sublane groups and k_tile to a full lane tile."""
    return -(-x_rows // _SUBLANES) * _SUBLANES * LANE_TILE * 4


def gather_body(k: int, x_rows: int) -> str:
    """Which tile body a fused SpMM launch over ``k`` RHS columns and an X
    of ``x_rows`` (padded) rows runs: ``"row"`` when the k-tile is at
    least :data:`K_ROW` wide and X fits :data:`ROW_X_BUDGET` whole,
    ``"lane"`` otherwise."""
    kt, _ = _k_grid(_padded_k(k))
    if kt >= K_ROW and _resident_x_bytes(x_rows) <= ROW_X_BUDGET:
        return "row"
    return "lane"


def _fused_spmm(rowgroup, colblock, first, data, cols, x_blocked, width, **kw):
    """The fused SpMM launches under the body :func:`gather_body` picks."""
    n_cb, col_block, k = x_blocked.shape
    if gather_body(k, n_cb * col_block) == "lane":
        return _fused(rowgroup, colblock, first, data, cols, x_blocked, **kw)
    return _fused_rows(rowgroup, colblock, first, width, data, cols, x_blocked, **kw)


def _fused(rowgroup, colblock, first, data, cols, x_blocked, *, n_rowgroups,
           combine, interpret, name):
    T, group, lane = data.shape
    xs = _segments(x_blocked)
    _, k, seg = xs.shape
    kt, n_kt = _k_grid(k)
    identity = 0.0 if combine == "sum" else -jnp.inf
    # row groups no tile visits keep the monoid identity
    y = jnp.full((n_rowgroups, group, k), identity, jnp.float32)
    for lo, n in _chunks(T):
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_kt, n),
            in_specs=[
                pl.BlockSpec((1, group, lane), lambda j, t, rg, cb, fs, lo=lo: (t + lo, 0, 0)),
                pl.BlockSpec((1, group, lane), lambda j, t, rg, cb, fs, lo=lo: (t + lo, 0, 0)),
                pl.BlockSpec((1, kt, seg), lambda j, t, rg, cb, fs: (cb[t], j, 0)),
                # the block of the launch's first row group, fetched once
                pl.BlockSpec((1, group, kt), lambda j, t, rg, cb, fs: (rg[0], 0, j)),
            ],
            out_specs=pl.BlockSpec((1, group, kt), lambda j, t, rg, cb, fs: (rg[t], 0, j)),
            scratch_shapes=[pltpu.VMEM((group, LANE_TILE), jnp.int32)],
        )
        y = pl.pallas_call(
            functools.partial(_fused_kernel, combine=combine),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(y.shape, jnp.float32),
            input_output_aliases={6: 0},
            interpret=interpret,
            name=name,
        )(rowgroup[lo : lo + n], colblock[lo : lo + n], first[lo : lo + n],
          data, cols, xs, y)
    return y


def _fused_rows(rowgroup, colblock, first, width, data, cols, x_blocked, *,
                n_rowgroups, combine, interpret, name):
    """The fused launches under the row gather: X is ``[n_cb * col_block,
    k]`` (matrix columns on sublanes, RHS columns on lanes), one k-tile of
    it resident in VMEM for the launch; tiles sit in SMEM."""
    T, group, lane = data.shape
    n_cb, col_block, k = x_blocked.shape
    kp = _padded_k(k)
    x = jnp.pad(x_blocked.reshape(n_cb * col_block, k), ((0, 0), (0, kp - k)))
    kt, n_kt = _k_grid(kp)
    identity = 0.0 if combine == "sum" else -jnp.inf
    y = jnp.full((n_rowgroups, group, kp), identity, jnp.float32)
    vmem = _resident_x_bytes(x.shape[0]) + ROW_VMEM_SLACK
    for lo, n in _chunks(T):
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n_kt, n),
            in_specs=[
                pl.BlockSpec((1, group, lane), lambda j, t, rg, cb, fs, w, lo=lo: (t + lo, 0, 0),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((1, group, lane), lambda j, t, rg, cb, fs, w, lo=lo: (t + lo, 0, 0),
                             memory_space=pltpu.SMEM),
                # the whole k-tile of X, copied once per k-tile, one buffer
                pl.BlockSpec((x.shape[0], kt), lambda j, t, rg, cb, fs, w: (0, j),
                             pipeline_mode=pl.Buffered(1)),
                pl.BlockSpec((1, group, kt), lambda j, t, rg, cb, fs, w: (rg[0], 0, j)),
            ],
            out_specs=pl.BlockSpec((1, group, kt), lambda j, t, rg, cb, fs, w: (rg[t], 0, j)),
        )
        y = pl.pallas_call(
            functools.partial(_row_kernel, combine=combine, col_block=col_block),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(y.shape, jnp.float32),
            input_output_aliases={7: 0},
            compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem),
            interpret=interpret,
            name=name,
        )(rowgroup[lo : lo + n], colblock[lo : lo + n], first[lo : lo + n],
          width[lo : lo + n], data, cols, x, y)
    return y


def _partials(colblock, data, cols, x_blocked, *, combine, interpret, name):
    T, group, lane = data.shape
    xs = _segments(x_blocked)
    _, k, seg = xs.shape
    kt, n_kt = _k_grid(k)
    y = jnp.zeros((T, group, k), jnp.float32)
    for lo, n in _chunks(T):
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n, n_kt),
            in_specs=[
                pl.BlockSpec((1, group, lane), lambda t, j, cb, lo=lo: (t + lo, 0, 0)),
                pl.BlockSpec((1, group, lane), lambda t, j, cb, lo=lo: (t + lo, 0, 0)),
                pl.BlockSpec((1, kt, seg), lambda t, j, cb: (cb[t], j, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, group, kt), lambda t, j, cb, lo=lo: (t + lo, 0, j)),
            scratch_shapes=[pltpu.VMEM((group, LANE_TILE), jnp.int32)],
        )
        y = pl.pallas_call(
            functools.partial(_partials_kernel, combine=combine),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(y.shape, jnp.float32),
            input_output_aliases={4: 0},
            interpret=interpret,
            name=name,
        )(colblock[lo : lo + n], data, cols, xs, y)
    return y


@functools.partial(jax.jit, static_argnames=("n_rowgroups", "interpret"))
def hbp_spmv_fused(rowgroup, colblock, first, data, cols, x_blocked, *,
                   n_rowgroups: int, interpret: bool = False) -> jax.Array:
    """Fused-combine HBP SpMV over ``x_blocked: f32[n_col_blocks, col_block]``.
    Returns y in hashed row order, shape [n_rowgroups, group]."""
    return _fused(rowgroup, colblock, first, data, cols, x_blocked,
                  n_rowgroups=n_rowgroups, combine="sum", interpret=interpret,
                  name="hbp_spmv_fused")[..., 0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def hbp_spmv_partials(colblock, data, cols, x_blocked, *,
                      interpret: bool = False) -> jax.Array:
    """SpMV part only (paper-faithful): per-tile partial vectors [T, group];
    the combine part reduces them by row group."""
    return _partials(colblock, data, cols, x_blocked, combine="sum",
                     interpret=interpret, name="hbp_spmv_partials")[..., 0]


@functools.partial(jax.jit, static_argnames=("n_rowgroups", "interpret"))
def hbp_spmm_fused(rowgroup, colblock, first, data, cols, x_blocked, width, *,
                   n_rowgroups: int, interpret: bool = False) -> jax.Array:
    """Fused-combine HBP SpMM: ``Y = A @ X`` over
    ``x_blocked: f32[n_col_blocks, col_block, k]``.

    One launch serves all ``k`` right-hand sides: the tile stream (data +
    cols, the dominant HBM traffic) is read once per k-tile instead of
    ``k`` times.  ``width`` is :func:`tile_widths` of ``data``, staged with
    the plan (``DeviceTiles.width``).  Returns y in hashed row order,
    [n_rowgroups, group, k]."""
    k = x_blocked.shape[-1]
    return _fused_spmm(rowgroup, colblock, first, data, cols, x_blocked, width,
                       n_rowgroups=n_rowgroups, combine="sum", interpret=interpret,
                       name="hbp_spmm_fused")[..., :k]


@functools.partial(jax.jit, static_argnames=("interpret",))
def hbp_spmm_partials(colblock, data, cols, x_blocked, *,
                      interpret: bool = False) -> jax.Array:
    """SpMM part only (two-phase multi-RHS): per-tile partial blocks
    [T, group, k]; the combine part reduces them by row group."""
    k = x_blocked.shape[-1]
    return _partials(colblock, data, cols, x_blocked, combine="sum",
                     interpret=interpret, name="hbp_spmm_partials")[..., :k]


@functools.partial(jax.jit, static_argnames=("n_rowgroups", "interpret"))
def hbp_spmm_fused_max(rowgroup, colblock, first, data, cols, x_blocked, width, *,
                       n_rowgroups: int, interpret: bool = False) -> jax.Array:
    """Fused-combine HBP SpMM under the max monoid (GNN max-aggregation):
    ``y[i, c] = max_j a_ij * x_jc`` over stored entries.  Padded slots are
    masked to ``-inf``, the max identity, so rows with no live entry come
    back ``-inf`` for the caller to zero (``ops._hbp_spmm_device``)."""
    k = x_blocked.shape[-1]
    return _fused_spmm(rowgroup, colblock, first, data, cols, x_blocked, width,
                       n_rowgroups=n_rowgroups, combine="max", interpret=interpret,
                       name="hbp_spmm_fused_max")[..., :k]


@functools.partial(jax.jit, static_argnames=("interpret",))
def hbp_spmm_partials_max(colblock, data, cols, x_blocked, *,
                          interpret: bool = False) -> jax.Array:
    """SpMM part only under the max monoid: per-tile partial blocks
    [T, group, k]; the combine part reduces them with ``segment_max``."""
    k = x_blocked.shape[-1]
    return _partials(colblock, data, cols, x_blocked, combine="max",
                     interpret=interpret, name="hbp_spmm_partials_max")[..., :k]
