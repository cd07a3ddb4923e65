"""Pallas TPU kernels for HBP SpMV / SpMM.

One kernel family, the **fused combine** (:func:`hbp_spmv_fused`,
:func:`hbp_spmm_fused`, :func:`hbp_spmm_fused_max`), consuming the tile
format of :mod:`repro.core.tile`.  Tiles come sorted by (row-group,
col-block); each tile folds its rows straight into the output, so the
"combine part" of the paper's Fig. 1 disappears into the SpMV pass.  On
the GPU the paper tried this fusion and found atomics too expensive
(Discussion section); the TPU's sequential grid gives it for free.

The three entry points share the lane gather body (:func:`_tile_columns`);
SpMV is the ``k = 1`` case of SpMM.  The two SpMM entry points also have a
second body, the row gather (:func:`_row_kernel`), chosen per launch by
:func:`gather_body`.  Each entry point passes its own name to
``pallas_call`` (``name=``, the entry point's name), so a profile tells
the three kernels apart.

**Blocked grid (fused).**  A fused launch runs the grid ``(n_k_tiles,
cdiv(tiles, B))``: each step carries a block of ``B`` consecutive tiles
and walks them in a ``fori_loop``; the last block may be short, and the
loop stops at the launch's tile count (nothing is padded in HBM).  Each
tile reads its row group and column block from scalar prefetch.  Under
the lane gather one iteration computes :data:`INTERLEAVE_LANE` tiles
before it folds any of them into the output, so their latency chains
overlap; the folds keep the stream's order.  ``B`` is
a constant per body (:func:`tiles_per_step`): :data:`BLOCK_LANE` tiles
under the lane gather, :data:`BLOCK_ROW` under the row gather, 1 where the
lane gather's X cannot be resident.  A grid step has a fixed cost of about
a quarter of a microsecond on a v5e (PERF.md), which a block shares among
its tiles; ``benchmarks/gather_sweep.py`` times every ``B`` of both
bodies on ``kron16``'s tiles.

**Layouts.**  Every block obeys the TPU's rule for the last two block
dimensions (divisible by (8, 128) or equal to the array's):

* tile stream ``data``/``cols``: ``[T, group, lane]``, block ``(B, group,
  lane)``; in VMEM under the lane gather, in SMEM (slot values and column
  ids as scalars) under the row gather;
* x under the lane gather: segments ``[n_col_blocks, k, seg]``
  (:func:`_segments`), RHS columns on sublanes and the segment's columns on
  lanes, ``seg`` = ``col_block`` rounded up to whole 128-lane chunks; block
  ``(n_col_blocks, k_tile, seg)``, index ``(0, j, 0)``: the whole k-tile,
  resident for the launch, each tile reading segment ``colblock[t]``.
  When that exceeds :data:`X_BUDGET` the block is ``(1, k_tile, seg)`` at
  ``colblock[t]`` and a step carries one tile;
* x under the row gather: ``[n_col_blocks * col_block, k]``, matrix columns
  on sublanes and RHS columns on lanes; block ``(n_col_blocks * col_block,
  k_tile)``, index ``(0, j)``: the whole column range of k-tile ``j``;
* output: ``[rows, group, 128 * n_k_tiles]`` in HBM, aliased across the
  launches, sliced to ``k`` after the last.  A launch holds a window of
  it in VMEM (:func:`_blocked`).

**Output window (the launch-cut rule).**  Tiles of one step span several
row groups, and Pallas keeps an output block only while its index stays
put, so the output is not a pipelined block: a launch accumulates into a
VMEM window of ``window`` row groups of one k-tile (:func:`_window`: as
many as fit :data:`WINDOW_BYTES`, at most the output's), and copies it in
and out of HBM itself.  The rows of the output are cut into windows
``[w * window, (w + 1) * window)``; the sorted tile stream visits them in
order, so a launch copies in the window of its first tile, moves to the
next window (copy back, copy in) when a tile's row group leaves the
current one, and copies the last one back at its last step.  The cut
follows from shapes alone (``group``, ``n_rowgroups``), and every matrix,
whatever its row count, runs the same way: ``kron16`` (8,192 row groups)
in 8 windows, a matrix of 321,821 rows in 40.  A launch that starts
inside a row group's run continues from what the previous launch copied
back; launches are cut by :data:`TILE_CHUNK` alone (SMEM), at whole
blocks (:func:`_chunks`).

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
least :data:`K_ROW` wide and one k-tile of X fits :data:`X_BUDGET` whole;
otherwise the lane gather.  ``K_ROW`` = 8 comes from a chip sweep of both
bodies over ``K_BUCKETS`` on ``kron16``'s tiles (``benchmarks/
gather_sweep.py``; PERF.md).  SpMV always runs the lane gather.

**VMEM and SMEM** (group 8, lane 128, col_block 4096).  Lane gather, per
launch: the resident X, ``n_col_blocks × 8 × 16 KiB`` for a k-tile of up
to 8 (2 MiB on 65,536 columns), ``n_col_blocks × 2 MiB`` at ``k_tile`` =
128, one buffer; the window, 4 KiB a row group (``[8, k_tile]`` takes a
whole (8, 128) tile), :data:`WINDOW_BYTES` at most; data and cols ``B ×
4 KiB`` each, double-buffered; the id scratch 4 KiB for each tile of a
loop iteration.  Row gather: the resident X, ``n_col_blocks * col_block ×
128 lanes × 4 B`` whatever ``k_tile`` (32 MiB for 65,536 columns), one
buffer, and the window; data and cols sit in SMEM, ``B × 4 KiB`` each,
double-buffered, beside the three scalar-prefetch arrays (3 × 128 KiB at
:data:`TILE_CHUNK` tiles).
Each launch asks for X, the window and its VMEM tile blocks plus
:data:`VMEM_SLACK`.  The compile tests (``tests/test_tpu_compile.py``)
check both bodies against a described v5e at ``kron16``'s size.

**2D k-tiled grid.**  One grid step carries at most :data:`LANE_TILE` RHS
columns; wider blocks are padded to a LANE_TILE multiple and run the grid
``(k // LANE_TILE, cdiv(T, B))`` in one launch, k-tile-major: the window
and the resident X belong to one k-tile, so each k-tile re-reads the
stream.

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
    "hbp_spmm_fused",
    "hbp_spmm_fused_max",
]

# Lanes of one VREG: the gather's chunk width and the widest RHS block one
# grid step carries.  Wider k runs the 2D k-tiled grid.
LANE_TILE = 128
_SUBLANES = 8  # rows of one VREG: RHS columns are read 8 at a time
# Most tiles one launch carries (see _chunks): 2 (lane gather) or 3 (row
# gather) x 4 B x 32768 = 256 or 384 KiB of scalar prefetch, inside the
# v5e's 1 MiB of SMEM.
TILE_CHUNK = 32768
# Narrowest k-tile the fused SpMM runs under the row gather: below it the
# lane gather, whose cost grows with k, is the cheaper body (on a v5e over
# kron16's tiles: lane 43.8 / 66.2 ms a call at k = 4 / 8, row 56.3 ms at
# any k; PERF.md).
K_ROW = 8
# Tiles one fused grid step carries, per body; benchmarks/gather_sweep.py
# times every B on the chip (PERF.md).  The row gather's tiles sit in SMEM
# beside the scalar prefetch, which bounds it: 32 fits a v5e, 64 does not.
BLOCK_LANE = 128
BLOCK_ROW = 16
# Tiles one iteration of the lane gather's tile loop computes before it
# folds them: a k=1 tile is bound by the latency of its own chain, which
# independent tiles overlap (on a v5e over kron16's tiles at B = 128, a
# k=1 call takes 22.2 / 17.5 / 16.4 / 15.2 ms at 1 / 2 / 3 / 4; 2 is the
# count measured end to end; PERF.md).
INTERLEAVE_LANE = 2
# Most VMEM a resident X k-tile may take (the v5e has 128 MiB): wider X
# keeps the lane gather, and under the lane gather streams one segment a
# step, one tile a step.
X_BUDGET = 96 * 2**20
# VMEM of the output window a fused launch accumulates in.
WINDOW_BYTES = 4 * 2**20
# VMEM asked for beyond X, the window and the tile blocks: Mosaic's
# internal scratch and the body's temporaries.
VMEM_SLACK = 16 * 2**20


def _padded_k(k: int) -> int:
    """RHS width the kernels run: whole 8-row groups beyond one group,
    whole lane tiles beyond one lane tile (padded columns are zero)."""
    if k <= _SUBLANES:
        return k
    if k <= LANE_TILE:
        return -(-k // _SUBLANES) * _SUBLANES
    return -(-k // LANE_TILE) * LANE_TILE


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


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


def _tile_columns(data_ref, cols_ref, x_ref, idx_ref, *, combine: str, tile, segment):
    """Tile ``tile`` of the data/cols block against segment ``segment`` of
    the x block, ``k_tile`` RHS columns: ``[group, k_tile]`` with
    ``out[g, j] = reduce_l data[g, l] * x[j, cols[g, l]]``.

    ``combine="sum"`` reduces with ``+``; ``"max"`` with ``maximum`` over
    the live slots (stored value != 0), ``-inf`` where a row has none."""
    group, lane = data_ref.shape[1], data_ref.shape[2]
    k_tile, seg = x_ref.shape[1], x_ref.shape[2]
    if lane > LANE_TILE:
        raise ValueError(f"lane = {lane} exceeds one lane tile ({LANE_TILE})")
    cols = cols_ref[tile]
    if lane < LANE_TILE:
        # the gather needs 128-lane ids; lanes >= `lane` hold junk that the
        # mask below keeps in range and the [:, :lane] slice drops
        idx_ref[:, :lane] = cols
        cols = idx_ref[...]
    chunk = lax.shift_right_logical(cols, LANE_TILE.bit_length() - 1)
    offset = cols & (LANE_TILE - 1)
    data = data_ref[tile]
    col_id = lax.broadcasted_iota(jnp.int32, (group, k_tile), 1)
    rows = min(k_tile, _SUBLANES)

    def row_group(base, out):
        gathered = [jnp.zeros(cols.shape, jnp.float32)] * rows
        for c in range(seg // LANE_TILE):
            block = x_ref[segment, pl.ds(base, rows), pl.ds(c * LANE_TILE, LANE_TILE)]
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


def _blocked(rowgroup_ref, y_ref, acc_ref, cur_ref, tile_part, *, n, block,
             n_rowgroups, combine, interleave=1):
    """One grid step of a fused launch: its block of up to ``block`` tiles
    (``n`` tiles in the launch; the last block may be short), each folded
    into row ``rowgroup[t]`` of the output window in ``acc_ref``.
    ``tile_part(i, t, u)`` computes tile ``t``, the block's ``i``-th, as
    the ``u``-th of an iteration's ``interleave`` tiles; a block's last
    ``cnt % interleave`` tiles run one an iteration.

    The window holds ``window`` row groups of the k-tile in VMEM; window
    ``w`` covers row groups ``[w * window, (w + 1) * window)``, its copy
    starting at ``min(w * window, n_rowgroups - window)``.  Tiles come
    sorted by row group, so the launch walks the windows in order: the
    first step copies in the window of the launch's first tile, a tile of
    a later window copies the current one back and the next one in, and
    the last step copies the current one back.  A launch that starts
    inside a row group's run therefore continues from what the previous
    launch wrote back."""
    j, b = pl.program_id(0), pl.program_id(1)
    window = acc_ref.shape[0]
    # the k-tile's lane tile of y; the whole width when it is one k-tile
    lanes = slice(None) if y_ref.shape[2] == LANE_TILE else pl.ds(j * LANE_TILE, LANE_TILE)

    def hbm(w):
        start = jnp.minimum(w * window, n_rowgroups - window)
        return y_ref.at[pl.ds(start, window), :, lanes], start

    @pl.when(b == 0)
    def _load():
        cur_ref[0] = rowgroup_ref[0] // window
        pltpu.sync_copy(hbm(cur_ref[0])[0], acc_ref)

    def fold(t, part):
        """Fold ``part()``, tile ``t``'s rows, into the window."""
        rg = rowgroup_ref[t]
        w = rg // window

        @pl.when(w != cur_ref[0])
        def _move():
            pltpu.sync_copy(acc_ref, hbm(cur_ref[0])[0])
            pltpu.sync_copy(hbm(w)[0], acc_ref)
            cur_ref[0] = w

        r = rg - hbm(w)[1]
        part = part()
        cols = slice(None, part.shape[1])
        if combine == "sum":
            acc_ref[r, :, cols] += part
        else:
            acc_ref[r, :, cols] = jnp.maximum(acc_ref[r, :, cols], part)

    def tiles(first, count):
        def body(p, carry):
            i = first + p * count
            t = b * block + i
            if count == 1:
                fold(t, lambda: tile_part(i, t, 0))
                return carry
            parts = [tile_part(i + u, t + u, u) for u in range(count)]
            for u, part in enumerate(parts):
                fold(t + u, lambda part=part: part)
            return carry
        return body

    cnt = jnp.minimum(block, n - b * block)
    lax.fori_loop(0, cnt // interleave, tiles(0, interleave), 0)
    if interleave > 1:
        rest = cnt // interleave * interleave
        lax.fori_loop(0, cnt - rest, tiles(rest, 1), 0)

    @pl.when(b == pl.num_programs(1) - 1)
    def _store():
        pltpu.sync_copy(acc_ref, hbm(cur_ref[0])[0])


def _lane_kernel(rowgroup_ref, colblock_ref, data_ref, cols_ref, x_ref, y_in_ref, y_ref,
                 acc_ref, cur_ref, idx_ref, *, resident, **kw):
    """The lane gather body: each tile against its x segment, read from the
    resident X at ``colblock[t]`` (or, streamed, the step's one segment),
    :data:`INTERLEAVE_LANE` tiles an iteration, each with its own id
    scratch."""
    del y_in_ref  # aliased to y_ref

    def tile_part(i, t, u):
        segment = colblock_ref[t] if resident else 0
        return _tile_columns(data_ref, cols_ref, x_ref, idx_ref.at[u], combine=kw["combine"],
                             tile=i, segment=segment)

    _blocked(rowgroup_ref, y_ref, acc_ref, cur_ref, tile_part,
             interleave=idx_ref.shape[0], **kw)


def _row_kernel(rowgroup_ref, colblock_ref, width_ref, data_ref, cols_ref, x_ref, y_in_ref,
                y_ref, acc_ref, cur_ref, *, col_block, **kw):
    """The row gather body: for each live slot ``s < width[t]`` of a tile,
    row ``g`` reads one X row (all ``k_tile`` RHS columns on its lanes)
    from the VMEM-resident X and folds ``data[g, s] * row`` into its own
    accumulator.  Padded slots of shorter rows hold data 0 and column 0:
    they add 0, and under max the ``data != 0`` rule drops them."""
    del y_in_ref  # aliased to y_ref
    group, kt = data_ref.shape[1], x_ref.shape[1]
    identity = 0.0 if kw["combine"] == "sum" else -jnp.inf

    def tile_part(i, t, _u):
        base = colblock_ref[t] * col_block

        def slot(s, acc):
            out = []
            for g in range(group):  # unrolled: independent accumulator chains
                a = data_ref[i, g, s]
                prod = a * x_ref[pl.ds(base + cols_ref[i, g, s], 1), :]
                if kw["combine"] == "sum":
                    out.append(acc[g] + prod)
                else:
                    out.append(jnp.maximum(acc[g], jnp.where(a != 0, prod, -jnp.inf)))
            return tuple(out)

        init = (jnp.full((1, kt), identity, jnp.float32),) * group
        return jnp.concatenate(lax.fori_loop(0, width_ref[t], slot, init), axis=0)

    _blocked(rowgroup_ref, y_ref, acc_ref, cur_ref, tile_part, **kw)


def _k_grid(k: int):
    """(k_tile, n_k_tiles) of a launch over a :func:`_padded_k` width."""
    if k <= LANE_TILE:
        return k, 1
    return LANE_TILE, k // LANE_TILE


def _chunks(T: int, block: int):
    """(first tile, tiles) of each launch: the per-tile scalars a launch
    prefetches live in SMEM (1 MiB on v5e), so a launch carries at most
    :data:`TILE_CHUNK` tiles, a whole number of ``block``-tile grid steps
    (one block at least).  Launches share one output buffer
    (``input_output_aliases``): rows a launch never writes keep what the
    earlier ones wrote."""
    chunk = max(block, TILE_CHUNK - TILE_CHUNK % block)
    return [(lo, min(chunk, T - lo)) for lo in range(0, T, chunk)]


def tile_widths(data: jax.Array) -> jax.Array:
    """``i32[T]``: one past each tile's last live slot (stored value != 0)
    over its rows — the row gather's loop bound.  Rows are packed to the
    left of their tile, so this is the longest row's live count."""
    pos = jnp.arange(1, data.shape[2] + 1, dtype=jnp.int32)
    return jnp.max(jnp.where(data != 0, pos, 0), axis=(1, 2))


def _row_x_bytes(x_rows: int) -> int:
    """VMEM of one k-tile of X held whole under the row gather: ``[x_rows,
    k_tile]`` f32, rows padded to whole sublane groups and k_tile to a full
    lane tile."""
    return _round_up(x_rows, _SUBLANES) * LANE_TILE * 4


def _lane_x_bytes(n_cb: int, kt: int, seg: int) -> int:
    """VMEM of one k-tile of the segments ``[n_cb, kt, seg]`` held whole."""
    return n_cb * _round_up(kt, _SUBLANES) * seg * 4


def _window(group: int, n_rowgroups: int) -> int:
    """Row groups of the output window: as many ``[group, k_tile]`` blocks
    (a whole ``(8, 128)`` VMEM tile at least) as fit :data:`WINDOW_BYTES`,
    at most the output's."""
    per = _round_up(group, _SUBLANES) * LANE_TILE * 4
    return max(1, min(n_rowgroups, WINDOW_BYTES // per))


def gather_body(k: int, x_rows: int) -> str:
    """Which tile body a fused SpMM launch over ``k`` RHS columns and an X
    of ``x_rows`` (padded) rows runs: ``"row"`` when the k-tile is at
    least :data:`K_ROW` wide and X fits :data:`X_BUDGET` whole,
    ``"lane"`` otherwise."""
    kt, _ = _k_grid(_padded_k(k))
    if kt >= K_ROW and _row_x_bytes(x_rows) <= X_BUDGET:
        return "row"
    return "lane"


def tiles_per_step(k: int, x_rows: int, col_block: int) -> int:
    """Tiles one grid step of a fused launch carries: :data:`BLOCK_ROW`
    under the row gather, :data:`BLOCK_LANE` under the lane gather with X
    resident, 1 when the lane gather's X exceeds :data:`X_BUDGET` and
    streams one segment a step."""
    if gather_body(k, x_rows) == "row":
        return BLOCK_ROW
    kt, _ = _k_grid(_padded_k(k))
    seg = _round_up(col_block, LANE_TILE)
    return BLOCK_LANE if _lane_x_bytes(x_rows // col_block, kt, seg) <= X_BUDGET else 1


def _fused_spmm(rowgroup, colblock, data, cols, x_blocked, width, **kw):
    """The fused SpMM launches under the body :func:`gather_body` picks."""
    n_cb, col_block, k = x_blocked.shape
    if gather_body(k, n_cb * col_block) == "lane":
        return _fused(rowgroup, colblock, data, cols, x_blocked, **kw)
    return _fused_rows(rowgroup, colblock, width, data, cols, x_blocked, **kw)


def _launches(kernel, scalars, data, cols, x, x_spec, *, x_vmem, tile_space, block, kp,
              n_rowgroups, combine, scratch, interpret, name, **kernel_kw):
    """The fused launches over the tile stream: grid ``(n_k_tiles,
    cdiv(tiles, block))`` each, the data/cols blocks ``(block, group,
    lane)`` in ``tile_space``, the output in HBM (aliased across launches)
    and its window in VMEM.  Each asks for ``x_vmem`` (X's buffers), the
    window and the VMEM tile blocks, plus :data:`VMEM_SLACK`."""
    T, group, lane = data.shape
    _, n_kt = _k_grid(kp)
    window = _window(group, n_rowgroups)
    tile_bytes = _round_up(group, _SUBLANES) * LANE_TILE * 4  # one (8, 128) tile or more
    vmem = x_vmem + window * tile_bytes + VMEM_SLACK
    if tile_space == pltpu.VMEM:
        vmem += 2 * 2 * block * tile_bytes  # data and cols, double-buffered
    identity = 0.0 if combine == "sum" else -jnp.inf
    # row groups no tile visits keep the monoid identity; each k-tile takes
    # a whole lane tile of y (Mosaic slices HBM refs by whole lane tiles)
    y = jnp.full((n_rowgroups, group, n_kt * LANE_TILE), identity, jnp.float32)
    tiles = lambda j, b, *s, base: (base + b, 0, 0)  # noqa: E731
    for lo, n in _chunks(T, block):
        tile_spec = pl.BlockSpec((block, group, lane),
                                 functools.partial(tiles, base=lo // block),
                                 memory_space=tile_space)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(n_kt, pl.cdiv(n, block)),
            in_specs=[tile_spec, tile_spec, x_spec, pl.BlockSpec(memory_space=pltpu.HBM)],
            out_specs=pl.BlockSpec(memory_space=pltpu.HBM),
            scratch_shapes=[pltpu.VMEM((window, group, LANE_TILE), jnp.float32),
                            pltpu.SMEM((1,), jnp.int32), *scratch],
        )
        y = pl.pallas_call(
            functools.partial(kernel, n=n, block=block, n_rowgroups=n_rowgroups,
                              combine=combine, **kernel_kw),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(y.shape, jnp.float32),
            input_output_aliases={len(scalars) + 3: 0},
            compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem),
            interpret=interpret,
            name=name,
        )(*(s[lo : lo + n] for s in scalars), data, cols, x, y)
    return y[..., :kp]


def _fused(rowgroup, colblock, data, cols, x_blocked, *, n_rowgroups, combine,
           interpret, name, block=None, interleave=None):
    """The fused launches under the lane gather.  X, ``[n_cb, k, seg]``
    segments, is resident in VMEM for the launch, one k-tile of it, when it
    fits :data:`X_BUDGET`; otherwise a step carries one tile and streams
    its segment.  ``block`` overrides :data:`BLOCK_LANE` and
    ``interleave`` :data:`INTERLEAVE_LANE` (the sweep)."""
    xs = _segments(x_blocked)
    n_cb, k, seg = xs.shape
    kt, _ = _k_grid(k)
    x_vmem = _lane_x_bytes(n_cb, kt, seg)
    resident = x_vmem <= X_BUDGET
    if resident:
        block = block or BLOCK_LANE
        # the whole k-tile of X, copied once per k-tile, one buffer
        x_spec = pl.BlockSpec((n_cb, kt, seg), lambda j, b, rg, cb: (0, j, 0),
                              pipeline_mode=pl.Buffered(1))
    else:
        block, x_vmem = 1, 2 * x_vmem // n_cb  # one segment, double-buffered
        x_spec = pl.BlockSpec((1, kt, seg), lambda j, b, rg, cb: (cb[b], j, 0))
    return _launches(
        _lane_kernel, (rowgroup, colblock), data, cols, xs, x_spec, x_vmem=x_vmem,
        tile_space=pltpu.VMEM, block=block, kp=k, n_rowgroups=n_rowgroups, combine=combine,
        scratch=[pltpu.VMEM((interleave or INTERLEAVE_LANE, data.shape[1], LANE_TILE),
                            jnp.int32)],
        interpret=interpret,
        name=name, resident=resident,
    )


def _fused_rows(rowgroup, colblock, width, data, cols, x_blocked, *, n_rowgroups,
                combine, interpret, name, block=None):
    """The fused launches under the row gather: X is ``[n_cb * col_block,
    k]`` (matrix columns on sublanes, RHS columns on lanes), one k-tile of
    it resident in VMEM for the launch; tiles sit in SMEM.  ``block``
    overrides :data:`BLOCK_ROW` (the sweep)."""
    n_cb, col_block, k = x_blocked.shape
    kp = _padded_k(k)
    x = jnp.pad(x_blocked.reshape(n_cb * col_block, k), ((0, 0), (0, kp - k)))
    kt, _ = _k_grid(kp)
    # the whole k-tile of X, copied once per k-tile, one buffer
    x_spec = pl.BlockSpec((x.shape[0], kt), lambda j, b, rg, cb, w: (0, j),
                          pipeline_mode=pl.Buffered(1))
    return _launches(
        _row_kernel, (rowgroup, colblock, width), data, cols, x, x_spec,
        x_vmem=_row_x_bytes(x.shape[0]), tile_space=pltpu.SMEM, block=block or BLOCK_ROW,
        kp=kp, n_rowgroups=n_rowgroups, combine=combine, scratch=[], interpret=interpret,
        name=name, col_block=col_block,
    )


@functools.partial(jax.jit, static_argnames=("n_rowgroups", "interpret"))
def hbp_spmv_fused(rowgroup, colblock, data, cols, x_blocked, *,
                   n_rowgroups: int, interpret: bool = False) -> jax.Array:
    """Fused-combine HBP SpMV over ``x_blocked: f32[n_col_blocks, col_block]``.
    Returns y in hashed row order, shape [n_rowgroups, group]."""
    return _fused(rowgroup, colblock, data, cols, x_blocked,
                  n_rowgroups=n_rowgroups, combine="sum", interpret=interpret,
                  name="hbp_spmv_fused")[..., 0]


@functools.partial(jax.jit, static_argnames=("n_rowgroups", "interpret"))
def hbp_spmm_fused(rowgroup, colblock, data, cols, x_blocked, width, *,
                   n_rowgroups: int, interpret: bool = False) -> jax.Array:
    """Fused-combine HBP SpMM: ``Y = A @ X`` over
    ``x_blocked: f32[n_col_blocks, col_block, k]``.

    One launch serves all ``k`` right-hand sides: the tile stream (data +
    cols, the dominant HBM traffic) is read once per k-tile instead of
    ``k`` times.  ``width`` is :func:`tile_widths` of ``data``, staged with
    the plan (``DeviceTiles.width``).  Returns y in hashed row order,
    [n_rowgroups, group, k]."""
    k = x_blocked.shape[-1]
    return _fused_spmm(rowgroup, colblock, data, cols, x_blocked, width,
                       n_rowgroups=n_rowgroups, combine="sum", interpret=interpret,
                       name="hbp_spmm_fused")[..., :k]


@functools.partial(jax.jit, static_argnames=("n_rowgroups", "interpret"))
def hbp_spmm_fused_max(rowgroup, colblock, data, cols, x_blocked, width, *,
                       n_rowgroups: int, interpret: bool = False) -> jax.Array:
    """Fused-combine HBP SpMM under the max monoid (GNN max-aggregation):
    ``y[i, c] = max_j a_ij * x_jc`` over stored entries.  Padded slots are
    masked to ``-inf``, the max identity, so rows with no live entry come
    back ``-inf`` for the caller to zero (``ops._hbp_spmm_device``)."""
    k = x_blocked.shape[-1]
    return _fused_spmm(rowgroup, colblock, data, cols, x_blocked, width,
                       n_rowgroups=n_rowgroups, combine="max", interpret=interpret,
                       name="hbp_spmm_fused_max")[..., :k]
