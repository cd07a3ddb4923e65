"""The fused kernels' lane gather on tiles of every chunk pattern.

The lane body splits each slot's column into a 128-column chunk of its
tile's segment and an offset, gathers the segment chunk by chunk, and
keeps the value of the chunk the slot points into.  These tests hold it
to ``ref.py`` on matrices whose tiles hit one or two chunks, every chunk,
chunks that avoid the one padded slots point into (column 0), stored
zeros in chunks no live slot hits, and a segment of 64 chunks; and hold
the loop that computes several tiles an iteration to one tile an
iteration, bit for bit.
"""
import numpy as np
import pytest

from repro.core import PartitionConfig, build_tiles
from repro.core.formats import CSRMatrix, csr_from_dense
from repro.kernels import hbp_spmm, hbp_spmv

CHUNK = 128


def _chunk_hits(tiles) -> np.ndarray:
    """``bool[T, n_chunks]``: chunk ``c`` of tile ``t``'s segment holds
    the column of a live slot (stored value != 0)."""
    n_chunks = -(-tiles.cfg.col_block // CHUNK)
    out = np.zeros((tiles.n_tiles, n_chunks), bool)
    t, g, s = np.nonzero(tiles.data)
    out[t, tiles.cols[t, g, s] // CHUNK] = True
    return out


def _banded(rng):
    """Rows whose few entries sit near a diagonal that crosses the
    segment; a row block of 16 rows spans 128 columns, so a tile hits 1
    or 2 chunks."""
    m, n = 256, 4096
    dense = np.zeros((m, n), np.float32)
    for i in range(m):
        dense[i, 8 * i : 8 * i + 1 + i % 5] = rng.standard_normal(1 + i % 5)
    return dense, 4096, 16


def _dense(rng):
    """Random rows over the whole 4096-column segment, about 90 entries
    each: a tile of 128 lanes hits every one of its 32 chunks."""
    m, n = 32, 4096
    dense = rng.standard_normal((m, n)) * (rng.random((m, n)) < 0.022)
    return dense.astype(np.float32), 4096, 32


def _avoid_chunk0(rng, hi=1000):
    """Rows of uneven length with entries only past the first chunk, up
    to column ``hi``: the padded slots of their tiles point at column 0,
    a chunk no live slot hits."""
    m, n = 40, 1000
    dense = np.zeros((m, n), np.float32)
    for i in range(m):
        cols = rng.choice(np.arange(CHUNK, hi), size=1 + i % 7, replace=False)
        dense[i, cols] = rng.standard_normal(cols.size)
    return dense, 1024, 40


def _wide(rng):
    """A segment of 8192 columns, 64 chunks, with entries in both halves."""
    m, n = 48, 8192 + 500
    dense = rng.standard_normal((m, n)) * (rng.random((m, n)) < 0.004)
    dense[::3, 6000:6003] = 1.5  # the second half, in every third row
    return dense.astype(np.float32), 8192, 48


def _stored_zeros(rng):
    """Live entries below column 640 and, added by :func:`_csr`, stored
    zeros past 900, in a chunk no live slot hits: their products are 0,
    and under max the ``data != 0`` rule drops them."""
    return _avoid_chunk0(rng, hi=640)


MATRICES = {
    "banded": _banded,
    "dense": _dense,
    "avoid_chunk0": _avoid_chunk0,
    "stored_zeros": _stored_zeros,
    "wide_col_block": _wide,
}


def _csr(kind, dense):
    csr = csr_from_dense(dense)
    if kind != "stored_zeros":
        return csr
    # a stored zero in column 900 + i of every fourth row
    indptr, indices, data = [0], [], []
    for i in range(csr.shape[0]):
        cols = list(csr.indices[csr.indptr[i] : csr.indptr[i + 1]])
        vals = list(csr.data[csr.indptr[i] : csr.indptr[i + 1]])
        if i % 4 == 0 and 900 + i not in cols:
            cols.append(900 + i)
            vals.append(0.0)
        order = np.argsort(cols)
        indices += [cols[j] for j in order]
        data += [vals[j] for j in order]
        indptr.append(len(indices))
    return CSRMatrix(np.array(indptr), np.array(indices), np.array(data, np.float32),
                     csr.shape)


def _check_kind(kind, tiles):
    """The chunk pattern each matrix exists for holds in its tiles."""
    hits = _chunk_hits(tiles)
    padded = (tiles.data == 0) & (tiles.cols == 0)
    if kind == "banded":
        assert hits.sum(axis=1).max() <= 2
    elif kind == "dense" and tiles.cfg.lane == 128:
        assert hits.all(axis=1).any()
    elif kind == "avoid_chunk0":
        assert (~hits[:, 0] & padded.any(axis=(1, 2))).any()
    elif kind == "stored_zeros":
        t, g, s = np.nonzero((tiles.data == 0) & (tiles.cols != 0))
        assert (~hits[t, tiles.cols[t, g, s] // CHUNK]).any()
    elif kind == "wide_col_block":
        assert hits.shape[1] == 64 and hits[:, 32:].any()


# X resident at lanes 8 and 128 at every k; streamed once a k, at lane 128
# for k = 2 and lane 8 otherwise
LANE_CASES = [
    (kind, k, combine, lane, streamed)
    for kind in MATRICES for k in (1, 2, 4) for combine in ("sum", "max")
    for lane, streamed in ((8, False), (128, False), (128 if k == 2 else 8, True))
]


@pytest.mark.parametrize("kind,k,combine,lane,streamed", LANE_CASES)
def test_lane_gather_matches_reference(kind, k, combine, lane, streamed, kernel_strategy):
    """The lane body against ``ref.py`` at k = 1, 2 and 4 (below
    ``K_ROW``, so every launch runs the lane gather), under sum and max,
    lane 8 and 128, with X resident and streamed a segment a step."""
    rng = np.random.default_rng([list(MATRICES).index(kind), k, lane])
    dense, col_block, row_block = MATRICES[kind](rng)
    tiles = build_tiles(_csr(kind, dense), PartitionConfig(
        row_block=row_block, col_block=col_block, group=8, lane=lane))
    _check_kind(kind, tiles)
    strategy = kernel_strategy("fused_streamed" if streamed else "fused")
    X = rng.standard_normal((dense.shape[1], k)).astype(np.float32)
    if k == 1 and combine == "sum":
        Y = np.asarray(hbp_spmv(tiles, X[:, 0], strategy=strategy, interpret=True))[:, None]
    else:
        Y = np.asarray(hbp_spmm(tiles, X, strategy=strategy, combine=combine, interpret=True))
    want = np.asarray(hbp_spmm(tiles, X, strategy="reference", combine=combine))
    if combine == "max":
        np.testing.assert_array_equal(Y, want)
    else:
        np.testing.assert_allclose(Y, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("kind,interleave,block", [
    ("dense", 2, 5), ("dense", 3, 7), ("dense", 4, 128), ("banded", 2, 5), ("banded", 3, 128),
])
@pytest.mark.parametrize("combine", ["sum", "max"])
def test_interleaved_tiles_match_one_an_iteration(kind, interleave, block, combine,
                                                  monkeypatch):
    """The lane gather's loop computes ``interleave`` tiles an iteration
    and folds them in stream order, so it gives the result of one tile an
    iteration bit for bit: over whole iterations and a block's short tail,
    tiles of one row group folded in one iteration (``dense``: 51 tiles, 4
    row groups), and window moves between them (``banded``: a row group a
    tile, a window of 3)."""
    import importlib

    import jax.numpy as jnp

    from repro.kernels import ops

    kernels = importlib.import_module("repro.kernels.hbp_spmv")
    if kind == "banded":
        monkeypatch.setattr(kernels, "WINDOW_BYTES", 3 * 8 * CHUNK * 4)
    rng = np.random.default_rng(interleave)
    dense, col_block, row_block = MATRICES[kind](rng)
    tiles = build_tiles(csr_from_dense(dense), PartitionConfig(
        row_block=row_block, col_block=col_block, group=8, lane=8))
    assert min(block, tiles.n_tiles) % interleave  # blocks end in a short tail
    dt = ops.device_tiles(tiles)
    X = rng.standard_normal((dense.shape[1], 2)).astype(np.float32)
    xb = ops.blocked_matrix(jnp.asarray(X), col_block)
    kw = dict(n_rowgroups=tiles.n_rowgroups, combine=combine, interpret=True,
              name="hbp_spmm_fused", block=block)
    one, many = (
        np.asarray(kernels._fused(dt.rowgroup, dt.colblock, dt.data, dt.cols, xb,
                                  interleave=u, **kw))
        for u in (1, interleave)
    )
    np.testing.assert_array_equal(many, one)
