"""Every Pallas kernel compiles for a described TPU v5e (no chip needed).

The TPU compiler is installed with jaxlib and compiles for a chip that is
described, not attached, so Mosaic's refusals (block shapes off the
(8, 128) rule, gathers it cannot lower, VMEM over budget) fail here rather
than at the first launch on the chip.  A compile that passes says nothing
about results or time.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.hbp_spmv import (
    LANE_TILE,
    TILE_CHUNK,
    X_BUDGET,
    _lane_x_bytes,
    hbp_spmm_fused,
    hbp_spmm_fused_max,
    hbp_spmv_fused,
    tiles_per_step,
)

T = 4096  # tiles per launch
T_KRON16 = 178_466  # m4_kron16's tile count at lane 8: several SMEM-sized launches
# the benchmark's kron16 at its pinned geometry (col_block 4096, lane 128)
KRON16 = dict(tiles=103_372, cols=65_536, rowgroups=8192)
GROUP = 8
N_COL_BLOCKS = 16
N_ROWGROUPS = 2048


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    """Compiles for a described chip are written to the persistent cache
    but cannot be read back without one; keep them out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _streamed_col_blocks(k, col_block):
    """Fewest column blocks whose X the lane gather cannot hold resident:
    its launches stream one segment of X a grid step, one tile a step."""
    per_block = _lane_x_bytes(1, min(k, LANE_TILE), -(-col_block // LANE_TILE) * LANE_TILE)
    n_cb = X_BUDGET // per_block + 1
    assert tiles_per_step(k, n_cb * col_block, col_block) == 1
    return n_cb


def _shapes(sharding, lane, k, col_block=4096, tiles=T, n_col_blocks=N_COL_BLOCKS):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    x = (n_col_blocks, col_block) if k == 1 else (n_col_blocks, col_block, k)
    return dict(
        scalars=s((tiles,), jnp.int32),
        data=s((tiles, GROUP, lane), jnp.float32),
        cols=s((tiles, GROUP, lane), jnp.int32),
        x=s(x, jnp.float32),
    )


def _fused(fn, spmm=False, n_rowgroups=N_ROWGROUPS):
    def call(a):
        width = (a["scalars"],) if spmm else ()  # the fused SpMM's per-tile widths
        return fn.lower(
            a["scalars"], a["scalars"], a["data"], a["cols"], a["x"], *width,
            n_rowgroups=n_rowgroups,
        )
    return call


LAUNCHES = {
    "spmv_fused": _fused(hbp_spmv_fused),
    "spmm_fused": _fused(hbp_spmm_fused, spmm=True),
    "spmm_fused_max": _fused(hbp_spmm_fused_max, spmm=True),
}

# (kernel, lane, k, col_block, streamed): lanes 8 and 128 (the heuristic's
# narrowest and widest) on every kernel, the lanes between once each, one
# RHS column, one 8-row group, and the 2D k-grid (k=256); ``streamed``
# widens X past the lane gather's VMEM budget, so each grid step streams
# one segment of X
CASES = [
    ("spmv_fused", 8, 1, 4096, False),
    ("spmv_fused", 128, 1, 4096, False),
    ("spmv_fused", 16, 1, 1024, False),
    ("spmm_fused", 64, 128, 1024, True),
    ("spmv_fused", 8, 1, 4096, True),
    ("spmv_fused", 128, 1, 4096, True),
    ("spmm_fused", 8, 8, 4096, False),
    ("spmm_fused", 128, 256, 4096, False),
    ("spmm_fused", 32, 16, 1024, False),
    ("spmm_fused", 8, 256, 4096, True),
    ("spmm_fused", 128, 8, 4096, True),
    ("spmm_fused_max", 8, 8, 4096, False),
    ("spmm_fused_max", 128, 256, 4096, False),
    ("spmm_fused_max", 8, 256, 4096, True),
    ("spmm_fused_max", 128, 8, 4096, True),
]


def _case_id(case):
    kernel, lane, k, col_block, streamed = case
    return f"{kernel}{'_streamed' if streamed else ''}-{lane}-{k}-{col_block}"


@pytest.mark.parametrize("kernel,lane,k,col_block,streamed", CASES, ids=map(_case_id, CASES))
def test_kernel_compiles_for_v5e(one_chip, kernel, lane, k, col_block, streamed):
    n_cb = _streamed_col_blocks(k, col_block) if streamed else N_COL_BLOCKS
    a = _shapes(one_chip, lane, k, col_block, n_col_blocks=n_cb)
    compiled = LAUNCHES[kernel](a).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kernel,k,streamed", [("spmv_fused", 1, False),
                                               ("spmm_fused_max", 8, True)],
                         ids=["spmv_fused-1", "spmm_fused_max_streamed-8"])
def test_published_size_stream_fits_smem(one_chip, kernel, k, streamed):
    """A published-size tile stream: the per-tile scalars of one launch
    must fit the chip's 1 MiB SMEM, so the stream runs as several launches."""
    n_cb = _streamed_col_blocks(k, 4096) if streamed else N_COL_BLOCKS
    a = _shapes(one_chip, 8, k, tiles=T_KRON16, n_col_blocks=n_cb)
    compiled = LAUNCHES[kernel](a).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 2


def _device_tiles(sharding, lane, n_rows):
    a = _shapes(sharding, lane, 1)
    return ops.DeviceTiles(
        rowgroup=a["scalars"], colblock=a["scalars"],
        data=a["data"], cols=a["cols"],
        perm=jax.ShapeDtypeStruct((n_rows,), jnp.int32, sharding=sharding),
        width=a["scalars"],
    )


@pytest.mark.parametrize("k", [1, 16])
def test_serving_entry_runs_the_kernel_on_v5e(one_chip, k):
    """The jitted entry a plan launches holds the Pallas kernel, not a
    jnp fallback."""
    n_rows = N_ROWGROUPS * GROUP
    dt = _device_tiles(one_chip, 8, n_rows)
    meta = dict(n_rowgroups=N_ROWGROUPS, n_rows=n_rows, strategy="fused", interpret=False)
    x = _shapes(one_chip, 8, k)["x"]
    entry = ops._hbp_spmv_device if k == 1 else ops._hbp_spmm_device
    compiled = entry.lower(dt, x, **meta).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kernel", ["spmm_fused", "spmm_fused_max"])
@pytest.mark.parametrize("k,n_cols,body", [
    (8, KRON16["cols"], "row"),
    (16, KRON16["cols"], "row"),
    (128, KRON16["cols"], "row"),
    (256, KRON16["cols"], "row"),
    (128, 327_680, "lane"),  # X of 160 MiB: over the budget, lane gather
])
def test_fused_spmm_gather_compiles_at_kron16_size(one_chip, kernel, k, n_cols, body):
    """The fused SpMM at kron16's geometry: 103,372 tiles in four launches,
    the body the choice rule picks (a resident X under the row gather)."""
    from repro.kernels.hbp_spmv import gather_body

    assert gather_body(k, n_cols) == body
    a = _shapes(one_chip, 128, k, tiles=KRON16["tiles"])
    a["x"] = jax.ShapeDtypeStruct((n_cols // 4096, 4096, k), jnp.float32, sharding=one_chip)
    fn = hbp_spmm_fused if kernel == "spmm_fused" else hbp_spmm_fused_max
    compiled = fn.lower(
        a["scalars"], a["scalars"], a["data"], a["cols"], a["x"], a["scalars"],
        n_rowgroups=KRON16["rowgroups"],
    ).compile()
    assert compiled.as_text().count("tpu_custom_call") == 4


def _kernels():
    import importlib

    return importlib.import_module("repro.kernels.hbp_spmv")


@pytest.mark.parametrize("tiles", [KRON16["tiles"], 103_424])  # as built, and bucketed
@pytest.mark.parametrize("body", ["lane", "row"])
@pytest.mark.parametrize("k", [1, 8, 128, 256])
def test_blocked_launches_compile_at_kron16_size(one_chip, tiles, body, k):
    """Either body's blocked launches at kron16's size, at any k: a whole
    k-tile of X resident, the output window and ``B`` tiles a step fit the
    v5e's VMEM, and the scalar prefetch and, under the row gather, the
    tile blocks its SMEM (the compiler refuses an allocation over either)."""
    from functools import partial

    K = _kernels()
    a = _shapes(one_chip, 128, k, tiles=tiles)
    x = jax.ShapeDtypeStruct((KRON16["cols"] // 4096, 4096, k), jnp.float32, sharding=one_chip)
    kw = dict(n_rowgroups=KRON16["rowgroups"], combine="sum", interpret=False,
              name="hbp_spmm_fused")
    s = a["scalars"]
    if body == "lane":
        assert K._lane_x_bytes(16, min(k, K.LANE_TILE), 4096) <= K.X_BUDGET  # resident
        lowered = jax.jit(partial(K._fused, **kw)).lower(s, s, a["data"], a["cols"], x)
    else:
        lowered = jax.jit(partial(K._fused_rows, **kw)).lower(s, s, s, a["data"], a["cols"], x)
    assert lowered.compile().as_text().count("tpu_custom_call") == 4


# an output of 40,228 row groups (321,821 rows, ASIC_320k's) over 327,680
# columns: 157 MiB of VMEM whole, so its launches walk 40 windows
WIDE = dict(rowgroups=40_228, cols=327_680)


@pytest.mark.parametrize("k,body,block", [
    (1, "lane", "BLOCK_LANE"),
    (8, "lane", "BLOCK_LANE"),  # X over the row gather's budget
    (128, "lane", 1),  # X over every budget: one segment, one tile a step
])
def test_fused_output_in_windows_compiles(one_chip, k, body, block):
    """A matrix whose output cannot be resident whole runs its launches
    over several output windows."""
    K = _kernels()
    block = getattr(K, block) if isinstance(block, str) else block
    assert K._window(GROUP, WIDE["rowgroups"]) * 8 < WIDE["rowgroups"]
    assert K.gather_body(k, WIDE["cols"]) == body
    assert K.tiles_per_step(k, WIDE["cols"], 4096) == block
    a = _shapes(one_chip, 128, k, tiles=KRON16["tiles"])
    x = jax.ShapeDtypeStruct((WIDE["cols"] // 4096, 4096, k), jnp.float32, sharding=one_chip)
    compiled = hbp_spmm_fused.lower(
        a["scalars"], a["scalars"], a["data"], a["cols"], x, a["scalars"],
        n_rowgroups=WIDE["rowgroups"],
    ).compile()
    assert compiled.as_text().count("tpu_custom_call") == 4


@pytest.mark.parametrize("k,streamed,col_block", [
    (1, False, 4096), (1, True, 4096), (4, False, 4096), (4, True, 4096),
    (1, False, 8192),  # 64 chunks a segment
])
def test_lane_body_compiles_at_kron16_size(one_chip, k, streamed, col_block):
    """The lane body at kron16's size through the entry point a call of
    that k launches (SpMV at k=1, SpMM below ``K_ROW``), X resident or
    streamed a segment a step: launches of :data:`TILE_CHUNK` tiles, whose
    scalar prefetch must fit the v5e's SMEM beside the body's blocks."""
    assert -(-KRON16["tiles"] // TILE_CHUNK) == 4
    n_cb = _streamed_col_blocks(k, col_block) if streamed else KRON16["cols"] // col_block
    assert (tiles_per_step(k, n_cb * col_block, col_block) == 1) == streamed
    a = _shapes(one_chip, 128, k, col_block, tiles=KRON16["tiles"], n_col_blocks=n_cb)
    fn = _fused(hbp_spmv_fused if k == 1 else hbp_spmm_fused, spmm=k > 1,
                n_rowgroups=KRON16["rowgroups"])
    assert fn(a).compile().as_text().count("tpu_custom_call") == 4
