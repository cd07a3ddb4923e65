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
    hbp_spmm_fused,
    hbp_spmm_fused_max,
    hbp_spmm_partials,
    hbp_spmm_partials_max,
    hbp_spmv_fused,
    hbp_spmv_partials,
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


def _shapes(sharding, lane, k, col_block=4096, tiles=T):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    x = (N_COL_BLOCKS, col_block) if k == 1 else (N_COL_BLOCKS, col_block, k)
    return dict(
        scalars=s((tiles,), jnp.int32),
        data=s((tiles, GROUP, lane), jnp.float32),
        cols=s((tiles, GROUP, lane), jnp.int32),
        x=s(x, jnp.float32),
    )


def _fused(fn, spmm=False):
    def call(a):
        width = (a["scalars"],) if spmm else ()  # the fused SpMM's per-tile widths
        return fn.lower(
            a["scalars"], a["scalars"], a["data"], a["cols"], a["x"], *width,
            n_rowgroups=N_ROWGROUPS,
        )
    return call


def _partials(fn):
    def call(a):
        return fn.lower(a["scalars"], a["data"], a["cols"], a["x"])
    return call


LAUNCHES = {
    "spmv_fused": _fused(hbp_spmv_fused),
    "spmv_partials": _partials(hbp_spmv_partials),
    "spmm_fused": _fused(hbp_spmm_fused, spmm=True),
    "spmm_partials": _partials(hbp_spmm_partials),
    "spmm_fused_max": _fused(hbp_spmm_fused_max, spmm=True),
    "spmm_partials_max": _partials(hbp_spmm_partials_max),
}

# (kernel, lane, k, col_block): lanes 8 and 128 (the heuristic's narrowest
# and widest) on every kernel, the lanes between once each, one RHS column,
# one 8-row group, and the 2D k-grid (k=256)
CASES = [
    ("spmv_fused", 8, 1, 4096),
    ("spmv_fused", 128, 1, 4096),
    ("spmv_fused", 16, 1, 1024),
    ("spmm_partials", 64, 128, 1024),
    ("spmv_partials", 8, 1, 4096),
    ("spmv_partials", 128, 1, 4096),
    ("spmm_fused", 8, 8, 4096),
    ("spmm_fused", 128, 256, 4096),
    ("spmm_fused", 32, 16, 1024),
    ("spmm_partials", 8, 256, 4096),
    ("spmm_partials", 128, 8, 4096),
    ("spmm_fused_max", 8, 8, 4096),
    ("spmm_fused_max", 128, 256, 4096),
    ("spmm_partials_max", 8, 256, 4096),
    ("spmm_partials_max", 128, 8, 4096),
]


@pytest.mark.parametrize("kernel,lane,k,col_block", CASES)
def test_kernel_compiles_for_v5e(one_chip, kernel, lane, k, col_block):
    compiled = LAUNCHES[kernel](_shapes(one_chip, lane, k, col_block)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kernel,k", [("spmv_fused", 1), ("spmm_partials_max", 8)])
def test_published_size_stream_fits_smem(one_chip, kernel, k):
    """A published-size tile stream: the per-tile scalars of one launch
    must fit the chip's 1 MiB SMEM, so the stream runs as several launches."""
    compiled = LAUNCHES[kernel](_shapes(one_chip, 8, k, tiles=T_KRON16)).compile()
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

