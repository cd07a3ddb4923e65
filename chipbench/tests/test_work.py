"""CSR-equivalent work counts and the peak table."""
import time

import numpy as np
import pytest

from chipbench import harness, work
from chipbench.matrices import Csr


def test_hand_counted_work_of_a_tiny_csr():
    # 3 x 4, 5 stored entries
    csr = Csr(np.array([0, 2, 2, 5]), np.array([0, 3, 0, 1, 2]),
              np.ones(5, np.float32), (3, 4))
    flops, nbytes = work.csr_work(csr.nnz, *csr.shape, 1)
    assert flops == 2 * 5
    # values and column ids 5 * 8, row pointers 4 * 4, x 4 * 4, y 3 * 4
    assert nbytes == 40 + 16 + 16 + 12
    flops, nbytes = work.csr_work(csr.nnz, *csr.shape, 128)
    assert flops == 2 * 5 * 128
    assert nbytes == 40 + 16 + 128 * 16 + 128 * 12


@pytest.mark.parametrize("partition", [
    {"row_block": 256, "col_block": 1024, "group": 8, "lane": 128},
    {"row_block": 512, "col_block": 4096, "group": 8, "lane": 8},
    {"row_block": 256, "col_block": 1024, "group": 8, "lane": 32},
])
def test_work_does_not_depend_on_the_tile_geometry(tiny, partition):
    bench, root = tiny
    run = harness.prepare(bench, "kron16.agg_k128", 3, root)
    run.config["partition"] = partition
    harness.execute(bench, run, seconds=0.3, trace=False, t_start=time.perf_counter(),
                    compiles=harness.Compiles(), root=root)
    assert run.state["plan"].cfg.lane == partition["lane"]
    flops, nbytes = work.csr_work(run.csr.nnz, *run.csr.shape, run.traffic["k"])
    calls = run.window["attempted"]
    assert run.window["work_flops"] == flops * calls
    assert run.window["work_bytes"] == nbytes * calls


def test_roofline_names_its_bound():
    peak = work.peaks("TPU v5 lite")
    share, bound = work.roofline(2e6, 8e6, 1e-3, peak)
    assert bound == "hbm" and share == pytest.approx(100 * 8e6 / 819e9 / 1e-3)
    share, bound = work.roofline(1e12, 1e3, 10.0, peak)
    assert bound == "compute" and share == pytest.approx(100 * 1e12 / 197e12 / 10.0)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="TPU v9"):
        work.peaks("TPU v9")
