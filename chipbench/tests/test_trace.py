"""The trace reduction, on a hand-made trace and on one recorded on a v5e."""
import json
from pathlib import Path

import pytest

from chipbench import trace

DATA = Path(__file__).resolve().parent / "data"

HAND = {"planes": [
    {"name": "/host:CPU", "lines": [{"name": "python", "events": [
        ["chipbench.window", 1000, 10000],
        ["chipbench.poll", 1000, 1000],
        ["chipbench.sleep", 5000, 4000],
        ["chipbench.make_matrix", 0, 900],
    ]}]},
    {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [
            ["hbp_spmm_fused", 1500, 1000, "kernel"],
            ["fusion", 2000, 1000, ""],  # overlaps the kernel: counted once as busy
            ["hbp_spmm_fused", 4000, 500, "kernel"],
            ["copy", 10500, 1500, ""],  # runs past the window: clipped
            ["copy", 20000, 100, ""],  # after the window: ignored
        ]},
        {"name": "XLA Modules", "events": [["jit_step", 0, 30000, ""]]},
    ]},
]}


def test_busy_is_the_union_of_device_ops_in_the_window():
    r = trace.reduce(HAND)
    assert r["window_s"] == pytest.approx(10000e-9)
    assert r["busy_s"] == pytest.approx((1500 + 500 + 500) * 1e-9)
    assert r["devices"] == 1


def test_kernel_time_counts_only_the_pallas_kernels():
    r = trace.reduce(HAND)
    assert r["kernel_s"] == pytest.approx(1500e-9)
    assert dict((k, v) for k, v in r["device_ops"]) == pytest.approx(
        {"hbp_spmm_fused": 1500e-9, "fusion": 1000e-9, "copy": 500e-9})


def test_idle_gaps_go_to_the_host_span_that_covers_them():
    gaps = dict(trace.reduce(HAND)["idle_gaps"])
    assert gaps == pytest.approx({"chipbench.poll": 500e-9, "chipbench.window": 1000e-9,
                                  "chipbench.sleep": 6000e-9})
    r = trace.reduce(HAND)
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])


def test_no_window_span_is_an_error():
    bad = {"planes": [p for p in HAND["planes"] if p["name"].startswith("/device")]}
    with pytest.raises(ValueError, match="chipbench.window"):
        trace.reduce(bad)


def test_recorded_v5e_trace():
    """0.35 s of an SpMV chain on an ASIC_320k-sized circuit matrix, traced on
    a TPU v5 lite: six SpMVs of seven launches each (210,564 tiles, at most
    32,768 per launch)."""
    recorded = json.loads((DATA / "chain_small_trace.json").read_text())
    r = trace.reduce(recorded)
    assert r["window_s"] == pytest.approx(0.347930124)
    assert r["devices"] == 1
    kernels = [e for p in recorded["planes"] if p["name"] == "/device:TPU:0"
               for line in p["lines"] for e in line["events"] if e[3] == "kernel"]
    assert len(kernels) == 42 and {e[0] for e in kernels} == {"hbp_spmv_fused"}
    assert r["kernel_s"] == pytest.approx(0.33364467)
    assert r["device_ops"][0] == ["hbp_spmv_fused", pytest.approx(0.33364467)]
    assert r["kernel_s"] <= r["busy_s"] <= r["window_s"]
    gaps = dict(r["idle_gaps"])
    assert set(gaps) == {"chipbench.wait", "chipbench.matvec"}
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])
