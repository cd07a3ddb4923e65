"""The harness end to end at a tiny size on the CPU, Pallas interpreted."""
import json
import os
import subprocess
import sys
import time

import pytest

from chipbench import harness

from conftest import CELLS, CHIPBENCH, cell_of


def _run(bench, root, cell, *, seed=3_000_000_019, seconds=1.0, trace=False):
    run = harness.prepare(bench, cell, seed, root)
    result = harness.execute(bench, run, seconds=seconds, trace=trace,
                             t_start=time.perf_counter(),
                             compiles=harness.Compiles(), root=root)
    return run, result


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_correct_on_pallas(tiny, pallas, cell):
    bench, root = tiny
    cell_of(bench, cell)
    run, result = _run(bench, root, cell)
    assert result["correct"], result["limits"]
    assert result["failed"] == 0 and result["attempted"] > 0
    e2e = {m["name"] for m in harness.metrics_of(bench, cell, "end_to_end")}
    assert set(result["metrics"]) == e2e and "setup_s" in e2e
    assert all(v["value"] > 0 for v in result["metrics"].values())
    assert list(result)[-1] == "limits"
    if "plan" in run.state:
        assert run.state["plan"].strategy == "fused"


def test_same_seed_same_inputs(tiny):
    bench, root = tiny
    a, _ = _run(bench, root, "kron16.serve_poisson", seed=5)
    b, _ = _run(bench, root, "kron16.serve_poisson", seed=5)
    c, _ = _run(bench, root, "kron16.serve_poisson", seed=6)
    assert (a.state["xs"] == b.state["xs"]).all()
    assert (a.csr.data == b.csr.data).all()
    assert (a.state["due"] == b.state["due"]).all()
    # another seed: other values and vectors on the same pattern and load
    assert (a.csr.indices == c.csr.indices).all() and (a.csr.data != c.csr.data).any()
    assert a.state["due"].size == c.state["due"].size


def test_traced_run_reports_per_layer_metrics(tiny):
    bench, root = tiny
    run, result = _run(bench, root, "kron16.serve_poisson", trace=True)
    assert result["correct"]
    names = set(result["metrics"])
    # no device plane on the CPU: the trace readers find nothing and stay out
    assert {"queue_wait_p95_ms.serve", "batch_k_mean.serve", "gen_lag_p95_ms.serve",
            "admit_s", "compile_s"} <= names
    assert not any(n.startswith(("hbp_fused_roofline", "idle_pct")) for n in names)
    assert run.trace is not None and run.trace["window_s"] > 0


def test_new_config_traffic_and_metric_are_found_by_name(tiny):
    bench, root = tiny
    cfg = json.loads((root / "configs" / "kron16.json").read_text())
    cfg["generator"].update(scale=10, edges=4000)
    (root / "configs" / "tiny_other.json").write_text(json.dumps(cfg))
    traffic = json.loads((root / "traffic" / "spmv_chain.json").read_text())
    traffic["check"]["sample"] = 4
    (root / "traffic" / "short_chain.json").write_text(json.dumps(traffic))
    (root / "metrics" / "steps_seen.py").write_text(
        "def read(name, run):\n    return run.window['attempted']\n")
    bench["workloads"].append({"name": "tiny_other.short_chain", "config": "tiny_other",
                               "traffic": "short_chain", "chips": 1, "why": "a test"})
    bench["per_layer"].append({"name": "steps_seen", "unit": "steps", "better": "higher",
                               "source": "host_clock", "layer": "test", "moves": "sparse_gflops",
                               "workloads": ["tiny_other.short_chain"]})
    gflops = next(m for m in bench["end_to_end"] if m["name"] == "sparse_gflops")
    gflops["workloads"].append("tiny_other.short_chain")
    run, result = _run(bench, root, "tiny_other.short_chain", trace=True)
    assert run.csr.shape == (1024, 1024)
    assert result["correct"]
    assert result["metrics"]["steps_seen"]["value"] == result["attempted"]


def test_command_refuses_a_machine_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(CHIPBENCH / "run.py"), "--workload", "kron16.pagerank",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr


def test_sweep_reports_each_rate(tiny):
    from chipbench import sweep

    bench, root = tiny
    lines = []
    sweep.sweep(bench, "kron16.serve_poisson", [10.0, 40.0], 0.5, 9, root=root,
                out=lines.append)
    rows = [json.loads(line) for line in lines]
    assert [r["rate_per_s"] for r in rows] == [10.0, 40.0]
    assert [r["requests"] for r in rows] == [5, 20]
    assert all(r["failed"] == 0 and r["completed_per_s"] > 0 for r in rows)


def test_serving_latency_is_timed_by_the_benchmark(tiny, monkeypatch):
    """Latency comes from the benchmark's own clock: an engine whose clock
    runs 10,000 s ahead moves neither the latency nor the queue wait."""
    import repro.serving

    class Skewed(repro.serving.ServingEngine):
        def __init__(self, *args, **kw):
            super().__init__(*args, clock=lambda: time.perf_counter() + 1e4, **kw)

    monkeypatch.setattr(repro.serving, "ServingEngine", Skewed)
    bench, root = tiny
    run, result = _run(bench, root, "kron16.serve_poisson")
    assert result["correct"], result["limits"]
    assert 0 < result["metrics"]["latency_p95_ms"]["value"] < 5e3
    assert 0 < run.window["queue_wait_p95_ms"] < 5e3
