"""The multi-tenant serving cell at a tiny size on the CPU: six tenants under
a budget that holds three, Zipf popularity, the residency readers."""
import time
from types import SimpleNamespace

import numpy as np
import pytest

from chipbench import harness

from conftest import cell_of

CELL = "multi.serve_zipf"
READERS = ("restage_host_p95_ms.zipf", "miss_pct.zipf")


def _run(bench, root, *, seed=3_000_000_019, seconds=1.0, trace=False):
    run = harness.prepare(bench, CELL, seed, root)
    result = harness.execute(bench, run, seconds=seconds, trace=trace,
                             t_start=time.perf_counter(),
                             compiles=harness.Compiles(), root=root)
    return run, result


def _driver(root):
    return harness.driver("serve_zipf", root)


def test_zipf_counts_add_up_and_fall_with_rank(tiny):
    _, root = tiny
    drv = _driver(root)
    for n in (1, 7, 100, 1601):
        counts = drv.zipf_counts(n, 16, 0.99)
        assert counts.sum() == n
        assert (np.diff(counts) <= 0).all()
    # H_10 / H_16 of the requests go to the ten most popular tenants
    counts = drv.zipf_counts(100_000, 16, 0.99)
    assert 0.85 < counts[:10].sum() / 1e5 < 0.88


def test_every_seed_replays_the_same_trace(tiny):
    bench, root = tiny
    cell_of(bench, CELL)
    a, _ = _run(bench, root, seed=5)
    b, _ = _run(bench, root, seed=2**31 + 11)
    assert np.array_equal(a.state["tenant"], b.state["tenant"])
    assert np.array_equal(a.state["due"], b.state["due"])
    # each tenant is its own pattern; values and vectors come from the run's seed
    pats = {c.indices.tobytes() for c in a.state["csrs"]}
    assert len(pats) == len(a.config["tenants"])
    assert not np.array_equal(a.csr.data, b.csr.data)
    assert not np.array_equal(a.state["xs"], b.state["xs"])


def test_traced_run_reports_residency(tiny):
    bench, root = tiny
    cell_of(bench, CELL)
    run, result = _run(bench, root, trace=True)
    assert result["correct"], result["limits"]
    assert set(result["limits"]) == {"max_rel_err", "unanswered", "readmitted"}
    metrics = {n: m["value"] for n, m in result["metrics"].items()}
    assert {"batch_k_mean.zipf", "admit_s", "compile_s", *READERS} <= set(metrics)
    assert 0 < metrics["miss_pct.zipf"] < 100
    assert metrics["restage_host_p95_ms.zipf"] > 0
    assert {f"admit_phase_s.{p}" for p in ("key", "tiles", "stage", "quality")} <= set(metrics)
    assert {f"{m}.zipf" for m in ("queue_wait_p95_ms", "gen_lag_p95_ms", "dispatch_p95_ms",
                                  "inflight_p95_ms", "ahead_mean")} <= set(metrics)
    # the budget holds three plans: staged bytes stay at or under it
    reg = run.state["registry"]
    assert reg.evictor.resident_bytes <= run.config["hbm_budget_bytes"]
    assert sum(p.device is not None for p in map(reg.peek, reg.names())) == 3


def test_wrong_tenant_answer_reads_not_correct(tiny, monkeypatch):
    """Tenant t's requests answered from tenant t+1's matrix."""
    import repro.serving

    bench, root = tiny
    cell_of(bench, CELL)

    class Misrouted(repro.serving.ServingEngine):
        def submit(self, key, x):
            prefix, t = key.rsplit(".t", 1)
            return super().submit(f"{prefix}.t{(int(t) + 1) % 6}", x)

    monkeypatch.setattr(repro.serving, "ServingEngine", Misrouted)
    _, result = _run(bench, root, seconds=0.5)
    assert result["failed"] == 0
    assert not result["correct"], result["limits"]
    assert result["limits"]["max_rel_err"]["value"] > 1e-2


@pytest.mark.parametrize("name", READERS)
def test_residency_readers_find_nothing_in_an_older_program(tiny, name):
    """A program without ``restage_s`` stamps gives None."""
    _, root = tiny
    old = SimpleNamespace(context=SimpleNamespace(t_dispatch=1.0))
    run = SimpleNamespace(state={"tickets": [old], "done": np.array([True])}, window={})
    assert harness.reader(name, root).read(name, run) is None
