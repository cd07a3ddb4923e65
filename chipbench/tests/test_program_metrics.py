"""The per-layer metrics read from the program's own stamps and phases, at a
tiny size on the CPU: the serving engine's dispatch / in-flight split and
the batches ahead of each launch, and the admission's phases."""
import time
from types import SimpleNamespace

import numpy as np
import pytest

from chipbench import harness

PHASES = ("key", "tiles", "stage", "quality")
SERVE = ("dispatch_p95_ms.serve", "inflight_p95_ms.serve", "ahead_mean.serve")


def _run(bench, root, cell, *, seed=4_000_000_007, seconds=1.0):
    run = harness.prepare(bench, cell, seed, root)
    result = harness.execute(bench, run, seconds=seconds, trace=True,
                             t_start=time.perf_counter(),
                             compiles=harness.Compiles(), root=root)
    return run, result


def _read(root, name, run):
    return harness.reader(name, root).read(name, run)


def test_serving_cell_reports_the_split_and_the_phases(tiny):
    bench, root = tiny
    run, result = _run(bench, root, "kron16.serve_poisson")
    assert result["correct"], result["limits"]
    metrics = {n: m["value"] for n, m in result["metrics"].items()}
    for name in SERVE + tuple(f"admit_phase_s.{p}" for p in PHASES):
        assert np.isfinite(metrics[name]) and metrics[name] >= 0, name
    assert metrics["inflight_p95_ms.serve"] > 0
    # queue wait, dispatch and time in flight make up each answered
    # request's latency on the engine's clock
    for t, done in zip(run.state["tickets"], run.state["done"]):
        if done:
            c = t.context
            total = c.queue_wait_s + c.dispatch_s + c.inflight_s
            assert abs(total - c.latency_s) < 1e-3
    assert metrics["admit_phase_s.tiles"] == run.state["plan"].phases_s["tiles"]


def test_aggregation_cell_reports_the_phases_only(tiny):
    bench, root = tiny
    run, result = _run(bench, root, "kron16.agg_k128")
    assert result["correct"], result["limits"]
    names = set(result["metrics"])
    assert {f"admit_phase_s.{p}" for p in PHASES} <= names
    assert not names & set(SERVE)


def test_pagerank_cell_reports_none_of_them(tiny):
    bench, root = tiny
    _, result = _run(bench, root, "kron16.pagerank")
    assert not any(n.startswith("admit_phase_s") or n in SERVE for n in result["metrics"])


@pytest.mark.parametrize("name", SERVE + tuple(f"admit_phase_s.{p}" for p in PHASES))
def test_readers_find_nothing_in_a_program_without_the_stamps(tiny, name):
    """A program without the stamps or phases (an older commit) gives None,
    never an error: contexts without the fields, a plan without phases."""
    _, root = tiny
    old_ctx = SimpleNamespace(t_dispatch=1.0, t_complete=2.0)
    ticket = SimpleNamespace(context=old_ctx)
    run = SimpleNamespace(state={"tickets": [ticket], "done": np.array([True]),
                                 "plan": SimpleNamespace(preprocess_s=1.0)})
    assert _read(root, name, run) is None
    assert _read(root, name, SimpleNamespace(state={})) is None
