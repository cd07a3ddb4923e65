"""The program's spans on the profiler's timeline, and the served request
split at its launch.

Every ``obs.span`` is a ``jax.profiler.TraceAnnotation`` of its name whether
or not obs is enabled, so a profile captured around the engine holds its
``serve.*`` spans; the in-process tracer records only while enabled.  A
request's latency splits at its batch's flush and launch into queue wait,
dispatch and time in flight, and ``ahead`` counts the batches in flight at
the launch.  An admission records the host seconds of its phases.
"""
import time
from pathlib import Path

import numpy as np
import pytest

import jax
from jax.profiler import ProfileData

from repro import obs
from repro.core import PartitionConfig
from repro.core.matrices import circuit
from repro.obs.requesttrace import RequestLog, waterfall
from repro.serving import MatrixRegistry, ServingEngine

PHASES = {"key", "tiles", "stage", "quality"}


@pytest.fixture()
def obs_on():
    obs.reset()
    obs.enable()
    yield
    obs.disable()
    obs.reset()


def _host_events(log_dir) -> list:
    """Names of every host-plane event in the profile written under ``log_dir``."""
    (path,) = Path(log_dir).rglob("*.xplane.pb")
    pd = ProfileData.from_file(str(path))
    return [e.name for plane in pd.planes if not plane.name.startswith("/device:")
            for line in plane.lines for e in line.events]


def _profiled(log_dir, fn):
    jax.profiler.start_trace(str(log_dir))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    return _host_events(log_dir)


def _engine(tmp_path, clock, **kw):
    reg = MatrixRegistry(cache_dir=tmp_path / "cache", search=False)
    A = circuit(150, seed=1)
    reg.admit(A, "A")
    log = RequestLog()
    return A, ServingEngine(reg, clock=clock, request_log=log, **kw), log


def _ticking():
    """A virtual clock that advances 1 ms on every read: every stamp differs."""
    now = [0.0]

    def clock():
        now[0] += 1e-3
        return now[0]

    return clock


def _xs(A, n):
    rng = np.random.default_rng(0)
    return [rng.standard_normal(A.shape[1]).astype(np.float32) for _ in range(n)]


def test_profile_holds_the_engines_spans(obs_on, tmp_path):
    A, eng, log = _engine(tmp_path, _ticking(), max_wait_s=1e9, max_batch=4)
    xs = _xs(A, 3)

    def serve():
        for x in xs:
            eng.submit("A", x)
        eng.flush()

    names = _profiled(tmp_path / "profile", serve)
    assert names.count("serve.submit") == 3
    assert names.count("serve.flush") == 1 and names.count("serve.harvest") == 1
    # the same spans reach the enabled tracer's buffer, args and all
    flush = [e for e in obs.tracer().snapshot() if e["name"] == "serve.flush"]
    assert len(flush) == 1 and flush[0]["args"]["k"] == 3
    assert {"serve.submit", "serve.harvest"} <= {e["name"] for e in obs.tracer().snapshot()}


def test_disabled_span_is_an_annotation_only(tmp_path):
    obs.reset()
    assert not obs.enabled()
    value = np.zeros(2)

    def spans():
        with obs.span("probe.disabled", matrix="A") as sp:
            assert sp.annotate(x=1) is sp
            assert sp.sync(value) is value

    names = _profiled(tmp_path / "profile", spans)
    assert "probe.disabled" in names  # the name alone: args stay out of it
    assert obs.tracer().snapshot() == []
    assert obs.collect()["n_events"] == 0


@pytest.mark.parametrize("overlap", [False, True])
def test_stamps_are_ordered_on_a_virtual_clock(tmp_path, overlap):
    A, eng, log = _engine(tmp_path, _ticking(), max_wait_s=1e9, max_batch=4,
                          overlap=overlap)
    tickets = [eng.submit("A", x) for x in _xs(A, 6)]
    eng.flush()
    assert log.count == 6
    for t in tickets:
        c = t.context
        assert c.t_submit <= c.t_flush_start <= c.t_dispatch <= c.t_launched <= c.t_complete
        assert c.t_flush_start < c.t_launched < c.t_complete  # the clock ticked


def test_queue_dispatch_and_inflight_add_up_to_latency(tmp_path):
    A, eng, log = _engine(tmp_path, _ticking(), max_wait_s=1e9, max_batch=2,
                          overlap=True)
    tickets = [eng.submit("A", x) for x in _xs(A, 5)]
    eng.poll()
    eng.flush()
    for t in tickets:
        c = t.context
        assert c.dispatch_s > 0 and c.inflight_s > 0
        assert c.queue_wait_s + c.dispatch_s + c.inflight_s == pytest.approx(c.latency_s)
        d = c.to_dict()
        assert d["dispatch_s"] == c.dispatch_s and d["inflight_s"] == c.inflight_s
        assert d["t_launched"] == c.t_launched and d["ahead"] == c.ahead
    text = waterfall(log.snapshot())
    assert "dispatch_ms" in text and "inflight_ms" in text and "ahead" in text


@pytest.mark.parametrize("overlap", [False, True])
def test_ahead_counts_the_batches_in_flight_at_launch(tmp_path, overlap):
    A, eng, log = _engine(tmp_path, lambda: 0.0, max_wait_s=1e9, max_batch=2,
                          overlap=overlap)
    tickets = [eng.submit("A", x) for x in _xs(A, 6)]
    # one poll launches all three full batches before it harvests any
    eng.poll()
    eng.flush()
    ahead = [t.context.ahead for t in tickets]
    assert ahead == ([0, 0, 1, 1, 2, 2] if overlap else [0] * 6)
    assert eng.inflight() == 0


@pytest.mark.parametrize("pinned", [False, True])
def test_admission_records_its_phases(tmp_path, pinned):
    reg = MatrixRegistry(cache_dir=tmp_path / "cache", search=False)
    cfg = PartitionConfig(row_block=64, col_block=128, group=8, lane=16) if pinned else None
    t0 = time.perf_counter()
    plan = reg.admit(circuit(300, seed=2), "B", cfg=cfg)
    wall = time.perf_counter() - t0
    assert set(plan.phases_s) == PHASES
    assert all(v >= 0 for v in plan.phases_s.values())
    assert sum(plan.phases_s.values()) <= wall
    # the build and staging phases lie inside the preprocess clock
    assert plan.phases_s["tiles"] + plan.phases_s["stage"] <= plan.preprocess_s


def test_admission_phases_are_spans_under_serve_admit(obs_on, tmp_path):
    reg = MatrixRegistry(cache_dir=tmp_path / "cache", search=False)
    reg.admit(circuit(200, seed=3), "C")
    events = {e["name"]: e for e in obs.tracer().snapshot() if e.get("ph") == "X"}
    admit = events["serve.admit"]
    for name in ("admit.key", "admit.build_tiles", "serve.stage_device", "admit.plan_quality"):
        ev = events[name]
        assert ev["depth"] > admit["depth"]
        assert admit["ts"] <= ev["ts"] and ev["ts"] + ev["dur"] <= admit["ts"] + admit["dur"]
