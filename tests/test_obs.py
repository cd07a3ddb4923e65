"""The observability layer: metrics core, span tracer, gating, exports.

Covers the contracts the instrumented subsystems rely on: histogram
percentiles agree with numpy (exact inside the sample window, bucket-
interpolated beyond), span nesting/ordering survives the Chrome-trace
export, counters hold up under concurrent bumps, and — the overhead
contract — disabled mode retains exactly nothing.

The always-on layers get their own sections: the flight recorder's ring
wraparound, trigger dumps and concurrency; the SLO engine's burn-rate
math and multi-window classification; and the request log's renderings
in ``analysis/report.py``.
"""
import json
import threading

import numpy as np
import pytest

from repro import obs
from repro.obs.flight import FlightRecorder
from repro.obs.metrics import Histogram, MetricRegistry
from repro.obs.report import amortization_ledger, render
from repro.obs.slo import SLO, SLOEngine, worst_status
from repro.obs.trace import Tracer


@pytest.fixture()
def obs_on():
    """Enable obs for one test against clean global state."""
    obs.reset()
    obs.enable()
    yield
    obs.disable()
    obs.reset()


# --- histogram percentiles -------------------------------------------------


def test_histogram_percentiles_exact_within_window(rng):
    draws = rng.lognormal(mean=-6.0, sigma=2.0, size=1000)
    h = Histogram("t", {}, window=4096)
    for v in draws:
        h.observe(v)
    s = np.sort(draws)
    for q in (0.0, 0.5, 0.95, 0.99, 1.0):
        # the engine's historical convention: sorted[int(q * (n - 1))]
        assert h.percentile(q) == pytest.approx(s[int(q * (s.size - 1))])
    assert h.count == 1000
    assert h.mean == pytest.approx(draws.mean())


def test_histogram_percentiles_interpolated_beyond_window(rng):
    draws = rng.lognormal(mean=-6.0, sigma=2.0, size=5000)
    h = Histogram("t", {}, window=256)  # window evicts: bucket fallback
    for v in draws:
        h.observe(v)
    for q in (0.5, 0.95, 0.99):
        exact = float(np.sort(draws)[int(q * (draws.size - 1))])
        est = h.percentile(q)
        # default buckets are ~12% wide: interpolation stays within one
        assert est == pytest.approx(exact, rel=0.15)
    assert h.percentile(0.0) >= h.vmin
    assert h.percentile(1.0) <= h.vmax * (1 + 1e-12)


def test_histogram_empty_and_validation():
    h = Histogram("t", {})
    assert h.percentile(0.5) is None
    assert h.snapshot()["count"] == 0 and h.snapshot()["p99"] is None
    with pytest.raises(ValueError):
        h.percentile(1.5)
    with pytest.raises(ValueError):
        Histogram("bad", {}, buckets=[3.0, 1.0])


# --- counters / gauges / registry -----------------------------------------


def test_counter_monotone_and_thread_safe():
    reg = MetricRegistry()
    c = reg.counter("hits")
    threads = [
        threading.Thread(target=lambda: [c.inc() for _ in range(10_000)])
        for _ in range(8)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 80_000
    with pytest.raises(ValueError):
        c.inc(-1)


def test_histogram_thread_safe_observe():
    reg = MetricRegistry()
    h = reg.histogram("lat", window=0)  # bucket-only path under contention
    threads = [
        threading.Thread(target=lambda: [h.observe(1e-4) for _ in range(5_000)])
        for _ in range(8)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert h.count == 40_000
    assert int(h.bucket_counts.sum()) == 40_000


def test_registry_labels_and_type_conflicts():
    reg = MetricRegistry()
    a = reg.counter("req", matrix="A")
    b = reg.counter("req", matrix="B")
    assert a is not b
    assert reg.counter("req", matrix="A") is a  # get-or-create is stable
    a.inc(3)
    assert reg.value("req", matrix="A") == 3
    assert reg.value("req", matrix="C", default=-1) == -1
    assert sorted(reg.label_values("req", "matrix")) == ["A", "B"]
    with pytest.raises(TypeError):
        reg.gauge("req")  # same name, different type
    g = reg.gauge("depth")
    g.set(5)
    g.dec(2)
    assert g.value == 3


def test_series_is_iteration_indexed():
    reg = MetricRegistry()
    s = reg.series("resid", window=4)
    s.extend([4.0, 3.0, 2.0, 1.0, 0.5])
    assert s.count == 5
    assert s.points == [(1, 3.0), (2, 2.0), (3, 1.0), (4, 0.5)]  # window evicts
    snap = s.snapshot()
    assert snap["last"] == 0.5 and snap["min"] == 0.5


# --- span tracer -----------------------------------------------------------


def test_span_nesting_and_ordering_in_chrome_trace(tmp_path):
    tr = Tracer()
    with tr.span("outer", stage="admit"):
        with tr.span("inner_a"):
            pass
        with tr.span("inner_b") as sp:
            sp.annotate(found=3)
    trace = tr.chrome_trace()
    events = trace["traceEvents"]
    # children close before the parent: completion order, depth marks nesting
    assert [e["name"] for e in events] == ["inner_a", "inner_b", "outer"]
    by = {e["name"]: e for e in events}
    assert by["outer"]["depth"] == 0
    assert by["inner_a"]["depth"] == by["inner_b"]["depth"] == 1
    for child in ("inner_a", "inner_b"):
        assert by[child]["ts"] >= by["outer"]["ts"]
        assert by[child]["ts"] + by[child]["dur"] <= by["outer"]["ts"] + by["outer"]["dur"] + 1e-6
    assert by["inner_a"]["ts"] + by["inner_a"]["dur"] <= by["inner_b"]["ts"]
    assert by["inner_b"]["args"]["found"] == 3
    assert all(e["ph"] == "X" for e in events)
    # the export round-trips as the JSON object Perfetto loads
    path = tmp_path / "trace.json"
    tr.write_chrome(path)
    loaded = json.loads(path.read_text())
    assert loaded["traceEvents"] == json.loads(json.dumps(events))


def test_span_records_exceptions_and_rebalances_depth():
    tr = Tracer()
    with pytest.raises(RuntimeError):
        with tr.span("fails"):
            raise RuntimeError("boom")
    (ev,) = tr.snapshot()
    assert ev["args"]["error"] == "RuntimeError"
    with tr.span("after"):  # depth recovered despite the exception
        pass
    assert tr.snapshot()[-1]["depth"] == 0


def test_tracer_bounds_events_and_counts_drops():
    tr = Tracer(max_events=3)
    for i in range(5):
        with tr.span(f"s{i}"):
            pass
    assert len(tr.events) == 3 and tr.dropped == 2
    assert tr.chrome_trace()["otherData"]["dropped_events"] == 2
    tr.clear()
    assert tr.events == [] and tr.dropped == 0


def test_span_summary_aggregates_by_name():
    tr = Tracer()
    for _ in range(3):
        with tr.span("hot"):
            pass
    with tr.span("cold"):
        pass
    summary = {s["name"]: s for s in tr.summary()}
    assert summary["hot"]["count"] == 3 and summary["cold"]["count"] == 1
    assert summary["hot"]["total_ms"] >= summary["hot"]["mean_ms"]


# --- gating: disabled mode retains nothing ---------------------------------


def test_disabled_mode_retains_zero_events():
    obs.reset()
    assert not obs.enabled()
    with obs.span("never", matrix="A") as sp:
        sp.annotate(x=1)
        sp.sync(np.zeros(2))
    obs.counter("never").inc(100)
    obs.gauge("never").set(5)
    obs.histogram("never").observe(1.0)
    obs.series("never").append(1.0)
    assert obs.tracer().snapshot() == []
    assert obs.registry().metrics() == []
    snap = obs.collect()
    assert snap["enabled"] is False and snap["n_events"] == 0
    assert all(not r["metrics"] or r["registry"] != "global" for r in snap["registries"])


def test_enable_roundtrip_records_then_stops(obs_on):
    with obs.span("on"):
        obs.counter("hits").inc()
    assert len(obs.tracer().snapshot()) == 1
    assert obs.registry().value("hits") == 1
    obs.disable()
    with obs.span("off"):
        obs.counter("hits").inc()
    assert len(obs.tracer().snapshot()) == 1  # unchanged
    assert obs.registry().value("hits") == 1


# --- instrumented subsystems end to end ------------------------------------


def test_admission_emits_nested_spans_and_counters(obs_on):
    from repro.core import PartitionConfig, build_tiles
    from repro.core.matrices import circuit

    cfg = PartitionConfig(row_block=64, col_block=128, group=8, lane=16)
    build_tiles(circuit(200, seed=0), cfg)
    names = [e["name"] for e in obs.tracer().snapshot()]
    assert "admit.build_tiles" in names
    assert "admit.partition" in names and "admit.hash" in names
    by = {e["name"]: e for e in obs.tracer().snapshot()}
    assert by["admit.partition"]["depth"] > by["admit.build_tiles"]["depth"]
    assert obs.registry().value("admit.tile_builds") == 1
    assert obs.registry().value("admit.tiles_built") > 0


def test_kernel_launch_counters(obs_on):
    from repro.core import PartitionConfig, build_tiles, csr_from_dense
    from repro.kernels import ops

    rng = np.random.default_rng(5)
    dense = (rng.standard_normal((40, 50)) * (rng.random((40, 50)) < 0.2)).astype(
        np.float32
    )
    tiles = build_tiles(
        csr_from_dense(dense), PartitionConfig(row_block=64, col_block=128, lane=16)
    )
    ops.hbp_spmm(tiles, rng.standard_normal((50, 8)).astype(np.float32), strategy="stable")
    ops.hbp_spmv(tiles, rng.standard_normal(50).astype(np.float32), strategy="stable")
    reg = obs.registry()
    assert reg.value("kernels.launches", op="spmm", strategy="stable",
                     k_tiling="grid", combine="sum", gather="none") == 1
    assert reg.value("kernels.launches", op="spmv", strategy="stable",
                     k_tiling="grid", combine="sum", gather="none") == 1
    assert reg.value("kernels.traversals") == 2  # both k <= LANE_TILE: 1 pass each
    assert reg.value("kernels.bytes_modeled") > 0


def test_stream_passes_model():
    from repro.kernels.ops import LANE_TILE, stream_passes

    assert stream_passes(1, "stable", "grid") == 1
    assert stream_passes(LANE_TILE, "fused", "loop") == 1
    # one-pass geometries at wide k
    assert stream_passes(4 * LANE_TILE, "partials", "grid") == 1
    assert stream_passes(4 * LANE_TILE, "reference", "grid") == 1
    # chunked geometries pay one pass per lane tile
    assert stream_passes(4 * LANE_TILE, "partials", "loop") == 4
    assert stream_passes(4 * LANE_TILE, "stable", "grid") == 4
    assert stream_passes(3 * LANE_TILE + 1, "fused", "loop") == 4


def test_solver_history_streams_into_series(obs_on):
    from repro.solvers import cg

    rng = np.random.default_rng(0)
    n = 48
    R = rng.standard_normal((n, n)) * 0.05
    S = (np.eye(n) + R @ R.T).astype(np.float32)
    b = rng.standard_normal(n).astype(np.float32)
    res = cg(S, b, tol=1e-6, maxiter=100)
    s = obs.registry().get("solver.cg.residual", run=1)
    assert s is not None
    assert len(s.points) == int(res.iterations) + 1
    np.testing.assert_allclose(
        s.values, np.asarray(res.history)[: int(res.iterations) + 1], rtol=1e-6
    )
    # a second run gets its own stream
    cg(S, b, tol=1e-6, maxiter=100)
    assert obs.registry().get("solver.cg.residual", run=2) is not None


# --- artifacts and the dashboard ------------------------------------------


def test_dump_report_and_ledger(obs_on, tmp_path):
    from repro.core.matrices import circuit
    from repro.serving import MatrixRegistry, ServingEngine

    reg = MatrixRegistry(cache_dir=tmp_path / "cache", search=False)
    A = circuit(150, seed=1)
    reg.admit(A, "A")
    reg.admit(A, "A")  # content hit
    eng = ServingEngine(reg, max_wait_s=1e9, max_batch=8)
    rng = np.random.default_rng(0)
    for _ in range(4):
        eng.submit("A", rng.standard_normal(A.shape[1]).astype(np.float32))
    eng.flush()

    snap = obs.dump(tmp_path / "obs.json")
    assert json.loads((tmp_path / "obs.json").read_text())["schema"] == 1
    ledger = amortization_ledger(snap)
    (row,) = [r for r in ledger if r["matrix"] == "A"]
    assert row["requests"] == 4 and row["preprocess_s"] > 0
    assert row["amortized_preprocess_s"] == pytest.approx(row["preprocess_s"] / 4)

    text = render(snap)
    assert "registry.hits{matrix=A}" in text
    assert "serving.requests{matrix=A}" in text
    assert "amortization ledger" in text

    obs.write_trace(tmp_path / "trace.json")
    trace = json.loads((tmp_path / "trace.json").read_text())
    assert any(e["name"] == "serve.admit" for e in trace["traceEvents"])
    assert any(e["name"] == "serve.flush" for e in trace["traceEvents"])

    obs.write_events(tmp_path / "events.jsonl")
    lines = (tmp_path / "events.jsonl").read_text().splitlines()
    assert len(lines) == len(trace["traceEvents"])
    # complete spans plus request flow events (submit "s" → flush "f")
    assert all(json.loads(ln)["ph"] in ("X", "s", "f") for ln in lines)


def test_render_handles_empty_snapshot():
    out = render({"registries": [], "spans": []})
    assert "no metrics recorded" in out


# --- deterministic ordering (CI artifacts must diff cleanly) ----------------


def test_registry_collect_is_sorted_regardless_of_creation_order():
    reg = MetricRegistry()
    # scrambled creation order, mixed labels and types
    reg.counter("z.last", matrix="B").inc()
    reg.gauge("a.first", matrix="Z").set(1)
    reg.counter("m.mid", matrix="B").inc()
    reg.counter("m.mid", matrix="A").inc()
    reg.gauge("a.first", matrix="A").set(2)
    snap = reg.collect()
    keys = [
        (m["name"], tuple(sorted(m["labels"].items())), m["type"])
        for m in snap["metrics"]
    ]
    assert keys == sorted(keys)
    assert snap == reg.collect()  # stable across repeated collects


def test_render_rows_are_sorted(obs_on):
    obs.counter("zz.metric", matrix="B").inc()
    obs.counter("aa.metric", matrix="A").inc()
    obs.gauge("mm.gauge").set(1)
    text = render(obs.collect())
    assert text.index("aa.metric") < text.index("zz.metric")
    assert render(obs.collect()) == text


def test_span_summary_ties_break_by_name():
    tr = Tracer()
    # two zero-duration names: equal totals must still order deterministically
    tr.add_event("b_span", 0.0, 0.0, 0, {})
    tr.add_event("a_span", 0.0, 0.0, 0, {})
    names = [s["name"] for s in tr.summary()]
    assert names == ["a_span", "b_span"]


# --- flight recorder --------------------------------------------------------


def test_flight_ring_wraparound_keeps_newest():
    fl = FlightRecorder(capacity=8)
    for i in range(20):
        fl.record("ev", i=i)
    st = fl.stats()
    assert st["recorded_total"] == 20
    assert st["events"] == 8 and st["capacity"] == 8
    assert st["overwritten"] == 12
    kept = [e["args"]["i"] for e in fl.snapshot()]
    assert kept == list(range(12, 20))  # oldest overwritten, order preserved


def test_flight_span_records_duration_and_sampling():
    fl = FlightRecorder(capacity=16, seed=0)
    with fl.span("timed", matrix="A") as sp:
        sp.annotate(k=4)
    (ev,) = fl.snapshot()
    assert ev["name"] == "timed" and ev["ph"] == "X"
    assert ev["dur"] >= 0 and ev["args"] == {"matrix": "A", "k": 4}
    # sample=0.0 never records; the returned no-op still context-manages
    with fl.span("never", sample=0.0) as sp:
        sp.annotate(x=1)
    assert len(fl.snapshot()) == 1
    # errors inside a sampled span are annotated, not swallowed
    with pytest.raises(RuntimeError):
        with fl.span("fails"):
            raise RuntimeError("boom")
    assert fl.snapshot()[-1]["args"]["error"] == "RuntimeError"


def test_flight_trigger_writes_perfetto_loadable_dump(tmp_path):
    fl = FlightRecorder(capacity=32, dump_dir=tmp_path)
    fl.record("before", site="x")
    path = fl.trigger("unit_test", detail="why")
    assert path is not None
    loaded = json.loads((tmp_path / "flight_unit_test_0.json").read_text())
    names = [e["name"] for e in loaded["traceEvents"]]
    assert names == ["before", "flight.trigger"]  # trigger lands in the ring
    assert loaded["otherData"]["reason"] == "unit_test"
    assert loaded["otherData"]["context"]["detail"] == "why"
    # Chrome-trace invariants Perfetto relies on
    ts = [e["ts"] for e in loaded["traceEvents"]]
    assert ts == sorted(ts)
    for e in loaded["traceEvents"]:
        assert e["ph"] in ("X", "i")
        if e["ph"] == "X":
            assert e["dur"] >= 0
        else:
            assert e["s"] == "t"
    assert fl.stats()["dumps"] == [str(path)]


def test_flight_trigger_rate_limit_and_cap(tmp_path):
    fl = FlightRecorder(
        capacity=8, dump_dir=tmp_path, max_dumps=3, min_dump_interval_s=3600.0
    )
    assert fl.trigger("same") is not None
    assert fl.trigger("same") is None  # rate-limited per reason
    assert fl.trigger("other") is not None  # a different reason still dumps
    assert fl.trigger("third") is not None
    assert fl.trigger("fourth") is None  # global max_dumps cap
    st = fl.stats()
    assert len(st["dumps"]) == 3 and st["suppressed_triggers"] == 2


def test_flight_latency_anomaly_detector(tmp_path):
    fl = FlightRecorder(
        capacity=64,
        dump_dir=tmp_path,
        latency_window=128,
        latency_min_samples=16,
        latency_factor=4.0,
        latency_refresh=16,
    )
    # a stable baseline never triggers
    for _ in range(64):
        assert fl.observe_latency("site", 1e-3) is None
    # a 100x spike past the rolling threshold does
    path = fl.observe_latency("site", 0.1, matrix="A")
    assert path is not None
    loaded = json.load(open(path))
    assert loaded["otherData"]["reason"] == "latency_anomaly"
    assert loaded["otherData"]["context"]["site"] == "site"


def test_flight_queue_depth_detector(tmp_path):
    fl = FlightRecorder(capacity=8, dump_dir=tmp_path)
    assert fl.observe_queue_depth("q", 3, 8) is None
    assert fl.observe_queue_depth("q", 7, 8) is None
    path = fl.observe_queue_depth("q", 8, 8)
    assert path is not None
    assert json.load(open(path))["otherData"]["reason"] == (
        "queue_saturation"
    )
    assert fl.observe_queue_depth("q", 9, 0) is None  # limit 0 disables


def test_flight_concurrent_record_and_trigger(tmp_path):
    fl = FlightRecorder(capacity=64, dump_dir=tmp_path, min_dump_interval_s=0.0)
    n_threads, per_thread = 8, 500
    barrier = threading.Barrier(n_threads)

    def worker(tid):
        barrier.wait()
        for i in range(per_thread):
            fl.record("ev", tid=tid, i=i)
            if i % 100 == 0:
                fl.trigger(f"t{tid}")

    threads = [threading.Thread(target=worker, args=(t,)) for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    st = fl.stats()
    # every record landed exactly once (triggers add one ring event each)
    assert st["recorded_total"] >= n_threads * per_thread
    assert st["events"] == 64 and st["overwritten"] == st["recorded_total"] - 64
    snap = fl.snapshot()
    assert len(snap) == 64
    assert all(e is not None for e in snap)  # no torn slots under contention
    for p in st["dumps"]:  # every dump parses as a complete artifact
        assert "traceEvents" in json.load(open(p))


def test_flight_reset_and_global_accessor():
    fl = obs.flight()
    assert fl is obs.get_flight()
    fl.record("something")
    assert obs.collect()["flight"]["recorded_total"] >= 1
    obs.reset()
    assert obs.flight().stats()["recorded_total"] == 0


# --- SLO engine -------------------------------------------------------------


def test_slo_validation_and_budget():
    slo = SLO("deadline", "deadline_hit_ratio", 0.99)
    assert slo.budget == pytest.approx(0.01)
    assert slo.good(123.0, True) and not slo.good(0.0, False)
    lat = SLO("p99", "latency_p99", 0.005)
    assert lat.budget == pytest.approx(0.01)
    assert lat.good(0.004, False) and not lat.good(0.006, True)
    with pytest.raises(ValueError):
        SLO("bad", "nope", 0.5)
    with pytest.raises(ValueError):
        SLO("bad", "deadline_hit_ratio", 1.5)
    with pytest.raises(ValueError):
        SLO("bad", "latency_p99", 0.0)
    with pytest.raises(ValueError):
        SLO("bad", "deadline_hit_ratio", 0.99, windows=(60.0, 30.0))
    with pytest.raises(ValueError):
        SLOEngine([slo, SLO("deadline", "latency_p99", 1.0)])  # duplicate name


def test_slo_burn_rates_and_paging():
    clk = [1000.0]
    eng = SLOEngine(
        [SLO("deadline", "deadline_hit_ratio", 0.99, windows=(10.0, 60.0, 300.0))],
        clock=lambda: clk[0],
    )
    # 100 requests in the last 10s, half missing their deadline:
    # bad_ratio 0.5 / budget 0.01 = burn 50 >> fast_burn on both short windows
    for i in range(100):
        eng.record("A", latency_s=0.001, deadline_hit=(i % 2 == 0), now=1000.0 - i * 0.05)
    out = eng.evaluate("A", now=1000.0)["A"]["deadline"]
    assert out["status"] == "page"
    w10 = out["windows"]["10s"]
    assert w10["events"] == 100 and w10["bad"] == 50
    assert w10["burn_rate"] == pytest.approx(50.0)
    assert w10["attainment"] == pytest.approx(0.5)
    # the gauges refreshed into the engine's metric registry
    assert eng.metrics.value(
        "slo.burn_rate", matrix="A", slo="deadline", window="10s"
    ) == pytest.approx(50.0)


def test_slo_warn_on_longest_window_only():
    eng = SLOEngine(
        [SLO("deadline", "deadline_hit_ratio", 0.9, windows=(10.0, 60.0, 300.0))]
    )
    # misses concentrated 100s ago: short windows are clean, the long one burns
    for i in range(40):
        eng.record("A", latency_s=0.001, deadline_hit=False, now=900.0 - i * 0.1)
    for i in range(10):
        eng.record("A", latency_s=0.001, deadline_hit=True, now=1000.0 - i * 0.1)
    out = eng.evaluate("A", now=1000.0)["A"]["deadline"]
    assert out["windows"]["10s"]["bad"] == 0
    assert out["windows"]["300s"]["burn_rate"] >= 2.0
    assert out["status"] == "warn"


def test_slo_no_data_is_ok_not_outage():
    eng = SLOEngine()
    assert eng.evaluate() == {}
    eng.record("A", latency_s=0.001, deadline_hit=True, now=100.0)
    out = eng.evaluate("A", now=100.0 + 7200.0)["A"]["deadline"]
    assert all(w["events"] == 0 for w in out["windows"].values())
    assert all(w["burn_rate"] is None for w in out["windows"].values())
    assert out["status"] == "ok"


def test_slo_latency_objective_and_worst_status():
    eng = SLOEngine([SLO("p99", "latency_p99", 0.005, windows=(60.0, 300.0))])
    for i in range(50):
        eng.record("A", latency_s=0.5, deadline_hit=True, now=100.0 + i * 0.01)
    out = eng.evaluate("A", now=101.0)["A"]["p99"]
    assert out["status"] == "page"  # every request blows the latency bound
    assert worst_status(["ok", "warn"]) == "warn"
    assert worst_status(["warn", "page", "ok"]) == "page"
    assert worst_status([]) == "ok"


# --- serving integration: flight + SLO + gating -----------------------------


def _serve_matrix(tmp_path, **engine_kw):
    from repro.core.matrices import circuit
    from repro.serving import MatrixRegistry, ServingEngine

    reg = MatrixRegistry(cache_dir=tmp_path / "cache", search=False)
    A = circuit(150, seed=1)
    reg.admit(A, "A")
    vclock = [0.0]
    eng = ServingEngine(reg, clock=lambda: vclock[0], **engine_kw)
    return reg, A, eng, vclock


def test_induced_deadline_miss_dumps_flush_span(tmp_path):
    """Acceptance criterion: a deadline miss produces a Perfetto-loadable
    dump containing the offending serve.flush span."""
    fl = FlightRecorder(capacity=256, dump_dir=tmp_path / "dumps")
    reg, A, eng, vclock = _serve_matrix(
        tmp_path, max_wait_s=0.001, max_batch=8, flight=fl
    )
    rng = np.random.default_rng(0)
    for _ in range(4):
        eng.submit("A", rng.standard_normal(A.shape[1]).astype(np.float32))
    vclock[0] = 1.0  # every pending request is now way past its deadline
    eng.flush()
    (dump_path,) = fl.stats()["dumps"]
    loaded = json.load(open(dump_path))
    assert loaded["otherData"]["reason"] == "deadline_miss"
    assert loaded["otherData"]["context"]["matrix"] == "A"
    flushes = [e for e in loaded["traceEvents"] if e["name"] == "serve.flush"]
    assert flushes, "the offending flush span must be in the dump"
    assert flushes[-1]["ph"] == "X" and flushes[-1]["dur"] > 0
    assert flushes[-1]["args"]["matrix"] == "A"
    # the SLO view pages on the same evidence
    assert eng.health(now=vclock[0])["matrices"]["A"]["status"] == "page"


def test_queue_saturation_triggers_dump(tmp_path):
    fl = FlightRecorder(capacity=64, dump_dir=tmp_path / "dumps")
    reg, A, eng, vclock = _serve_matrix(
        tmp_path, max_wait_s=1e9, max_batch=8, queue_limit=3, flight=fl
    )
    rng = np.random.default_rng(0)
    for _ in range(3):  # third submit hits the limit
        eng.submit("A", rng.standard_normal(A.shape[1]).astype(np.float32))
    dumps = fl.stats()["dumps"]
    assert len(dumps) == 1
    assert json.load(open(dumps[0]))["otherData"]["reason"] == (
        "queue_saturation"
    )
    eng.flush()


def test_hot_loop_gating_is_consistent_when_disabled(tmp_path, monkeypatch):
    """Satellite: with obs disabled the engine must never touch the gated
    constructors — the disabled path allocates no label dicts and creates
    no global-registry metrics."""
    obs.reset()
    assert not obs.enabled()

    def boom(*a, **k):
        raise AssertionError("gated obs constructor called on disabled path")

    reg, A, eng, vclock = _serve_matrix(tmp_path, max_wait_s=1e9, max_batch=8)
    rng = np.random.default_rng(0)
    monkeypatch.setattr(obs, "counter", boom)
    monkeypatch.setattr(obs, "gauge", boom)
    monkeypatch.setattr(obs, "histogram", boom)
    for _ in range(4):
        eng.submit("A", rng.standard_normal(A.shape[1]).astype(np.float32))
    eng.flush()
    assert obs.registry().metrics() == []  # nothing leaked into the registry
    # the always-live ledgers still worked
    assert eng.metrics.value("serving.requests", matrix="A") == 4
    assert eng.metrics.value("serving.batches", matrix="A") > 0


# --- request-scoped tracing: contexts, exemplars, flows, waterfall ----------


def test_histogram_keeps_most_recent_exemplar_per_bucket():
    h = Histogram("lat", {}, buckets=[0.001, 0.01, 0.1])
    h.observe(0.005, exemplar="r1-a")
    h.observe(0.007, exemplar="r1-b")  # same bucket: replaces r1-a
    h.observe(0.5, exemplar="r1-c")  # overflow slot
    h.observe(0.05)  # no exemplar: bucket stays empty
    ex = h.exemplars()
    assert [(e["trace_id"], e["value"]) for e in ex] == [
        ("r1-b", 0.007),
        ("r1-c", 0.5),
    ]
    assert ex[0]["le"] == 0.01 and ex[1]["le"] == float("inf")
    # exemplars ride the snapshot (and therefore obs.dump())
    assert h.snapshot()["exemplars"] == ex
    # a histogram that never saw an exemplar allocates nothing and omits
    h2 = Histogram("lat2", {}, buckets=[0.001])
    h2.observe(0.5)
    assert h2.exemplars() == [] and "exemplars" not in h2.snapshot()


def test_noop_observe_accepts_exemplar_kwarg():
    assert not obs.enabled()
    # the disabled path must accept the full enabled-path signature
    obs.histogram("t").observe(0.5, exemplar="r-1")
    obs.flow("request", "r-1", "s")  # gated: no tracer event while disabled
    assert obs.tracer().snapshot() == []


def test_tracer_flow_events_shape_and_validation():
    tr = Tracer()
    tr.flow("request", "r3-1", "s", matrix="A")
    tr.flow("request", "r3-1", "f")
    s_ev, f_ev = tr.snapshot()
    assert s_ev["ph"] == "s" and f_ev["ph"] == "f"
    assert s_ev["id"] == f_ev["id"] == "r3-1"
    assert s_ev["cat"] == f_ev["cat"] == "request"
    assert f_ev["bp"] == "e"  # finish binds to the enclosing slice
    assert "bp" not in s_ev
    assert s_ev["args"] == {"matrix": "A"}
    with pytest.raises(ValueError, match="flow phase"):
        tr.flow("request", "r3-1", "x")
    # flow events carry no duration, so the span summary skips them
    assert tr.summary() == []


def test_engine_emits_flow_events_when_enabled(obs_on, tmp_path):
    reg, A, eng, vclock = _serve_matrix(tmp_path, max_wait_s=1e9, max_batch=4)
    rng = np.random.default_rng(0)
    tickets = [
        eng.submit("A", rng.standard_normal(A.shape[1]).astype(np.float32))
        for _ in range(3)
    ]
    eng.flush()
    events = obs.tracer().snapshot()
    starts = {e["id"] for e in events if e["ph"] == "s"}
    finishes = {e["id"] for e in events if e["ph"] == "f"}
    ids = {t.trace_id for t in tickets}
    assert starts == finishes == ids
    # finish events land inside the serve.flush slice (bp="e" binding)
    flush = next(e for e in events if e["ph"] == "X" and e["name"] == "serve.flush")
    for e in events:
        if e["ph"] == "f":
            assert flush["ts"] <= e["ts"] <= flush["ts"] + flush["dur"]


def test_request_context_decomposition_on_virtual_clock(tmp_path):
    from repro.obs.requesttrace import RequestLog

    log = RequestLog()
    reg, A, eng, vclock = _serve_matrix(
        tmp_path, max_wait_s=0.5, max_batch=4, request_log=log
    )
    rng = np.random.default_rng(0)
    tickets = []
    for i in range(4):
        vclock[0] = 0.01 * i  # submits at t=0.00, 0.01, 0.02, 0.03
        tickets.append(
            eng.submit("A", rng.standard_normal(A.shape[1]).astype(np.float32))
        )
    vclock[0] = 0.1
    eng.flush()
    assert log.count == 4
    ctxs = {c.trace_id: c for c in log.contexts()}
    assert set(ctxs) == {t.trace_id for t in tickets}
    for i, t in enumerate(tickets):
        c = ctxs[t.trace_id]
        assert c is t.context and c.done
        # stamps are in the virtual-clock domain: fully deterministic
        assert c.t_submit == pytest.approx(0.01 * i)
        assert c.queue_wait_s == pytest.approx(0.1 - 0.01 * i)
        assert c.latency_s == pytest.approx(0.1 - 0.01 * i)
        assert c.t_flush_start == c.t_dispatch == c.t_complete == 0.1
        assert c.batch_share == pytest.approx(0.25)
        assert c.batch_k == 4 and c.flush_reason == "drain"
        assert c.deadline_hit is (c.latency_s <= 0.5)
        # compute is wall time, attributed by share
        assert c.compute_s > 0
        assert c.compute_share_s == pytest.approx(c.compute_s * 0.25)
        d = c.to_dict()
        assert d["trace_id"] == c.trace_id and d["matrix"] == "A"
        assert d["queue_wait_s"] == pytest.approx(c.queue_wait_s)
    # the per-batch exemplar ends up on the latency histogram
    h = eng.metrics.get("serving.latency_s", matrix="A")
    assert {e["trace_id"] for e in h.exemplars()} <= set(ctxs)
    # and the engine defaulting to the process log feeds obs.collect()
    assert all(r["matrix"] == "A" for r in log.snapshot())


def test_collect_includes_process_request_log(tmp_path):
    obs.reset()
    reg, A, eng, vclock = _serve_matrix(tmp_path, max_wait_s=1e9, max_batch=2)
    rng = np.random.default_rng(0)
    t = eng.submit("A", rng.standard_normal(A.shape[1]).astype(np.float32))
    eng.flush()
    snap = obs.collect()
    assert any(r["trace_id"] == t.trace_id for r in snap["requests"])
    obs.reset()  # reset() clears the request log too
    assert obs.collect()["requests"] == []


def test_deadline_miss_dump_names_late_requests(tmp_path):
    """Acceptance criterion: the deadline_miss trigger event carries the
    trace ids of the late requests, and the dump filename is greppable by
    the first of them."""
    from repro.obs.requesttrace import RequestLog

    fl = FlightRecorder(capacity=256, dump_dir=tmp_path / "dumps")
    log = RequestLog()
    reg, A, eng, vclock = _serve_matrix(
        tmp_path, max_wait_s=0.001, max_batch=8, flight=fl, request_log=log
    )
    rng = np.random.default_rng(0)
    for _ in range(4):
        eng.submit("A", rng.standard_normal(A.shape[1]).astype(np.float32))
    vclock[0] = 1.0  # every pending request misses
    eng.flush()
    late = [c.trace_id for c in log.contexts() if c.deadline_hit is False]
    assert len(late) == 4
    (dump_path,) = fl.stats()["dumps"]
    loaded = json.load(open(dump_path))
    assert loaded["otherData"]["context"]["trace_ids"] == late
    (trig,) = [e for e in loaded["traceEvents"] if e["name"] == "flight.trigger"]
    assert trig["args"]["trace_ids"] == late
    # the filename names the first late request
    assert late[0] in dump_path
    # the flush ring event lists every coalesced request
    flush = next(e for e in loaded["traceEvents"] if e["name"] == "serve.flush")
    assert flush["args"]["trace_ids"] == late


def test_flight_reset_clears_rate_limiter_and_dump_seq(tmp_path):
    """Satellite: reset() must clear the per-reason rate limiter and the
    dump sequence counter, or post-reset triggers are silently suppressed
    and filenames collide across test runs."""
    fl = FlightRecorder(capacity=8, dump_dir=tmp_path, min_dump_interval_s=60.0)
    first = fl.trigger("deadline_miss", matrix="A")
    assert first is not None and "_0" in first
    assert fl.trigger("deadline_miss") is None  # rate-limited
    assert fl.stats()["suppressed_triggers"] == 1
    fl.reset()
    # post-reset: not suppressed, and the sequence restarts at 0
    again = fl.trigger("deadline_miss", matrix="A")
    assert again is not None
    assert json.load(open(again))["otherData"]["seq"] == 0
    st = fl.stats()
    assert st["suppressed_triggers"] == 0 and st["dumps"] == [str(again)]


def test_waterfall_renders_decomposition_and_handles_gaps():
    from repro.obs.requesttrace import waterfall

    rows = [
        {
            "trace_id": "r1-0", "matrix": "A", "latency_s": 0.10,
            "queue_wait_s": 0.08, "compute_share_s": 0.02,
            "batch_share": 0.25, "flush_reason": "size",
        },
        {
            "trace_id": "r1-1", "matrix": "B", "latency_s": 0.05,
            "queue_wait_s": None, "compute_share_s": None,
            "batch_share": None, "flush_reason": None,
        },
        {"trace_id": "r1-2", "matrix": "C", "latency_s": None},  # incomplete
    ]
    out = waterfall(rows, n=10, width=10)
    lines = out.splitlines()
    assert "slowest 2 requests" in lines[0]  # incomplete row dropped
    assert lines[2].startswith("r1-0")  # sorted by latency desc
    assert "░░░░░░░░██" in lines[2]  # 8/10 queue cells, 2/10 compute
    assert "1/4" in lines[2] and "size" in lines[2]
    # None fields render as n/a, never crash and never print "None"
    assert "n/a" in lines[3] and "None" not in out
    # n bounds the table; dict input reads snapshot["requests"]
    assert "slowest 1 requests" in waterfall({"requests": rows}, n=1)
    assert "no completed requests" in waterfall([])


def test_report_renders_requests_section_and_na(tmp_path):
    obs.reset()
    reg, A, eng, vclock = _serve_matrix(tmp_path, max_wait_s=1e9, max_batch=2)
    rng = np.random.default_rng(0)
    eng.submit("A", rng.standard_normal(A.shape[1]).astype(np.float32))
    eng.flush()
    # an empty histogram's percentiles must render as n/a, not None
    eng.metrics.histogram("serving.empty_hist", matrix="A")
    text = render(obs.collect())
    assert "slowest 1 requests" in text
    assert "n/a" in text and "None" not in text
    obs.reset()


def test_analysis_report_cli_round_trips_dump(tmp_path, capsys, monkeypatch):
    """Satellite: --obs / --explain / --requests must all re-render a
    real repro.obs.dump() snapshot file."""
    from repro.analysis import report as analysis_report

    obs.reset()
    reg, A, eng, vclock = _serve_matrix(tmp_path, max_wait_s=1e9, max_batch=4)
    rng = np.random.default_rng(0)
    tickets = [
        eng.submit("A", rng.standard_normal(A.shape[1]).astype(np.float32))
        for _ in range(3)
    ]
    eng.flush()
    snap_path = tmp_path / "obs.json"
    obs.dump(snap_path)

    monkeypatch.setattr("sys.argv", ["report", "--obs", str(snap_path)])
    analysis_report.main()
    out = capsys.readouterr().out
    assert "repro.obs report" in out
    assert "serving.requests{matrix=A}" in out
    assert "slowest 3 requests" in out  # dump carries the request log

    monkeypatch.setattr(
        "sys.argv", ["report", "--explain", "A", "--obs", str(snap_path)]
    )
    analysis_report.main()
    out = capsys.readouterr().out
    # the served requests' split at flush and launch, from the request log
    assert "served requests (queue wait / dispatch / in flight)" in out
    assert "3 requests in the log" in out
    for label in ("queue wait", "dispatch", "in flight"):
        assert f"  {label}" in out and "p95" in out
    assert "batches in flight ahead of the launch: mean 0.000" in out

    monkeypatch.setattr(
        "sys.argv", ["report", "--requests", str(snap_path), "--top", "2"]
    )
    analysis_report.main()
    out = capsys.readouterr().out
    assert "slowest 2 requests" in out  # --top bounds the table
    assert any(t.trace_id in out for t in tickets)
    obs.reset()
