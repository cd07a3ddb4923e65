"""Multi-tenant serving policy: QoS classes, backpressure, fairness, eviction.

The overload contracts under test:

* admission control sheds with a **typed** ``BackpressureError`` before
  the request holds a queue slot — never a silent drop (every submit
  either completes or raises, and the ledger's ``qos.shed`` counter
  accounts each rejection);
* weighted-fair flushing changes *which tenant* is served next, never the
  order **within** a tenant (per-tenant FIFO is preserved);
* HBM-budget eviction is transparent: an unstaged plan re-stages on the
  next ``get`` with bitwise-identical results, and a fully evicted matrix
  re-admits under the same content hash via the autotune disk cache;
* overlap dispatch is a scheduling change, not a numerics change: results
  are bitwise equal to the synchronous engine's.
"""
import numpy as np
import pytest

from repro.core import csr_from_dense
from repro.core.matrices import banded_fem, circuit
from repro.serving import (
    BackpressureError,
    LRUEvictor,
    MatrixRegistry,
    QoSClass,
    ServingEngine,
    WeightedFairScheduler,
    matrix_hash,
    plan_device_bytes,
)


@pytest.fixture()
def registry(tmp_path):
    return MatrixRegistry(cache_dir=tmp_path / "cache", search=False)


@pytest.fixture()
def two_matrices():
    A = circuit(150, seed=1, n_dense_rows=2, dense_row_frac=0.05)
    B = banded_fem(130, seed=3, band=4, fill=0.9)
    return A, B


def _xs(n_cols, count, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.standard_normal(n_cols).astype(np.float32) for _ in range(count)]


# --- QoS classes ----------------------------------------------------------


def test_qos_class_validation():
    with pytest.raises(ValueError):
        QoSClass("bad", deadline_s=0.0)
    with pytest.raises(ValueError):
        QoSClass("bad", deadline_s=0.01, weight=0.0)
    with pytest.raises(ValueError):
        QoSClass("bad", deadline_s=0.01, max_queue=0)
    with pytest.raises(ValueError):
        QoSClass("bad", deadline_s=0.01, max_wait_s=0.0)


def test_qos_max_wait_overrides_batching_window(registry, two_matrices):
    A, _ = two_matrices
    registry.admit(A, "a")
    vt = [0.0]
    eng = ServingEngine(
        registry,
        max_wait_s=0.010,
        clock=lambda: vt[0],
        qos={"a": QoSClass("tight", deadline_s=0.05, max_wait_s=0.001)},
    )
    eng.submit("a", _xs(A.shape[1], 1)[0])
    vt[0] = 0.002  # past the class window, well inside the engine default
    assert eng.poll() == 1


# --- admission control ----------------------------------------------------


def test_backpressure_is_typed_and_never_silent(registry, two_matrices):
    A, _ = two_matrices
    registry.admit(A, "a")
    vt = [0.0]
    eng = ServingEngine(
        registry,
        clock=lambda: vt[0],
        qos={"a": QoSClass("capped", deadline_s=0.05, max_queue=2)},
    )
    xs = _xs(A.shape[1], 3)
    t1 = eng.submit("a", xs[0])
    t2 = eng.submit("a", xs[1])
    with pytest.raises(BackpressureError) as exc:
        eng.submit("a", xs[2])
    # the error carries the evidence, the ledger counts the shed, and the
    # shed request holds no queue slot (the two admitted ones still do)
    assert exc.value.key == "a"
    assert exc.value.qos == "capped"
    assert exc.value.depth == 2 and exc.value.limit == 2
    assert eng.metrics.value("qos.shed", matrix="a", qos="capped") == 1
    assert eng.batcher.pending("a") == 2
    # the admitted requests are unaffected: both complete with results
    vt[0] = 1.0
    assert eng.poll() == 2
    assert t1.done() and t2.done()


def test_default_class_never_sheds(registry, two_matrices):
    A, _ = two_matrices
    registry.admit(A, "a")
    vt = [0.0]
    eng = ServingEngine(registry, clock=lambda: vt[0], queue_limit=10**6)
    for x in _xs(A.shape[1], 40):
        eng.submit("a", x)  # far past any default: must not raise
    assert eng.batcher.pending("a") == 40


def test_shed_triggers_flight_dump(registry, two_matrices, tmp_path):
    from repro.obs.flight import FlightRecorder

    A, _ = two_matrices
    registry.admit(A, "a")
    flight = FlightRecorder(dump_dir=tmp_path / "dumps")
    eng = ServingEngine(
        registry,
        flight=flight,
        qos={"a": QoSClass("capped", deadline_s=0.05, max_queue=1)},
    )
    eng.submit("a", _xs(A.shape[1], 1)[0])
    with pytest.raises(BackpressureError):
        eng.submit("a", _xs(A.shape[1], 1)[0])
    dumps = list((tmp_path / "dumps").glob("flight_load_shed_*.json"))
    assert len(dumps) == 1
    eng.flush()


# --- weighted-fair scheduling ---------------------------------------------


def test_scheduler_orders_by_virtual_work():
    sched = WeightedFairScheduler({"a": 4.0, "b": 1.0}.__getitem__)
    assert sched.vwork("a") == sched.vwork("b") == 0.0  # both join at zero
    # equal columns served: the weight-1 tenant accumulates 4x the vwork
    sched.charge("a", 8)
    sched.charge("b", 8)
    assert sched.vwork("a") == 2.0 and sched.vwork("b") == 8.0
    assert sched.order(["b", "a"]) == ["a", "b"]


def test_scheduler_status_boost_and_tiebreaks():
    sched = WeightedFairScheduler(lambda k: 1.0)
    sched.charge("a", 1)  # a has MORE vwork than b
    # a paging tenant flushes first regardless of accumulated vwork
    assert sched.order(["a", "b"], status={"a": "page"}) == ["a", "b"]
    # equal vwork: longer head-of-line wait wins
    sched2 = WeightedFairScheduler(lambda k: 1.0)
    waits = {"x": 0.001, "y": 0.005}
    assert sched2.order(["x", "y"], head_wait=waits.__getitem__) == ["y", "x"]


def test_scheduler_late_joiner_gets_no_retroactive_credit():
    sched = WeightedFairScheduler(lambda k: 1.0)
    sched.charge("a", 100)  # a: 0 -> 100
    sched.charge("b", 50)  # b joins at the live min (100) -> 150
    # "c" joins at the live minimum (100), not zero — a late joiner cannot
    # starve incumbents by replaying history it never participated in
    assert sched.vwork("b") == 150.0
    assert sched.vwork("c") == 100.0


def test_weighted_fair_preserves_per_tenant_fifo(registry, two_matrices):
    A, B = two_matrices
    registry.admit(A, "a")
    registry.admit(B, "b")
    vt = [0.0]
    eng = ServingEngine(
        registry,
        max_wait_s=0.001,
        clock=lambda: vt[0],
        qos={
            "a": QoSClass("gold", deadline_s=0.1, weight=4.0),
            "b": QoSClass("be", deadline_s=0.1, weight=0.25),
        },
    )
    tickets = {"a": [], "b": []}
    for i in range(6):
        vt[0] = i * 1e-5
        tickets["a"].append(eng.submit("a", _xs(A.shape[1], 1, seed=i)[0]))
        tickets["b"].append(eng.submit("b", _xs(B.shape[1], 1, seed=100 + i)[0]))
    vt[0] = 1.0
    eng.poll()
    for key in ("a", "b"):
        done = [t.context.t_complete for t in tickets[key]]
        assert all(t is not None for t in done)
        ids = [t.req_id for t in tickets[key]]
        # completion order within a tenant follows submission order
        assert ids == sorted(ids)
        assert done == sorted(done)


# --- LRU eviction policy (pure) -------------------------------------------


def test_lru_evicts_oldest_first():
    ev = LRUEvictor(100)
    assert ev.admit("a", 40) == []
    assert ev.admit("b", 40) == []
    assert ev.admit("c", 40) == ["a"]  # over budget: LRU goes
    ev.touch("b")  # b is now most recent
    assert ev.admit("d", 40) == ["c"]
    assert ev.resident() == ["b", "d"]


def test_lru_pair_evicted_as_unit():
    ev = LRUEvictor(100)
    ev.admit("f", 30)
    ev.admit("f::T", 30)
    ev.link("f", "f::T")
    assert set(ev.admit("g", 60)) == {"f", "f::T"}
    assert ev.resident() == ["g"]


def test_lru_single_oversized_unit_overshoots():
    ev = LRUEvictor(10)
    assert ev.admit("huge", 50) == []  # nothing else to evict: stays
    assert ev.over_budget() == 40
    assert ev.admit("next", 5) == ["huge"]


# --- registry eviction integration ----------------------------------------


def test_budget_eviction_restages_bitwise_equal(tmp_path, two_matrices):
    A, B = two_matrices
    probe = MatrixRegistry(cache_dir=tmp_path / "cache", search=False)
    nbytes = plan_device_bytes(probe.admit(A, "probe").tiles)
    reg = MatrixRegistry(
        cache_dir=tmp_path / "cache", search=False, hbm_budget_bytes=int(nbytes * 1.5)
    )
    plan_a = reg.admit(A, "a")
    x = _xs(A.shape[1], 1)[0]
    y_before = np.asarray(plan_a.matvec(x))
    reg.admit(B, "b")  # overflows the budget: "a" is unstaged
    assert reg._plans["a"].device is None
    assert reg.metrics.value("evict.unstaged", matrix="a") == 1
    # get() transparently re-stages; no re-preprocessing, same tiles
    plan_again = reg.get("a")
    assert plan_again is plan_a and plan_a.device is not None
    assert reg.metrics.value("evict.restages", matrix="a") == 1
    np.testing.assert_array_equal(np.asarray(plan_again.matvec(x)), y_before)


def test_budget_eviction_is_transparent_to_engine(tmp_path, two_matrices):
    A, B = two_matrices
    probe = MatrixRegistry(cache_dir=tmp_path / "cache", search=False)
    nbytes = plan_device_bytes(probe.admit(A, "probe").tiles)
    reg = MatrixRegistry(
        cache_dir=tmp_path / "cache", search=False, hbm_budget_bytes=int(nbytes * 1.5)
    )
    reg.admit(A, "a")
    reg.admit(B, "b")  # "a" unstaged before any traffic
    vt = [0.0]
    eng = ServingEngine(reg, clock=lambda: vt[0])
    t = eng.submit("a", _xs(A.shape[1], 1)[0])  # re-staged at the launch
    y = t.result()
    assert y.shape == (A.shape[0],)
    assert reg.metrics.value("evict.restages", matrix="a") == 1


def test_full_evict_readmits_same_hash_via_disk_cache(tmp_path, two_matrices):
    A, _ = two_matrices
    reg = MatrixRegistry(cache_dir=tmp_path / "cache", search=True)
    plan1 = reg.admit(A, "a")
    h1, cfg1 = plan1.matrix_hash, plan1.cfg
    assert plan1.autotune_searched  # cold cache: the search ran
    x = _xs(A.shape[1], 1)[0]
    y1 = np.asarray(plan1.matvec(x))
    reg.evict("a")
    assert "a" not in reg
    plan2 = reg.admit(A, "a")  # same content: same hash, cached geometry
    assert plan2.matrix_hash == h1 == matrix_hash(A)
    assert plan2.cfg == cfg1
    assert plan2.autotune_cache_hit and not plan2.autotune_searched
    np.testing.assert_array_equal(np.asarray(plan2.matvec(x)), y1)


def test_pair_restaged_as_unit(tmp_path):
    A = circuit(90, seed=5)
    C = banded_fem(120, seed=7, band=5, fill=0.9)
    probe = MatrixRegistry(cache_dir=tmp_path / "cache", search=False)
    pp = probe.admit_pair(A, "p")
    pair_bytes = plan_device_bytes(pp.tiles) + plan_device_bytes(
        probe.transpose_of(pp).tiles
    )
    reg = MatrixRegistry(
        cache_dir=tmp_path / "cache",
        search=False,
        hbm_budget_bytes=int(pair_bytes * 1.2),
    )
    plan = reg.admit_pair(A, "p")
    plan_T = reg.transpose_of(plan)
    reg.admit(C, "c")  # evicts the pair as one unit
    assert plan.device is None and plan_T.device is None
    got = reg.get("p")  # restages BOTH sides together
    assert got.device is not None
    assert reg.transpose_of(got).device is not None


# --- overlap dispatch ------------------------------------------------------


def test_overlap_results_bitwise_equal_to_sync(registry, two_matrices):
    A, B = two_matrices
    registry.admit(A, "a")
    registry.admit(B, "b")
    vt = [0.0]
    eng_sync = ServingEngine(registry, max_wait_s=0.001, clock=lambda: vt[0])
    eng_over = ServingEngine(
        registry, max_wait_s=0.001, clock=lambda: vt[0], overlap=True
    )
    xs_a = _xs(A.shape[1], 5, seed=1)
    xs_b = _xs(B.shape[1], 5, seed=2)
    ts, to = [], []
    for xa, xb in zip(xs_a, xs_b):
        ts += [eng_sync.submit("a", xa), eng_sync.submit("b", xb)]
        to += [eng_over.submit("a", xa), eng_over.submit("b", xb)]
    vt[0] = 1.0
    eng_sync.poll()
    eng_over.poll()
    for t_s, t_o in zip(ts, to):
        np.testing.assert_array_equal(t_s.result(), t_o.result())
    assert eng_over.inflight() == 0  # everything harvested after result()


def test_overlap_ticket_result_is_the_blocking_edge(registry, two_matrices):
    A, _ = two_matrices
    registry.admit(A, "a")
    vt = [0.0]
    eng = ServingEngine(registry, max_wait_s=0.001, clock=lambda: vt[0], overlap=True)
    t = eng.submit("a", _xs(A.shape[1], 1)[0])
    # nothing due yet: poll dispatches nothing, completes nothing
    assert eng.poll() == 0 and not t.done()
    y = t.result()  # drains + harvests regardless of clock
    assert t.done() and y.shape == (A.shape[0],)
    assert eng.inflight() == 0


def test_overlap_completion_accounting_matches_sync(registry, two_matrices):
    A, _ = two_matrices
    registry.admit(A, "a")
    vt = [0.0]
    eng = ServingEngine(registry, max_wait_s=0.001, clock=lambda: vt[0], overlap=True)
    n = 7
    tickets = [eng.submit("a", x) for x in _xs(A.shape[1], n)]
    vt[0] = 1.0
    served = eng.poll()
    assert served == n  # dispatched AND harvested within the poll
    s = eng.stats()["a"]
    assert s["requests"] == n and s["batches"] == 1
    assert all(t.done() for t in tickets)
    # per-request lifecycle stamps are filled exactly as in sync mode
    ctx = tickets[0].context
    assert ctx.t_dispatch is not None and ctx.t_complete is not None
    assert ctx.compute_s is not None and ctx.batch_k == n
