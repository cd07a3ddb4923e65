"""Multi-tenant serving under an HBM budget: six small R-MAT tenants, a
budget that holds three of their staged plans.

The contracts under test:

* every answer is its own tenant's product (float64 scipy, 1e-5 relative
  to the magnitude each row sums), whatever was evicted in between;
* ``submit`` never stages: residency is decided when a batch launches;
* a plan with a request queued or a batch in flight is never unstaged;
* when every other unit is pinned a re-stage goes over the budget and
  the ``evict.overshoot_bytes`` gauge says by how much;
* without a budget nothing is pinned.
"""
import gc

import numpy as np
import pytest
import scipy.sparse

from repro.core import PartitionConfig, build_tiles
from repro.core.matrices import rmat
from repro.core.tile import staged_tile_count
from repro.kernels import ops
from repro.serving import LRUEvictor, MatrixRegistry, ServingEngine, plan_device_bytes

CFG = PartitionConfig(row_block=64, col_block=128, group=8, lane=16)
TENANTS = [f"t{i}" for i in range(6)]


@pytest.fixture(autouse=True)
def _collect_engines():
    """Free each test's engines and registries (the engine's scheduler
    closes over the engine) before the next test: every live metric
    registry shows in the process-wide ``repro.obs`` snapshot."""
    yield
    gc.collect()


@pytest.fixture(scope="module")
def tenants():
    return {name: rmat(256, 2400, seed=11 + i) for i, name in enumerate(TENANTS)}


def _budget_for_three(tenants, tmp_path):
    """A budget that holds any three tenants' plans and no four."""
    probe = MatrixRegistry(cache_dir=tmp_path / "probe", search=False)
    sizes = sorted(plan_device_bytes(probe.admit(a, n, cfg=CFG).tiles)
                   for n, a in tenants.items())
    budget = sum(sizes[-3:])
    assert sum(sizes[:4]) > budget
    return budget


def _registry(tenants, tmp_path, *, budget="three"):
    if budget == "three":
        budget = _budget_for_three(tenants, tmp_path)
    reg = MatrixRegistry(cache_dir=tmp_path / "cache", search=False,
                         hbm_budget_bytes=budget)
    for name, a in tenants.items():
        reg.admit(a, name, cfg=CFG)
    return reg


def _staged(reg):
    return {n for n in reg.names() if reg.peek(n).device is not None}


def _rel_err(a, x, y):
    """``max_i |y_i - (A x)_i| / (|A| |x|)_i`` in float64."""
    s = scipy.sparse.csr_matrix((a.data.astype(np.float64), a.indices, a.indptr),
                                shape=a.shape)
    x64 = x.astype(np.float64)
    ref, scale = s @ x64, abs(s) @ np.abs(x64)
    return float(np.max(np.abs(y - ref) / np.maximum(scale, 1e-30)))


def _traffic(count, seed=0):
    """``count`` requests over the tenants, the first most popular."""
    rng = np.random.default_rng(seed)
    p = 1.0 / np.arange(1, len(TENANTS) + 1)
    keys = rng.choice(TENANTS, size=count, p=p / p.sum())
    return [(k, rng.standard_normal(256).astype(np.float32)) for k in keys]


@pytest.mark.parametrize("overlap", [False, True])
def test_every_answer_is_its_own_tenants_product(tenants, tmp_path, overlap):
    reg = _registry(tenants, tmp_path)
    vt = [0.0]
    eng = ServingEngine(reg, max_batch=4, max_wait_s=0.01, overlap=overlap,
                        clock=lambda: vt[0])
    tickets = []
    for i, (key, x) in enumerate(_traffic(120)):
        tickets.append((key, x, eng.submit(key, x)))
        vt[0] += 0.004
        if i % 3 == 0:
            eng.poll()
    eng.flush()
    assert reg.metrics.value("evict.restages", 0, matrix="t0") >= 1
    for key, x, t in tickets:
        assert _rel_err(tenants[key], x, t.result()) <= 1e-5, key
        ctx = t.context
        assert ctx.restage_s is not None and ctx.restage_s >= 0.0
    # requests that re-staged carry the re-stage's time; hits carry 0
    restaged = [t.context.restage_s for _, _, t in tickets if t.context.restage_s > 0]
    assert 0 < len(restaged) < len(tickets)
    total = sum(reg.metrics.value("evict.restage_bytes", 0, matrix=n) for n in TENANTS)
    restages = sum(reg.metrics.value("evict.restages", 0, matrix=n) for n in TENANTS)
    assert total == sum(
        plan_device_bytes(reg.peek(n).tiles) * reg.metrics.value("evict.restages", 0, matrix=n)
        for n in TENANTS) and restages > 0
    assert reg.evictor.snapshot()["pinned"] == {}


def test_submit_never_stages(tenants, tmp_path, monkeypatch):
    reg = _registry(tenants, tmp_path)
    calls = []
    real = ops.device_tiles
    monkeypatch.setattr(ops, "device_tiles", lambda tiles: calls.append(1) or real(tiles))
    unstaged = [n for n in TENANTS if n not in _staged(reg)]
    assert len(unstaged) == 3
    eng = ServingEngine(reg, max_batch=4, overlap=True, clock=lambda: 0.0)
    tickets = [eng.submit(n, np.ones(256, np.float32)) for n in unstaged for _ in range(2)]
    assert calls == []
    assert all(reg.peek(n).device is None for n in unstaged)
    for t in tickets:
        t.result()
    assert len(calls) == 3  # one re-stage per unstaged tenant, at its launch


@pytest.mark.parametrize("overlap", [False, True])
def test_plan_with_queued_or_inflight_work_is_never_unstaged(tenants, tmp_path,
                                                             monkeypatch, overlap):
    reg = _registry(tenants, tmp_path)
    eng = ServingEngine(reg, max_batch=2, max_wait_s=0.01, overlap=overlap,
                        clock=lambda: 0.0)
    unstaged_while_busy = []
    real = reg._unstage

    def watched(name):
        busy = eng.batcher.pending(name) or any(b.key == name for b in eng._inflight)
        if busy or reg.evictor.pinned(name):
            unstaged_while_busy.append(name)
        real(name)

    monkeypatch.setattr(reg, "_unstage", watched)
    tickets = []
    for i, (key, x) in enumerate(_traffic(90, seed=1)):
        tickets.append(eng.submit(key, x))
        if i % 4 == 3:
            eng.poll(now=1.0)  # every queue is past its deadline
    eng.flush()
    assert sum(reg.metrics.value("evict.unstaged", 0, matrix=n) for n in TENANTS) > 3
    assert unstaged_while_busy == []
    assert all(t.done() for t in tickets)


def test_queued_request_keeps_its_plan_staged(tenants, tmp_path):
    reg = _registry(tenants, tmp_path)
    eng = ServingEngine(reg, max_batch=4, overlap=True, clock=lambda: 0.0)
    resident = sorted(_staged(reg))
    oldest = reg.evictor.resident()[0]
    held = eng.submit(oldest, np.ones(256, np.float32))  # queued, not launched
    for name in (n for n in TENANTS if n not in resident):
        eng.submit(name, np.ones(256, np.float32)).result()
        assert reg.peek(oldest).device is not None
    held.result()
    # harvested: the pin is gone and the plan may be unstaged again
    assert not reg.evictor.pinned(oldest)


@pytest.mark.parametrize("overlap", [False, True])
def test_overshoot_gauge_when_every_other_unit_is_pinned(tenants, tmp_path, overlap):
    reg = _registry(tenants, tmp_path)
    eng = ServingEngine(reg, max_batch=4, overlap=overlap, clock=lambda: 0.0)
    tickets = [eng.submit(n, np.ones(256, np.float32)) for n in TENANTS]
    eng.flush()  # each launch finds every other tenant pinned
    assert reg.metrics.value("evict.overshoot_bytes") > 0
    assert all(t.done() for t in tickets)
    assert len(_staged(reg)) > 3  # over the budget rather than waiting
    assert reg.evictor.over_budget() == reg.metrics.value("evict.overshoot_bytes")
    assert reg.evictor.snapshot()["pinned"] == {}


def test_no_budget_takes_no_pin_path(tenants, tmp_path, monkeypatch):
    reg = _registry(tenants, tmp_path, budget=None)

    def refuse(*a, **k):
        raise AssertionError("pinned without a budget")

    monkeypatch.setattr(reg, "pin", refuse)
    monkeypatch.setattr(reg, "unpin", refuse)
    for overlap in (False, True):
        eng = ServingEngine(reg, max_batch=4, overlap=overlap, clock=lambda: 0.0)
        tickets = [(k, x, eng.submit(k, x)) for k, x in _traffic(20, seed=2)]
        for key, x, t in tickets:
            assert _rel_err(tenants[key], x, t.result()) <= 1e-5
            assert t.context.restage_s == 0.0
    assert _staged(reg) == set(TENANTS)


def test_lru_never_picks_a_pinned_victim():
    ev = LRUEvictor(100)
    ev.admit("a", 40)
    ev.admit("b", 40)
    ev.pin("a")
    assert ev.admit("c", 40) == ["b"]  # "a" is older but pinned
    ev.pin("c", 2)
    assert ev.admit("d", 40) == []  # everything else pinned: overshoot
    assert ev.over_budget() == 20
    ev.unpin("a")
    ev.unpin("c", 2)
    assert ev.admit("e", 10) == ["a"]  # back under the budget
    assert ev.over_budget() == 0
    with pytest.raises(ValueError):
        ev.unpin("a")


def test_same_sized_tenants_share_staged_shapes(tmp_path):
    """Plans of about the same size are built with the same tile count (so
    they share compiled kernels), at most 1/64 over their own, and the
    budget charges what is staged."""
    counts = [102_401 + d for d in (0, 7, 311, 1023)]  # one bucket of 1024
    assert len({staged_tile_count(t) for t in counts}) == 1
    for t in (1, 127, 128, 129, 1000, 103_372, 2**20 + 1):
        assert t <= staged_tile_count(t) <= t * (1 + 1 / 64)
    assert staged_tile_count(0) == 0
    a = rmat(2048, 40_000, seed=3)
    plain = build_tiles(a, CFG)
    tiles = build_tiles(a, CFG, bucket_tiles=True)
    assert tiles.n_tiles == staged_tile_count(plain.n_tiles) > plain.n_tiles
    # the registry builds its plans bucketed
    reg = MatrixRegistry(cache_dir=tmp_path, search=False)
    assert reg.admit(a, "a", cfg=CFG).tiles.n_tiles == tiles.n_tiles
    dt = ops.device_tiles(tiles)
    assert plan_device_bytes(tiles) == sum(a.nbytes for a in dt)
