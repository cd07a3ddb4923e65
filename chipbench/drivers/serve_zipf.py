"""Open-loop serving of many tenants' matrices on one chip, under an HBM budget.

A graph service holds more tenants' matrices than its device memory
stages at once.  Each tenant is the configuration's generator with a
pattern seed of its own (``tenants``); all are admitted through one
``MatrixRegistry(search=False, hbm_budget_bytes=...)`` under the pinned
geometry, so the least recently used plans are unstaged and re-staged
when a request needs them again.

Independent users each send one ``y = A_t @ x`` to one tenant ``t``.
Requests are due on a wall-clock schedule (``sampling.arrivals``), and the
tenant of each follows Zipf's law with exponent ``s`` over the ranks of
the traffic's ``popularity.ranks`` (rank ``r``, from 0, is tenant
``ranks[r]``): each tenant gets its rounded share of the window's requests
(largest remainders, so the counts add up) in a shuffled order.  The due
times and the tenant order are one trace, drawn from the traffic's
``schedule_seed`` and replayed by every run, so that a run's tail latency
does not turn on where its own draw put the bursts of arrivals and
misses; the run's seed draws the values and the request vectors.  One
host thread generates and serves, as ``serve.py`` does, and latency is
measured the same way: the benchmark's clock once ``Ticket.result()`` has
returned, minus the due time.

The check compares a sample of answered requests, drawn by the seed across
tenants, each with the float64 product of its own tenant's matrix.  It
also holds the registry to its guarantee that a re-stage reuses the host
tiles: no plan may be built again during the window (``readmitted``).
"""
from __future__ import annotations

import time
from types import SimpleNamespace

import numpy as np

from chipbench import matrices, reference, sampling
from chipbench.harness import Run, partition_config
from chipbench.matrices import values_rng
from chipbench.work import csr_work

# a request not answered this long after the last one was due has failed
DRAIN_S = 60.0
# the control's answer in the served vectors' place: bfloat16 values and inputs
CONTROL = reference.product_bf16


def names(run: Run) -> list:
    """The tenants' plan names, in the configuration's order."""
    return [f"{run.config['name']}.t{i}" for i in range(len(run.config["tenants"]))]


def zipf_counts(n: int, tenants: int, s: float) -> np.ndarray:
    """Requests per popularity rank: ``n`` split in proportion to
    ``(r + 1) ** -s``, rounded by largest remainders."""
    share = np.arange(1, tenants + 1, dtype=np.float64) ** -s
    exact = n * share / share.sum()
    counts = np.floor(exact).astype(np.int64)
    extra = np.argsort(-(exact - counts), kind="stable")[: n - counts.sum()]
    counts[extra] += 1
    return counts


def _engine(run: Run):
    from repro.obs.flight import FlightRecorder
    from repro.serving import ServingEngine

    p = run.traffic["engine"]
    # max_dumps=0: the flight ring keeps recording, but no post-mortem file
    # is written into the checkout on a deadline miss
    return ServingEngine(run.state["registry"], max_batch=p["max_batch"],
                         max_wait_s=p["max_wait_s"], overlap=p["overlap"],
                         flight=FlightRecorder(max_dumps=0))


def setup(run: Run, seconds: float) -> None:
    from repro.core.formats import CSRMatrix
    from repro.serving import MatrixRegistry

    if not hasattr(MatrixRegistry, "pin"):
        # a registry that cannot hold a plan with work on the device staged
        # unstages it anyway: its HBM then passes the budget, which this
        # deployment states, so the program cannot serve it
        raise RuntimeError("this program's MatrixRegistry does not pin plans "
                           "with queued or launched work, so it cannot keep "
                           "multi-tenant serving within an HBM budget")
    cfg = run.config
    if cfg["tenants"][0] != cfg["generator"]["pattern_seed"]:
        raise ValueError("tenant 0 is the configuration's own pattern_seed")
    ranks = run.traffic["popularity"]["ranks"]
    if sorted(ranks) != list(range(len(cfg["tenants"]))):
        raise ValueError(f"popularity ranks {ranks} are not a tenant permutation")
    registry = MatrixRegistry(search=False, hbm_budget_bytes=cfg["hbm_budget_bytes"])
    geometry = partition_config(run)
    csrs = []
    for name, seed in zip(names(run), cfg["tenants"]):
        if csrs:  # tenant 0 is the harness's run.csr
            tenant = dict(cfg, generator=dict(cfg["generator"], pattern_seed=seed))
            with run.span("make_matrix"):
                c = matrices.make(tenant, run.seed)
        else:
            c = run.csr
        csrs.append(c)
        t0 = time.perf_counter()
        with run.span("admit"):
            registry.admit(CSRMatrix(c.indptr, c.indices, c.data, c.shape), name,
                           cfg=geometry)
        run.admit_s += time.perf_counter() - t0
    # the tenants' plans as one, where readers look for the cell's plan:
    # their common kernel strategy and their admission phases summed
    plans = [registry.peek(n) for n in names(run)]
    run.state.update(registry=registry, csrs=csrs, plan=SimpleNamespace(
        strategy=plans[0].strategy,
        phases_s={p: sum(pl.phases_s[p] for pl in plans) for p in plans[0].phases_s}))
    schedule(run, seconds)
    # warm every tenant at every k bucket the engine launches up to max_batch
    # (the kernels compile per tile count and bucket), the least popular
    # first: the most popular plans are then the staged ones.  The padding of
    # a batch to its bucket compiles per width, alike for tenants of one
    # shape, so the last tenant also takes every width in between
    engine = _engine(run)
    max_batch = run.traffic["engine"]["max_batch"]
    buckets = [k for k in engine.buckets if k <= max_batch]
    x = values_rng(run.seed, 3).standard_normal((max_batch, run.csr.shape[1]),
                                                dtype=np.float32)
    tenant_names = names(run)
    with run.span("warmup"):
        for t in reversed(ranks):
            widths = range(1, max_batch + 1) if t == ranks[0] else buckets
            for k in widths:
                tickets = [engine.submit(tenant_names[t], x[j]) for j in range(k)]
                engine.flush(tenant_names[t])
                for tk in tickets:
                    tk.result()


def schedule(run: Run, seconds: float) -> None:
    """The window's due times, tenants and request vectors, made before it
    opens.  The due times and the tenants are the traffic's own trace,
    drawn from its ``schedule_seed``: every run replays it, and the run's
    seed draws the vectors."""
    trace_seed = run.traffic["schedule_seed"]
    due = sampling.arrivals(run.traffic["arrivals"], seconds, values_rng(trace_seed, 1))
    pop = run.traffic["popularity"]
    if pop["kind"] != "zipf":
        raise ValueError(f"unknown popularity kind {pop['kind']!r}")
    counts = zipf_counts(due.size, len(pop["ranks"]), pop["s"])
    tenant = np.repeat(np.asarray(pop["ranks"], np.int64), counts)
    values_rng(trace_seed, 5).shuffle(tenant)
    run.state["due"] = due
    run.state["tenant"] = tenant
    run.state["xs"] = values_rng(run.seed, 2).standard_normal(
        (due.size, run.csr.shape[1]), dtype=np.float32)


def _summed(metrics, counter: str, tenant_names) -> float:
    return sum(metrics.value(counter, 0, matrix=n) for n in tenant_names)


def window(run: Run, seconds: float) -> dict:
    due, xs, tenant = run.state["due"], run.state["xs"], run.state["tenant"]
    tenant_names = names(run)
    n = due.size
    engine = _engine(run)
    metrics = engine.metrics
    counters = ("serving.batches", "serving.columns", "registry.misses")
    before = {c: _summed(metrics, c, tenant_names) for c in counters}
    poll_s = run.traffic["poll_s"]

    tickets = [None] * n
    submitted = np.full(n, np.nan)
    completed = np.full(n, np.nan)
    failed = np.zeros(n, bool)
    outstanding = []  # submitted, not yet completed
    answered = i = 0
    clock = time.perf_counter
    t0 = clock()
    t0_engine = engine.clock()  # the engine's dispatch stamps are on its clock
    due_at = t0 + due
    while True:
        now = clock()
        while i < n and due_at[i] <= now:
            with run.span("submit"):
                try:
                    tickets[i] = engine.submit(tenant_names[tenant[i]], xs[i])
                    outstanding.append(i)
                except Exception:  # a refused request misses every limit
                    failed[i] = True
            submitted[i] = clock()
            i += 1
        with run.span("poll"):
            harvested = engine.poll()
        if harvested:
            with run.span("complete"):
                still = []
                for j in outstanding:
                    if tickets[j].done():
                        tickets[j].result()
                        completed[j] = clock()
                        answered += 1
                    else:
                        still.append(j)
                outstanding = still
        if i == n and answered + failed.sum() >= n:
            break
        now = clock()
        if i == n and now > due_at[-1] + DRAIN_S:
            break
        wait = min(due_at[i] - now, poll_s) if i < n else poll_s
        if wait > 0:
            with run.span("sleep"):
                time.sleep(wait)

    # a request never answered counts as answered when the run gave up on it
    done = np.isfinite(completed)
    gave_up = clock()
    completed[~done] = gave_up
    latency = completed - due_at
    t_dispatch = np.array([tk.context.t_dispatch - t0_engine if d else gave_up - t0
                           for tk, d in zip(tickets, done)])
    delta = {c: _summed(metrics, c, tenant_names) - before[c] for c in counters}
    work = np.array([csr_work(c.nnz, c.shape[0], c.shape[1], 1) for c in run.state["csrs"]])
    served = np.bincount(tenant[done], minlength=len(tenant_names))
    run.state.update(tickets=tickets, done=done)
    return {
        "latency_s": latency,
        "latency_p95_ms": float(np.percentile(latency, 95)) * 1e3,
        "attempted": n,
        "failed": int(n - done.sum()),
        "queue_wait_p95_ms": float(np.percentile(t_dispatch - due, 95)) * 1e3,
        "gen_lag_p95_ms": float(np.percentile(submitted - due_at, 95)) * 1e3,
        "batch_k_mean": (delta["serving.columns"] / delta["serving.batches"]
                         if delta["serving.batches"] else None),
        "work_flops": float(served @ work[:, 0]),
        "work_bytes": float(served @ work[:, 1]),
        "readmitted": delta["registry.misses"],
    }


def _sample(run: Run) -> np.ndarray:
    """Indices of the answered requests the check compares, drawn by the seed."""
    done = np.flatnonzero(run.state["done"])
    size = min(run.traffic["check"]["sample"], done.size)
    return np.sort(values_rng(run.seed, 4).choice(done, size=size, replace=False))


def check(run: Run, answer=None) -> list:
    """``(name, value, limit)`` of the sampled requests, each against its own
    tenant's matrix; ``answer(csr, x)`` in place of the served vectors where
    given (the control)."""
    idx = _sample(run)
    lim = run.traffic["check"]
    err = float("inf") if idx.size == 0 else 0.0
    tenant = run.state["tenant"][idx]
    for t in np.unique(tenant):
        rows = idx[tenant == t]
        csr = run.state["csrs"][t]
        x = run.state["xs"][rows].T
        if answer is None:
            y = np.stack([run.state["tickets"][j].result() for j in rows], axis=1)
        else:
            y = answer(csr, x)
        y_ref, scale = reference.product(csr, x)
        err = max(err, reference.rel_err(y, y_ref, scale))
    return [("max_rel_err", err, lim["max_rel_err"]),
            ("unanswered", float(run.window["failed"]), 0.0),
            ("readmitted", float(run.window["readmitted"]), 0.0)]
