"""Open-loop serving: single-vector requests through ``ServingEngine``.

Independent users each send one ``y = A @ x``; requests are due on a
wall-clock schedule (``sampling.arrivals``) whether or not earlier ones
have finished.  The path is the program's main one: ``MatrixRegistry.admit``
with the pinned geometry, ``ServingEngine.submit`` → coalesced bucketed
SpMM → harvest on ``poll``.  One host thread generates and serves: it
submits every request that is due, polls the engine, and sleeps until the
next due time, at most ``poll_s``.

Every time is the benchmark's own clock (``time.perf_counter``).  A request
completes when its ``Ticket.result()`` has returned: after each ``poll``
that harvested something, the driver takes the result of every ticket that
has become done and stamps it.  Latency is that stamp minus the request's
due time, so a stall counts against every request queued behind it; a
request that fails or never completes counts as missing every limit.  Only
``queue_wait_p95_ms`` reads the engine's own stamps (its dispatch time).
"""
from __future__ import annotations

import time

import numpy as np

from chipbench import reference, sampling
from chipbench.harness import Run, admit
from chipbench.matrices import values_rng
from chipbench.work import csr_work

# a request not answered this long after the last one was due has failed
DRAIN_S = 60.0
# the control's answer in the served vectors' place: bfloat16 values and inputs
CONTROL = reference.product_bf16


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
    admit(run)
    t = run.traffic
    n_cols = run.csr.shape[1]
    schedule(run, seconds)
    # warm the engine's own path at every batch width it can coalesce to
    engine = _engine(run)
    x = values_rng(run.seed, 3).standard_normal((t["engine"]["max_batch"], n_cols),
                                                dtype=np.float32)
    with run.span("warmup"):
        for k in range(1, t["engine"]["max_batch"] + 1):
            tickets = [engine.submit(run.config["name"], x[j]) for j in range(k)]
            engine.flush(run.config["name"])
            for tk in tickets:
                tk.result()


def schedule(run: Run, seconds: float) -> None:
    """The window's due times and request vectors, made before it opens."""
    due = sampling.arrivals(run.traffic["arrivals"], seconds, values_rng(run.seed, 1))
    run.state["due"] = due
    run.state["xs"] = values_rng(run.seed, 2).standard_normal(
        (due.size, run.csr.shape[1]), dtype=np.float32)


def window(run: Run, seconds: float) -> dict:
    name = run.config["name"]
    due, xs = run.state["due"], run.state["xs"]
    n = due.size
    engine = _engine(run)
    metrics = engine.metrics
    batches0 = metrics.value("serving.batches", 0, matrix=name)
    columns0 = metrics.value("serving.columns", 0, matrix=name)
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
                    tickets[i] = engine.submit(name, xs[i])
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
    batches = metrics.value("serving.batches", 0, matrix=name) - batches0
    columns = metrics.value("serving.columns", 0, matrix=name) - columns0
    nnz, shape = run.csr.nnz, run.csr.shape
    flops, nbytes = csr_work(nnz, shape[0], shape[1], 1)
    run.state.update(tickets=tickets, done=done)
    return {
        "latency_s": latency,
        "latency_p95_ms": float(np.percentile(latency, 95)) * 1e3,
        "attempted": n,
        "failed": int(n - done.sum()),
        "queue_wait_p95_ms": float(np.percentile(t_dispatch - due, 95)) * 1e3,
        "gen_lag_p95_ms": float(np.percentile(submitted - due_at, 95)) * 1e3,
        "batch_k_mean": columns / batches if batches else None,
        "work_flops": flops * done.sum(),
        "work_bytes": nbytes * done.sum(),
    }


def _sample(run: Run) -> np.ndarray:
    """Indices of the answered requests the check compares, drawn by the seed."""
    done = np.flatnonzero(run.state["done"])
    size = min(run.traffic["check"]["sample"], done.size)
    return np.sort(values_rng(run.seed, 4).choice(done, size=size, replace=False))


def check(run: Run, answer=None) -> list:
    """``(name, value, limit)`` of the sampled requests; ``answer(csr, x)``
    in place of the served vectors where given (the control)."""
    idx = _sample(run)
    lim = run.traffic["check"]
    if idx.size == 0:
        return [("max_rel_err", float("inf"), lim["max_rel_err"])]
    x = run.state["xs"][idx].T
    if answer is None:
        y = np.stack([run.state["tickets"][j].result() for j in idx], axis=1)
    else:
        y = answer(run.csr, x)
    y_ref, scale = reference.product(run.csr, x)
    return [("max_rel_err", reference.rel_err(y, y_ref, scale), lim["max_rel_err"]),
            ("unanswered", float(run.window["failed"]), 0.0)]
