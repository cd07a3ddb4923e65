"""Observability overhead: what does instrumentation cost when it's on/off?

The obs layer promises "off by default, free when off".  This bench holds
it to that with three measurements:

* ``obs/serve_disabled`` vs ``obs/serve_enabled`` — the serving hot loop
  (submit → poll → flush over an admitted suite matrix) timed with the
  gated instrumentation compiled out vs fully live (spans + counters +
  gauges + histograms).  ``overhead`` in the derived column is the
  enabled/disabled median ratio; the CI smoke gate runs the *disabled*
  configuration against ``baseline.json``, so any cost on the default
  path fails the existing regression pipeline.
* ``obs/counter`` / ``obs/span`` — per-op microcosts of one labelled
  counter increment and one empty span, enabled and disabled, so a
  regression in the primitives is visible before it shows up in the
  engine numbers.
* ``obs/flight_record`` — per-op cost of one always-on flight-recorder
  ring write: unlike the gated primitives this path has no off state, so
  its microcost IS the serving hot loop's telemetry floor.
* ``obs/request_context`` — per-op cost of the always-on request-trace
  path (mint a context, stamp its lifecycle, complete it into the
  bounded log): the per-request tax every submit pays, gated by the same
  regression pipeline as the flight ring.
* ``obs/openmetrics_render`` — one full OpenMetrics exposition render of
  a populated registry: the per-scrape cost a Prometheus endpoint pays
  (off the serving hot path, but a runaway here would starve a scraper).
* ``obs/plan_quality`` — per-admission cost of the partition-quality
  introspection (:func:`repro.obs.planview.partition_quality`: occupancy
  stats, LPT competitive-ratio replay, hash-group cohesion vs a random
  baseline).  Unlike the ratio-gated benches this one carries a pinned
  absolute budget: exceeding it raises, failing the whole bench run.

All timings restore the obs enable state they found, and the registries
are reset afterwards so a ``--trace`` run's artifact is not polluted by
benchmark-loop spans.

Set ``REPRO_OBS_DUMP=PATH`` to write the full obs snapshot (including the
serving engines' request log) before the benchmark's registries go out of
scope — the input ``python -m repro.analysis.report --obs PATH`` (or
``--requests PATH``) renders.
"""
from __future__ import annotations

import os

import numpy as np

from repro import obs
from repro.serving import MatrixRegistry, ServingEngine

from .common import emit, load_suite, timeit

_MICRO_OPS = 10_000

# per-admission ceiling for the partition-quality introspection bench:
# far above the measured cost (single-digit ms on the smoke suite) but low
# enough that an accidental Python-loop rewrite of the metrics trips it
_PLAN_QUALITY_BUDGET_MS = 250.0


def _serve_cycle(engine: ServingEngine, key: str, xs, vclock) -> None:
    """One hot-loop pass: every request submitted, coalesced, flushed."""
    for i, x in enumerate(xs):
        vclock[0] = 1e-5 * i
        engine.submit(key, x)
        engine.poll()
    vclock[0] = 1e-5 * len(xs) + engine.batcher.max_wait_s
    engine.poll()
    engine.flush()


def _time_serving(csr, name: str, n_req: int, repeats: int, keep: list):
    reg = MatrixRegistry(search=False, cache_dir=".hbp_autotune")
    plan = reg.admit(csr, name)
    rng = np.random.default_rng(2)
    xs = [rng.standard_normal(csr.n_cols).astype(np.float32) for _ in range(n_req)]
    # warm per-bucket compiles outside the timed region
    for k in (1, 2, 4, 8, 16):
        plan.matmat(np.zeros((csr.n_cols, k), np.float32)).block_until_ready()
    vclock = [0.0]
    eng = ServingEngine(reg, max_wait_s=0.002, clock=lambda: vclock[0])
    # metric registries are weakly aggregated — keep the MatrixRegistry
    # alive so a REPRO_OBS_DUMP snapshot still sees its serving counters
    keep.append(reg)
    return timeit(lambda: _serve_cycle(eng, name, xs, vclock), repeats=repeats)


def _with_obs(flag: bool, fn):
    was = obs.enabled()
    (obs.enable if flag else obs.disable)()
    try:
        return fn()
    finally:
        (obs.enable if was else obs.disable)()


def _micro_counter() -> None:
    c = obs.counter("bench.obs_micro", site="counter")
    for _ in range(_MICRO_OPS):
        c.inc()


def _micro_span() -> None:
    for _ in range(_MICRO_OPS):
        with obs.span("bench.obs_micro_span"):
            pass


def _micro_flight() -> None:
    fl = obs.flight()
    for _ in range(_MICRO_OPS):
        fl.record("bench.flight_micro")


def _micro_request_context() -> None:
    from repro.obs.requesttrace import RequestLog, new_context

    log = RequestLog()
    for i in range(_MICRO_OPS):
        ctx = new_context("bench", 0.0)  # the one per-request allocation
        ctx.t_enqueue = 1e-6
        ctx.t_flush_start = 2e-6
        ctx.t_dispatch = 3e-6
        ctx.t_complete = 4e-6
        ctx.compute_s = 1e-6
        ctx.batch_share = 0.125
        ctx.batch_k = 8
        ctx.flush_reason = "size"
        ctx.deadline_hit = True
        log.complete(ctx)


def _render_registry():
    """A populated standalone registry sized like a busy serving ledger."""
    from repro.obs.metrics import MetricRegistry

    reg = MetricRegistry(name="bench-render")
    rng = np.random.default_rng(3)
    for m in ("A", "B", "C", "D"):
        reg.counter("serving.requests", matrix=m).inc(1000)
        reg.gauge("serving.queue_depth", matrix=m).set(4)
        h = reg.histogram("serving.latency_s", matrix=m)
        for i, v in enumerate(rng.uniform(1e-5, 1e-2, 256)):
            h.observe(float(v), exemplar=f"rb-{i:x}")
    return reg


def main(full: bool = False) -> None:
    n_req = 256 if full else 64
    repeats = 7 if full else 5
    name, csr = next(iter(load_suite(False).items()))  # smallest suite matrix

    keep: list = []
    t_off = _with_obs(False, lambda: _time_serving(csr, name, n_req, repeats, keep))
    t_on = _with_obs(True, lambda: _time_serving(csr, name, n_req, repeats, keep))
    overhead = t_on.stats["median_us"] / t_off.stats["median_us"]
    emit(
        f"obs/serve_disabled/{name}",
        t_off,
        f"req_per_s={n_req / float(t_off):.1f}",
        config={"n_req": n_req},
    )
    emit(
        f"obs/serve_enabled/{name}",
        t_on,
        f"req_per_s={n_req / float(t_on):.1f} overhead={overhead:.3f}x",
        config={"n_req": n_req},
    )

    for site, fn in (("counter", _micro_counter), ("span", _micro_span)):
        for flag in (False, True):
            t = _with_obs(flag, lambda: timeit(fn, repeats=repeats))
            state = "enabled" if flag else "disabled"
            emit(
                f"obs/{site}_{state}",
                float(t) / _MICRO_OPS,
                f"ns_per_op={1e9 * float(t) / _MICRO_OPS:.0f}",
                config={"ops": _MICRO_OPS},
            )

    # the flight recorder has no disabled state — one bench, always on
    t = timeit(_micro_flight, repeats=repeats)
    emit(
        "obs/flight_record",
        float(t) / _MICRO_OPS,
        f"ns_per_op={1e9 * float(t) / _MICRO_OPS:.0f}",
        config={"ops": _MICRO_OPS},
    )

    # the request-trace path is always on too: mint + stamp + complete
    t = timeit(_micro_request_context, repeats=repeats)
    emit(
        "obs/request_context",
        float(t) / _MICRO_OPS,
        f"ns_per_op={1e9 * float(t) / _MICRO_OPS:.0f}",
        config={"ops": _MICRO_OPS},
    )

    # per-scrape cost of the OpenMetrics exposition render
    from repro.obs.export import render_openmetrics

    render_reg = _render_registry()
    t = timeit(lambda: render_openmetrics([render_reg]), repeats=repeats)
    emit(
        "obs/openmetrics_render",
        t,
        f"us_per_scrape={t.stats['median_us']:.0f}",
        config={"matrices": 4, "hist_samples": 256},
    )
    del render_reg

    # admission-time introspection: partition_quality (occupancy stats +
    # LPT competitive-ratio replay + hash-group cohesion vs the random
    # baseline) runs once per admit, so its cost IS the explain feature's
    # overhead.  Pinned to a generous absolute budget: blowing it means
    # the introspection stopped being vectorised, and admission latency
    # regressed for every caller — fail the bench run outright.
    from repro.obs.planview import partition_quality

    plan = keep[0].get(name)
    t = timeit(lambda: partition_quality(plan.tiles, csr), repeats=repeats)
    emit(
        f"obs/plan_quality/{name}",
        t,
        f"ms_per_admission={1e3 * float(t):.2f} tiles={plan.tiles.n_tiles}",
        config={"tiles": plan.tiles.n_tiles, "budget_ms": _PLAN_QUALITY_BUDGET_MS},
    )
    if t.stats["median_us"] > _PLAN_QUALITY_BUDGET_MS * 1e3:
        raise RuntimeError(
            f"partition_quality took {t.stats['median_us'] / 1e3:.1f}ms per "
            f"admission on {name} — over the {_PLAN_QUALITY_BUDGET_MS:.0f}ms "
            "budget; the admission-introspection path must stay vectorised"
        )

    # snapshot before the registries in `keep` go out of scope (their
    # MetricRegistry instances are weakly aggregated into the dump)
    dump_path = os.environ.get("REPRO_OBS_DUMP")
    if dump_path:
        obs.dump(dump_path)
        print(f"# obs snapshot -> {dump_path}")
    del keep

    # don't leak benchmark-loop metrics/spans into a --trace artifact
    if not obs.enabled():
        obs.reset()


if __name__ == "__main__":
    main()
