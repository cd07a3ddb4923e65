"""Measured partition-config search with a persistent on-disk cache.

``tuned_partition_config`` (core/tile.py) picks a lane width from the nnz
profile — a heuristic.  A serving system can afford better: the matrix is
admitted once and then multiplied thousands of times, so a few measured
SpMM launches per candidate geometry are noise against the traffic they
optimise.  :func:`autotune_partition` times every candidate from the
:func:`repro.core.partition.enumerate_configs` search space and keeps the
fastest, caching the winner on disk keyed by the matrix's content hash so
the next admission — same process or next process — skips the search
entirely.

The objective is steady-state multiply time (one ``hbp_spmm`` launch at the
traffic's typical RHS width), not build time: preprocessing amortizes away
under serving traffic, the per-request multiply does not.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import time
from pathlib import Path
from typing import Callable, Optional, Sequence

import numpy as np

from repro import obs
from repro.core.formats import CSRMatrix
from repro.core.partition import PartitionConfig, enumerate_configs
from repro.core.tile import build_tiles, tuned_partition_config

__all__ = [
    "matrix_hash",
    "AutotuneCache",
    "AutotuneResult",
    "Probe",
    "spmm_probe",
    "cg_probe",
    "autotune_partition",
    "device_kind",
    "DEFAULT_CACHE_DIR",
]

DEFAULT_CACHE_DIR = ".hbp_autotune"
_CACHE_VERSION = 1


def matrix_hash(csr: CSRMatrix) -> str:
    """Content hash of a CSR matrix: shape + structure + values.

    Two admissions of the same matrix — different objects, different
    processes — hash identically, which is what keys both the registry's
    resident-plan lookup and the on-disk autotune cache.
    """
    h = hashlib.sha256()
    h.update(np.asarray(csr.shape, np.int64).tobytes())
    h.update(np.ascontiguousarray(csr.indptr).tobytes())
    h.update(np.ascontiguousarray(csr.indices).tobytes())
    h.update(np.ascontiguousarray(csr.data, dtype=np.float64).tobytes())
    return h.hexdigest()


@dataclasses.dataclass(frozen=True)
class AutotuneResult:
    """Outcome of one :func:`autotune_partition` call."""

    cfg: PartitionConfig
    cache_hit: bool  # config came from the on-disk cache; no search ran
    searched: bool  # a measured search ran this call
    evaluations: int  # candidate geometries actually timed
    objective_us: Optional[float]  # best measured SpMM time (None: heuristic)
    # decision provenance: every candidate measured, as
    # ``{"config": {...}, "objective_us": float}`` dicts sorted fastest
    # first — persisted into the cache entry, so a cache-hit admission can
    # still explain WHY its geometry won the original search
    trials: tuple = ()


def device_kind() -> str:
    """The device the measurements run on (``jax.devices()[0].device_kind``)."""
    import jax

    return jax.devices()[0].device_kind


class AutotuneCache:
    """On-disk partition-config cache: one JSON file per matrix hash.

    The directory (default ``.hbp_autotune/``, or ``$HBP_AUTOTUNE_DIR``) is
    safe to persist across runs of the same matrix corpus.  Entries are
    keyed by matrix content AND the device kind that measured them: a
    geometry timed on one device (a CPU host, say) is a miss on another,
    whose kernels it never ranked.  Unreadable, version-mismatched or
    foreign-device entries are treated as misses, never errors.
    """

    def __init__(self, path: str | os.PathLike | None = None):
        if path is None:
            path = os.environ.get("HBP_AUTOTUNE_DIR", DEFAULT_CACHE_DIR)
        self.path = Path(path)

    def _entry(self, key: str) -> Path:
        return self.path / f"{key}.json"

    def get(self, key: str) -> Optional[dict]:
        try:
            entry = json.loads(self._entry(key).read_text())
        except (OSError, ValueError):
            return None
        if entry.get("version") != _CACHE_VERSION or "config" not in entry:
            return None
        if entry.get("device_kind") != device_kind():
            return None
        return entry

    def get_config(self, key: str) -> Optional[PartitionConfig]:
        entry = self.get(key)
        if entry is None:
            return None
        try:
            return PartitionConfig(**entry["config"])
        except TypeError:
            return None

    def put(self, key: str, cfg: PartitionConfig, **extra) -> None:
        self.path.mkdir(parents=True, exist_ok=True)
        entry = {
            "version": _CACHE_VERSION,
            "device_kind": device_kind(),
            "config": dataclasses.asdict(cfg),
            **extra,
        }
        # per-process tmp name + atomic rename: concurrent admits of the
        # same matrix each install a complete entry, last writer wins
        tmp = self._entry(key).with_suffix(f".{os.getpid()}.tmp")
        tmp.write_text(json.dumps(entry, indent=2, sort_keys=True))
        os.replace(tmp, self._entry(key))


def _space_fingerprint(
    candidates: Sequence[PartitionConfig], k: int, strategy: str, probe: "Probe"
) -> str:
    """Content key of a measured search: candidate set plus the objective
    that ranked it.  Stored with searched cache entries so a search over a
    narrow space, a different kernel path, or a different objective (e.g.
    CG time-to-tolerance vs raw SpMM time) does not satisfy later
    admissions searching a different one.

    An SpMM probe is fingerprinted by ITS OWN (k, strategy) — not the
    ``autotune_partition`` call's — so e.g. a spmm_probe(k=128) search
    never satisfies a default k=8 admission; when the probe is the
    default one built from the call's arguments the two coincide, which
    keeps the historical ``(geoms, k, strategy)`` fingerprint and existing
    caches warm."""
    geoms = sorted((c.row_block, c.col_block, c.group, c.lane) for c in candidates)
    if probe.kind == "spmm" and len(probe.params) == 2:
        key = (geoms, *probe.params)
    else:
        key = (geoms, k, strategy, probe.kind, probe.params)
    return hashlib.sha256(repr(key).encode()).hexdigest()[:16]


@dataclasses.dataclass(frozen=True)
class Probe:
    """A measured-search objective: what one candidate geometry costs.

    ``measure(csr, cfg, repeats)`` returns the objective in microseconds
    (lower is better); ``kind`` names the objective and — together with
    ``params``, the objective's own parameters — enters the cache
    fingerprint, so entries tuned under one objective never satisfy
    admissions tuning under another.
    """

    kind: str
    measure: Callable[[CSRMatrix, PartitionConfig, int], float]
    params: tuple = ()

    def __call__(self, csr: CSRMatrix, cfg: PartitionConfig, repeats: int) -> float:
        return self.measure(csr, cfg, repeats)


def spmm_probe(k: int = 8, strategy: str = "stable") -> Probe:
    """The default serving objective: one steady-state k-wide SpMM launch.

    The params tuple is ``(k, strategy)`` at every width, the fingerprint
    cache entries have always carried at ``k <= LANE_TILE``."""
    return Probe(
        kind="spmm",
        measure=lambda csr, cfg, repeats: _measure_spmm_us(csr, cfg, k, repeats, strategy),
        params=(k, strategy),
    )


def cg_probe(
    iters: int = 10, k: int = 1, strategy: str = "stable", seed: int = 0
) -> Probe:
    """Solver-objective probe: wall time of ``iters`` CG iterations.

    Ranks candidate geometries by what an iterative-solver workload
    actually pays — time to (a proxy for) tolerance rather than raw
    multiply time, folding in the per-iteration vector work and, for
    blocked RHS (``k > 1``), the SpMM amortization the solver sees.
    ``tol=0`` pins the iteration count so every candidate runs exactly
    ``iters`` steps of the same Krylov recurrence.
    """

    def measure(csr: CSRMatrix, cfg: PartitionConfig, repeats: int) -> float:
        from repro.solvers import cg
        from repro.solvers.operator import aslinearoperator

        tiles = build_tiles(csr, cfg)
        op = aslinearoperator(tiles, strategy=strategy)
        rng = np.random.default_rng(seed)
        shape = (csr.n_rows,) if k == 1 else (csr.n_rows, k)
        b = rng.standard_normal(shape).astype(np.float32)
        def jax_block(r):
            return r.x.block_until_ready()

        jax_block(cg(op, b, tol=0.0, maxiter=iters))  # compile outside the clock
        ts = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            jax_block(cg(op, b, tol=0.0, maxiter=iters))
            ts.append(time.perf_counter() - t0)
        return float(np.median(ts) * 1e6)

    return Probe(kind=f"cg{iters}x{k}_{strategy}", measure=measure)


def _measure_spmm_us(
    csr: CSRMatrix,
    cfg: PartitionConfig,
    k: int,
    repeats: int,
    strategy: str,
) -> float:
    """Median microseconds of one k-wide SpMM launch under ``cfg``.

    ``strategy`` should be the path serving will actually run (the
    registry passes its own), so the search ranks configs under the cost
    model traffic pays — the jnp paths' k-scaling differs from the fused
    kernel's, and off-TPU the kernels execute in interpret mode whose
    timings are meaningless.
    """
    from repro.kernels import ops

    tiles = build_tiles(csr, cfg)
    dt = ops.device_tiles(tiles)
    meta = dict(
        n_rowgroups=tiles.n_rowgroups,
        n_rows=tiles.shape[0],
        col_block=cfg.col_block,
        strategy=strategy,
    )
    x = np.random.default_rng(0).standard_normal((csr.n_cols, k)).astype(np.float32)
    ops.hbp_spmm(dt, x, **meta).block_until_ready()  # compile outside the clock
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        ops.hbp_spmm(dt, x, **meta).block_until_ready()
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts) * 1e6)


def autotune_partition(
    csr: CSRMatrix,
    *,
    key: Optional[str] = None,
    cache: AutotuneCache | None = None,
    search: bool = True,
    candidates: Optional[Sequence[PartitionConfig]] = None,
    k: int = 8,
    repeats: int = 3,
    strategy: str = "stable",
    probe: Optional[Probe] = None,
) -> AutotuneResult:
    """Pick a :class:`PartitionConfig` for ``csr``, cheapest source first.

    1. on-disk cache hit for the matrix's content hash → no search;
    2. ``search=True`` → time every candidate (``enumerate_configs`` by
       default) and keep the fastest;
    3. ``search=False`` → the ``tuned_partition_config`` nnz-profile
       heuristic.

    Either way the chosen config is written back to the cache, so the next
    admission of the same matrix is a pure read.  Cached entries remember
    *how* they were produced: a heuristic entry satisfies only
    ``search=False`` callers, and a searched entry satisfies ``search=True``
    callers only when it covered the same candidate space (and probe
    width) — so neither a heuristic admission nor a narrow example-sized
    search can permanently pin a matrix that a full-space admission would
    have tuned better; the mismatched admission simply re-searches and
    overwrites.

    ``probe`` swaps the search objective: the default ranks candidates by
    one steady-state ``k``-wide SpMM launch under ``strategy``
    (:func:`spmm_probe`); a solver workload can rank by time-to-tolerance
    instead (:func:`cg_probe`, ``iters`` fixed CG steps).  The probe kind
    is part of the cache fingerprint, so entries tuned under different
    objectives never satisfy each other.
    """
    cache = cache or AutotuneCache()
    key = key or matrix_hash(csr)
    if probe is None:
        probe = spmm_probe(k=k, strategy=strategy)
    if search:
        # materialize once: generators must survive both the fingerprint
        # and the measurement loop
        candidates = (
            enumerate_configs(csr.shape) if candidates is None else list(candidates)
        )
    space = _space_fingerprint(candidates, k, strategy, probe) if search else None
    entry = cache.get(key)
    if entry is not None:
        satisfied = (
            (entry.get("searched") and entry.get("space") == space)
            if search
            else True
        )
        cached = cache.get_config(key)
        if satisfied and cached is not None:
            return AutotuneResult(
                cfg=cached, cache_hit=True, searched=False, evaluations=0,
                objective_us=entry.get("objective_us"),
                trials=tuple(entry.get("trials") or ()),
            )

    if not search:
        cfg = tuned_partition_config(csr)
        cache.put(key, cfg, searched=False, objective_us=None)
        return AutotuneResult(
            cfg=cfg, cache_hit=False, searched=False, evaluations=0, objective_us=None
        )

    best_cfg, best_us = None, float("inf")
    trials = []
    with obs.span(
        "serve.autotune", probe=probe.kind, candidates=len(candidates)
    ) as search_sp:
        for cand in candidates:
            with obs.span(
                "serve.autotune_trial",
                row_block=cand.row_block,
                col_block=cand.col_block,
                lane=cand.lane,
            ) as sp:
                us = probe(csr, cand, repeats)
                sp.annotate(objective_us=round(us, 1))
            trials.append(
                {"config": dataclasses.asdict(cand), "objective_us": round(us, 1)}
            )
            if us < best_us:
                best_cfg, best_us = cand, us
        search_sp.annotate(best_us=round(best_us, 1))
    trials.sort(key=lambda t: (t["objective_us"], sorted(t["config"].items())))
    if best_cfg is not None:
        # searches are rare + expensive: a flight-ring record of the winner
        # makes a later post-mortem show which geometry this plan serves
        from repro.obs.flight import get_flight

        get_flight().record(
            "serve.autotune",
            probe=probe.kind,
            candidates=len(candidates),
            best_us=round(best_us, 1),
        )
    if best_cfg is None:  # empty candidate list: fall back to the heuristic
        return autotune_partition(csr, key=key, cache=cache, search=False)
    cache.put(
        key, best_cfg, searched=True, objective_us=best_us, space=space,
        probe=probe.kind, trials=trials,
    )
    return AutotuneResult(
        cfg=best_cfg,
        cache_hit=False,
        searched=True,
        evaluations=len(candidates),
        objective_us=best_us,
        trials=tuple(trials),
    )
