"""Matrix admission: CSR in, device-resident autotuned HBP plan out.

A serving system's defining asymmetry is admit-once / multiply-many: the
HBP preprocessing pipeline (2D partition → nonlinear hash → tile packing)
runs once per matrix, and every subsequent request reuses the device-
resident tiles.  :class:`MatrixRegistry` owns that lifecycle:

* **content addressing** — matrices are keyed by a sha256 over shape +
  structure + values, so re-admitting an already-resident matrix returns
  the existing plan without touching the preprocessing pipeline;
* **autotuned geometry** — the partition config comes from
  :func:`repro.serving.autotune.autotune_partition` (measured search with a
  persistent on-disk cache), unless the caller pins an explicit config;
* **device residency** — tiles are staged to the device once at admission
  (:func:`repro.kernels.ops.device_tiles`); requests only launch kernels.
  Under an HBM budget a plan unstaged for others is re-staged when a
  batch of it is launched;
* **amortization bookkeeping** — the one-time preprocessing cost is
  recorded so :meth:`MatrixRegistry.stats` can report how far traffic has
  amortized it (the paper's Fig. 7 cost, divided by requests served).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, Optional

import numpy as np

from repro import obs
from repro.obs.flight import get_flight
from repro.obs.requesttrace import mint_trace_id
from repro.core.formats import CSRMatrix
from repro.core.partition import PartitionConfig
from repro.core.tile import HBPTiles, build_tiles
from repro.obs.metrics import MetricRegistry
from repro.obs import planview

from .autotune import AutotuneCache, autotune_partition, matrix_hash
from .eviction import LRUEvictor, plan_device_bytes

__all__ = ["MatrixPlan", "MatrixRegistry"]


@dataclasses.dataclass
class MatrixPlan:
    """Everything the serving path needs about one resident matrix."""

    name: str
    matrix_hash: str
    shape: tuple
    nnz: int
    cfg: PartitionConfig
    tiles: HBPTiles  # host copy (rebuilds, debugging)
    device: object  # DeviceTiles pytree, staged once
    diag: np.ndarray  # main diagonal, host-resident at tile-build time
    row_nnz: np.ndarray  # per-row stored-entry count (graph in-degree)
    preprocess_s: float  # autotune + tile build + device staging
    autotune_cache_hit: bool
    autotune_searched: bool
    strategy: str = "fused"
    interpret: Optional[bool] = None
    # admission-time introspection: static partition-quality metrics
    # (:func:`repro.obs.planview.partition_quality`) and the autotune
    # decision provenance — which geometry candidates were measured and
    # what each cost.  Deliberately NOT part of ``_meta()``: these describe
    # the plan, the kernels never see them.
    quality: dict = dataclasses.field(default_factory=dict, repr=False, compare=False)
    provenance: dict = dataclasses.field(
        default_factory=dict, repr=False, compare=False
    )
    # host seconds of the admission's phases, read at the boundaries of
    # their spans: "key" (content hash), "tiles" (build_tiles), "stage"
    # (device staging), "quality" (partition_quality)
    phases_s: dict = dataclasses.field(default_factory=dict, repr=False, compare=False)
    # A <-> A^T link, set by MatrixRegistry.admit_pair: the transpose
    # plan's name plus a direct reference (a symmetric matrix links to
    # itself — one residency serves both directions for free)
    transpose_name: Optional[str] = None
    _transpose: object = dataclasses.field(default=None, repr=False, compare=False)
    # device-staged clamped in-degree [n, 1], built on first mean aggregate
    _mean_div: object = dataclasses.field(default=None, repr=False, compare=False)
    # the owning registry's shared MetricRegistry — single source of truth
    # for the admission counters this plan's views read
    _metrics: object = dataclasses.field(default=None, repr=False, compare=False)

    @property
    def admissions(self) -> int:
        """admit() calls that resolved to this plan — a *view* over the
        owning registry's shared metrics, not a second ledger."""
        if self._metrics is None:
            return 1
        return int(self._metrics.value("registry.admissions", 1, matrix=self.name))

    def _meta(self) -> dict:
        return dict(
            n_rowgroups=self.tiles.n_rowgroups,
            n_rows=self.shape[0],
            col_block=self.cfg.col_block,
            strategy=self.strategy,
            interpret=self.interpret,
        )

    def matvec(self, x) -> np.ndarray:
        """One-off ``A @ x`` against the resident plan (bypasses batching)."""
        from repro.kernels import ops

        return ops.hbp_spmv(self.device, x, **self._meta())

    def matmat(self, x, *, bucketed: bool = True, buckets=None, combine: str = "sum"):
        """``A (x) X`` for an ``[n, k]`` block; ``bucketed`` pads k to the
        serving buckets (``buckets`` overrides the default set) so the
        compile count stays bounded.  ``combine`` selects the reduction
        monoid ("sum" | "max") — feature widths beyond the top bucket
        lane-tile inside the kernel wrapper."""
        from repro.kernels import ops

        if not bucketed:
            return ops.hbp_spmm(self.device, x, combine=combine, **self._meta())
        if buckets is None:
            buckets = ops.K_BUCKETS
        return ops.hbp_spmm_bucketed(
            self.device, x, buckets=buckets, combine=combine, **self._meta()
        )

    def aggregate(self, x, *, op: str = "sum", bucketed: bool = True):
        """Neighborhood aggregation over the resident plan: the registered
        matrix read as a graph adjacency (rows aggregate their stored
        neighbors).  ``op`` is "sum", "mean" (sum / in-degree, captured at
        admission) or "max" (the max-monoid kernel path); repeated GNN
        layer calls all reuse the device tiles and autotuned geometry.
        """
        if op == "sum":
            return self.matmat(x, bucketed=bucketed)
        if op == "mean":
            if self._mean_div is None:  # staged once, like the tiles
                from repro.kernels.autodiff import mean_divisor

                self._mean_div = mean_divisor(self.row_nnz, self.shape[0])
            return self.matmat(x, bucketed=bucketed) / self._mean_div
        if op == "max":
            return self.matmat(x, bucketed=bucketed, combine="max")
        raise ValueError(f"unknown aggregation {op!r} (sum | mean | max)")

    def diff_aggregator(self, *, op: str = "sum", mode: str = "vjp"):
        """Differentiable aggregation closure over the resident plan.

        Backward for sum/mean launches the *linked transpose plan's*
        tiles (``x̄ = Aᵀ @ ȳ``), so the plan must have been admitted with
        :meth:`MatrixRegistry.admit_pair`; max routes cotangents through
        the argmax indices its forward saves and needs no transpose.
        Mean divides by the in-degree captured at admission.
        """
        from repro.kernels import autodiff

        needs_t = autodiff.needs_transpose(op, mode)
        if needs_t and self._transpose is None:
            raise ValueError(
                f"plan {self.name!r} has no linked transpose — admit the "
                "matrix with MatrixRegistry.admit_pair() for differentiable "
                "sum/mean aggregation"
            )
        plan_T = self._transpose
        return autodiff.device_diff_aggregator(
            self.device,
            plan_T.device if plan_T is not None else None,
            self._meta(),
            plan_T._meta() if plan_T is not None else None,
            op=op,
            degree=self.row_nnz if op == "mean" else None,
            mode=mode,
        )

    def operator(self):
        """The plan as a solver-ready :class:`LinearOperator`."""
        from repro.solvers.operator import LinearOperator

        return LinearOperator(self.shape, matvec=self.matvec, matmat=self.matmat)

    def jacobi(self):
        """Jacobi preconditioner built from the admission-time diagonal."""
        from repro.solvers.precond import jacobi

        return jacobi(self.diag)


class MatrixRegistry:
    """Admit CSR matrices once; hand out device-resident HBP plans.

    ``search=False`` replaces the measured autotune search with the
    ``tuned_partition_config`` heuristic (still cached); ``candidates``
    narrows the measured search space; ``strategy``/``interpret`` select
    the kernel path every plan's launches use.  The default strategy is
    backend-aware: the fused Pallas kernel on TPU, the batch-width-
    invariant ``"stable"`` jnp path elsewhere (off-TPU the kernels would
    run in interpret mode — slow, and ~1 ulp dependent on batch width,
    which would break the engine's coalescing-invariance guarantee).

    ``metrics`` is the shared :class:`~repro.obs.metrics.MetricRegistry`
    backing this registry's admission counters *and* every
    :class:`~repro.serving.engine.ServingEngine` built over it — one
    ledger, two ``stats()`` views.  Each registry defaults to its own
    instance (test isolation); all live instances aggregate into
    ``repro.obs.dump()``/``report()``.

    ``hbm_budget_bytes`` caps the **device** footprint of staged tiles:
    when admissions (or re-stages) push past the budget, the least-
    recently-used plans are *unstaged* — device arrays dropped, host
    tiles and autotuned geometry kept — and the next :meth:`get` (or
    :meth:`stage`) against an unstaged plan re-stages it in one
    ``device_tiles`` call (zero re-preprocessing; a full re-admission
    would hit the ``.hbp_autotune/`` disk cache by content hash anyway).
    The serving engine stages only when it launches a batch, so residency
    is decided once per launch; :meth:`peek` looks a plan up without
    staging.  Transpose pairs are evicted and re-staged as a unit.

    A plan with requests queued or batches in flight is *pinned*
    (:meth:`pin`, :meth:`unpin`; the engine pins each request at submit and
    unpins it at harvest): it is never unstaged, since the runtime would
    keep its buffers alive for the launched work anyway.  When every other
    unit is pinned a re-stage goes ahead over the budget, and the
    ``evict.overshoot_bytes`` gauge records by how much.  ``None``
    (default) disables the budget — every admitted plan stays
    device-resident, and nothing is pinned.
    """

    def __init__(
        self,
        *,
        cache_dir=None,
        search: bool = True,
        candidates=None,
        autotune_k: int = 8,
        strategy: Optional[str] = None,
        interpret: Optional[bool] = None,
        probe=None,
        metrics: Optional[MetricRegistry] = None,
        hbm_budget_bytes: Optional[int] = None,
    ):
        if strategy is None:
            from repro.kernels.ops import default_strategy

            strategy = default_strategy()
        self.cache = AutotuneCache(cache_dir)
        self.search = search
        self.candidates = candidates
        self.autotune_k = autotune_k
        self.strategy = strategy
        self.interpret = interpret
        self.probe = probe  # None: steady-state SpMM time (spmm_probe)
        self.metrics = metrics if metrics is not None else MetricRegistry(name="serving")
        self.evictor = (
            LRUEvictor(hbm_budget_bytes) if hbm_budget_bytes is not None else None
        )
        self._plans: Dict[str, MatrixPlan] = {}
        self._by_hash: Dict[str, str] = {}

    def admit(
        self,
        csr: CSRMatrix,
        name: Optional[str] = None,
        *,
        cfg: Optional[PartitionConfig] = None,
    ) -> MatrixPlan:
        """Admit ``csr`` and return its plan.

        Same content twice → the resident plan (no rebuild, no search).
        Fresh content with a warm on-disk cache → tile build only (the
        measured search is skipped).  ``cfg`` pins the geometry explicitly
        and bypasses autotuning altogether.

        The phases run in spans under ``serve.admit`` (``admit.key``,
        ``admit.build_tiles``, ``serve.stage_device``,
        ``admit.plan_quality``); their host seconds land in the plan's
        ``phases_s``.
        """
        from repro.kernels import ops

        with obs.span("serve.admit", matrix=name, nnz=csr.nnz) as sp:
            t_key = time.perf_counter()
            with obs.span("admit.key", matrix=name):
                key = matrix_hash(csr)
            t0 = time.perf_counter()
            if key in self._by_hash:
                plan = self._plans[self._by_hash[key]]
                if cfg is not None and cfg != plan.cfg:
                    raise ValueError(
                        f"matrix {key[:12]} is already resident as {plan.name!r} "
                        f"with config {plan.cfg}; re-admission pinned {cfg} — "
                        "evict the plan first to rebuild under a different geometry"
                    )
                self.metrics.counter("registry.hits", matrix=plan.name).inc()
                self.metrics.counter("registry.admissions", matrix=plan.name).inc()
                self._ensure_staged(plan)
                return plan
            if name is not None and name in self._plans:
                raise ValueError(
                    f"name {name!r} is already bound to matrix "
                    f"{self._plans[name].matrix_hash[:12]}"
                )
            # admissions get trace ids too (kind "a"): the one-time preprocess
            # cost is attributable in dumps the same way requests are
            admit_id = mint_trace_id("a")
            sp.annotate(trace_id=admit_id)
            pinned = cfg is not None
            if pinned:
                tune_hit, tune_searched = False, False
                trials, evaluations, objective_us = (), 0, None
            else:
                tuned = autotune_partition(
                    csr,
                    key=key,
                    cache=self.cache,
                    search=self.search,
                    candidates=self.candidates,
                    k=self.autotune_k,
                    strategy=self.strategy,  # rank configs under the served path
                    probe=self.probe,  # e.g. cg_probe: rank by time-to-tolerance
                )
                cfg = tuned.cfg
                tune_hit, tune_searched = tuned.cache_hit, tuned.searched
                trials = tuned.trials
                evaluations, objective_us = tuned.evaluations, tuned.objective_us
            t_tiles = time.perf_counter()
            # bucketed tile counts: tenants of about the same size share
            # their compiled kernels
            tiles = build_tiles(csr, cfg, bucket_tiles=True)
            t_stage = time.perf_counter()
            with obs.span("serve.stage_device", matrix=name):
                device = ops.device_tiles(tiles)
            t_staged = time.perf_counter()
            diag = csr.diagonal()
            row_nnz = csr.row_nnz().astype(np.int64)
            preprocess_s = time.perf_counter() - t0
            name = name or f"m_{key[:12]}"
            sp.annotate(matrix=name, preprocess_s=round(preprocess_s, 6))
            # partition-quality introspection runs once per admission,
            # after the preprocess clock stops: it describes the plan, it
            # is not part of the amortizable build cost
            t_quality = time.perf_counter()
            with obs.span("admit.plan_quality", matrix=name, tiles=tiles.n_tiles):
                quality = planview.partition_quality(tiles, csr)
            phases_s = {
                "key": t0 - t_key,
                "tiles": t_stage - t_tiles,
                "stage": t_staged - t_stage,
                "quality": time.perf_counter() - t_quality,
            }
        provenance = {
            "searched": tune_searched,
            "cache_hit": tune_hit,
            "pinned": pinned,
            "evaluations": evaluations,
            "objective_us": objective_us,
            "trials": [dict(t) for t in trials],
        }

        plan = MatrixPlan(
            name=name,
            matrix_hash=key,
            shape=csr.shape,
            nnz=csr.nnz,
            cfg=cfg,
            tiles=tiles,
            device=device,
            diag=diag,
            row_nnz=row_nnz,
            preprocess_s=preprocess_s,
            autotune_cache_hit=tune_hit,
            autotune_searched=tune_searched,
            strategy=self.strategy,
            interpret=self.interpret,
            quality=quality,
            provenance=provenance,
            phases_s=phases_s,
            _metrics=self.metrics,
        )
        self._plans[name] = plan
        self._by_hash[key] = name
        m = self.metrics
        planview.register_plan_metrics(m, name, quality, provenance)
        m.counter("registry.misses", matrix=name).inc()
        m.counter("registry.admissions", matrix=name).inc()
        m.counter("registry.preprocess_s", matrix=name).inc(preprocess_s)
        if tune_hit:
            m.counter("registry.autotune_cache_hits", matrix=name).inc()
        if tune_searched:
            m.counter("registry.autotune_searches", matrix=name).inc()
        m.gauge("registry.resident").set(len(self._plans))
        # admissions are rare and expensive — always worth a flight-ring
        # slot, so a post-mortem dump shows what was admitted and when
        get_flight().record(
            "serve.admit",
            matrix=name,
            nnz=csr.nnz,
            preprocess_s=round(preprocess_s, 6),
            trace_id=admit_id,
        )
        self._charge(plan)
        return plan

    def admit_pair(
        self,
        csr: CSRMatrix,
        name: Optional[str] = None,
        *,
        cfg: Optional[PartitionConfig] = None,
        cfg_T: Optional[PartitionConfig] = None,
    ) -> MatrixPlan:
        """Admit ``csr`` AND its transpose, linked for differentiable use.

        The pair is what training needs: the backward of ``A @ X`` is an
        SpMM against ``Aᵀ`` (:mod:`repro.kernels.autodiff`), so both
        directions become resident plans cross-linked via
        ``transpose_name``.  Content hashing makes every re-admission
        free, and a *symmetric* matrix (e.g. GCN's normalized adjacency)
        hashes identically to its transpose — one plan serves both
        directions, no second build.  Returns the forward plan; reach the
        transpose through the link (``plan.transpose_name`` /
        ``registry.transpose_of(plan)``).
        """
        plan = self.admit(csr, name, cfg=cfg)
        if plan._transpose is not None:  # pair already linked (re-admission)
            partner = plan._transpose
            if cfg_T is not None and cfg_T != partner.cfg:
                raise ValueError(
                    f"transpose of {plan.name!r} is already resident as "
                    f"{partner.name!r} with config {partner.cfg}; re-admission "
                    f"pinned {cfg_T} — evict the pair first to rebuild"
                )
            if partner is not plan:  # keep both sides' admission stats in step
                self.metrics.counter(
                    "registry.admissions", matrix=partner.name
                ).inc()
            return plan
        csr_T = csr.transpose()
        plan_T = self.admit(csr_T, f"{plan.name}::T", cfg=cfg_T)
        plan.transpose_name = plan_T.name
        plan._transpose = plan_T
        plan_T.transpose_name = plan.name
        plan_T._transpose = plan
        if self.evictor is not None and plan_T is not plan:
            # forward + backward are one residency unit: evicting one side
            # would silently re-stage the other on the next training step
            self.evictor.link(plan.name, plan_T.name)
        return plan

    def transpose_of(self, plan: MatrixPlan) -> MatrixPlan:
        """The linked Aᵀ plan (admit with :meth:`admit_pair` first)."""
        if plan._transpose is None:
            raise KeyError(f"plan {plan.name!r} has no linked transpose")
        return plan._transpose

    def get(self, name: str) -> MatrixPlan:
        """The plan for ``name``, staged on the device (raises ``KeyError``
        if absent).

        Under an HBM budget this is also the re-admission path: an
        unstaged plan is re-staged here (and its recency refreshed), at
        the cost of one ``device_tiles`` call; :meth:`stage` also returns
        that cost.  The serving engine stages through :meth:`stage` when
        it launches a batch, never at submit.
        """
        return self.stage(name)[0]

    def stage(self, name: str):
        """``(plan, restage_s)``: the plan for ``name`` staged on the device,
        and the host seconds its unit's re-stage took (0.0 when resident)."""
        plan = self._plans[name]
        return plan, self._ensure_staged(plan)

    def peek(self, name: str) -> MatrixPlan:
        """The plan for ``name`` as it is: no staging, recency untouched
        (raises ``KeyError`` if absent)."""
        return self._plans[name]

    def pin(self, name: str, count: int = 1) -> None:
        """Hold ``name``'s unit staged for ``count`` requests (no-op
        without a budget)."""
        if self.evictor is not None:
            self.evictor.pin(name, count)

    def unpin(self, name: str, count: int = 1) -> None:
        """Release ``count`` requests pinned by :meth:`pin`."""
        if self.evictor is not None:
            self.evictor.unpin(name, count)

    def __contains__(self, name: str) -> bool:
        return name in self._plans

    def __len__(self) -> int:
        return len(self._plans)

    def names(self):
        """Names of every resident plan (staged or budget-unstaged)."""
        return list(self._plans)

    def evict(self, name: str) -> None:
        """Fully remove ``name``: plan, content-hash binding, pair link.

        Unlike budget-driven *unstaging* (device arrays only), this drops
        the host plan too — the next admit of the same content rebuilds
        tiles (the autotune disk cache still avoids the measured search).
        """
        plan = self._plans.pop(name)
        del self._by_hash[plan.matrix_hash]
        partner = plan._transpose
        if partner is not None and partner is not plan:
            partner.transpose_name = None
            partner._transpose = None
        if self.evictor is not None:
            self.evictor.drop(name)
            self.evictor.unlink(name)
        self.metrics.counter("registry.evictions", matrix=name).inc()
        self.metrics.gauge("registry.resident").set(len(self._plans))

    # --- HBM-budget residency ---------------------------------------------

    def _charge(self, plan: MatrixPlan) -> None:
        """Charge ``plan``'s device bytes to the budget; unstage victims."""
        if self.evictor is None:
            return
        victims = self.evictor.admit(plan.name, plan_device_bytes(plan.tiles))
        for victim in victims:
            self._unstage(victim)
        self.metrics.gauge("evict.resident_bytes").set(self.evictor.resident_bytes)
        self.metrics.gauge("evict.overshoot_bytes").set(self.evictor.over_budget())

    def _unstage(self, name: str) -> None:
        """Drop ``name``'s device arrays (host tiles and geometry stay)."""
        plan = self._plans.get(name)
        if plan is None or plan.device is None:
            return
        plan.device = None
        plan._mean_div = None  # staged alongside the tiles; rebuilt on demand
        self.metrics.counter("evict.unstaged", matrix=name).inc()
        get_flight().record("evict.unstage", matrix=name)
        if obs.enabled():
            obs.counter("evict.unstaged", matrix=name).inc()

    def _ensure_staged(self, plan: MatrixPlan) -> float:
        """Refresh recency; re-stage the plan's unit if budget-evicted.
        Returns the host seconds the re-stage took (0.0 when resident)."""
        if self.evictor is None:
            return 0.0
        self.evictor.touch(plan.name)
        # the pair is one unit: restage both sides together so a training
        # step never finds half of its forward/backward residency missing
        unit = [plan]
        if plan._transpose is not None and plan._transpose is not plan:
            unit.append(plan._transpose)
        total_s = 0.0
        for p in unit:
            if p.device is not None:
                continue
            from repro.kernels import ops

            t0 = time.perf_counter()
            with obs.span("serve.restage", matrix=p.name):
                p.device = ops.device_tiles(p.tiles)
            restage_s = time.perf_counter() - t0
            total_s += restage_s
            m = self.metrics
            m.counter("evict.restages", matrix=p.name).inc()
            m.counter("evict.restage_s", matrix=p.name).inc(restage_s)
            m.counter("evict.restage_bytes", matrix=p.name).inc(
                plan_device_bytes(p.tiles)
            )
            get_flight().record(
                "evict.restage", matrix=p.name, restage_s=round(restage_s, 6)
            )
            self._charge(p)
        return total_s

    def stats(self) -> dict:
        """Per-matrix admission/preprocessing snapshot (engine adds traffic).

        A *view*: admission counts are read back from the shared
        :class:`~repro.obs.metrics.MetricRegistry` (``self.metrics``), the
        same store every engine over this registry reports traffic into.
        """
        return {
            name: {
                "matrix_hash": p.matrix_hash[:12],
                "shape": tuple(p.shape),
                "nnz": p.nnz,
                "config": dataclasses.asdict(p.cfg),
                "admissions": p.admissions,
                "preprocess_s": p.preprocess_s,
                "autotune_cache_hit": p.autotune_cache_hit,
                "autotune_searched": p.autotune_searched,
                "quality": {
                    k: v for k, v in p.quality.items() if k != "occupancy_sample"
                },
                "provenance": p.provenance,
            }
            for name, p in self._plans.items()
        }
