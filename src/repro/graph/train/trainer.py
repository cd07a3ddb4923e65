"""Node-classification training loop over differentiable HBP aggregation.

The trainer composes the pieces the rest of the library already owns:

* forward — :mod:`repro.graph.layers_gnn` GCN/GraphSAGE stacks over a
  differentiable aggregator (:mod:`repro.kernels.autodiff`), so
  ``jax.grad`` of the loss launches the transpose-adjacency SpMM for the
  backward instead of tracing into the kernels;
* optimizer — :func:`repro.optim.adamw.adamw_update` (warmup + cosine
  schedule, global-norm clipping);
* residency — an optional serving :class:`~repro.serving.registry.
  MatrixRegistry`: adjacencies are admitted as linked (A, Aᵀ) pairs, and
  in mini-batch mode each sampled subgraph is content-hashed, so epochs
  after the first re-admit every batch for free.

Two regimes: :meth:`NodeClassifierTrainer.fit` trains full-graph (one
resident adjacency, every step aggregates all nodes);
:meth:`~NodeClassifierTrainer.fit_sampled` trains GraphSAGE-style
neighbor-sampled mini-batches (:mod:`repro.graph.train.sampling`), with
supervision restricted to each batch's seed nodes.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.formats import CSRMatrix
from repro.kernels.ops import default_strategy
from repro.optim.adamw import AdamWConfig, adamw_update, init_opt_state

from ..aggregate import make_diff_aggregator, plan_diff_aggregator
from ..graph import add_self_loops, normalize_adjacency
from ..layers_gnn import gcn_forward, init_gcn, init_sage, sage_forward
from .loss import accuracy, softmax_cross_entropy
from .sampling import sample_neighbors

__all__ = ["TrainState", "NodeClassifierTrainer"]

MODELS = ("gcn", "sage")


class TrainState(NamedTuple):
    """Parameters + optimizer state; advance with ``trainer.step``."""

    params: Any
    opt_state: Dict[str, Any]


class NodeClassifierTrainer:
    """Cross-entropy node classification with GCN or GraphSAGE.

    ``dims`` is the layer stack ``[n_features, hidden..., n_classes]``.
    ``model`` picks the forward and the adjacency convention: ``"gcn"``
    sum-aggregates over the symmetric-normalized self-loop adjacency,
    ``"sage"`` mean/max-aggregates over the raw adjacency (``op``
    defaults accordingly and must be "sum" | "mean" | "max").  Pass a
    ``registry`` to serve aggregation from resident, content-hashed
    (A, Aᵀ) plan pairs — required for mini-batch cache reuse to pay off.
    """

    def __init__(
        self,
        dims: Sequence[int],
        *,
        model: str = "gcn",
        op: Optional[str] = None,
        adamw: Optional[AdamWConfig] = None,
        registry=None,
        strategy: Optional[str] = None,
        interpret: Optional[bool] = None,
        mode: str = "vjp",
    ):
        if model not in MODELS:
            raise ValueError(f"unknown model {model!r} (expected one of {MODELS})")
        if len(dims) < 2:
            raise ValueError("dims needs at least [n_features, n_classes]")
        self.dims = list(dims)
        self.model = model
        self.op = op or ("sum" if model == "gcn" else "mean")
        self.adamw = adamw or AdamWConfig(
            lr_peak=2e-2, warmup_steps=5, decay_steps=500, weight_decay=0.0
        )
        self.registry = registry
        self.strategy = strategy or default_strategy()
        self.interpret = interpret
        self.mode = mode

    # --- setup -------------------------------------------------------------

    def init(self, key) -> TrainState:
        if isinstance(key, int):
            key = jax.random.PRNGKey(key)
        init = init_gcn if self.model == "gcn" else init_sage
        params = init(key, self.dims)
        return TrainState(params=params, opt_state=init_opt_state(params, self.adamw))

    def prepare_adjacency(self, adj: CSRMatrix) -> CSRMatrix:
        """The model's adjacency convention: Â for GCN, raw for SAGE."""
        if self.model == "gcn":
            return normalize_adjacency(add_self_loops(adj), "sym")
        return adj

    def aggregator(self, adj: CSRMatrix) -> Callable[[jax.Array], jax.Array]:
        """Differentiable aggregator over a *prepared* adjacency.

        With a registry the adjacency is admitted as a linked (A, Aᵀ)
        pair — re-admitting the same content (the resident full graph, or
        a repeated sampled batch) is free; without one, tiles are built
        directly per call.  Ops whose backward never launches Aᵀ (max,
        or the jvp mode) admit only the forward direction.
        """
        if self.registry is not None:
            from repro.kernels.autodiff import needs_transpose

            if needs_transpose(self.op, self.mode):
                plan = self.registry.admit_pair(adj)
            else:
                plan = self.registry.admit(adj)
            return plan_diff_aggregator(plan, op=self.op, mode=self.mode)
        return make_diff_aggregator(
            adj,
            op=self.op,
            strategy=self.strategy,
            interpret=self.interpret,
            mode=self.mode,
        )

    # --- one step ----------------------------------------------------------

    def _forward(self, agg, params, x: jax.Array) -> jax.Array:
        fwd = gcn_forward if self.model == "gcn" else sage_forward
        return fwd(agg, params, x)

    def step(
        self,
        state: TrainState,
        agg: Callable[[jax.Array], jax.Array],
        x: jax.Array,
        labels,
        mask=None,
    ) -> Tuple[TrainState, Dict[str, float]]:
        """One train step: loss + grads (VJP = transpose SpMM) + AdamW."""

        def loss_fn(params):
            logits = self._forward(agg, params, x)
            return softmax_cross_entropy(logits, labels, mask), logits

        (loss, logits), grads = jax.value_and_grad(loss_fn, has_aux=True)(state.params)
        params, opt_state, metrics = adamw_update(
            state.params, grads, state.opt_state, self.adamw
        )
        out = {
            "loss": float(loss),
            "accuracy": float(accuracy(logits, labels, mask)),
            "grad_norm": float(metrics["grad_norm"]),
            "lr": float(metrics["lr"]),
            "step": int(metrics["step"]),
        }
        # one always-on flight instant per step (values are already host
        # floats — no extra syncs); a post-mortem shows training progress
        # around whatever anomaly triggered the dump
        obs.get_flight().record(
            "train.step",
            model=self.model,
            step=out["step"],
            loss=out["loss"],
            grad_norm=out["grad_norm"],
        )
        if obs.enabled():
            # the step dict already forced these to host floats, so the
            # streams cost no extra syncs; indexed by optimizer step
            i = out["step"]
            obs.series("train.loss", model=self.model).append(out["loss"], index=i)
            obs.series("train.grad_norm", model=self.model).append(
                out["grad_norm"], index=i
            )
            obs.series("train.accuracy", model=self.model).append(
                out["accuracy"], index=i
            )
            obs.counter("train.steps", model=self.model).inc()
        return TrainState(params, opt_state), out

    def evaluate(self, state: TrainState, agg, x, labels, mask=None) -> Dict[str, float]:
        logits = self._forward(agg, state.params, x)
        return {
            "loss": float(softmax_cross_entropy(logits, labels, mask)),
            "accuracy": float(accuracy(logits, labels, mask)),
        }

    # --- training regimes --------------------------------------------------

    def fit(
        self,
        adj: CSRMatrix,
        x,
        labels,
        *,
        steps: int,
        state: Optional[TrainState] = None,
        key: int = 0,
        mask=None,
    ) -> Tuple[TrainState, List[Dict[str, float]]]:
        """Full-graph training: one resident adjacency, ``steps`` updates."""
        state = state or self.init(key)
        agg = self.aggregator(self.prepare_adjacency(adj))
        x = jnp.asarray(x, jnp.float32)
        history = []
        for _ in range(steps):
            state, metrics = self.step(state, agg, x, labels, mask)
            history.append(metrics)
        return state, history

    def fit_sampled(
        self,
        adj: CSRMatrix,
        x,
        labels,
        *,
        steps: int,
        batch_size: int,
        fanouts: Sequence[int] = (10, 5),
        state: Optional[TrainState] = None,
        key: int = 0,
        seed: int = 0,
        train_nodes=None,
    ) -> Tuple[TrainState, List[Dict[str, float]]]:
        """Neighbor-sampled mini-batch training (GraphSAGE's regime).

        One epoch is a fixed partition of ``train_nodes`` (default: all)
        into ``batch_size`` seed groups; epochs cycle the same batches
        with the same per-batch sampler seeds, so every subgraph after
        the first epoch is a registry content-hash hit (when a registry
        is attached) — per-batch preprocessing is paid once per run.
        Supervision applies to each batch's seed rows only.
        """
        state = state or self.init(key)
        n = adj.shape[0]
        train_nodes = (
            np.arange(n, dtype=np.int64)
            if train_nodes is None
            else np.asarray(train_nodes, dtype=np.int64)
        )
        if train_nodes.size == 0:
            raise ValueError("train_nodes selected no nodes to supervise")
        rng = np.random.default_rng(seed)
        perm = rng.permutation(train_nodes)
        batches = [perm[i : i + batch_size] for i in range(0, perm.size, batch_size)]
        x = np.asarray(x, np.float32)
        labels = np.asarray(labels)
        history = []
        for s in range(steps):
            b = s % len(batches)
            batch = sample_neighbors(adj, batches[b], fanouts, seed=seed + b)
            agg = self.aggregator(self.prepare_adjacency(batch.adj))
            state, metrics = self.step(
                state,
                agg,
                jnp.asarray(x[batch.nodes]),
                labels[batch.nodes],
                jnp.asarray(batch.seed_mask()),
            )
            metrics["batch_nodes"] = int(batch.nodes.size)
            history.append(metrics)
        return state, history
