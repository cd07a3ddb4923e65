"""Whole PageRank solves, closed loop, with the iteration on the device.

The transition matrix comes from the program's ``transition_matrix``; its
tiles are built with the configuration's pinned geometry and staged by
``aslinearoperator`` (the path ``chip_smoke.py`` proves on the chip).  The
program's ``pagerank`` runs under one ``jax.jit`` over the staged tiles, so
the whole ``lax.while_loop`` is one compiled program: called outside
``jit``, ``pagerank`` traces and compiles its loop again on every call.

Each solve starts from the uniform vector and runs to the solver's default
tolerance (``||p' - p||_1 <= 1e-8 n``); the host waits for each answer
before it asks for the next.  ``solve_ms`` is the window's wall time over
the solves it completed.  The check compares a solve drawn by the seed
with the same number of float64 steps.
"""
from __future__ import annotations

import time

import numpy as np

from chipbench import reference
from chipbench.harness import Run, partition_config
from chipbench.matrices import values_rng
from chipbench.sampling import Reservoir
from chipbench.work import csr_work

# the control's answer in the solve's place: the same steps in bfloat16
CONTROL = reference.pagerank_bf16


def setup(run: Run, seconds: float) -> None:
    import jax
    from repro.core.tile import build_tiles
    from repro.kernels import ops
    from repro.solvers import aslinearoperator, pagerank, transition_matrix
    from repro.solvers.operator import LinearOperator

    t = run.traffic
    t0 = time.perf_counter()
    with run.span("admit"):
        M, dangling = transition_matrix(run.matrix)
        tiles = build_tiles(M, partition_config(run))
        dt, meta = aslinearoperator(tiles).launch_args
        dang = jax.device_put(dangling)
        jax.block_until_ready((dt, dang))
    run.admit_s = time.perf_counter() - t0

    @jax.jit
    def solve(dt, dang):
        op = LinearOperator(tiles.shape, matvec=lambda x: ops.hbp_spmv(dt, x, **meta))
        return pagerank(op, dangling=dang, damping=t["damping"], tol=t["tol"],
                        maxiter=t["maxiter"])

    with run.span("warmup"):
        solve(dt, dang).x.block_until_ready()
    run.state.update(solve=solve, dt=dt, dang=dang)


def window(run: Run, seconds: float) -> dict:
    solve, dt, dang = run.state["solve"], run.state["dt"], run.state["dang"]
    sample = Reservoir(1, values_rng(run.seed, 4))
    iters, converged = [], []
    longest = 0.0  # the longest solve, for the record
    t0 = t_last = time.perf_counter()
    while t_last < t0 + seconds:
        with run.span("pagerank"):
            res = solve(dt, dang)
            iters.append(int(res.iterations))
        converged.append(bool(res.converged))
        sample.offer(res)
        longest = max(longest, time.perf_counter() - t_last)
        t_last = time.perf_counter()
    run.state.update(sample=sample.items[0], iters=iters, converged=converged)
    flops, nbytes = csr_work(run.csr.nnz, *run.csr.shape, 1)
    n_iters = sum(iters)
    return {
        "solve_ms": (t_last - t0) / len(iters) * 1e3,
        "attempted": len(iters),
        "failed": 0,
        "iters_mean": n_iters / len(iters),
        "work_flops": flops * n_iters,
        "work_bytes": nbytes * n_iters,
        "longest_call_s": longest,
    }


def check(run: Run, answer=None) -> list:
    """``(name, value, limit)`` of the sampled solve; ``answer(csr,
    iterations, damping)`` in place of its vector where given (the control)."""
    res, t = run.state["sample"], run.traffic
    n_iter = int(res.iterations)
    p_ref, scale = reference.pagerank(run.csr, n_iter, t["damping"])
    p = np.asarray(res.x) if answer is None else answer(run.csr, n_iter, t["damping"])
    err = reference.rel_err(p, p_ref, scale)
    unconverged = len(run.state["converged"]) - sum(run.state["converged"])
    return [("max_rel_err", err, t["check"]["max_rel_err"]),
            ("unconverged_solves", float(unconverged), 0.0)]
