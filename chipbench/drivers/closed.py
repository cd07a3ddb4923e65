"""Closed-loop products through ``MatrixPlan``: a chain or an aggregation.

Each call is ``Y = A X`` on the admitted plan, ``plan.matvec`` for ``k == 1``
and ``plan.aggregate(op=...)`` for a ``[n, k]`` block; the serving engine is
bypassed, so the kernel's time per call is all of the work.  The host
dispatches call ``i + 1`` before it waits for call ``i``, so one call is
always queued on the device.  The traffic file sets:

* ``k`` — columns per call, and ``op`` where ``k > 1`` (``"sum"``);
* ``feedback`` — ``true``: ``x <- A x / ||A x||``, step after step, as
  inside a Krylov or power loop; ``false``: the window cycles over
  ``blocks`` inputs made from the seed and staged before it opens (GNN
  feature aggregation).

``sparse_gflops`` is ``2 * nnz * k`` per completed call over the time from
the window's start to the last completion.  The check compares a sample of
calls, drawn by the seed, each against the float64 product of its own input.
"""
from __future__ import annotations

import time

import numpy as np

from chipbench import reference
from chipbench.harness import Run, admit
from chipbench.matrices import values_rng
from chipbench.sampling import Reservoir
from chipbench.work import csr_work

# the control's answer in the program's place: bfloat16 values and inputs
CONTROL = reference.product_bf16


def _call(run: Run):
    plan, t = run.state["plan"], run.traffic
    if t["k"] == 1:
        return plan.matvec
    return lambda x: plan.aggregate(x, op=t["op"])


def setup(run: Run, seconds: float) -> None:
    import jax
    import jax.numpy as jnp

    t = run.traffic
    admit(run)
    call = _call(run)
    rng = values_rng(run.seed, 2)
    shape = (run.csr.shape[1],) if t["k"] == 1 else (run.csr.shape[1], t["k"])
    if t["feedback"]:
        normalize = jax.jit(lambda y: y / jnp.linalg.norm(y))
        inputs = [normalize(jnp.asarray(rng.standard_normal(shape, dtype=np.float32)))]
        step = lambda x, y: normalize(y)  # noqa: E731
    else:
        inputs = [jax.device_put(rng.standard_normal(shape, dtype=np.float32))
                  for _ in range(t["blocks"])]
        step = None
    with run.span("warmup"):
        x = inputs[0]
        for _ in range(2):
            y = call(x)
            x = step(x, y) if step else x
        # both: without feedback ``x`` is an input, and the warm-up's
        # products would run on into the window
        jax.block_until_ready((x, y))
    run.state.update(call=call, inputs=inputs, step=step)


def window(run: Run, seconds: float) -> dict:
    call, inputs, step = run.state["call"], run.state["inputs"], run.state["step"]
    sample = Reservoir(run.traffic["check"]["sample"], values_rng(run.seed, 4))
    x = inputs[0]
    x.block_until_ready()
    calls = issued = 0
    longest = 0.0  # the longest wait between two completions, for the record
    pending = None
    t0 = t_last = time.perf_counter()
    deadline = t0 + seconds
    while t_last < deadline:
        with run.span("call"):
            y = call(x)
        issued += 1
        sample.offer((x, y))
        x = step(x, y) if step else inputs[issued % len(inputs)]
        if pending is not None:
            with run.span("wait"):
                pending.block_until_ready()
            calls += 1
            longest = max(longest, time.perf_counter() - t_last)
            t_last = time.perf_counter()
        pending = y
    with run.span("wait"):
        pending.block_until_ready()
    calls += 1
    t_last = time.perf_counter()
    run.state["sample"] = sample.items
    flops, nbytes = csr_work(run.csr.nnz, *run.csr.shape, run.traffic["k"])
    return {
        "sparse_gflops": flops * calls / (t_last - t0) / 1e9,
        "attempted": calls,
        "failed": 0,
        "work_flops": flops * calls,
        "work_bytes": nbytes * calls,
        "longest_call_s": longest,
    }


def check(run: Run, answer=None) -> list:
    """``(name, value, limit)`` of the sampled calls; ``answer(csr, x)`` in
    place of the program's output where given (the control)."""
    err = 0.0
    for x, y in run.state["sample"]:
        x = np.asarray(x)
        y = np.asarray(y) if answer is None else answer(run.csr, x)
        y_ref, scale = reference.product(run.csr, x)
        err = max(err, reference.rel_err(y, y_ref, scale))
    return [("max_rel_err", err, run.traffic["check"]["max_rel_err"])]
