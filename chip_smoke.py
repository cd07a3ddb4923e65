#!/usr/bin/env python3
"""Run the HBP serving path once on a TPU and check every result.

    python chip_smoke.py                # one chip: the phases below
    python chip_smoke.py --four-chips   # only the sharded SpMV, on four chips

One chip.  Two matrices at their published sizes, made from ``--seed``:
the power-law ``m4_kron16`` (Graph500 Kronecker, 65,536 rows, the size of
SuiteSparse ``kron_g500-logn16``) and a circuit matrix at ASIC_320k's size
(321,821 rows).  Each is admitted through ``MatrixRegistry()`` with its
defaults (the measured autotune search, the backend's kernel strategy),
served 25 single-vector requests through ``ServingEngine`` (coalesced to
batches of 1, 8 and 16; one engine dispatches asynchronously), then
multiplied once by ``plan.matvec`` (k=1), ``plan.matmat`` (k=256, the 2D
k-grid) and ``plan.aggregate(op="max")`` (k=8).  Five PageRank iterations
on the power-law matrix run the SpMV kernel inside ``lax.while_loop``.

Four chips.  ``build_sharded_spmv`` in both placements on a 4-device mesh,
on ``m5_kron17`` (131,072 rows), compared with the float64 reference and
with the one-chip plan of the same matrix.

Every result is checked against a float64 CSR product:
``max_i |y_i - y^_i| / (|A| |x|)_i <= 1e-4`` (for max-aggregation, against a
float64 max-product).  Each phase prints one JSON line (wall and compile
seconds, error, the plan's strategy/interpret/geometry, and whether the
program handed to the compiler holds the Pallas kernel, ``tpu_custom_call``).
The last line is ``{"ok": true, "device": {...}}`` only if every phase
passed, every kernel phase ran the fused kernel natively, and the platform
is a TPU; otherwise the script exits non-zero without it.

The compile cache is ``$JAX_COMPILATION_CACHE_DIR`` when set, else
``.jax_cache/`` next to this script.  Records and the autotune cache go
under ``--out`` (default ``smoke_out/``).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import shutil
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import scipy.sparse  # noqa: E402

from repro.core.formats import CSRMatrix  # noqa: E402
from repro.core.matrices import SUITE_SPECS, circuit  # noqa: E402
from repro.core.tile import build_tiles  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.serving import MatrixRegistry, ServingEngine  # noqa: E402
from repro.solvers import aslinearoperator, pagerank, transition_matrix  # noqa: E402

BOUND = 1e-4  # max_i |y_i - y^_i| / (|A| |x|)_i
SERVE_KS = (1, 8, 16)  # batch widths the served requests coalesce to

_COMPILE_S = [0.0]


def _count_compile(event: str, duration_s: float, **_) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILE_S[0] += duration_s


jax.monitoring.register_event_duration_secs_listener(_count_compile)


# --- inputs and references ---------------------------------------------------


def power_law(seed: int) -> CSRMatrix:
    return SUITE_SPECS["m4_kron16"](seed)


def asic_320k(seed: int) -> CSRMatrix:
    return circuit(321_821, seed=1 + seed, avg_offdiag=4.9)


def _scipy(csr: CSRMatrix, *, absolute: bool = False):
    data = np.abs(csr.data) if absolute else csr.data
    return scipy.sparse.csr_matrix(
        (data.astype(np.float64), csr.indices, csr.indptr), shape=csr.shape
    )


def reference(csr: CSRMatrix, x: np.ndarray):
    """(A @ x, |A| @ |x|) in float64."""
    x64 = np.asarray(x, np.float64)
    return _scipy(csr) @ x64, _scipy(csr, absolute=True) @ np.abs(x64)


def max_reference(csr: CSRMatrix, x: np.ndarray) -> np.ndarray:
    """``y[i, c] = max_j a_ij x_jc`` over stored nonzeros (0 for none)."""
    x64 = np.asarray(x, np.float64)
    prod = csr.data.astype(np.float64)[:, None] * x64[csr.indices]
    prod[csr.data == 0] = -np.inf
    out = np.zeros((csr.n_rows, x64.shape[1]))
    live = csr.row_nnz() > 0
    if live.any():
        out[live] = np.maximum.reduceat(prod, csr.indptr[:-1][live], axis=0)
    out[np.isneginf(out)] = 0.0
    return out


def rel_err(y, y_ref: np.ndarray, scale: np.ndarray) -> float:
    y = np.asarray(y, np.float64)
    if y.shape != y_ref.shape or not np.isfinite(y).all():
        return float("inf")
    if y.size == 0:
        return 0.0
    tiny = np.finfo(np.float32).tiny
    return float(np.max(np.abs(y - y_ref) / np.maximum(scale, tiny)))


# --- what ran ----------------------------------------------------------------


def launch_record(dt: ops.DeviceTiles, meta: dict, x_shape: tuple, *,
                  combine: str = "sum") -> dict:
    """The kernel path of a wrapper call with the caller's own device tiles
    and keywords, and whether the program its jitted entry hands to the
    compiler holds the Pallas kernel."""
    x = jax.ShapeDtypeStruct(x_shape, jnp.float32)
    lowered = ops.lower_launch(dt, x, combine=combine, **meta)
    return {
        "strategy": meta["strategy"],
        "interpret": ops.resolve_interpret(meta["interpret"]),
        "k_tiling": meta.get("k_tiling", "grid"),
        "tpu_custom_call": "tpu_custom_call" in lowered.as_text(),
    }


def plan_launch(plan, k: int, *, combine: str = "sum") -> dict:
    """:func:`launch_record` of the plan's own call at width ``k``."""
    x_shape = (plan.shape[1],) if k == 1 else (plan.shape[1], k)
    rec = launch_record(plan.device, plan._meta(), x_shape, combine=combine)
    rec.update(cfg=dataclasses.asdict(plan.cfg), tiles=plan.tiles.n_tiles)
    return rec


# --- phases (each returns a record with "ok") --------------------------------


def admit(registry: MatrixRegistry, csr: CSRMatrix, name: str):
    plan = registry.admit(csr, name)
    rec = plan_launch(plan, 8)
    rec.update(
        matrix=name, rows=csr.n_rows, nnz=csr.nnz, ok=True,
        searched=plan.autotune_searched, autotune_cache_hit=plan.autotune_cache_hit,
        candidates=plan.provenance.get("evaluations"),
    )
    return plan, rec


def serve(registry: MatrixRegistry, name: str, csr: CSRMatrix, rng, *,
          overlap: bool, ks=SERVE_KS) -> dict:
    """Single-vector requests, flushed so they coalesce to each width in ks."""
    plan = registry.get(name)
    engine = ServingEngine(registry, max_wait_s=3600.0, overlap=overlap)
    xs = rng.standard_normal((sum(ks), csr.n_cols)).astype(np.float32)
    tickets, widths, i = [], [], 0
    for k in ks:
        cols_before = engine.metrics.value("serving.columns", 0, matrix=name)
        tickets += [engine.submit(name, xs[i + j]) for j in range(k)]
        i += k
        engine.flush(name)
        widths.append(int(engine.metrics.value("serving.columns", 0, matrix=name) - cols_before))
    y = np.stack([t.result() for t in tickets], axis=1)
    y_ref, scale = reference(csr, xs.T)
    err = rel_err(y, y_ref, scale)
    rec = plan_launch(plan, max(ks))
    rec.update(matrix=name, overlap=overlap, requests=len(tickets), batch_k=widths,
               err=err, ok=err <= BOUND and widths == list(ks))
    return rec


def matvec(plan, csr: CSRMatrix, rng) -> dict:
    x = rng.standard_normal(csr.n_cols).astype(np.float32)
    y = np.asarray(plan.matvec(x))
    y_ref, scale = reference(csr, x)
    err = rel_err(y, y_ref, scale)
    rec = plan_launch(plan, 1)
    rec.update(matrix=plan.name, k=1, err=err, ok=err <= BOUND)
    return rec


def matmat(plan, csr: CSRMatrix, rng, k: int = 256) -> dict:
    x = rng.standard_normal((csr.n_cols, k)).astype(np.float32)
    y = np.asarray(plan.matmat(x))
    y_ref, scale = reference(csr, x)
    err = rel_err(y, y_ref, scale)
    rec = plan_launch(plan, ops.bucket_k(k))
    rec.update(matrix=plan.name, k=k, err=err, ok=err <= BOUND)
    return rec


def aggregate_max(plan, csr: CSRMatrix, rng, k: int = 8) -> dict:
    x = rng.standard_normal((csr.n_cols, k)).astype(np.float32)
    y = np.asarray(plan.aggregate(x, op="max"))
    _, scale = reference(csr, x)
    err = rel_err(y, max_reference(csr, x), scale)
    rec = plan_launch(plan, ops.bucket_k(k), combine="max")
    rec.update(matrix=plan.name, k=k, combine="max", err=err, ok=err <= BOUND)
    return rec


def pagerank_phase(csr: CSRMatrix, cfg, *, iters: int = 5, damping: float = 0.85,
                   strategy: str | None = None, interpret: bool | None = None) -> dict:
    """``iters`` PageRank steps through ``aslinearoperator`` on the tiles of
    the transition matrix, against the same steps in float64."""
    M, dangling = transition_matrix(csr)
    tiles = build_tiles(M, cfg)
    op = aslinearoperator(tiles, strategy=strategy, interpret=interpret)
    res = pagerank(op, dangling=dangling, damping=damping, tol=0.0, maxiter=iters)
    p = np.asarray(res.x)
    n = M.n_rows
    Msp, Mabs = _scipy(M), _scipy(M, absolute=True)
    v = np.full(n, 1.0 / n)
    p_ref = v
    for _ in range(iters):
        p_prev = p_ref
        p_ref = damping * (Msp @ p_prev + (dangling @ p_prev) * v) + (1 - damping) * v
    scale = Mabs @ np.abs(p_prev) + np.abs(p_ref)
    err = rel_err(p, p_ref, scale)
    rec = launch_record(*op.launch_args, (n,))
    rec.update(cfg=dataclasses.asdict(tiles.cfg), tiles=tiles.n_tiles, matrix="pagerank(m4_kron16)", iterations=int(res.iterations), err=err,
               ok=err <= BOUND and int(res.iterations) == iters)
    return rec


def sharded(csr: CSRMatrix, mesh, plan, rng) -> list:
    """Both placements on ``mesh`` vs float64 and vs the one-chip plan; each
    record's seconds are its own placement's (build, staging and matvec)."""
    from repro.core.distributed import build_sharded_spmv

    x = rng.standard_normal(csr.n_cols).astype(np.float32)
    y_ref, scale = reference(csr, x)
    y_one = np.asarray(plan.matvec(x))
    recs = []
    for mode in ("balanced", "grid"):
        c0, t0 = _COMPILE_S[0], time.perf_counter()
        sh = build_sharded_spmv(csr, mesh, cfg=plan.cfg, mode=mode)
        y = np.asarray(sh.matvec(x))
        shard_devices = sorted(s.device.id for s in sh.data.addressable_shards)
        err = rel_err(y, y_ref, scale)
        err_one = rel_err(y, y_one.astype(np.float64), scale)
        wall_s, compile_s = time.perf_counter() - t0, _COMPILE_S[0] - c0
        recs.append({
            "phase": f"sharded_{mode}", "matrix": plan.name, "mode": mode,
            "cfg": dataclasses.asdict(plan.cfg), "tiles_per_device": sh.loads.tolist(),
            "shard_devices": shard_devices,
            "bytes_in_use": [(d.memory_stats() or {}).get("bytes_in_use")
                             for d in mesh.devices.flat],
            "err": err, "err_vs_one_chip": err_one,
            "wall_s": wall_s, "compile_s": compile_s,
            "ok": err <= BOUND and err_one <= BOUND
            and len(shard_devices) == mesh.devices.size,
        })
        del sh
    return recs


# --- driver --------------------------------------------------------------------


def _run(name: str, fn, *args, **kwargs):
    """Run one phase; return its result and print its records (a record
    that times itself keeps its own seconds)."""
    c0, t0 = _COMPILE_S[0], time.perf_counter()
    try:
        result = fn(*args, **kwargs)
    except Exception as e:  # a failed phase is a record, not a crash
        result = {"ok": False, "error": f"{type(e).__name__}: {e}"}
    recs = result[1] if isinstance(result, tuple) else result
    for rec in recs if isinstance(recs, list) else [recs]:
        rec.setdefault("phase", name)
        rec.setdefault("wall_s", time.perf_counter() - t0)
        rec.setdefault("compile_s", _COMPILE_S[0] - c0)
        print(json.dumps(rec), flush=True)
    return result


def _native(rec: dict) -> bool:
    """A kernel phase ran the fused Pallas kernel natively."""
    if "strategy" not in rec:
        return True
    return rec["strategy"] == "fused" and not rec["interpret"] and rec["tpu_custom_call"]


def one_chip(seed: int, out: Path) -> list:
    rng = np.random.default_rng(seed)
    registry = MatrixRegistry(cache_dir=out / "autotune")
    records = []
    kron = None
    for name, make, overlap in (("m4_kron16", power_law, True),
                                ("asic_320k", asic_320k, False)):
        csr = make(seed)
        result = _run("admit", admit, registry, csr, name)
        if not isinstance(result, tuple):
            records.append(result)
            continue
        plan, rec = result
        records.append(rec)
        if kron is None:
            kron = (csr, plan.cfg)
        records.append(_run("serve", serve, registry, name, csr, rng, overlap=overlap))
        records.append(_run("matvec", matvec, plan, csr, rng))
        records.append(_run("matmat", matmat, plan, csr, rng, 256))
        records.append(_run("aggregate_max", aggregate_max, plan, csr, rng, 8))
    if kron is not None:
        records.append(_run("pagerank", pagerank_phase, *kron))
    return records


def four_chips(seed: int, out: Path) -> list:
    devices = jax.devices()
    if len(devices) < 4:
        return [{"phase": "mesh", "ok": False, "error": f"{len(devices)} devices, need 4"}]
    mesh = jax.make_mesh((4,), ("data",), devices=devices[:4])
    csr = SUITE_SPECS["m5_kron17"](seed)
    registry = MatrixRegistry(cache_dir=out / "autotune", search=False)
    result = _run("admit", admit, registry, csr, "m5_kron17")
    if not isinstance(result, tuple):
        return [result]
    plan, rec = result
    return [rec, *_run("sharded", sharded, csr, mesh, plan, np.random.default_rng(seed))]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=str(ROOT / "smoke_out"))
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded SpMV on a 4-device mesh")
    args = ap.parse_args(argv)

    from repro.runtime import use_compile_cache

    cache = use_compile_cache(ROOT)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}", file=sys.stderr)
        return 1
    out = Path(args.out)
    shutil.rmtree(out / "autotune", ignore_errors=True)  # a fresh search
    out.mkdir(parents=True, exist_ok=True)
    print(json.dumps({"phase": "start", "compile_cache": str(cache),
                      "device_kind": dev.device_kind, "devices": len(jax.devices())}),
          flush=True)

    records = (four_chips if args.four_chips else one_chip)(args.seed, out)
    stats = dev.memory_stats() or {}
    print(json.dumps({"phase": "memory", "peak_bytes_in_use": stats.get("peak_bytes_in_use")}),
          flush=True)
    with open(out / "records.jsonl", "w") as f:
        for rec in records:
            f.write(json.dumps(rec) + "\n")

    failed = [r.get("phase") for r in records if not (r.get("ok") and _native(r))]
    if failed:
        print(f"chip_smoke: failed phases {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
