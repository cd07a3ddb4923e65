"""The harness: one cell of ``BENCHMARK.json``, run once, found by name.

A cell names a configuration and a traffic mix; everything that belongs to
one of them, or to one per-layer metric, is a file of its own under this
directory, so that adding a cell adds files and entries and edits none:

* ``configs/<config>.json`` — the matrix: generator and parameters, the
  pinned tile geometry, the source and what was changed from it;
* ``traffic/<traffic>.json`` — the mix: ``driver`` (a module in
  ``drivers/``), its parameters, and the limits of the correctness check;
* ``metrics/<metric>.py`` (or ``metrics/<metric before its first dot>.py``)
  — a per-layer reader, ``read(name, run) -> float | None``; ``None`` leaves
  the metric out of the line.

A ``tiny`` entry in a configuration or traffic file is read only by the
benchmark's own tests (``tests/conftest.py``), which run each cell at that
size on the CPU.

A driver module has ``setup(run)`` (admission and warm-up),
``window(run, seconds) -> dict`` (the measured window; the end-to-end
metrics by name, plus what the readers and the check need),
``check(run, answer=None) -> list`` of ``(name, value, limit)``, each
``value <= limit`` for a correct run, and ``CONTROL``, the lower-precision
answer that ``check(run, answer=CONTROL)`` judges in the program's place
(``control.py``).

Set-up (``setup_s``) runs from process start to the window's start:
matrix generation, admission, staging, compile or cache load, warm-up.
JAX's persistent compilation cache is ``<checkout>/.jax_cache``, a fixed
path, so only a cell's first run in a checkout compiles.
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import math
import re
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import ModuleType
from typing import Optional

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parent
BENCHMARK = CHECKOUT / "BENCHMARK.json"
COMPILE_CACHE = CHECKOUT / ".jax_cache"
_NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")


class Compiles:
    """JAX's compile events, from ``jax.monitoring`` (as ``chip_smoke.py``
    counts them): backend compiles, loads from the persistent cache, and
    compile requests of either kind."""

    def __init__(self):
        self.backend_s = 0.0
        self.cache_load_s = 0.0
        self.requests = 0

    def listen(self) -> "Compiles":
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def _duration(self, event: str, duration_s: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.backend_s += duration_s
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
            self.cache_load_s += duration_s

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.requests += 1

    @property
    def seconds(self) -> float:
        return self.backend_s + self.cache_load_s


@dataclasses.dataclass
class Run:
    """One run of one cell: what the harness, the driver and the readers share."""

    name: str
    cell: dict
    config: dict
    traffic: dict
    seed: int
    csr: object = None  # matrices.Csr, the benchmark's own copy
    matrix: object = None  # the same matrix as the program's CSRMatrix
    admit_s: float = 0.0  # host clock around admission (readers: admit_s)
    compile_s: float = 0.0  # compiles and cache loads during set-up
    state: dict = dataclasses.field(default_factory=dict)  # the driver's
    window: dict = dataclasses.field(default_factory=dict)  # window results
    trace: Optional[dict] = None  # trace.reduce() of a traced window
    device_kind: str = ""

    @staticmethod
    def span(name: str):
        """A host span in the profiler's trace, named ``chipbench.<name>``."""
        import jax

        return jax.profiler.TraceAnnotation(f"chipbench.{name}")


def _load_json(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def _module(path: Path, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _checked(name: str, what: str) -> str:
    if not isinstance(name, str) or not _NAME.fullmatch(name):
        raise ValueError(f"bad {what} name {name!r}")
    return name


def driver(kind: str, root: Path = HERE) -> ModuleType:
    """The driver module ``drivers/<kind>.py``."""
    return _module(root / "drivers" / f"{_checked(kind, 'driver')}.py",
                   f"chipbench_driver_{kind}")


def reader(metric: str, root: Path = HERE) -> ModuleType:
    """The reader of a per-layer metric: ``metrics/<metric>.py``, else the
    file of its name before the first dot (one reader, several cells)."""
    _checked(metric, "metric")
    for stem in (metric, metric.split(".")[0]):
        path = root / "metrics" / f"{stem}.py"
        if path.is_file():
            return _module(path, f"chipbench_metric_{stem.replace('.', '_')}")
    raise FileNotFoundError(f"no reader for metric {metric!r} under {root / 'metrics'}")


def prepare(bench: dict, name: str, seed: int, root: Path = HERE) -> Run:
    """The run of cell ``name``: its entry, configuration and traffic files."""
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in the benchmark")
    cell = cells[name]
    config = _load_json(root / "configs" / f"{_checked(cell['config'], 'config')}.json")
    traffic = _load_json(root / "traffic" / f"{_checked(cell['traffic'], 'traffic')}.json")
    return Run(name=name, cell=cell, config=config, traffic=traffic, seed=int(seed))


def make_matrix(run: Run) -> None:
    """The configuration's matrix from the seed, as the benchmark's arrays and
    as the program's ``CSRMatrix``."""
    from chipbench import matrices
    from repro.core.formats import CSRMatrix

    with run.span("make_matrix"):
        run.csr = matrices.make(run.config, run.seed)
        c = run.csr
        run.matrix = CSRMatrix(c.indptr, c.indices, c.data, c.shape)


def partition_config(run: Run):
    """The configuration's pinned tile geometry as the program's type."""
    from repro.core.partition import PartitionConfig

    return PartitionConfig(**run.config["partition"])


def admit(run: Run):
    """The configuration's matrix admitted through ``MatrixRegistry.admit``
    with its pinned geometry (autotune bypassed); returns the plan and
    records ``run.admit_s``."""
    from repro.serving import MatrixRegistry

    registry = MatrixRegistry(search=False)
    t0 = time.perf_counter()
    with run.span("admit"):
        plan = registry.admit(run.matrix, run.config["name"], cfg=partition_config(run))
    run.admit_s = time.perf_counter() - t0
    run.state.update(registry=registry, plan=plan)
    return plan


def metrics_of(bench: dict, name: str, kind: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries that cell ``name`` reports."""
    return [m for m in bench[kind] if name in m.get("workloads", [name])]


def execute(bench: dict, run: Run, *, seconds: float, trace: bool,
            t_start: float, compiles: Compiles, root: Path = HERE,
            peak_memory=None) -> dict:
    """Set up, measure, check; return the result line as a dict.

    ``peak_memory`` returns the device's peak bytes (``None`` where the
    backend does not report it).
    """
    import jax

    from chipbench import trace as tracing

    drv = driver(run.traffic["driver"], root)
    run.device_kind = jax.devices()[0].device_kind
    c0 = compiles.seconds
    t0 = time.perf_counter()
    make_matrix(run)
    t1 = time.perf_counter()
    drv.setup(run, seconds)
    run.compile_s = compiles.seconds - c0
    setup_s = time.perf_counter() - t_start
    # where set-up went, for the record (host clock)
    run.state["setup_phases"] = {"before_matrix_s": t0 - t_start, "matrix_s": t1 - t0,
                                 "driver_setup_s": setup_s - (t1 - t_start)}

    requests0 = compiles.requests
    log_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    try:
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # host spans are our own annotations
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(log_dir, profiler_options=opts)
        with run.span("window"):
            run.window = drv.window(run, seconds)
        if trace:
            jax.profiler.stop_trace()
            run.trace = tracing.reduce(tracing.load_dir(log_dir))
    finally:
        if log_dir is not None:
            shutil.rmtree(log_dir, ignore_errors=True)
    window_compiles = compiles.requests - requests0
    memory_peak = peak_memory() if peak_memory is not None else None

    compared = drv.check(run)

    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    metrics = {}
    if trace:
        for m in metrics_of(bench, run.name, "per_layer"):
            value = reader(m["name"], root).read(m["name"], run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        if run.trace is not None and run.trace["busy_s"] > 0:
            device["busy_s"] = run.trace["busy_s"]
            device["window_s"] = run.trace["window_s"]
    else:
        for m in metrics_of(bench, run.name, "end_to_end"):
            value = setup_s if m["name"] == "setup_s" else run.window[m["name"]]
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    result = {
        "correct": all(v <= lim for _, v, lim in compared),
        "attempted": int(run.window["attempted"]),
        "failed": int(run.window["failed"]),
        "metrics": metrics,
        "device": device,
    }
    if trace and run.trace is not None:
        result["breakdown"] = {"device_ops": run.trace["device_ops"],
                               "idle_gaps": run.trace["idle_gaps"]}
    result["window_compiles"] = window_compiles  # main() prints it apart
    result["limits"] = {n: {"value": v, "limit": lim} for n, v, lim in compared}
    return result


def _peak_memory() -> Optional[int]:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in jax.devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def use_compile_cache(path: Path = COMPILE_CACHE) -> None:
    """Keep every program in the persistent cache at ``path``."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def chips_ok(cell: dict) -> Optional[str]:
    """Why this machine cannot run ``cell`` (no TPU, too few chips), or None."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        return f"needs a TPU, JAX found {devices[0].platform!r}"
    if len(devices) < cell["chips"]:
        return f"needs {cell['chips']} chips, JAX found {len(devices)}"
    return None


def report(result: dict) -> None:
    """The compared numbers as the last lines of stderr; the result as the
    last line of stdout."""
    for c in result["limits"].values():
        if not math.isfinite(c["value"]):  # no answer, or a non-finite one
            c["value"] = sys.float_info.max
    for name, c in result["limits"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def main(argv, *, t_start: float) -> int:
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = _load_json(BENCHMARK)
    run = prepare(bench, args.workload, args.seed)
    sys.path.insert(0, str(CHECKOUT / "src"))
    use_compile_cache()
    compiles = Compiles().listen()
    why = chips_ok(run.cell)
    if why is not None:
        print(f"chipbench: {why}", file=sys.stderr)
        return 1
    result = execute(bench, run, seconds=args.seconds, trace=bool(args.trace),
                     t_start=t_start, compiles=compiles, peak_memory=_peak_memory)
    print(json.dumps({"window_compiles": result.pop("window_compiles"),
                      "admit_s": run.admit_s, "compile_s": run.compile_s,
                      **run.state["setup_phases"],
                      "longest_call_s": run.window.get("longest_call_s")}), flush=True)
    report(result)
    return 0
