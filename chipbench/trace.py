"""Reduce a JAX profiler trace to the device numbers the benchmark reports.

``load_dir`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and keeps
only what the reduction reads, as plain lists (the form the checked-in test
trace has):

* from each device plane (``/device:TPU:<n>``), the ``XLA Ops`` line: one
  event per operation that ran on the device, Pallas kernels included;
* from the host plane, the benchmark's own spans (``chipbench.*``, written by
  ``jax.profiler.TraceAnnotation``).

``reduce`` then works inside the window span (``chipbench.window``):

* ``busy_s``: the union of the op intervals, averaged over the devices that ran
  any op; ``window_s``: the span's length;
* ``kernel_s``: the summed device time of the Pallas kernels: the events
  whose HLO is a ``tpu_custom_call`` (the trace names each op by its HLO
  text, e.g. ``%hbp_spmv_fused.8 = f32[40256,8,1]... custom-call(...),
  custom_call_target="tpu_custom_call"``; the HBP kernels' instructions are
  named after their jitted wrappers, ``hbp_spmv_fused``/``hbp_spmm_fused``);
* ``device_ops``: device seconds per op name (the instruction's name without
  its ``%`` and numeric suffix), the ten largest; loops (``while``), which
  contain the ops they run, are left out of it;
* ``idle_gaps``: each gap between device ops, named by the innermost host span
  that covers its middle (``chipbench.window`` where no inner span does),
  summed per name, the ten largest.
"""
from __future__ import annotations

import re
from pathlib import Path

__all__ = ["load_dir", "from_profile", "device_event", "reduce"]

OPS_LINE = "XLA Ops"
HOST_PREFIX = "chipbench."
WINDOW = "chipbench.window"
KERNEL = "kernel"  # the mark of a Pallas kernel's event
_CONTAINERS = {"while", "conditional", "call"}
_HLO_NAME = re.compile(r"%?([^\s=]+?)(?:\.\d+)* = ")


def _is_device(plane_name: str) -> bool:
    return plane_name.startswith("/device:TPU")


def device_event(text: str, start_ns: float, dur_ns: float) -> list:
    """``[name, start_ns, dur_ns, mark]`` of one ``XLA Ops`` event, from the
    HLO text the trace names it by: ``mark`` is ``KERNEL`` for a Pallas
    kernel (a ``tpu_custom_call``), else empty."""
    m = _HLO_NAME.match(text)
    mark = KERNEL if 'custom_call_target="tpu_custom_call"' in text else ""
    return [m.group(1) if m else text[:64], start_ns, dur_ns, mark]


def from_profile(pd) -> dict:
    """The parts of a ``jax.profiler.ProfileData`` the reduction reads."""
    planes = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            if _is_device(plane.name):
                if line.name != OPS_LINE:
                    continue
                events = [device_event(e.name, e.start_ns, e.duration_ns)
                          for e in line.events]
            else:
                events = [[e.name, e.start_ns, e.duration_ns] for e in line.events
                          if e.name.startswith(HOST_PREFIX)]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def load_dir(log_dir) -> dict:
    """:func:`from_profile` of the newest ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData

    files = sorted(Path(log_dir).rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return from_profile(ProfileData.from_file(str(files[-1])))


def _clip(events, lo: float, hi: float):
    """(name, start, end, mark) of events overlapping [lo, hi], clipped to it."""
    out = []
    for name, start, dur, mark in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            out.append((name, s, e, mark))
    return out


def _union(intervals):
    """Merged, sorted (start, end) intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _top(totals: dict, n: int = 10):
    return [[k, v] for k, v in sorted(totals.items(), key=lambda kv: -kv[1])[:n]]


def reduce(trace: dict) -> dict:
    """Device busy and kernel time, op and idle breakdowns, in the window."""
    host = [(name, s, s + d) for p in trace["planes"] if not _is_device(p["name"])
            for line in p["lines"] for name, s, d in line["events"]]
    windows = [(s, e) for name, s, e in host if name == WINDOW]
    if not windows:
        raise ValueError(f"the trace holds no {WINDOW} span")
    w0, w1 = windows[0]
    spans = [(name, s, e) for name, s, e in host if name != WINDOW and s < w1 and e > w0]

    busy, kernel_ns, per_op, gaps = [], 0.0, {}, {}
    for plane in trace["planes"]:
        if not _is_device(plane["name"]):
            continue
        ops = [ev for line in plane["lines"] if line["name"] == OPS_LINE
               for ev in _clip(line["events"], w0, w1)]
        if not ops:
            continue
        for name, s, e, mark in ops:
            if name not in _CONTAINERS:
                per_op[name] = per_op.get(name, 0.0) + (e - s) / 1e9
            if mark == KERNEL:
                kernel_ns += e - s
        merged = _union((s, e) for _, s, e, _ in ops)
        busy.append(sum(e - s for s, e in merged))
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 <= g0:
                continue
            mid = (g0 + g1) / 2
            covering = [(e - s, name) for name, s, e in spans if s <= mid <= e]
            name = min(covering)[1] if covering else WINDOW
            gaps[name] = gaps.get(name, 0.0) + (g1 - g0) / 1e9
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": sum(busy) / len(busy) / 1e9 if busy else 0.0,
        "devices": len(busy),
        "kernel_s": kernel_ns / 1e9,
        "device_ops": _top(per_op),
        "idle_gaps": _top(gaps),
    }
