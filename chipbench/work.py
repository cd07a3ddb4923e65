"""The work a sparse product needs, counted the same way for every format.

A kernel's roofline share is its least possible time over its measured
device time.  The least time comes from the work of the CSR product the
call stands for, not from the tiles that implement it: the padded tile
stream of the program's own byte model (``ops.modeled_launch_bytes``)
shrinks when a later change removes padding, and would then hide the gain.

Per call of ``Y = A X`` with ``X`` of ``k`` columns:

* FLOPs ``2 * nnz * k`` (one multiply and one add per stored entry and column);
* bytes ``nnz * (4 + 4)`` for f32 values and i32 column ids, ``4 * (n_rows + 1)``
  row pointers, ``4 * k * n_cols`` of x read once and ``4 * k * n_rows`` of y
  written once.

Peaks are per ``device_kind`` in ``peaks.json``; a device that is not there
is an error, never a default.
"""
from __future__ import annotations

import json
from pathlib import Path

__all__ = ["csr_work", "peaks", "roofline"]

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def csr_work(nnz: int, n_rows: int, n_cols: int, k: int = 1) -> tuple[float, float]:
    """``(flops, bytes)`` of one ``A @ X`` with ``k`` right-hand sides."""
    flops = 2.0 * nnz * k
    nbytes = 8.0 * nnz + 4.0 * (n_rows + 1) + 4.0 * k * (n_cols + n_rows)
    return flops, nbytes


def peaks(device_kind: str, path: Path = PEAKS) -> dict:
    """The peak table's row for ``device_kind`` (``KeyError`` if absent)."""
    table = json.loads(Path(path).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path.name}")
    return table[device_kind]


def roofline(flops: float, nbytes: float, kernel_s: float, peak: dict):
    """``(share in %, "hbm" | "compute")``: the least time over ``kernel_s``,
    and which of the two peaks sets the least time."""
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    t_flop = flops / peak["flops_per_s"]
    bound = "hbm" if t_mem >= t_flop else "compute"
    return 100.0 * max(t_mem, t_flop) / kernel_s, bound
