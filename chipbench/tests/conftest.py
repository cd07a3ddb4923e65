"""Fixtures for the benchmark's own tests: a throwaway harness root at a tiny
size, run on the CPU through the harness's functions.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest chipbench/tests

Each configuration file carries a ``tiny`` entry, and a traffic file may:
keys that override the file's own for these tests (a nested group is
merged key by key).  A cell whose configuration has no ``tiny`` entry is
skipped.
"""
import json
import shutil
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
CHIPBENCH = HERE.parent
sys.path[:0] = [str(CHIPBENCH.parent), str(CHIPBENCH.parent / "src")]

BENCH = json.loads((CHIPBENCH.parent / "BENCHMARK.json").read_text())
CELLS = [c["name"] for c in BENCH["workloads"]]


def _merged(base: dict, over: dict) -> dict:
    out = dict(base)
    for key, value in over.items():
        out[key] = _merged(base.get(key, {}), value) if isinstance(value, dict) else value
    return out


def _tiny(path: Path):
    """The file's content with its ``tiny`` entry applied (None without one)."""
    data = json.loads(path.read_text())
    over = data.pop("tiny", None)
    return None if over is None else _merged(data, over), data


@pytest.fixture
def tiny(tmp_path):
    """``(bench, root)``: the benchmark's cells and metrics at a tiny size,
    with the real drivers and readers copied into a throwaway root."""
    bench = json.loads(json.dumps(BENCH))
    for sub in ("drivers", "metrics"):
        shutil.copytree(CHIPBENCH / sub, tmp_path / sub)
    small = set()
    for sub in ("configs", "traffic"):
        (tmp_path / sub).mkdir()
        for path in (CHIPBENCH / sub).glob("*.json"):
            shrunk, data = _tiny(path)
            if shrunk is not None:
                small.add((sub, path.stem))
            elif sub == "traffic":
                shrunk = data
            if shrunk is not None:
                (tmp_path / sub / path.name).write_text(json.dumps(shrunk))
    bench["workloads"] = [c for c in bench["workloads"] if ("configs", c["config"]) in small]
    return bench, tmp_path


def cell_of(bench: dict, name: str) -> dict:
    """The tiny benchmark's cell ``name``; skips a cell with no tiny stand-in."""
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    pytest.skip(f"{name}: its configuration has no tiny entry")


@pytest.fixture
def pallas(monkeypatch):
    """Serve through the fused Pallas kernels, interpreted on the CPU."""
    from repro.kernels import ops

    monkeypatch.setattr(ops, "default_strategy", lambda: "fused")
