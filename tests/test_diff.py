"""Differential performance attribution: the diff tool + the CI wiring.

Pinned behaviors: a synthetic 2x slowdown in one phase must surface as
the top-ranked culprit (for both artifact kinds — obs dumps and bench
JSONs); the output is deterministic; mixed artifact kinds are rejected;
and ``benchmarks.compare --diff-out`` leaves the markdown culprit report
exactly when the gate fails.
"""
import json

import pytest

from benchmarks.compare import main as compare_main
from repro.analysis.diff import (
    artifact_kind,
    diff_artifacts,
    main as diff_main,
    render_markdown,
    render_text,
)


def _obs_dump(scale_serve: float = 1.0) -> dict:
    """A fabricated obs snapshot with admit/kernels/serve phases, request
    aggregates and attr counters; ``scale_serve`` multiplies the serve
    phase (the synthetic regression)."""
    return {
        "schema": 1,
        "registries": [
            {
                "registry": "serving",
                "metrics": [
                    {
                        "name": "attr.compute_s",
                        "labels": {"matrix": "A", "strategy": "stable"},
                        "type": "counter",
                        "value": 0.010 * scale_serve,
                    },
                    {
                        "name": "attr.launches",
                        "labels": {"matrix": "A", "strategy": "stable"},
                        "type": "counter",
                        "value": 5.0,
                    },
                ],
            }
        ],
        "spans": [
            {"name": "admit.hash", "count": 2, "total_ms": 8.0, "mean_ms": 4.0, "max_ms": 5.0},
            {
                "name": "kernels.launch",
                "count": 10,
                "total_ms": 20.0,
                "mean_ms": 2.0,
                "max_ms": 3.0,
            },
            {
                "name": "serve.flush",
                "count": 4,
                "total_ms": 40.0 * scale_serve,
                "mean_ms": 10.0 * scale_serve,
                "max_ms": 12.0,
            },
        ],
        "requests": [
            {
                "key": "A",
                "queue_wait_s": 0.002,
                "compute_share_s": 0.001 * scale_serve,
                "latency_s": 0.004,
            }
        ],
    }


def _bench(scale_spmm: float = 1.0) -> dict:
    return {
        "schema": 1,
        "benches": [
            {"name": "preprocess/hash", "min_us": 100.0, "median_us": 110.0},
            {"name": "spmm/grid", "min_us": 200.0 * scale_spmm, "median_us": 220.0 * scale_spmm},
        ],
    }


# --- detection ---------------------------------------------------------------


def test_obs_diff_ranks_the_2x_phase_as_top_culprit():
    result = diff_artifacts(_obs_dump(), _obs_dump(scale_serve=2.0))
    assert result["kind"] == "obs"
    top = result["rows"][0]
    assert top["name"] == "serve.flush" and top["phase"] == "serve"
    assert top["ratio"] == pytest.approx(2.0)
    assert result["culprit"]["name"] == "serve.flush"
    # the phase rollup agrees
    assert result["phases"][0]["phase"] == "serve"
    assert result["phases"][0]["ratio"] == pytest.approx(2.0)
    # untouched phases sit at 1.0
    by_phase = {p["phase"]: p for p in result["phases"]}
    assert by_phase["admit"]["ratio"] == pytest.approx(1.0)
    assert by_phase["kernels"]["ratio"] == pytest.approx(1.0)


def test_bench_diff_ranks_the_2x_record_as_top_culprit():
    result = diff_artifacts(_bench(), _bench(scale_spmm=2.0))
    assert result["kind"] == "bench"
    top = result["rows"][0]
    assert top["name"] == "spmm/grid" and top["phase"] == "spmm"
    assert top["ratio"] == pytest.approx(2.0)
    assert "spmm" in render_text(result).split("\n")[1]  # verdict names it


def test_counters_never_outrank_timed_rows():
    a, b = _obs_dump(), _obs_dump()
    # blow up a pure-count counter; timed rows are unchanged
    b["registries"][0]["metrics"][1]["value"] = 5000.0
    result = diff_artifacts(a, b)
    timed = [r for r in result["rows"] if r["excess"] is not None]
    counters = [r for r in result["rows"] if r["excess"] is None]
    assert counters and timed
    assert result["rows"].index(counters[0]) > result["rows"].index(timed[-1])
    assert result["culprit"] is None  # a counter is never the culprit


def test_seconds_counters_diff_as_time():
    result = diff_artifacts(_obs_dump(), _obs_dump(scale_serve=2.0))
    row = next(r for r in result["rows"] if r["name"].startswith("attr.compute_s"))
    assert row["unit"] == "ms" and row["excess"] == pytest.approx(10.0)


# --- safety / determinism ----------------------------------------------------


def test_mixed_kinds_are_rejected_and_unknown_payloads_raise():
    with pytest.raises(ValueError, match="cannot diff"):
        diff_artifacts(_obs_dump(), _bench())
    with pytest.raises(ValueError, match="unrecognized"):
        artifact_kind({"something": 1})


def test_diff_is_deterministic_and_na_safe_on_empty_dumps():
    empty = {"schema": 1, "registries": [], "spans": [], "requests": []}
    result = diff_artifacts(empty, empty)
    assert result["rows"] == [] and result["culprit"] is None
    text = render_text(result)
    assert "n/a" in text
    assert render_text(result) == text
    full = diff_artifacts(_obs_dump(), _obs_dump(scale_serve=2.0))
    assert render_markdown(full) == render_markdown(full)


def test_cli_writes_markdown_report(tmp_path, capsys):
    a, b = tmp_path / "a.json", tmp_path / "b.json"
    a.write_text(json.dumps(_obs_dump()))
    b.write_text(json.dumps(_obs_dump(scale_serve=2.0)))
    out = tmp_path / "diff.md"
    assert diff_main([str(a), str(b), "--out", str(out)]) == 0
    assert "serve.flush" in capsys.readouterr().out
    md = out.read_text()
    assert md.startswith("# Performance diff")
    assert "serve.flush" in md and "2.00x" in md


# --- compare.py integration --------------------------------------------------


def _write(path, payload):
    path.write_text(json.dumps(payload))
    return str(path)


def test_compare_gate_failure_writes_culprit_report(tmp_path, capsys):
    base = _write(tmp_path / "base.json", _bench())
    cur = _write(tmp_path / "cur.json", _bench(scale_spmm=2.0))
    out = tmp_path / "BENCH_diff.md"
    rc = compare_main([cur, "--baseline", base, "--diff-out", str(out)])
    assert rc == 1
    md = out.read_text()
    assert "spmm/grid" in md  # the report names the regressed record...
    assert "| spmm |" in md  # ...and the regressed phase
    assert "verdict: worst regression is spmm/grid" in md


def test_compare_clean_gate_writes_no_report(tmp_path):
    base = _write(tmp_path / "base.json", _bench())
    cur = _write(tmp_path / "cur.json", _bench())
    out = tmp_path / "BENCH_diff.md"
    assert compare_main([cur, "--baseline", base, "--diff-out", str(out)]) == 0
    assert not out.exists()


def test_compare_diff_report_respects_prefix_gating(tmp_path):
    """Records outside the gated prefixes regressing must neither fail the
    gate nor appear in the culprit report."""
    base = _write(tmp_path / "base.json", _bench())
    cur_payload = _bench(scale_spmm=2.0)
    cur_payload["benches"][0]["min_us"] = 1000.0  # huge, but ungated below
    cur = _write(tmp_path / "cur.json", cur_payload)
    out = tmp_path / "BENCH_diff.md"
    rc = compare_main(
        [cur, "--baseline", base, "--prefix", "spmm", "--diff-out", str(out)]
    )
    assert rc == 1
    md = out.read_text()
    assert "spmm/grid" in md
    assert "preprocess/hash" not in md
