"""The phases of ``chip_smoke.py`` at a tiny size on the CPU.

The kernels run in interpret mode here, chosen by the test through the
registry and operator arguments, never through a script option; the
script itself refuses to run off a TPU.
"""
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.core.matrices import circuit, rmat
from repro.core.partition import PartitionConfig
from repro.serving import MatrixRegistry

ROOT = Path(__file__).resolve().parent.parent
CFG = PartitionConfig(row_block=64, col_block=128, group=8, lane=8)


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def matrices():
    return {"power_law": rmat(1 << 9, 6000, seed=4), "circuit": circuit(700, seed=1)}


def _interpreted(rec):
    assert rec["ok"], rec
    assert rec["strategy"] == "fused" and rec["interpret"] is True
    assert rec["err"] <= 1e-4


@pytest.mark.parametrize("name,overlap", [("power_law", True), ("circuit", False)])
def test_serving_phases_match_reference(smoke, matrices, tmp_path, name, overlap):
    csr = matrices[name]
    rng = np.random.default_rng(0)
    registry = MatrixRegistry(
        cache_dir=tmp_path, search=False, strategy="fused", interpret=True
    )
    plan, rec = smoke.admit(registry, csr, name)
    assert rec["ok"] and rec["strategy"] == "fused" and rec["interpret"] is True
    served = smoke.serve(registry, name, csr, rng, overlap=overlap)
    _interpreted(served)
    assert served["batch_k"] == list(smoke.SERVE_KS)
    assert served["requests"] == sum(smoke.SERVE_KS)
    _interpreted(smoke.matvec(plan, csr, rng))
    _interpreted(smoke.matmat(plan, csr, rng, 256))
    _interpreted(smoke.aggregate_max(plan, csr, rng, 8))


def test_pagerank_phase_matches_reference(smoke, matrices):
    rec = smoke.pagerank_phase(
        matrices["power_law"], CFG, strategy="fused", interpret=True
    )
    _interpreted(rec)
    assert rec["iterations"] == 5


def test_references_catch_a_wrong_answer(smoke, matrices):
    """The error measure is not vacuous: one wrong entry fails the bound."""
    csr = matrices["circuit"]
    x = np.random.default_rng(1).standard_normal((csr.n_cols, 3))
    y, scale = smoke.reference(csr, x)
    assert smoke.rel_err(y, y, scale) == 0.0
    bad = y.copy()
    bad[5, 1] += 1e-3 * scale[5, 1]
    assert smoke.rel_err(bad, y, scale) > smoke.BOUND
    ymax = smoke.max_reference(csr, x)
    dense = csr.to_dense()
    expect = np.where(dense[:, :, None] != 0, dense[:, :, None] * x[None], -np.inf).max(1)
    np.testing.assert_allclose(ymax, np.where(np.isneginf(expect), 0.0, expect))


def test_sharded_phase_on_four_virtual_devices(tmp_path):
    code = f"""
import importlib.util, numpy as np, jax
from repro.core.matrices import rmat
from repro.serving import MatrixRegistry
spec = importlib.util.spec_from_file_location("chip_smoke", {str(ROOT / "chip_smoke.py")!r})
smoke = importlib.util.module_from_spec(spec); spec.loader.exec_module(smoke)
mesh = jax.make_mesh((4,), ("data",), devices=jax.devices()[:4])
csr = rmat(1 << 10, 12000, seed=5)
reg = MatrixRegistry(cache_dir={str(tmp_path)!r}, search=False, strategy="fused", interpret=True)
plan, _ = smoke.admit(reg, csr, "kron")
recs = smoke.sharded(csr, mesh, plan, np.random.default_rng(0))
assert [r["mode"] for r in recs] == ["balanced", "grid"], recs
assert all(r["ok"] and r["shard_devices"] == [0, 1, 2, 3] for r in recs), recs
print("SHARDED-SMOKE-OK")
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = str(ROOT / "src")
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                       text=True, timeout=600)
    assert "SHARDED-SMOKE-OK" in r.stdout, r.stdout + r.stderr


def test_script_refuses_a_cpu(tmp_path):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py"), "--out", str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert not any(json.loads(line).get("ok") for line in r.stdout.splitlines()
                   if line.startswith("{"))
