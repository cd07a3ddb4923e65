"""``correct`` fails where it should: the bfloat16 control, and faults planted
under the timed path (the harness's look for a chip is skipped)."""
import json
import time

import jax.numpy as jnp
import pytest

from chipbench import control, harness
from repro.kernels import ops

from conftest import CELLS, cell_of


@pytest.mark.parametrize("cell", CELLS)
def test_control_reads_not_correct(tiny, cell):
    bench, root = tiny
    cell_of(bench, cell)
    summary = control.readings(bench, cell, [11, 12, 13], 0.5, root=root,
                               out=lambda line: None)
    run = harness.prepare(bench, cell, 11, root)
    limit = run.traffic["check"]["max_rel_err"]
    assert summary["lower"] <= limit < summary["upper"], summary
    assert not summary["control_ever_correct"], summary


def _unchanged(real):
    return lambda tiles, x, **kw: jnp.asarray(x, jnp.float32)


def _half_batch(real):
    """The first half of the columns computed, the rest given their mean."""
    def spmm(tiles, x, **kw):
        x = jnp.asarray(x, jnp.float32)
        keep = -(-x.shape[1] // 2)
        y = real(tiles, x[:, :keep], **kw)
        rest = jnp.repeat(y.mean(axis=1, keepdims=True), x.shape[1] - keep, axis=1)
        return jnp.concatenate([y, rest], axis=1)
    return spmm


def _altered(real):
    """Each answer's largest entry off by 1%."""
    def f(tiles, x, **kw):
        y = real(tiles, x, **kw)
        i = jnp.argmax(jnp.abs(y), axis=0)
        if y.ndim == 1:
            return y.at[i].multiply(1.01)
        return y.at[i, jnp.arange(y.shape[1])].multiply(1.01)
    return f


FAULTS = {"unchanged": (_unchanged, _unchanged), "half_batch": (None, _half_batch),
          "altered": (_altered, _altered)}


@pytest.mark.parametrize("fault", list(FAULTS))
@pytest.mark.parametrize("cell", CELLS)
def test_planted_fault_reads_not_correct(tiny, monkeypatch, cell, fault):
    """Each fault the cell can have (one chip: no exchange between chips to
    drop; a batch to halve only where a call carries several columns)."""
    bench, root = tiny
    path = root / "traffic" / f"{cell_of(bench, cell)['traffic']}.json"
    t = json.loads(path.read_text())
    if fault == "half_batch":
        if "engine" in t:
            # a burst the engine coalesces, so batches are wider than one request
            t["arrivals"]["rate_per_s"] = 400.0
            t["engine"]["max_wait_s"] = 0.05
            path.write_text(json.dumps(t))
        elif t.get("k", 1) == 1:
            pytest.skip("one column per call: no batch to halve")
    spmv, spmm = FAULTS[fault]
    if spmv is not None:
        monkeypatch.setattr(ops, "hbp_spmv", spmv(ops.hbp_spmv))
    monkeypatch.setattr(ops, "hbp_spmm", spmm(ops.hbp_spmm))
    run = harness.prepare(bench, cell, 7, root)
    result = harness.execute(bench, run, seconds=0.5, trace=False,
                             t_start=time.perf_counter(), compiles=harness.Compiles(),
                             root=root)
    assert not result["correct"], result["limits"]
