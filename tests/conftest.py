import os

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture(autouse=True, scope="session")
def _flight_dumps_tmpdir(tmp_path_factory):
    """Route flight-recorder trigger dumps into a session tmp dir.

    The recorder is always on, and tests that legitimately induce
    deadline misses (virtual-clock serving tests) would otherwise litter
    the repo root with flight_*.json artifacts.
    """
    d = tmp_path_factory.mktemp("flight_dumps")
    old = os.environ.get("REPRO_FLIGHT_DIR")
    os.environ["REPRO_FLIGHT_DIR"] = str(d)
    yield
    if old is None:
        os.environ.pop("REPRO_FLIGHT_DIR", None)
    else:
        os.environ["REPRO_FLIGHT_DIR"] = old


@pytest.fixture
def kernel_strategy(monkeypatch):
    """Resolve a kernel-path case to the ``strategy`` a call passes.

    ``"fused_streamed"`` is ``strategy="fused"`` with the lane gather's X
    over :data:`~repro.kernels.hbp_spmv.X_BUDGET` (patched to 0 here), so
    every launch runs the lane gather and each grid step streams one
    segment of X and carries one tile: the branch a matrix too wide for a
    resident X takes.  The jitted wrappers are retraced on the way in and
    out, since the budget is read at trace time.  Any other case is the
    strategy itself."""
    import importlib

    import jax

    streamed = []

    def resolve(case: str) -> str:
        if case != "fused_streamed":
            return case
        kernels = importlib.import_module("repro.kernels.hbp_spmv")
        monkeypatch.setattr(kernels, "X_BUDGET", 0)
        jax.clear_caches()
        streamed.append(case)
        return "fused"

    yield resolve
    if streamed:
        jax.clear_caches()


def hypothesis_or_shim():
    """(given, settings, st) — real hypothesis, or decorators that skip.

    Lets a module keep its deterministic unit tests runnable when
    hypothesis is absent, with only the ``@given`` property tests
    skipping.  Usage::

        from conftest import hypothesis_or_shim
        given, settings, st = hypothesis_or_shim()
    """
    try:
        from hypothesis import given, settings, strategies as st

        return given, settings, st
    except ImportError:
        class _NoHypStrategies:
            def __getattr__(self, name):
                return lambda *a, **k: None

        def settings(*a, **k):
            return lambda f: f

        def given(*a, **k):
            return lambda f: pytest.mark.skip(reason="hypothesis not installed")(f)

        return given, settings, _NoHypStrategies()
