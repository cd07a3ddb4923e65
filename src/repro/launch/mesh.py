"""Production mesh construction.

A *function*, not a module-level constant, so importing this module never
touches jax device state (the dry-run must set XLA_FLAGS before first init).

Topology: TPU v5e, 16×16 = 256 chips per pod; the multi-pod mesh stacks a
"pod" data-parallel axis across 2 pods (512 chips).  When the process holds
more devices than a single-pod mesh needs (the 512-device dry-run), the
single-pod mesh is built on the first 256 devices.
"""
from __future__ import annotations

import numpy as np

import jax

__all__ = ["make_production_mesh", "mesh_devices"]


def mesh_devices(n: int):
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"mesh needs {n} devices, found {len(devs)} — the dry-run must "
            "set XLA_FLAGS=--xla_force_host_platform_device_count=512 before "
            "any jax import"
        )
    return np.array(devs[:n])


def _auto_mesh(shape, axes, n):
    # Auto axes: the models place arrays with with_sharding_constraint under
    # logical rules, which explicit-axis meshes (make_mesh's default) refuse
    auto = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(shape, axes, devices=mesh_devices(n), axis_types=auto)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes, int(np.prod(shape)))


def make_host_mesh():
    """Degenerate 1×1 mesh for smoke tests / single-host examples."""
    return _auto_mesh((1, 1), ("data", "model"), 1)
