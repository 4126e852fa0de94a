"""Production meshes.

Defined as FUNCTIONS (not module constants) so importing this module never
touches jax device state — the dry-run must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* the
first jax device query, and smoke tests must keep seeing 1 device.

Mesh geometry (TPU v5e pods):

    single-pod : (data=16, model=16)            = 256 chips
    multi-pod  : (pod=2, data=16, model=16)     = 512 chips

``model`` stays inside one pod's ICI domain; the ``pod`` axis carries only
data parallelism (one gradient all-reduce per step over DCN).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape, names) -> Mesh:
    """``jax.make_mesh`` with every axis typed Auto."""
    return jax.make_mesh(tuple(shape), tuple(names),
                         axis_types=(AxisType.Auto,) * len(names))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(*, model: int = 1) -> Mesh:
    """Single-host mesh for smoke tests/examples (1 device by default)."""
    n = len(jax.devices())
    assert n % model == 0
    return make_mesh((n // model, model), ("data", "model"))
