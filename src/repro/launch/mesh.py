"""Production mesh construction.

Defined as FUNCTIONS (not module constants) so importing never touches jax
device state — required because the dry-run must set
``xla_force_host_platform_device_count`` before jax initializes.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis Auto (sharding propagated by XLA)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Single-device mesh for tests/examples (everything replicated)."""
    return make_mesh((1, 1), ("data", "model"))


def make_test_mesh(data: int = 2, model: int = 2, pod: int = 0):
    """Small mesh for subprocess sharding tests (requires host-device flag)."""
    if pod:
        return make_mesh((pod, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))
