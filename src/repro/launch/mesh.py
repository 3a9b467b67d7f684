"""Production mesh builders.

IMPORTANT: functions, not module-level constants — importing this module
never touches jax device state. The dry-run entry point sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` BEFORE importing
jax (see dryrun.py); smoke tests and benches see the real single device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16×16 = 256 chips (data, model).
    Multi-pod: 2×16×16 = 512 chips (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_test_mesh(shape=(2, 4), axes=("data", "model")):
    """Small virtual mesh for CI tests (requires host-device override)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))
