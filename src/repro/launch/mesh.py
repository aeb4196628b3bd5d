"""Production mesh definitions.

Functions, not module-level constants: importing this module never touches
jax device state (the dry-run must set XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    # Auto (not Explicit) axes: the emulator's sharded sweep places its
    # inputs with NamedSharding and lets XLA propagate the rest.
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi_pod adds the 2-pod axis (512)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_dev_mesh(model: int = 2, data: int | None = None):
    """Whatever this host has, as a (data, model) mesh — for integration
    tests with xla_force_host_platform_device_count."""
    n = len(jax.devices())
    model = min(model, n)
    data = data or n // model
    return _auto_mesh((data, model), ("data", "model"))
