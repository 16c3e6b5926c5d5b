"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first init.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    # Auto axes: the sharding specs in launch/specs.py are constraints the
    # compiler propagates, not the explicit-sharding types that newer JAX
    # gives ``make_mesh`` axes by default
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips when multi_pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(*, model: int = 1):
    """Tiny mesh over whatever devices exist (CPU tests)."""
    n = len(jax.devices())
    model = min(model, n)
    return _mesh((n // model, model), ("data", "model"))
