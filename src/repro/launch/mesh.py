"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state.  Single pod: 16x16 = 256 chips ('data','model');
multi-pod: 2x16x16 = 512 chips ('pod','data','model') — the 'pod' axis is
pure data parallelism across ICI-disconnected pods (DCN).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_mesh(shape, axes):
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_test_mesh(n_devices: int = 8):
    """Small mesh for subprocess tests (requires XLA_FLAGS device override)."""
    return jax.make_mesh((max(n_devices // 4, 1), min(4, n_devices)),
                         ("data", "model"),
                         axis_types=(AxisType.Auto, AxisType.Auto))
