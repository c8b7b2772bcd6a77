"""Mesh factories. Functions, not module-level constants — importing this
module never touches jax device state (the dry-run must set XLA_FLAGS first).
"""
from __future__ import annotations

import jax


def make_axes_mesh(shape, axes, devices=None):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """The assignment's production mesh: 16x16 = 256 chips per pod;
    multi-pod adds a leading pod axis (2 x 16 x 16 = 512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_axes_mesh(shape, axes)


def make_mesh(data: int, model: int, pods: int = 1, devices=None):
    """Elastic variant: any (pods x data x model) that fits the device count
    (used by tests and by elastic-restart re-sharding). ``devices`` picks
    the chips (default: the first ones visible)."""
    if pods > 1:
        return make_axes_mesh((pods, data, model), ("pod", "data", "model"),
                              devices)
    return make_axes_mesh((data, model), ("data", "model"), devices)
