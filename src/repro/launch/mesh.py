"""Production mesh construction.

A function, not a module-level constant: importing this module never touches
jax device state (device count locks on first backend init).
"""
from __future__ import annotations

import jax

__all__ = ["make_production_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips (v5e pod).
    Multi-pod: (pod=2, data=16, model=16) = 512 chips; `pod` carries the
    cross-pod data parallelism (DCN/ICI-X gradient all-reduce)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
