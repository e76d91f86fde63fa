"""Mesh construction.

FUNCTIONS, not module-level constants, so importing this module never
touches jax device state (device count locks on first jax init).

Every axis is ``AxisType.Auto``: the serving engine and the sharding rules
in ``runtime/sharding.py`` are written for GSPMD propagation, while
``jax.make_mesh`` defaults to explicit axes.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *,
              devices: Optional[Sequence] = None):
    """``jax.make_mesh`` with every axis Auto (see the module docstring)."""
    names = tuple(axis_names)
    return jax.make_mesh(tuple(axis_shapes), names,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(names),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips (data, model).
    Multi-pod: 2x16x16 = 512 chips (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Degenerate 1-device mesh for CPU tests/examples (same axis names)."""
    return make_mesh((1, 1), ("data", "model"))
