"""Production mesh builders (functions, never module-level constants — the
import must not touch jax device state).

Every mesh here has ``AxisType.Auto`` axes: the layers place activations
with ``with_sharding_constraint`` and leave the rest to GSPMD, which the
sharding-in-types default of ``jax.make_mesh`` (``Explicit`` axes) rejects.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_mesh", "make_production_mesh", "make_local_mesh"]


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; the multi-pod mesh adds a pure-DP 'pod'
    axis (2 pods = 512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(*, data: int | None = None, model: int = 1):
    """Small mesh over whatever devices exist (CPU tests / single host)."""
    n = len(jax.devices())
    data = data or (n // model)
    return make_mesh((data, model), ("data", "model"))
