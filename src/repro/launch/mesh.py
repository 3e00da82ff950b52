"""Production mesh construction.

A FUNCTION, not a module-level constant — importing this module never touches
jax device state.  Shapes fixed by the assignment:

  single-pod : (data=16, model=16)            = 256 chips (one v5e pod)
  multi-pod  : (pod=2, data=16, model=16)     = 512 chips

``make_host_mesh`` builds reduced same-topology meshes for CPU tests.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def _auto_mesh(shape, axes, devices=None):
    """Mesh with Auto axis types: shardings propagate as under
    ``with mesh:`` (``jax.make_mesh`` defaults to Explicit axes, under which
    an unannotated scatter inside the model raises ``ShardingTypeError``)."""
    types = (AxisType.Auto,) * len(axes)
    if devices is None:
        return jax.make_mesh(shape, axes, axis_types=types)
    return Mesh(np.array(devices).reshape(shape), axes, axis_types=types)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()
    if len(devices) != n:   # dry-run: 512 forced host devices, use first n
        return _auto_mesh(shape, axes, devices[:n])
    return _auto_mesh(shape, axes)


def make_host_mesh(data: int = 2, model: int = 2, pod: int = 0):
    """Small mesh over host (CPU) devices for tests; same axis names."""
    if pod:
        return _auto_mesh((pod, data, model), ("pod", "data", "model"))
    return _auto_mesh((data, model), ("data", "model"))


__all__ = ["make_production_mesh", "make_host_mesh"]
