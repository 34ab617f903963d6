"""Mesh construction.

FUNCTIONS, not module-level constants — importing this module never touches
jax device state (the dry-run sets XLA_FLAGS *before* any jax init).

Every mesh is built with ``Auto`` axes: the partitioner constrains
activations with ``with_sharding_constraint`` and lets GSPMD propagate, which
``Explicit`` axes (the ``jax.make_mesh`` default since jax 0.7) refuse.

    single-pod: (data=16, model=16)         256 chips, both axes on ICI
    multi-pod : (pod=2, data=16, model=16)  512 chips, "pod" axis on DCN
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape: tuple, axes: tuple, *, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 2, model: int = 2, pod: int = 0):
    """Small CPU mesh for tests (requires xla_force_host_platform_device_count)."""
    if pod:
        return make_mesh((pod, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))


__all__ = ["make_mesh", "make_production_mesh", "make_host_mesh"]
