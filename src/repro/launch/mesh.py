"""Mesh construction.  Functions, not module-level constants — importing this
module never touches jax device state.  Every axis is ``AxisType.Auto``
(``jax.make_mesh``'s default): shardings flow from the arguments and the
``constrain`` calls in model code.
"""
from __future__ import annotations

import jax

from repro.configs.base import ParallelConfig


def make_production_mesh(*, multi_pod: bool = False):
    """The production mesh: one v5e pod (16x16) or two pods (2x16x16)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_mesh(pcfg: ParallelConfig):
    return jax.make_mesh(pcfg.mesh_shape(), pcfg.axis_names())


def local_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (virtual) devices this host exposes."""
    return jax.make_mesh((data, model), ("data", "model"))
