"""Production mesh builders.

make_production_mesh is a FUNCTION (not a module-level constant) so importing
this module never touches jax device state. Single-pod: 256 chips (16, 16)
('data', 'model'); multi-pod: 2 pods x 256 = 512 chips ('pod', 'data',
'model') — the pod axis is an extra data-parallel dimension whose gradient
reduction crosses the inter-pod (DCN/ICI) boundary.

Every axis is ``AxisType.Auto``: the models place activations with
``with_sharding_constraint`` and leave the rest to the partitioner, which
``jax.make_mesh``'s default of Explicit axes refuses.
"""
from __future__ import annotations

from typing import Tuple

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 2, model: int = 4, pod: int = 0):
    """Small mesh over host (fake or real CPU) devices, for tests."""
    if pod:
        return _auto_mesh((pod, data, model), ("pod", "data", "model"))
    return _auto_mesh((data, model), ("data", "model"))


def dp_axes(mesh) -> Tuple[str, ...]:
    """Data-parallel axis names of a mesh (everything but 'model')."""
    return tuple(a for a in mesh.axis_names if a != "model")


def dp_size(mesh) -> int:
    n = 1
    for a in dp_axes(mesh):
        n *= mesh.shape[a]
    return n


def tp_size(mesh) -> int:
    return mesh.shape["model"]
