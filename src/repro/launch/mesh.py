"""Production mesh construction.

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips.

A FUNCTION, not a module constant: importing this module never touches jax
device state (the dry-run sets XLA_FLAGS before any jax init)."""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_test_mesh", "dp_axes", "MODEL_AXIS"]

MODEL_AXIS = "model"


def _auto_mesh(shape: tuple[int, ...], axes: tuple[str, ...], devices=None):
    """A mesh whose axes are all ``Auto``: the partitioner propagates
    shardings from the parameter and input specs (``jax.make_mesh`` gives
    ``Explicit`` axes by default, under which an embedding gather from a
    model-sharded table has no unambiguous output sharding)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_test_mesh(data: int = 2, model: int = 2, pod: int = 0, *,
                   devices=None):
    """Small mesh: over ``devices`` when given, else the first
    ``pod*data*model`` devices (CPU tests need
    xla_force_host_platform_device_count)."""
    if pod:
        return _auto_mesh((pod, data, model), ("pod", "data", "model"),
                          devices)
    return _auto_mesh((data, model), ("data", "model"), devices)


def dp_axes(mesh) -> tuple[str, ...]:
    """The data-parallel axes of a mesh (everything except 'model')."""
    return tuple(a for a in mesh.axis_names if a != MODEL_AXIS)
