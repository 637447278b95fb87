"""Production meshes. A FUNCTION, not a module-level constant: importing
this module never touches jax device state (the dry run must set XLA_FLAGS
before the first jax call)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16, 16) = 256 chips, axes (data, model).
    Multi-pod: (2, 16, 16) = 512 chips, axes (pod, data, model); only data
    parallelism (gradient all-reduce) crosses the pod (DCN) axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh helper for tests / small runs."""
    return _mesh(shape, axes)
