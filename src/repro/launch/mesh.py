"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before any jax initialization).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes):
    """A mesh whose axes are all ``Auto``: GSPMD propagates shardings and
    indexing a sharded array needs no out-sharding, which is what every
    caller here assumes (``jax.make_mesh`` defaults to ``Explicit``)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips/pod (TPU v5e pod slice); 2 pods = 512 chips when
    multi_pod. Axes: data-parallel replicas × model(tensor) parallelism,
    with the leading ``pod`` axis as the cross-DCI data-parallel dimension."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def dp_axes(multi_pod: bool):
    return ("pod", "data") if multi_pod else ("data",)


def make_test_mesh(shape=(2, 4), axes=("data", "model")):
    """Small mesh for CPU tests (8 forced host devices)."""
    return auto_mesh(shape, axes)
