"""Production meshes. Functions, not module constants — importing this must
never touch jax device state (the dry-run sets device-count flags first)."""
from __future__ import annotations

import jax

from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single pod (256 chips) or 2×16×16 two pods (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(shape))


def make_mesh(shape, axes):
    """Arbitrary mesh with Auto axis types (GSPMD propagation)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(shape))


def mesh_name(mesh) -> str:
    return "x".join(f"{mesh.shape[a]}{a}" for a in mesh.axis_names)
