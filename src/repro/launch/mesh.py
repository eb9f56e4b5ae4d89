"""Production mesh definitions.

Built lazily (functions, not module constants) so importing this module
never touches jax device state — the dry-run must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before anything
initializes jax.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips/pod; the multi-pod mesh adds a 2-pod DCN axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def dp_axes(mesh) -> tuple:
    """The batch/data-parallel axes of a mesh (pod axis included if present)."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)


def make_host_mesh():
    """Single-device mesh for CPU smoke runs (axes exist, size 1)."""
    return jax.make_mesh((1, 1), ("data", "model"))
