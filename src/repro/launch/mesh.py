"""Production mesh construction (function, not module constant — importing
this module never touches jax device state).

The sharded code (strata/sync steps, serving programs, the LM driver) is
written for GSPMD-style ``Auto`` axes: shardings are propagated, and
slicing or gathering a sharded array is allowed.  JAX 0.9 builds
``Explicit`` axes by default, so every mesh here names its axis types.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single-pod (256 chips) or 2×16×16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1):
    """Mesh over whatever devices exist (tests / local runs)."""
    n = len(jax.devices())
    mp = max(1, min(model_parallel, n))
    return auto_mesh((n // mp, mp), ("data", "model"))


def batch_axes(mesh) -> tuple[str, ...]:
    """The data-parallel axes of a mesh (pod composes with data)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))
