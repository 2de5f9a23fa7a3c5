"""Device meshes.  Functions only — importing this module never touches
jax device state."""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """A mesh over the first ``prod(shape)`` devices, every axis Auto
    (GSPMD propagates shardings through it)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_smoke_mesh(n_data: int = 2, n_model: int = 2):
    """Small mesh for CPU tests (requires host-device override)."""
    return make_mesh((n_data, n_model), ("data", "model"))


def dp_axes(mesh) -> tuple:
    """The data-parallel axes (pod folds into DP when present).

    K6 (perf): REPRO_FLAT_DP=1 flattens the WHOLE mesh into data
    parallelism (pure ZeRO-3) — the right operating point for models too
    small to feed 16-way tensor parallelism at 256 chips."""
    import os
    names = mesh.axis_names
    if os.environ.get("REPRO_FLAT_DP"):
        return tuple(names)
    return tuple(a for a in ("pod", "data") if a in names)
