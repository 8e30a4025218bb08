"""Production mesh definitions.

A function (not a module-level constant) so importing this module never
touches jax device state; the dry-run sets XLA_FLAGS before first jax init.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with Auto axes: GSPMD propagates shardings through
    gathers and jits from their committed inputs (explicit axes, the
    default, would demand ``out_sharding=`` on every such op)."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 (data, model) single pod; 2x16x16 (pod, data, model) two pods."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    import numpy as np
    n = int(np.prod(shape))
    return _mesh(shape, axes, devices=jax.devices()[:n])


def make_host_mesh(model_parallel: int = 1):
    """Degenerate mesh over the actually-available devices (smoke tests)."""
    n = len(jax.devices())
    return _mesh((n // model_parallel, model_parallel), ("data", "model"))


def make_engine_mesh(tp: int = 1):
    """Serving-engine mesh: exactly ``tp`` devices as a (1, tp)
    (data, model) grid.  One ``ServingEngine`` is one tensor-parallel
    group — replica scale-out happens at the instance level (the runtime
    routes across engines), never inside the engine, so the data axis is
    always 1.  CPU validation forces multiple host devices via
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (set before the
    first jax import)."""
    devs = jax.devices()
    if len(devs) < tp:
        raise ValueError(
            f"tensor-parallel degree {tp} needs {tp} devices but only "
            f"{len(devs)} are visible; on CPU set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={tp} "
            f"before importing jax")
    return _mesh((1, tp), ("data", "model"), devices=devs[:tp])


def dp_axes(mesh) -> tuple:
    """The data-parallel axis names of a mesh (pod axis folds into DP)."""
    names = mesh.axis_names
    return tuple(a for a in names if a in ("pod", "data"))


def dp_size(mesh) -> int:
    s = 1
    for a in dp_axes(mesh):
        s *= mesh.shape[a]
    return s
