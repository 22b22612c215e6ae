"""Production mesh construction (per-spec: function, no module-level state).

Single pod: (data=16, model=16) = 256 chips. Multi-pod: (pod=2, data=16,
model=16) = 512 chips, with "pod" as the slowest (DCN-connected) axis — data
parallelism spans pods, tensor/expert parallelism stays inside the fast ICI
domain, the standard hierarchy for 1000+-node deployments.
"""
from __future__ import annotations

from typing import Tuple

import jax

__all__ = ["make_production_mesh", "auto_mesh", "data_axes", "model_axis", "mesh_tp"]


def make_production_mesh(*, multi_pod: bool = False):
    import os

    debug = os.environ.get("REPRO_DEBUG_MESH")  # e.g. "2x4" or "2x2x4" (tests)
    if debug:
        shape = tuple(int(x) for x in debug.split("x"))
        axes = ("pod", "data", "model")[-len(shape):]
        return auto_mesh(shape, axes)
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def auto_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: shardings are placed by the
    partitioner from the with_sharding_constraint / in_shardings the code
    declares (the default Explicit axes would ask every op for one)."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def data_axes(mesh) -> Tuple[str, ...]:
    """Axes the global batch shards over (pod included when present)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def model_axis(mesh) -> str:
    return "model"


def mesh_tp(mesh) -> int:
    """Tensor-parallel degree (size of the model axis)."""
    return mesh.shape["model"]
