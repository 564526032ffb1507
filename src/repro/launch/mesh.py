"""Production mesh construction.

A function (NOT a module-level constant) so importing this module never
touches jax device state. Single pod: 16x16 = 256 chips ("data", "model").
Multi-pod: 2x16x16 = 512 chips ("pod", "data", "model") — the leading
"pod" axis is the data-parallel axis that crosses the inter-pod links
(DCN/ICI-over-optical), which is why gradient reductions are laid out
pod-major (cheapest collective crosses the slowest fabric exactly once).
"""
from __future__ import annotations

import jax

from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_solver_mesh_from", "DATA_AXES", "MODEL_AXIS"]

DATA_AXES = ("pod", "data")  # batch shards over whichever of these exist
MODEL_AXIS = "model"


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    import numpy as np

    n = int(np.prod(shape))
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices but only {len(devs)} are visible — "
            "the dry-run sets XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            "before any jax import"
        )
    return jax.make_mesh(shape, axes, devices=devs[:n], axis_types=(AxisType.Auto,) * len(axes))


def make_solver_mesh_from(mesh) -> "jax.sharding.Mesh":
    """1-D 'rows' view over the same devices for the shard_map solver."""
    import numpy as np
    from jax.sharding import Mesh

    return Mesh(np.asarray(mesh.devices).reshape(-1), ("rows",))
