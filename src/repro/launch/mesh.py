"""Production mesh construction.

Defined as functions (never module-level constants) so importing this
module never touches jax device state — required because the dry-run must
set XLA_FLAGS before any jax initialization.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

from repro.parallel.sharding import MeshInfo


def _mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_mesh_info(mesh, *, seq_shard: bool = True) -> MeshInfo:
    axes = mesh.axis_names
    dp_axes = tuple(a for a in axes if a != "model")
    return MeshInfo(mesh=mesh, dp_axes=dp_axes, model_axis="model",
                    seq_shard=seq_shard)


def make_debug_mesh(n_data: int = 2, n_model: int = 4):
    """Small host-device mesh for CPU sharding tests (needs
    --xla_force_host_platform_device_count >= n_data*n_model)."""
    return _mesh((n_data, n_model), ("data", "model"))
