"""Mesh and shard_map entry points, in one place.

Everything that builds meshes or shard_maps goes through this module, so
the call sites share one spelling of ``jax.shard_map``,
``jax.make_mesh(axis_types=...)`` and ``jax.set_mesh``.
"""
from __future__ import annotations

import jax


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = False):
    """``jax.shard_map`` with the replication check off by default (result
    types carry NamedTuples the checker cannot infer)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def make_mesh(axis_shapes, axis_names) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis Auto."""
    return jax.make_mesh(
        axis_shapes, axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names))


def set_mesh(mesh: jax.sharding.Mesh):
    """Context manager installing ``mesh`` as the ambient mesh."""
    return jax.set_mesh(mesh)
