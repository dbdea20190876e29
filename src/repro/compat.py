"""Mesh and ``shard_map`` construction, made in one place.

Everything in the repo that builds a mesh or enters ``shard_map`` goes
through this module, so the two policies below are set exactly once:
every mesh axis is ``AxisType.Auto``, and ``shard_map`` runs with its
varying-manual-axes check off.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh

__all__ = [
    "make_mesh",
    "mesh_fingerprint",
    "mesh_from_devices",
    "shard_map",
]


def _auto(n_axes: int) -> tuple:
    return (AxisType.Auto,) * n_axes


def make_mesh(axis_shapes, axis_names) -> Mesh:
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(
        axis_shapes, axis_names, axis_types=_auto(len(axis_names))
    )


def mesh_from_devices(devices, axis_names) -> Mesh:
    """``Mesh(devices, names)`` from an explicit device array (elastic
    shrink/rebuild paths), with Auto axis types."""
    return Mesh(devices, axis_names, axis_types=_auto(len(axis_names)))


def mesh_fingerprint(mesh: Mesh) -> tuple:
    """Hashable mesh-equivalence-class key: two meshes over the same devices
    in the same topology fingerprint identically, even when the ``Mesh``
    objects are distinct (the elastic ``rebuild_mesh`` path re-instantiates
    the template).  ``Mesh.__hash__`` is already value-based on current jax,
    but the trainer's step cache and the jit-cache keys must not depend on
    that implementation detail — this makes the equivalence class explicit.
    """
    return (
        mesh.axis_names,
        mesh.devices.shape,
        tuple(d.id for d in mesh.devices.flat),
    )


def shard_map(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the varying-manual-axes check disabled: the
    collective engine mixes host-planned ``ppermute`` routes with per-rank
    control values, which the static checker cannot type.
    """
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )
