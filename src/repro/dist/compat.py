"""The repo's spelling of the jax mesh / shard_map API.

* ``make_mesh`` builds meshes whose axes are all ``AxisType.Auto``.
* ``shard_map`` runs with replication checking (``check_vma``) off: the
  sharded bodies here exchange rows by explicit collectives.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` with auto axis types."""
    return jax.make_mesh(axis_shapes, axis_names, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axis_names))


def shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
