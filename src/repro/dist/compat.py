"""The dist layer's one door to ``jax.make_mesh`` and ``jax.shard_map``.

  make_mesh            ``jax.make_mesh`` with every axis ``AxisType.Auto``
                       unless told otherwise (the GSPMD-propagation
                       behaviour the whole codebase assumes)
  shard_map            ``jax.shard_map``
  shard_map_unchecked  ``jax.shard_map`` with ``check_vma=False``
  AxisType             ``jax.sharding.AxisType``
"""
from __future__ import annotations

import jax

__all__ = ["AxisType", "make_mesh", "shard_map", "shard_map_unchecked"]

AxisType = jax.sharding.AxisType
shard_map = jax.shard_map


def make_mesh(axis_shapes, axis_names, *, axis_types=None, devices=None):
    """``jax.make_mesh`` defaulting every axis to ``AxisType.Auto``."""
    axis_names = tuple(axis_names)
    if axis_types is None:
        axis_types = (AxisType.Auto,) * len(axis_names)
    kwargs = {} if devices is None else {"devices": devices}
    return jax.make_mesh(tuple(axis_shapes), axis_names,
                         axis_types=tuple(axis_types), **kwargs)


def shard_map_unchecked(f, *, mesh, in_specs, out_specs):
    """``shard_map`` with replication checking disabled: ``pallas_call``
    has no replication rule, so a shard-mapped Pallas kernel (the mesh
    engines' fused inner executor) must opt out of the check."""
    return shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                     check_vma=False)
