"""Device-mesh sharding layer (DESIGN.md §2).

The paper's scalability property — equivalence classes partitioned once,
mined communication-free per executor — maps onto JAX as a mesh + a small
set of placement rules:

  compat    the mesh and shard_map entry points (make_mesh / shard_map /
            AxisType)
  sharding  mesh registry, data-parallel axes, parameter/batch placement
            rules, activation sharding constraints

Everything model- and launch-side goes through :mod:`repro.dist.sharding`;
meshes and shard_map come from :mod:`repro.dist.compat`.
"""
from .compat import AxisType, make_mesh, shard_map
from .sharding import (batch_spec, constrain, dp_axes, get_mesh,
                       padded_word_count, param_spec, reset_mesh, set_mesh,
                       shard_words, sharding_tree, spec_tree, word_shard_spec)

__all__ = [
    "AxisType", "make_mesh", "shard_map",
    "batch_spec", "constrain", "dp_axes", "get_mesh", "param_spec",
    "reset_mesh", "set_mesh", "sharding_tree", "spec_tree",
    "word_shard_spec", "padded_word_count", "shard_words",
]
