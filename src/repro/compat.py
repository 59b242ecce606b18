"""The two mesh/SPMD entry points every sharded code path goes through.

``make_mesh`` builds meshes with Auto axis types; ``shard_map`` is
``jax.shard_map`` with the replication checker on by default.  Keeping the
calls here gives the SPMD code one place that fixes these defaults.
"""

from __future__ import annotations

from typing import Any, Sequence

import jax


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str],
              **kwargs) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with Auto axis types."""
    kwargs.setdefault("axis_types",
                      (jax.sharding.AxisType.Auto,) * len(axis_names))
    return jax.make_mesh(axis_shapes, axis_names, **kwargs)


def shard_map(f, mesh, in_specs: Any, out_specs: Any,
              check_vma: bool = True):
    """``jax.shard_map`` over ``mesh``."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)
