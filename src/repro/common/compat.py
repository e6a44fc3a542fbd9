"""Mesh helpers written against the installed jax (0.9).

``shard_map`` and ``axis_size`` are the public ``jax`` spellings; every
use goes through here so a future API move is a one-file change.
"""
from __future__ import annotations

import jax

shard_map = jax.shard_map
axis_size = jax.lax.axis_size


def make_mesh(axis_shapes, axis_names, **kwargs) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with Auto axis types: the serving stack places
    its arrays with explicit ``NamedSharding``s and ``shard_map``."""
    kwargs.setdefault("axis_types",
                      (jax.sharding.AxisType.Auto,) * len(axis_shapes))
    return jax.make_mesh(axis_shapes, axis_names, **kwargs)
