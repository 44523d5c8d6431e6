"""Abstract parameter trees: shape + dtype + PartitionSpec + init rule.

Models declare nested dicts of ``Param``; the same tree materializes as
  * random arrays              (init_params)          — placed by the layout
  * jax.ShapeDtypeStruct       (abstract_arrays)      — dry-run lowering
  * NamedSharding trees        (shardings)            — in_shardings for jit
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .topology import Layout


@dataclasses.dataclass(frozen=True)
class Param:
    shape: Tuple[int, ...]
    spec: P
    dtype: Any = jnp.bfloat16
    init: str = "fan_in"        # fan_in | normal | zeros | ones | embed
    fan_axis: int = -2          # contraction axis for fan_in scaling
    scale: float = 1.0

    @property
    def size(self) -> int:
        return math.prod(self.shape)


def is_param(x) -> bool:
    return isinstance(x, Param)


def tree_map_params(f, tree):
    return jax.tree.map(f, tree, is_leaf=is_param)


def init_params(tree, key, dtype=None, layout: Optional[Layout] = None):
    """Materialize random arrays for a Param tree (layer-stacked dims included).

    One jitted program draws every leaf.  With a ``layout`` each leaf is
    created directly in its ``NamedSharding``, so no device ever holds more
    than its own shards; either way a leaf's f32 draw fuses into its cast
    rather than sitting beside the cast copy."""
    leaves, treedef = jax.tree.flatten(tree, is_leaf=is_param)

    def one(p: Param, k):
        dt = dtype or p.dtype
        if p.init == "zeros":
            return jnp.zeros(p.shape, dt)
        if p.init == "ones":
            return jnp.ones(p.shape, dt)
        if p.init == "neg_ones":
            return jnp.full(p.shape, -1, dt)
        if p.init in ("embed", "normal"):
            return (jax.random.normal(k, p.shape, jnp.float32) * p.scale).astype(dt)
        # fan_in
        fan = p.shape[p.fan_axis] if p.shape else 1
        std = p.scale / math.sqrt(max(fan, 1))
        return (jax.random.normal(k, p.shape, jnp.float32) * std).astype(dt)

    def build(key):
        keys = jax.random.split(key, len(leaves))
        return [one(p, k) for p, k in zip(leaves, keys)]

    out_shardings = (None if layout is None else
                     [NamedSharding(layout.mesh, p.spec) for p in leaves])
    return treedef.unflatten(jax.jit(build, out_shardings=out_shardings)(key))


def abstract_arrays(tree, layout: Optional[Layout] = None):
    """ShapeDtypeStructs (with shardings when a layout is given) for dry-runs."""
    def one(p: Param):
        if layout is None:
            return jax.ShapeDtypeStruct(p.shape, p.dtype)
        return jax.ShapeDtypeStruct(p.shape, p.dtype,
                                    sharding=NamedSharding(layout.mesh, p.spec))
    return tree_map_params(one, tree)


def shardings(tree, layout: Layout):
    return tree_map_params(lambda p: NamedSharding(layout.mesh, p.spec), tree)


def specs(tree):
    return tree_map_params(lambda p: p.spec, tree)


def count_params(tree) -> int:
    return sum(p.size for p in jax.tree.leaves(tree, is_leaf=is_param))


def param_bytes(tree) -> int:
    return sum(p.size * np.dtype(p.dtype).itemsize
               for p in jax.tree.leaves(tree, is_leaf=is_param))


def sharded_bytes(tree, layout: Layout) -> int:
    """Per-device bytes of a Param tree under its specs: each leaf's global
    byte count divided by the product of the mesh-axis sizes its spec names
    (the dry-run memory model; assumes even divisibility, rounding up)."""
    total = 0
    for p in jax.tree.leaves(tree, is_leaf=is_param):
        shards = 1
        for entry in (p.spec or ()):
            for ax in (entry if isinstance(entry, (tuple, list)) else (entry,)):
                if ax:
                    shards *= layout.size(ax)
        total += -(-p.size // shards) * np.dtype(p.dtype).itemsize
    return total


def stack(p: Param, n: int, shard: Optional[str] = None) -> Param:
    """Stack a Param for scan-over-layers: prepend the layer dim.

    ``shard`` optionally names a mesh axis for the new leading dim — used by
    pipeline parallelism to spread the stage dim over 'pp'."""
    return dataclasses.replace(
        p, shape=(n, *p.shape), spec=P(shard, *(p.spec or ())),
        fan_axis=p.fan_axis if p.fan_axis < 0 else p.fan_axis + 1)


def stack_tree(tree, n: int, shard: Optional[str] = None):
    return tree_map_params(lambda p: stack(p, n, shard), tree)
