"""The benchmark's own weights: every leaf of the program's parameter tree
drawn from the seed on the device, in one jitted call, in the leaf's own
dtype and sharding.

The tree's structure (names, shapes, dtypes, placements) comes from the
program; the values come from here, so the reference reads weights that the
program did not make.  Norm gains start at 1 and norm biases at 0 (the
leaves the program declares with ``init="ones"``/``"zeros"``); every other
leaf is N(0, std^2), std being the configuration's ``initializer_range``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def key_for(seed: int, *salt: int):
    """A PRNG key for any whole ``seed`` (more bits than 32 are folded in)."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    hi = seed >> 32
    while hi:
        key = jax.random.fold_in(key, hi & 0xFFFFFFFF)
        hi >>= 32
    for s in salt:
        key = jax.random.fold_in(key, s)
    return key


def _leaf(p, key, std):
    if p.init == "ones":
        return jnp.ones(p.shape, p.dtype)
    if p.init in ("zeros", "neg_ones"):
        return jnp.full(p.shape, 0 if p.init == "zeros" else -1, p.dtype)
    return (jax.random.normal(key, p.shape, jnp.float32) * std).astype(p.dtype)


def make(abstract, layout, seed: int, std: float):
    """Materialize ``abstract`` (a tree of the program's ``Param``) from
    ``seed``: one program, each leaf created in its own sharding."""
    from jax.sharding import NamedSharding
    leaves, treedef = jax.tree.flatten(
        abstract, is_leaf=lambda x: hasattr(x, "spec") and hasattr(x, "init"))
    shardings = [NamedSharding(layout.mesh, p.spec) for p in leaves]

    def build(key):
        return [_leaf(p, jax.random.fold_in(key, i), std)
                for i, p in enumerate(leaves)]

    out = jax.jit(build, out_shardings=shardings)(key_for(seed, 0x5EED))
    return treedef.unflatten(out)
