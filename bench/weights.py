"""Seeded weights, independent of the program: what every architecture's
file in ``bench/arch/`` shares.

An architecture makes every weight from the seed alone, leaf by leaf and
layer by layer, through ``normal``, so that the reference can make the same
values again one layer at a time without anything the program made.

Weights are drawn in float32 and rounded to the served dtype; the reference
computes in float32 on those rounded values (``served``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed up to 64 bits."""
    k = jax.random.PRNGKey(0)
    k = jax.random.fold_in(k, seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)


def normal(key, stream: int, layer, shape, std: float) -> jax.Array:
    """Float32 normal values of one leaf: they depend on the seed's key, the
    leaf's ``stream`` id and its ``layer`` (which may be traced) only."""
    k = jax.random.fold_in(jax.random.fold_in(key, stream), layer)
    return std * jax.random.normal(k, shape, jnp.float32)


def served(tree, s):
    """Round float32 weights to the served dtype ``s.dtype`` and back: the
    values the program holds, in float32."""
    dt = jnp.dtype(s.dtype)
    return jax.tree.map(lambda a: a.astype(dt).astype(jnp.float32), tree)
