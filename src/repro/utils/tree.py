"""Pytree vector-space helpers.

DeltaGrad's L-BFGS machinery only needs inner products and linear
combinations of parameter-shaped objects, so the whole core operates on
pytrees directly.  This keeps the algorithm sharding-transparent: a pytree of
`NamedSharding`-placed arrays flows through unchanged, and `tree_vdot`
reductions lower to per-shard partial dots + a psum inserted by the compiler.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp


def tree_add(a, b):
    return jax.tree.map(jnp.add, a, b)


def tree_sub(a, b):
    return jax.tree.map(jnp.subtract, a, b)


def tree_scale(s, a):
    return jax.tree.map(lambda x: s * x, a)


def tree_axpy(s, x, y):
    """y + s * x (pytree AXPY)."""
    return jax.tree.map(lambda xi, yi: yi + s * xi, x, y)


def _leaf_dot(x, y):
    """<x, y> contracting every axis in place: no reshape, so a leaf sharded
    on any of its dims reduces shard-locally and all-reduces one scalar."""
    axes = tuple(range(x.ndim))
    return jax.lax.dot_general(
        x.astype(jnp.float32), y.astype(jnp.float32), ((axes, axes), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def tree_vdot(a, b):
    """Full-precision inner product <a, b> over every leaf (HIGHEST: a
    TPU's default f32 dot rounds its inputs to bf16)."""
    leaves_a = jax.tree.leaves(a)
    leaves_b = jax.tree.leaves(b)
    parts = [_leaf_dot(x, y) for x, y in zip(leaves_a, leaves_b)]
    return jnp.sum(jnp.stack(parts))


def tree_norm(a):
    return jnp.sqrt(tree_vdot(a, a))


def tree_zeros_like(a):
    return jax.tree.map(jnp.zeros_like, a)


def tree_lincomb(coeffs, trees: Sequence):
    """sum_k coeffs[k] * trees[k]; coeffs is a 1-D array or list of scalars."""
    assert len(trees) > 0
    out = tree_scale(coeffs[0], trees[0])
    for k in range(1, len(trees)):
        out = tree_axpy(coeffs[k], trees[k], out)
    return out


def tree_all_finite(a) -> jax.Array:
    leaves = jax.tree.leaves(a)
    ok = jnp.array(True)
    for x in leaves:
        ok = jnp.logical_and(ok, jnp.all(jnp.isfinite(x)))
    return ok


def tree_cast(a, dtype):
    return jax.tree.map(lambda x: x.astype(dtype), a)
