"""Pure-jnp oracles for the fused L-BFGS kernels (f32 contractions at
HIGHEST, as in the kernels)."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def multidot_ref(dW: jax.Array, dG: jax.Array, v: jax.Array):
    """All Gram/dot terms of the compact L-BFGS system in one logical pass.

    dW, dG: (m, p); v: (p,).
    Returns sw (m,m) = dW dW^T, sy (m,m) = dW dG^T, wv (m,) = dW v,
    gv (m,) = dG v.
    """
    f32 = jnp.float32
    dWf, dGf, vf = dW.astype(f32), dG.astype(f32), v.astype(f32)
    dot = partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)
    return dot(dWf, dWf.T), dot(dWf, dGf.T), dot(dWf, vf), dot(dGf, vf)


def rank_update_ref(dW: jax.Array, dG: jax.Array, v: jax.Array,
                    a: jax.Array, b: jax.Array, sigma: jax.Array) -> jax.Array:
    """Bv = sigma * v - a @ dW - b @ dG  (rank-2m correction)."""
    f32 = jnp.float32
    dot = partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)
    out = (sigma.astype(f32) * v.astype(f32)
           - dot(a.astype(f32), dW.astype(f32))
           - dot(b.astype(f32), dG.astype(f32)))
    return out.astype(v.dtype)
