"""Pallas TPU kernels for the DeltaGrad L-BFGS hot path.

The paper's own Discussion (§4.2) flags the L-BFGS correction as the
GPU-underutilizing part: a chain of (m x p) GEMV-like contractions plus a
rank-2m AXPY, each re-streaming the history from HBM.  On TPU we fuse:

  * `multidot`     — ONE pass over (dW, dG, v) emitting ALL reduction terms
                     (dW dW^T, dW dG^T, dW v, dG v).  Naively these are
                     2m^2 + 2m separate dot products = 2m+1 HBM reads of the
                     (m, p) history; fused it is exactly one read.
  * `rank_update`  — ONE pass computing sigma*v - a dW - b dG (the Bv
                     correction), again one read instead of 2m+1.

Both stream p in lane-aligned VMEM tiles (TILE_P multiple of 128; the m axis
is padded to 8 sublanes by the caller via ops.py) and accumulate partial
results into revisited output blocks (TPU grid is sequential over the p
tiles, so the accumulation pattern is the standard Pallas reduction idiom).
The O(m^3) compact solve stays in XLA (m <= 8).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


TILE_P = 2048  # f32 lanes: 8 sublanes x 128 lanes x 2 -> 8KB per (8, 2048) tile


def _dot(x, y, contract):
    """f32 contraction inside a kernel; HIGHEST keeps Mosaic from rounding
    the inputs to bf16 (the correction is f32 by contract)."""
    return jax.lax.dot_general(x, y, (contract, ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _multidot_kernel(dw_ref, dg_ref, v_ref, sw_ref, sy_ref, wv_ref, gv_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        sw_ref[...] = jnp.zeros_like(sw_ref)
        sy_ref[...] = jnp.zeros_like(sy_ref)
        wv_ref[...] = jnp.zeros_like(wv_ref)
        gv_ref[...] = jnp.zeros_like(gv_ref)

    dw = dw_ref[...].astype(jnp.float32)  # (m, TILE_P)
    dg = dg_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)  # (1, TILE_P)
    sw_ref[...] += _dot(dw, dw, ((1,), (1,)))
    sy_ref[...] += _dot(dw, dg, ((1,), (1,)))
    wv_ref[...] += _dot(dw, v, ((1,), (1,)))
    gv_ref[...] += _dot(dg, v, ((1,), (1,)))


@functools.partial(jax.jit, static_argnames=("interpret", "tile_p"))
def multidot(dW: jax.Array, dG: jax.Array, v: jax.Array, *,
             interpret: bool = False, tile_p: int = TILE_P):
    """dW, dG: (m, p) with p % tile_p == 0 and m % 8 == 0; v: (1, p)."""
    m, p = dW.shape
    grid = (p // tile_p,)
    out_shapes = (
        jax.ShapeDtypeStruct((m, m), jnp.float32),  # sw
        jax.ShapeDtypeStruct((m, m), jnp.float32),  # sy
        jax.ShapeDtypeStruct((m, 1), jnp.float32),  # wv
        jax.ShapeDtypeStruct((m, 1), jnp.float32),  # gv
    )
    full = lambda i: (0, 0)  # noqa: E731 — revisit the same output block
    return pl.pallas_call(
        _multidot_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((m, tile_p), lambda i: (0, i)),
            pl.BlockSpec((m, tile_p), lambda i: (0, i)),
            pl.BlockSpec((1, tile_p), lambda i: (0, i)),
        ],
        out_specs=(
            pl.BlockSpec((m, m), full),
            pl.BlockSpec((m, m), full),
            pl.BlockSpec((m, 1), full),
            pl.BlockSpec((m, 1), full),
        ),
        out_shape=out_shapes,
        interpret=interpret,
    )(dW, dG, v)


def _rank_update_kernel(dw_ref, dg_ref, v_ref, coef_ref, out_ref):
    dw = dw_ref[...].astype(jnp.float32)  # (m, TILE_P)
    dg = dg_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)  # (1, TILE_P)
    coefs = coef_ref[...]  # (3, m): rows = a, b, (sigma, pad...)
    a = coefs[0:1, :]  # (1, m)
    b = coefs[1:2, :]
    sigma = coefs[2, 0]
    out = sigma * v
    out -= _dot(a, dw, ((1,), (0,)))
    out -= _dot(b, dg, ((1,), (0,)))
    out_ref[...] = out.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "tile_p"))
def rank_update(dW: jax.Array, dG: jax.Array, v: jax.Array, coefs: jax.Array,
                *, interpret: bool = False, tile_p: int = TILE_P):
    """out (1, p) = sigma*v - a dW - b dG; coefs: (3, m) packed [a; b; sigma]."""
    m, p = dW.shape
    grid = (p // tile_p,)
    return pl.pallas_call(
        _rank_update_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((m, tile_p), lambda i: (0, i)),
            pl.BlockSpec((m, tile_p), lambda i: (0, i)),
            pl.BlockSpec((1, tile_p), lambda i: (0, i)),
            pl.BlockSpec((3, m), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, tile_p), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, p), v.dtype),
        interpret=interpret,
    )(dW, dG, v, coefs)
