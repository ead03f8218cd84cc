"""Jit'd wrappers for the fused dequant kernels (layout + scalar packing).

A vector (or a scalar) is padded to a whole number of `tile` lanes and
computed as one (1, p) row.  An array of rank 2 or more is computed in
its own layout: as the (rows, last dim) matrix its leading dims merge
into, which on a TPU is the same bytes, in blocks of `ROWS` x `COLS`, the
edge blocks clipped.
Flattening such an array instead would make the compiler relayout every
operand, which at an LM leaf's size costs it tens of seconds a call.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.dequant_update import kernel

ROWS = 32  # block rows: a whole tile of int8 (32 sublanes) and f32 (8)
COLS = 2048  # block columns: a multiple of the 128 lanes


def _prep(x, pp, p):
    return jnp.pad(x.reshape(1, -1), ((0, 0), (0, pp - p)))


def _call(fn, arrays, scalars, tile, interpret):
    """`fn` over `arrays` (all of one shape; None entries pass through)
    with the layout above; returns the result in that shape."""
    shape = arrays[0].shape
    if len(shape) >= 2:
        C = shape[-1]
        R = arrays[0].size // C
        block = (min(R, ROWS), min(C, COLS))
        out = fn(*(None if x is None else x.reshape(R, C) for x in arrays),
                 scalars, block=block, interpret=interpret)
        return out.reshape(shape)
    p = arrays[0].size
    pp = -(-p // tile) * tile
    out = fn(*(None if x is None else _prep(x, pp, p) for x in arrays),
             scalars, block=(1, tile), interpret=interpret)
    return out[0, :p].reshape(shape)


@partial(jax.jit, static_argnames=("interpret", "tile"))
def dequant_update(w, q, bv, g_changed, lr, n, dB, sign, scale, base=None, *,
                   interpret: bool = False, tile: int = 512):
    """Fused dequant + update of arrays of any shape (vectors pad to
    `tile`)."""
    scalars = jnp.stack([jnp.float32(lr), jnp.float32(n), jnp.float32(dB),
                         jnp.float32(sign), jnp.float32(scale)]).reshape(1, 5)
    fn = lambda w, q, bv, gc, base, s, **kw: \
        kernel.dequant_deltagrad_update(w, q, bv, gc, s, base, **kw)
    return _call(fn, (w, q, bv, g_changed, base), scalars, tile, interpret)


@partial(jax.jit, static_argnames=("interpret", "tile"))
def dequant_sub(w, q, scale, base=None, *,
                interpret: bool = False, tile: int = 512):
    """``w - dequant(q)`` of arrays of any shape (vectors pad to `tile`)."""
    scalars = jnp.float32(scale).reshape(1, 1)
    fn = lambda w, q, base, s, **kw: kernel.dequant_sub(w, q, s, base, **kw)
    return _call(fn, (w, q, base), scalars, tile, interpret)
