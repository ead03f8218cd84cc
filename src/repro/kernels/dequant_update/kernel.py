"""Fused dequantize + DeltaGrad update — Pallas TPU.

The streamed history store can ship ENCODED windows to device (int8 q with
a per-step scale, or a bf16 residual, optionally against a per-key-window
keyframe base — see `core.history.DeltaCodec`).  These kernels read the
encoded leaf directly and dequantize in registers fused with the hot-loop
elementwise work, so the scan consumes compressed bytes without ever
materializing an f32 copy of a window:

  * ``dequant_deltagrad_update`` — the leave-r-out approx step where the
    cached gradient operand stays encoded,
  * ``dequant_sub`` — ``v = w - w_t`` (the L-BFGS direction input) where
    the cached parameter operand stays encoded.

Decode math is exactly ``q.astype(f32) * scale (+ base)`` — the same
expression and association the jnp decode paths in `core.store` use — so
kernel-mode and fetch-mode replays agree bitwise.  Scalars travel in a
(1, N) operand like `fused_update`; the keyframe base, when present, is a
fifth full-width operand streamed alongside w.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE = 4096


def _upd_math(w, g, bv, gc, lr, n, dB, sign):
    denom = jnp.maximum(n - sign * dB, 1.0)
    num = n * (g + bv.astype(jnp.float32)) - sign * dB * gc.astype(jnp.float32)
    return w.astype(jnp.float32) - lr * num / denom


def _dq_upd_kernel(w_ref, q_ref, bv_ref, gc_ref, s_ref, out_ref):
    s = s_ref[...]  # (1, 5): lr, n, dB, sign, scale
    g = q_ref[...].astype(jnp.float32) * s[0, 4]
    out = _upd_math(w_ref[...], g, bv_ref[...], gc_ref[...],
                    s[0, 0], s[0, 1], s[0, 2], s[0, 3])
    out_ref[...] = out.astype(out_ref.dtype)


def _dq_upd_base_kernel(w_ref, q_ref, bv_ref, gc_ref, b_ref, s_ref, out_ref):
    s = s_ref[...]
    g = q_ref[...].astype(jnp.float32) * s[0, 4] \
        + b_ref[...].astype(jnp.float32)
    out = _upd_math(w_ref[...], g, bv_ref[...], gc_ref[...],
                    s[0, 0], s[0, 1], s[0, 2], s[0, 3])
    out_ref[...] = out.astype(out_ref.dtype)


def _dq_sub_kernel(w_ref, q_ref, s_ref, out_ref):
    x = q_ref[...].astype(jnp.float32) * s_ref[0, 0]
    out_ref[...] = (w_ref[...].astype(jnp.float32) - x).astype(out_ref.dtype)


def _dq_sub_base_kernel(w_ref, q_ref, b_ref, s_ref, out_ref):
    x = q_ref[...].astype(jnp.float32) * s_ref[0, 0] \
        + b_ref[...].astype(jnp.float32)
    out_ref[...] = (w_ref[...].astype(jnp.float32) - x).astype(out_ref.dtype)


def _grid_call(body, arrays, scalars, block, interpret):
    """``body`` over 2-D (R, C) `arrays` in (rows, cols) `block` blocks,
    the edge blocks clipped to the arrays; `scalars` whole in every
    block."""
    R, C = arrays[0].shape
    br, bc = block
    spec = pl.BlockSpec((br, bc), lambda i, j: (i, j))
    sspec = pl.BlockSpec(scalars.shape, lambda i, j: (0, 0))
    return pl.pallas_call(
        body, grid=(pl.cdiv(R, br), pl.cdiv(C, bc)),
        in_specs=[spec] * len(arrays) + [sspec], out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((R, C), arrays[0].dtype),
        interpret=interpret,
    )(*arrays, scalars)


@functools.partial(jax.jit, static_argnames=("interpret", "block"))
def dequant_deltagrad_update(w, q, bv, g_changed, scalars, base=None, *,
                             interpret: bool = False,
                             block=(1, TILE)):
    """All tensors 2-D (R, C), computed in `block` blocks; scalars
    (1, 5)."""
    if base is None:
        return _grid_call(_dq_upd_kernel, (w, q, bv, g_changed), scalars,
                          block, interpret)
    return _grid_call(_dq_upd_base_kernel, (w, q, bv, g_changed, base),
                      scalars, block, interpret)


@functools.partial(jax.jit, static_argnames=("interpret", "block"))
def dequant_sub(w, q, scalars, base=None, *,
                interpret: bool = False, block=(1, TILE)):
    """2-D (R, C) tensors, computed in `block` blocks; scalars (1, 1): the
    dequant scale."""
    if base is None:
        return _grid_call(_dq_sub_kernel, (w, q), scalars, block, interpret)
    return _grid_call(_dq_sub_base_kernel, (w, q, base), scalars, block,
                      interpret)
