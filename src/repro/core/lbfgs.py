"""Limited-memory BFGS quasi-Hessian, compact representation.

DeltaGrad (Algorithm 1, line "L-BFGS") needs the product ``B_t v`` of a
quasi-Hessian with ``v = w^I_t - w_t``, where ``B_t`` is the BFGS matrix
built from the last ``m`` parameter/gradient difference pairs

    dw_k = w^I_{j_k} - w_{j_k},     dg_k = grad(w^I_{j_k}) - grad(w_{j_k}).

We use the compact representation of Byrd, Nocedal & Schnabel (1994),
Theorem 2.3 (the paper's Algorithm 2): with ``S = [dw_0 .. dw_{m-1}]``,
``Y = [dg_0 .. dg_{m-1}]`` and ``B_0 = sigma I``,

    B v = sigma v - [sigma S, Y] M^{-1} [sigma S^T v; Y^T v],
    M   = [[sigma S^T S, L], [L^T, -D]],

where ``D = diag(S^T Y)`` and ``L`` is the strictly-lower part of ``S^T Y``.
Only m x m Gram matrices and two length-m dot vectors touch the full
parameter dimension, so the operator is O(mp) + O(m^3).

Two equivalent backends are provided:
  * stacked   — ``dW, dG: (m, p)`` matrices (kernel-friendly; the Pallas
                ``lbfgs_multidot`` / ``lbfgs_rank_update`` kernels accelerate
                exactly these contractions),
  * pytree    — lists of parameter pytrees (sharding-transparent; used by the
                distributed engine).

A dense recursive oracle (paper eq. (S11)/(S12)) is included for testing.
"""

from __future__ import annotations

from typing import List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.utils.tree import tree_lincomb, tree_scale, tree_vdot

# the correction is f32 by contract; a TPU's default f32 dot rounds its
# inputs to bf16, which the curvature pairs' differences do not survive
_HIGHEST = jax.lax.Precision.HIGHEST


class CompactCoeffs(NamedTuple):
    """Coefficients of the rank-2m correction: Bv = sigma*v - dW^T a - dG^T b."""

    sigma: jax.Array  # scalar
    a: jax.Array  # (m,) coefficients on the dW rows (already include sigma)
    b: jax.Array  # (m,) coefficients on the dG rows


def compact_coeffs(
    sw: jax.Array, sy: jax.Array, wv: jax.Array, gv: jax.Array
) -> CompactCoeffs:
    """Solve the 2m x 2m compact system.

    Args:
      sw: (m, m) Gram matrix  S^T S  (sw[i, j] = <dw_i, dw_j>).
      sy: (m, m) cross matrix S^T Y  (sy[i, j] = <dw_i, dg_j>).
      wv: (m,)   S^T v.
      gv: (m,)   Y^T v.
    """
    m = sw.shape[0]
    diag_sy = jnp.diag(sy)
    # B_0 = sigma I with sigma from the most recent pair (paper Alg. 2 line 21).
    sigma = diag_sy[-1] / jnp.where(sw[-1, -1] == 0, 1.0, sw[-1, -1])
    ell = jnp.tril(sy, k=-1)  # L_ij = <dw_i, dg_j>, i > j
    dmat = jnp.diag(diag_sy)
    top = jnp.concatenate([sigma * sw, ell], axis=1)
    bot = jnp.concatenate([ell.T, -dmat], axis=1)
    mid = jnp.concatenate([top, bot], axis=0)  # (2m, 2m)
    rhs = jnp.concatenate([sigma * wv, gv])  # (2m,)
    q = jnp.linalg.solve(mid, rhs)
    return CompactCoeffs(sigma=sigma, a=sigma * q[:m], b=q[m:])


def valid_pair_mask(count: jax.Array, m: int) -> jax.Array:
    """(m,) bool mask for a newest-last ring holding ``min(count, m)`` pairs.

    The engine's device ring appends by shifting left, so with ``count``
    admitted pairs the valid slots are the trailing ``min(count, m)`` rows.
    """
    return jnp.arange(m) >= (m - jnp.minimum(count, m))


def ring_valid_mask(dWs) -> jax.Array:
    """(m,) bool — derive ring occupancy FROM the ring: slot i holds an
    admitted pair iff its dw row is nonzero anywhere.

    Sound because admission requires ``<dw, dw> > 0`` (a zero dw can never
    be admitted) and empty slots of the zeros-initialized shift-append ring
    are exact zeros.  Deriving the mask on device means no separate count
    state crosses program boundaries — the fused explicit step's program is
    untouched, which keeps full-ring replays bitwise identical to the
    unmasked path.  The per-leaf any() reduces trailing axes shard-locally
    (boolean OR — associativity-safe under any reduction order)."""
    nz = [jnp.any(w != 0, axis=tuple(range(1, w.ndim)))
          for w in jax.tree.leaves(dWs)]
    valid = nz[0]
    for x in nz[1:]:
        valid = jnp.logical_or(valid, x)
    return valid


def compact_coeffs_masked(
    sw: jax.Array, sy: jax.Array, wv: jax.Array, gv: jax.Array, valid: jax.Array
) -> CompactCoeffs:
    """``compact_coeffs`` over a partially-filled ring.

    Requires invalid ring slots to be EXACT zeros (the device ring
    guarantees this: slots start at zero and rejected pairs never write).
    Then every Gram entry touching an invalid slot is already 0.0, and the
    2m x 2m system block-decouples: placing a 1 on the diagonal of invalid
    rows makes those rows ``e_i`` with a zero rhs, so their coefficients
    solve to exactly 0 and the valid sub-block is untouched.  With all m
    slots valid the mask is all-False and ``jnp.where`` returns ``mid``
    verbatim — bitwise identical to the unmasked solve.

    ``count == 0`` degenerates gracefully: ``sigma = 0/1 = 0`` (zero ring
    slots) and ``q = 0``, so the resulting operator is ``B v = 0`` — the
    exact leave-one-out estimate when ``w^I = w`` (the only way the first
    explicit step's pair is rejected).
    """
    m = sw.shape[0]
    diag_sy = jnp.diag(sy)
    sigma = diag_sy[-1] / jnp.where(sw[-1, -1] == 0, 1.0, sw[-1, -1])
    ell = jnp.tril(sy, k=-1)
    dmat = jnp.diag(diag_sy)
    top = jnp.concatenate([sigma * sw, ell], axis=1)
    bot = jnp.concatenate([ell.T, -dmat], axis=1)
    mid = jnp.concatenate([top, bot], axis=0)  # (2m, 2m)
    valid2 = jnp.concatenate([valid, valid])
    invalid_diag = jnp.eye(2 * m, dtype=bool) & ~valid2[None, :]
    mid = jnp.where(invalid_diag, 1.0, mid)
    rhs = jnp.concatenate([sigma * wv, gv])  # (2m,)
    q = jnp.linalg.solve(mid, rhs)
    return CompactCoeffs(sigma=sigma, a=sigma * q[:m], b=q[m:])


# --------------------------------------------------------------------------
# Stacked (m, p) backend
# --------------------------------------------------------------------------


def gram_terms_stacked(dW: jax.Array, dG: jax.Array, v: jax.Array):
    """All reduction terms in one logical pass over the (m, p) history.

    Returns (sw, sy, wv, gv). This is the contraction the Pallas
    ``lbfgs_multidot`` kernel fuses into a single HBM read of dW, dG, v.
    """
    f32 = jnp.float32
    dWf, dGf, vf = dW.astype(f32), dG.astype(f32), v.astype(f32)
    sw = jnp.matmul(dWf, dWf.T, precision=_HIGHEST)
    sy = jnp.matmul(dWf, dGf.T, precision=_HIGHEST)
    wv = jnp.matmul(dWf, vf, precision=_HIGHEST)
    gv = jnp.matmul(dGf, vf, precision=_HIGHEST)
    return sw, sy, wv, gv


def lbfgs_hvp_stacked(dW: jax.Array, dG: jax.Array, v: jax.Array) -> jax.Array:
    """B v with history stacked as (m, p) rows (oldest first)."""
    sw, sy, wv, gv = gram_terms_stacked(dW, dG, v)
    c = compact_coeffs(sw, sy, wv, gv)
    return (c.sigma * v - jnp.matmul(c.a, dW, precision=_HIGHEST)
            - jnp.matmul(c.b, dG, precision=_HIGHEST)).astype(v.dtype)


# --------------------------------------------------------------------------
# Pytree backend (sharding-transparent)
# --------------------------------------------------------------------------


def gram_terms_pytree(dws: Sequence, dgs: Sequence, v):
    m = len(dws)
    sw = jnp.stack(
        [jnp.stack([tree_vdot(dws[i], dws[j]) for j in range(m)]) for i in range(m)]
    )
    sy = jnp.stack(
        [jnp.stack([tree_vdot(dws[i], dgs[j]) for j in range(m)]) for i in range(m)]
    )
    wv = jnp.stack([tree_vdot(dws[i], v) for i in range(m)])
    gv = jnp.stack([tree_vdot(dgs[i], v) for i in range(m)])
    return sw, sy, wv, gv


def lbfgs_hvp_pytree(dws: Sequence, dgs: Sequence, v):
    """B v where history entries and v are parameter pytrees."""
    sw, sy, wv, gv = gram_terms_pytree(dws, dgs, v)
    c = compact_coeffs(sw, sy, wv, gv)
    out = tree_scale(c.sigma, v)
    out = tree_lincomb(jnp.concatenate([jnp.ones((1,)), -c.a, -c.b]),
                       [out] + list(dws) + list(dgs))
    return out


# --------------------------------------------------------------------------
# Stacked-pytree backend: every leaf carries a leading history axis m.
# This is the jit-fused path the DeltaGrad engine uses (one XLA program for
# Gram terms + solve + rank-2m update).
# --------------------------------------------------------------------------


def _pair_gram(a, b):
    """(m, ...) x (m, ...) -> (m, m), contracting ALL trailing axes.

    Implemented with a multi-axis dot_general (NOT reshape(m, -1) @ ...):
    a reshape collapses sharded parameter dims into one unshardable axis and
    forces GSPMD to all-gather the whole history buffer — measured 33 GB of
    gathers per DeltaGrad step at 1.8B params (EXPERIMENTS.md §Perf,
    deltagrad-step iteration 1).  dot_general keeps each shard's partial
    product local and psums only the (m, m) scalars.
    """
    axes = tuple(range(1, a.ndim))
    return jax.lax.dot_general(
        a.astype(jnp.float32), b.astype(jnp.float32),
        ((axes, axes), ((), ())), precision=_HIGHEST,
        preferred_element_type=jnp.float32)


def _vec_dot(a, x):
    """(m, ...) x (...) -> (m,), contracting all of x's axes shard-locally."""
    axes_a = tuple(range(1, a.ndim))
    axes_x = tuple(range(x.ndim))
    return jax.lax.dot_general(
        a.astype(jnp.float32), x.astype(jnp.float32),
        ((axes_a, axes_x), ((), ())), precision=_HIGHEST,
        preferred_element_type=jnp.float32)


def gram_terms_stacked_pytree(dWs, dGs, v):
    """dWs/dGs: pytrees whose leaves are stacked (m, ...); v: plain pytree."""
    wl = jax.tree.leaves(dWs)
    gl = jax.tree.leaves(dGs)
    vl = jax.tree.leaves(v)
    sw = sum(_pair_gram(w, w) for w in wl)
    sy = sum(_pair_gram(w, g) for w, g in zip(wl, gl))
    wv = sum(_vec_dot(w, x) for w, x in zip(wl, vl))
    gv = sum(_vec_dot(g, x) for g, x in zip(gl, vl))
    return sw, sy, wv, gv


def lbfgs_hvp_stacked_pytree(dWs, dGs, v, masked: bool = False):
    """B v with history stacked along a leading axis of every leaf.

    With ``masked=True`` the ring may be PARTIALLY filled: empty slots must
    be exact zeros (the engine's zeros-initialized shift-append ring), the
    occupancy mask is derived from the ring via `ring_valid_mask`, and the
    masked solve matches the occupied-pair operator — bitwise identical to
    the unmasked solve once the ring is full."""
    sw, sy, wv, gv = gram_terms_stacked_pytree(dWs, dGs, v)
    if masked:
        c = compact_coeffs_masked(sw, sy, wv, gv, ring_valid_mask(dWs))
    else:
        c = compact_coeffs(sw, sy, wv, gv)

    def upd(x, w, g):
        shape = (-1,) + (1,) * (x.ndim)
        a = c.a.reshape(shape)
        b = c.b.reshape(shape)
        return (c.sigma * x - jnp.sum(a * w, axis=0) - jnp.sum(b * g, axis=0)).astype(
            x.dtype
        )

    return jax.tree.map(upd, v, dWs, dGs)


# --------------------------------------------------------------------------
# Dense recursive oracle (paper eq. (S11)-(S12)) — tests only
# --------------------------------------------------------------------------


def bfgs_matrix_recursive(
    dW: jax.Array, dG: jax.Array, sigma: Optional[jax.Array] = None
) -> jax.Array:
    """Explicitly build B by the recursive BFGS update (S11) from B0 = sigma I.

    O(m p^2) — for unit tests with small p only.
    """
    m, p = dW.shape
    if sigma is None:
        sigma = (dG[-1] @ dW[-1]) / (dW[-1] @ dW[-1])
    B = sigma * jnp.eye(p, dtype=jnp.float32)
    for k in range(m):
        s = dW[k].astype(jnp.float32)
        y = dG[k].astype(jnp.float32)
        Bs = B @ s
        B = B - jnp.outer(Bs, Bs) / (s @ Bs) + jnp.outer(y, y) / (y @ s)
    return B


# --------------------------------------------------------------------------
# History ring buffer with curvature admission (Algorithm 4 guard hook)
# --------------------------------------------------------------------------


def _push_pair(dWs, dGs, dw, dg):
    """Append one (dw, dg) pair to stacked (k, ...) rings."""
    push = lambda r, x: jnp.concatenate([r, x[None].astype(r.dtype)])
    return jax.tree.map(push, dWs, dw), jax.tree.map(push, dGs, dg)


def _shift_pair(dWs, dGs, dw, dg):
    """Drop the oldest pair of full (m, ...) rings and append (dw, dg)."""
    shift = lambda r, x: jnp.concatenate([r[1:], x[None].astype(r.dtype)])
    return jax.tree.map(shift, dWs, dw), jax.tree.map(shift, dGs, dg)


_start_ring = jax.jit(lambda dw, dg: jax.tree.map(lambda x: x[None], (dw, dg)))
_grow_ring = jax.jit(_push_pair)
# a full ring is rewritten in place: the pairs live once on the device, at
# LM scale four parameter-sized buffers
_turn_ring = jax.jit(_shift_pair, donate_argnums=(0, 1))


class LbfgsBuffer:
    """Fixed-capacity ring buffer of (dw, dg) pytree pairs.

    Admission implements the convexity check DeltaGrad uses for non-convex
    models (paper Appendix C.3): a pair enters the buffer only if
    ``<dg, dw> >= curvature_eps * <dw, dw>`` — for strongly convex objectives
    this always holds with ``curvature_eps <= mu``.

    The pairs are kept ONLY stacked, (len, ...) per leaf with the newest
    last — the form the replay programs consume — so no second copy of
    the ring exists; a replaced ring is invalid once a pair is added to a
    full buffer (its buffers are donated to the new one).
    """

    def __init__(self, capacity: int, curvature_eps: float = 0.0):
        assert capacity >= 1
        self.capacity = capacity
        self.curvature_eps = float(curvature_eps)
        self._ring = None  # (dWs, dGs), every leaf (len, ...)
        self._len = 0
        self.rejected = 0
        self.admitted = 0

    def __len__(self) -> int:
        return self._len

    def _unstack(self, i: int) -> List:
        return [jax.tree.map(lambda x: x[k], self._ring[i])
                for k in range(self._len)]

    @property
    def dws(self) -> List:
        return self._unstack(0) if self._ring is not None else []

    @property
    def dgs(self) -> List:
        return self._unstack(1) if self._ring is not None else []

    def add(self, dw, dg) -> bool:
        """Returns True if the pair was admitted."""
        curv = float(tree_vdot(dg, dw))
        ss = float(tree_vdot(dw, dw))
        return self.add_pair(dw, dg, curv, ss)

    def add_pair(self, dw, dg, curv: float, ss: float) -> bool:
        """`add` with the admission inner products precomputed — the engine's
        fused explicit step evaluates them on-device and syncs once."""
        if ss <= 0.0 or curv < self.curvature_eps * ss:
            self.rejected += 1
            return False
        if self._ring is None:
            self._ring = _start_ring(dw, dg)
        elif self._len < self.capacity:
            self._ring = _grow_ring(*self._ring, dw, dg)
        else:
            self._ring = _turn_ring(*self._ring, dw, dg)
        self._len = min(self._len + 1, self.capacity)
        self.admitted += 1
        return True

    def hvp(self, v):
        """B v. Requires at least one admitted pair."""
        if self._ring is None:
            raise ValueError("LbfgsBuffer.hvp called with no admitted pairs")
        return lbfgs_hvp_pytree(self.dws, self.dgs, v)

    def stacked(self):
        """(dWs, dGs) with every leaf stacked along a leading axis, oldest
        first; the same arrays until the next admitted pair."""
        if self._ring is None:
            raise ValueError("LbfgsBuffer.stacked called with no admitted pairs")
        return self._ring

    def clear(self) -> None:
        self._ring = None
        self._len = 0
