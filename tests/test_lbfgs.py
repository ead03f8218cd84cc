"""L-BFGS compact representation: algebraic identities + paper lemmas."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis "
                    "(pip install -r requirements-dev.txt)")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core.lbfgs import (
    LbfgsBuffer,
    bfgs_matrix_recursive,
    lbfgs_hvp_pytree,
    lbfgs_hvp_stacked,
    lbfgs_hvp_stacked_pytree,
)


def make_history(m, p, seed=0, mu=1.0):
    """Curvature-consistent pairs: dg = H dw with H spd (so D_ii > 0)."""
    rng = np.random.default_rng(seed)
    A = rng.normal(size=(p, p)).astype(np.float32)
    H = A @ A.T / p + mu * np.eye(p, dtype=np.float32)
    dW = rng.normal(size=(m, p)).astype(np.float32)
    dG = (dW @ H.T).astype(np.float32)
    v = rng.normal(size=(p,)).astype(np.float32)
    return jnp.asarray(dW), jnp.asarray(dG), jnp.asarray(v), H


@pytest.mark.parametrize("m,p", [(1, 8), (2, 17), (3, 40), (5, 64), (8, 128)])
def test_compact_matches_recursive(m, p):
    dW, dG, v, _ = make_history(m, p)
    compact = lbfgs_hvp_stacked(dW, dG, v)
    B = bfgs_matrix_recursive(dW, dG)
    np.testing.assert_allclose(np.asarray(compact), np.asarray(B @ v),
                               rtol=2e-4, atol=2e-4)


def test_secant_equation():
    """B dw_last == dg_last — the defining quasi-Newton property."""
    dW, dG, v, _ = make_history(3, 32, seed=1)
    out = lbfgs_hvp_stacked(dW, dG, dW[-1])
    np.testing.assert_allclose(np.asarray(out), np.asarray(dG[-1]),
                               rtol=1e-4, atol=1e-4)


def test_quasi_hessian_positive_definite():
    """Lemma 6: z^T B z > 0 for curvature-consistent history."""
    dW, dG, _, _ = make_history(4, 24, seed=2)
    B = bfgs_matrix_recursive(dW, dG)
    eig = np.linalg.eigvalsh(np.asarray(B))
    assert eig.min() > 0


def test_pytree_and_stacked_pytree_agree_with_flat():
    m, p = 3, 30
    dW, dG, v, _ = make_history(m, p, seed=3)
    cut = 13
    tw = [{"a": dW[i, :cut], "b": dW[i, cut:]} for i in range(m)]
    tg = [{"a": dG[i, :cut], "b": dG[i, cut:]} for i in range(m)]
    tv = {"a": v[:cut], "b": v[cut:]}
    flat = np.asarray(lbfgs_hvp_stacked(dW, dG, v))
    out1 = lbfgs_hvp_pytree(tw, tg, tv)
    got1 = np.concatenate([np.asarray(out1["a"]), np.asarray(out1["b"])])
    np.testing.assert_allclose(got1, flat, rtol=1e-4, atol=1e-4)
    dWs = jax.tree.map(lambda *xs: jnp.stack(xs), *tw)
    dGs = jax.tree.map(lambda *xs: jnp.stack(xs), *tg)
    out2 = lbfgs_hvp_stacked_pytree(dWs, dGs, tv)
    got2 = np.concatenate([np.asarray(out2["a"]), np.asarray(out2["b"])])
    np.testing.assert_allclose(got2, flat, rtol=1e-4, atol=1e-4)


def test_buffer_admission_and_ring():
    buf = LbfgsBuffer(capacity=2, curvature_eps=0.0)
    dW, dG, v, _ = make_history(4, 16, seed=4)
    assert not buf.add(jnp.zeros(16), jnp.zeros(16))  # zero dw rejected
    for i in range(4):
        assert buf.add(dW[i], dG[i])
    assert len(buf) == 2  # ring keeps the last m
    out = buf.hvp(v)
    ref = lbfgs_hvp_stacked(dW[2:], dG[2:], v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4,
                               atol=1e-4)


def test_buffer_rejects_negative_curvature():
    buf = LbfgsBuffer(capacity=2, curvature_eps=0.0)
    dw = jnp.ones(8)
    assert not buf.add(dw, -dw)  # <dg, dw> < 0 — Algorithm-4 guard
    assert buf.rejected == 1


def test_stacked_cache_invalidation():
    buf = LbfgsBuffer(capacity=2)
    dW, dG, v, _ = make_history(3, 16, seed=5)
    buf.add(dW[0], dG[0])
    s1 = buf.stacked()
    assert buf.stacked() is s1  # cached
    buf.add(dW[1], dG[1])
    assert buf.stacked() is not s1  # invalidated


@settings(max_examples=20, deadline=None)
@given(m=st.integers(1, 6), p=st.integers(4, 48), seed=st.integers(0, 10**6))
def test_hvp_linear_in_v(m, p, seed):
    """B(av1 + v2) == a Bv1 + Bv2 (hypothesis)."""
    dW, dG, _, _ = make_history(m, p, seed=seed)
    rng = np.random.default_rng(seed + 1)
    v1 = jnp.asarray(rng.normal(size=(p,)).astype(np.float32))
    v2 = jnp.asarray(rng.normal(size=(p,)).astype(np.float32))
    a = 1.7
    lhs = lbfgs_hvp_stacked(dW, dG, a * v1 + v2)
    rhs = a * lbfgs_hvp_stacked(dW, dG, v1) + lbfgs_hvp_stacked(dW, dG, v2)
    scale = float(jnp.max(jnp.abs(rhs))) + 1.0
    np.testing.assert_allclose(np.asarray(lhs) / scale,
                               np.asarray(rhs) / scale, atol=5e-4)


def _f32_contractions():
    """(name, fn, args) for every f32 contraction on the replay path."""
    from functools import partial

    from repro.kernels.lbfgs.ops import lbfgs_hvp_fused
    from repro.kernels.lbfgs.ref import multidot_ref, rank_update_ref
    from repro.models.simple import (logreg_init, logreg_per_example_loss,
                                     mlp_init, mlp_per_example_loss)
    from repro.utils.tree import tree_vdot

    dW, dG, v, _ = make_history(2, 300)
    c = jnp.ones((2,))
    x = jnp.ones((4, 6))
    y = jnp.zeros((4,), jnp.int32)

    def grad_of(loss):
        return jax.grad(lambda p: loss(p, {"x": x, "y": y}).mean())

    return {
        "mlp_grad": (grad_of(mlp_per_example_loss), (mlp_init(6, 5, 3),)),
        "logreg_grad": (grad_of(logreg_per_example_loss), (logreg_init(6),)),
        "tree_vdot": (tree_vdot, ({"a": dW}, {"a": dG})),
        "hvp_stacked": (lbfgs_hvp_stacked, (dW, dG, v)),
        "hvp_stacked_pytree": (lbfgs_hvp_stacked_pytree,
                               ({"a": dW}, {"a": dG}, {"a": v})),
        "hvp_fused_kernels": (partial(lbfgs_hvp_fused, interpret=True),
                              (dW, dG, v)),
        "multidot_ref": (multidot_ref, (dW, dG, v)),
        "rank_update_ref": (rank_update_ref, (dW, dG, v, c, c, c[0])),
    }


@pytest.mark.parametrize("name", ["mlp_grad", "logreg_grad", "tree_vdot",
                                  "hvp_stacked", "hvp_stacked_pytree",
                                  "hvp_fused_kernels", "multidot_ref",
                                  "rank_update_ref"])
def test_f32_contractions_run_at_highest(name):
    """Histories, replays and the exact-retrain reference are f32 by
    contract.  A TPU's default f32 dot rounds its inputs to bf16, so every
    contraction on that path (forward and backward, in XLA and inside the
    Pallas kernels) must ask for HIGHEST itself."""
    fn, args = _f32_contractions()[name]
    jaxpr = str(jax.make_jaxpr(fn)(*args))
    n_dots = jaxpr.count("dot_general[")
    assert n_dots > 0
    assert jaxpr.count(
        "precision=(Precision.HIGHEST, Precision.HIGHEST)") == n_dots
