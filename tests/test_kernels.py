"""Per-kernel shape/dtype sweeps vs pure-jnp oracles (interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.lbfgs import lbfgs_hvp_stacked
from repro.kernels.dequant_update.ops import dequant_sub, dequant_update
from repro.kernels.dequant_update.ref import (dequant_ref, dequant_sub_ref,
                                              dequant_update_ref)
from repro.kernels.flash_attention.ops import attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.fused_update.ops import update
from repro.kernels.fused_update.ref import deltagrad_update_ref
from repro.kernels.lbfgs.ops import lbfgs_hvp_fused, multidot
from repro.kernels.lbfgs.ref import multidot_ref


# -- lbfgs multidot / rank update ---------------------------------------------


@pytest.mark.parametrize("m,p", [(1, 512), (2, 1000), (3, 4096), (8, 777)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_multidot_sweep(m, p, dtype):
    rng = np.random.default_rng(m * 1000 + p)
    dW = jnp.asarray(rng.normal(size=(m, p)), dtype)
    dG = jnp.asarray(rng.normal(size=(m, p)), dtype)
    v = jnp.asarray(rng.normal(size=(p,)), dtype)
    sw, sy, wv, gv = multidot(dW, dG, v, interpret=True)
    rsw, rsy, rwv, rgv = multidot_ref(dW, dG, v)
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    for got, ref in [(sw, rsw), (sy, rsy), (wv, rwv), (gv, rgv)]:
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=tol, atol=tol * p)


@pytest.mark.parametrize("m,p", [(2, 1024), (5, 2222)])
def test_hvp_fused_matches_core(m, p):
    rng = np.random.default_rng(7)
    A = rng.normal(size=(p, p)).astype(np.float32) / p
    H = A @ A.T + np.eye(p, dtype=np.float32)
    dW = jnp.asarray(rng.normal(size=(m, p)).astype(np.float32))
    dG = jnp.asarray(np.asarray(dW) @ H)
    v = jnp.asarray(rng.normal(size=(p,)).astype(np.float32))
    out = lbfgs_hvp_fused(dW, dG, v, interpret=True)
    ref = lbfgs_hvp_stacked(dW, dG, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4,
                               atol=1e-4)


# -- flash attention ------------------------------------------------------------


@pytest.mark.parametrize(
    "B,S,H,Hkv,D,bq,bk,causal",
    [
        (2, 128, 4, 2, 64, 64, 64, True),
        (1, 256, 8, 8, 32, 128, 128, True),
        (2, 100, 4, 1, 64, 32, 32, True),  # unaligned seq (padding path)
        (1, 128, 2, 2, 128, 128, 128, False),
        (1, 64, 4, 4, 16, 16, 32, True),
    ],
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, S, H, Hkv, D, bq, bk, causal, dtype):
    key = jax.random.PRNGKey(B * 100 + S)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, H, D), dtype)
    k = jax.random.normal(ks[1], (B, S, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (B, S, Hkv, D), dtype)
    out = attention(q, k, v, causal=causal, block_q=bq, block_k=bk,
                    interpret=True)
    ref = attention_ref(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                        v.transpose(0, 2, 1, 3),
                        causal=causal).transpose(0, 2, 1, 3)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=tol, atol=tol)


def test_flash_attention_matches_model_blockwise_path():
    """Kernel == the XLA blockwise path used inside the models."""
    from repro.models.layers import blockwise_attention
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 3)
    B, S, H, Hkv, D = 2, 128, 4, 2, 32
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, Hkv, D))
    v = jax.random.normal(ks[2], (B, S, Hkv, D))
    out_kernel = attention(q, k, v, causal=True, block_q=64, block_k=64,
                           interpret=True)
    out_xla = blockwise_attention(q, k, v, causal=True, block_k=64)
    np.testing.assert_allclose(np.asarray(out_kernel), np.asarray(out_xla),
                               rtol=2e-5, atol=2e-5)


# -- fused DeltaGrad update -------------------------------------------------------


@pytest.mark.parametrize("p", [512, 1000, 4096])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("sign", [1.0, -1.0])
def test_fused_update_sweep(p, dtype, sign):
    rng = np.random.default_rng(p)
    w, g, bv, gc = [jnp.asarray(rng.normal(size=(p,)), dtype)
                    for _ in range(4)]
    out = update(w, g, bv, gc, 0.1, 512.0, 3.0, sign, interpret=True)
    ref = deltagrad_update_ref(w, g, bv, gc, jnp.float32(0.1),
                               jnp.float32(512.0), jnp.float32(3.0),
                               jnp.float32(sign))
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=2e-2,
                               atol=2e-2)


# -- fused dequant + update (encoded streamed history) ------------------------


def _encoded_operand(rng, p, qdtype, delta):
    """(q, scale, base) mimicking an EncodedLeaf slice: int8 carries a
    per-entry scale, bf16 is a plain cast, `delta` adds an f32 keyframe."""
    x = rng.normal(size=(p,)).astype(np.float32) * 0.05
    if qdtype == jnp.int8:
        scale = np.float32(np.max(np.abs(x)) / 127.0)
        q = jnp.asarray(np.clip(np.round(x / scale), -127, 127), jnp.int8)
    else:
        scale = np.float32(1.0)
        q = jnp.asarray(x, jnp.bfloat16)
    base = jnp.asarray(rng.normal(size=(p,)).astype(np.float32)) \
        if delta else None
    return q, scale, base


@pytest.mark.parametrize("p", [512, 1000, 4096])
@pytest.mark.parametrize("qdtype", [jnp.int8, jnp.bfloat16])
@pytest.mark.parametrize("delta", [False, True])
def test_dequant_update_sweep(p, qdtype, delta):
    rng = np.random.default_rng(p + int(delta))
    q, scale, base = _encoded_operand(rng, p, qdtype, delta)
    w, bv, gc = [jnp.asarray(rng.normal(size=(p,)).astype(np.float32))
                 for _ in range(3)]
    out = dequant_update(w, q, bv, gc, 0.1, 512.0, 3.0, 1, scale, base,
                         interpret=True)
    f32 = jnp.float32
    ref = dequant_update_ref(w, q, bv, gc, f32(0.1), f32(512.0), f32(3.0),
                             f32(1.0), f32(scale), base)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-2,
                               atol=2e-2)


@pytest.mark.parametrize("p", [512, 1000])
@pytest.mark.parametrize("qdtype", [jnp.int8, jnp.bfloat16])
@pytest.mark.parametrize("delta", [False, True])
def test_dequant_sub_sweep(p, qdtype, delta):
    rng = np.random.default_rng(p + 2 * int(delta))
    q, scale, base = _encoded_operand(rng, p, qdtype, delta)
    w = jnp.asarray(rng.normal(size=(p,)).astype(np.float32))
    out = dequant_sub(w, q, scale, base, interpret=True)
    ref = dequant_sub_ref(w, q, jnp.float32(scale), base)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-2,
                               atol=2e-2)


@pytest.mark.parametrize("shape", [(40, 300), (3, 50, 2100), (8, 64)],
                         ids=["rows-edge", "rows-cols-edge", "one-block"])
@pytest.mark.parametrize("qdtype", [jnp.int8, jnp.bfloat16])
def test_dequant_kernels_keep_array_layout(shape, qdtype):
    """Arrays of rank 2 and more run in (rows, last dim) blocks, edge
    blocks clipped, and give the oracles' numbers in their own shape."""
    rng = np.random.default_rng(len(shape))
    p = int(np.prod(shape))
    q, scale, base = _encoded_operand(rng, p, qdtype, True)
    q, base = q.reshape(shape), base.reshape(shape)
    w, bv, gc = [jnp.asarray(rng.normal(size=shape).astype(np.float32))
                 for _ in range(3)]
    f32 = jnp.float32
    out = dequant_update(w, q, bv, gc, 0.1, 512.0, 3.0, 1, scale, base,
                         interpret=True)
    ref = dequant_update_ref(w, q, bv, gc, f32(0.1), f32(512.0), f32(3.0),
                             f32(1.0), f32(scale), base)
    # this scale is not the codec's exact-product one, so the kernel and
    # the oracle may round q * scale + base differently: one ulp
    tol = dict(rtol=1e-6, atol=1e-6)
    assert out.shape == shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **tol)
    out = dequant_sub(w, q, scale, base, interpret=True)
    assert out.shape == shape
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(dequant_sub_ref(w, q, f32(scale), base)),
        **tol)


def test_dequant_absmax_zero_scale_one():
    """An all-zero residual leaf stores scale 1.0 and q zeros — the kernel
    must return the base exactly (keyframe entries decode bitwise)."""
    p = 512
    base = jnp.asarray(np.random.default_rng(0)
                       .normal(size=(p,)).astype(np.float32))
    q = jnp.zeros((p,), jnp.int8)
    w = base * 2.0
    out = dequant_sub(w, q, np.float32(1.0), base, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(w - base))


def test_dequant_ref_is_the_decode_expression():
    """The ref oracle and the store's slice decode share one expression."""
    from repro.core.store import EncodedLeaf, _decode_leaf_slice
    rng = np.random.default_rng(3)
    p = 64
    q = jnp.asarray(rng.integers(-127, 127, size=(2, p)), jnp.int8)
    scale = jnp.asarray(rng.random(2).astype(np.float32))
    base = jnp.asarray(rng.normal(size=(1, p)).astype(np.float32))
    leaf = EncodedLeaf(q=q, scale=scale, base=base,
                       kidx=jnp.zeros((2,), jnp.int32))
    got = jax.jit(lambda lf: _decode_leaf_slice(lf, 1))(leaf)
    ref = jax.jit(dequant_ref)(q[1], scale[1], base[0])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
