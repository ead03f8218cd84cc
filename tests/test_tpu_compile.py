"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Nothing here runs on a chip: each kernel is lowered against a described
``v5e:2x2`` topology and compiled by the TPU compiler installed with JAX,
which refuses what interpret mode accepts (unaligned slices, too much fast
memory, unsupported casts).  Widths: the paper MLP's flat parameter vector
(784-300-10, 238,510 parameters) and one 2048x8192 LM projection leaf, with
an m=2 L-BFGS ring; flash attention at InternLM2-1.8B's heads.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library, and the
test workers all import this file.  Keep these tests in this one file so
the worker that loads the library runs all of them.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

P_MLP = 784 * 300 + 300 + 300 * 10 + 10
P_LM_LEAF = 2048 * 8192
M_HISTORY = 2


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _assert_kernel_compiles(fn, *args):
    compiled = fn.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _lower_args(op: str, p: int, one_chip):
    def arr(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    vec, scalar = arr((p,)), arr(())
    if op == "fused_update":
        from repro.kernels.fused_update.ops import update
        return update, (vec, vec, vec, vec, scalar, scalar, scalar, scalar)
    if op == "dequant_update":
        from repro.kernels.dequant_update.ops import dequant_update
        return dequant_update, (vec, arr((p,), jnp.int8), vec, vec, scalar,
                                scalar, scalar, scalar, scalar, vec)
    if op == "dequant_sub":
        from repro.kernels.dequant_update.ops import dequant_sub
        return dequant_sub, (vec, arr((p,), jnp.int8), scalar, vec)
    ring = arr((M_HISTORY, p))
    if op == "multidot":
        from repro.kernels.lbfgs.ops import multidot
        return multidot, (ring, ring, vec)
    from repro.kernels.lbfgs.ops import rank_update
    coef = arr((M_HISTORY,))
    return rank_update, (ring, ring, vec, coef, coef, scalar)


@pytest.mark.parametrize("p", [P_MLP, P_LM_LEAF], ids=["mlp", "lm_leaf"])
@pytest.mark.parametrize("op", ["fused_update", "dequant_update",
                                "dequant_sub", "multidot", "rank_update"])
def test_kernel_compiles_for_v5e(op, p, one_chip):
    fn, args = _lower_args(op, p, one_chip)
    _assert_kernel_compiles(fn, *args)


def test_flash_forward_compiles_for_v5e(one_chip):
    from repro.kernels.flash_attention.ops import attention

    def act(heads):
        return jax.ShapeDtypeStruct((1, 2048, heads, 128), jnp.bfloat16,
                                    sharding=one_chip)

    _assert_kernel_compiles(attention, act(16), act(8), act(8))


@pytest.mark.parametrize("shape,spec", [
    ((92544, 2048), ("model", None)),            # embedding rows
    ((8, 2048, 8192), (None, None, "model")),    # stacked column-parallel
], ids=["embed", "w_gate"])
def test_sharded_dequant_update_compiles_per_device(topo, shape, spec):
    """On a (data, model) = (1, 4) v5e mesh the replay's fused dequant
    update runs per device on each one's block of an InternLM2 leaf
    (`core.store.per_shard`): the Pallas call compiles, and no operand is
    gathered across the chips."""
    import numpy as np
    from jax.sharding import AxisType, Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.core.engine import _dequant_fused_update
    from repro.core.store import EncodedLeaf

    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    ps = P(*spec)

    def arr(shp, dtype=jnp.float32, s=ps):
        return jax.ShapeDtypeStruct(shp, dtype,
                                    sharding=NamedSharding(mesh, s))

    rep = lambda shp, dtype=jnp.float32: arr(shp, dtype, P())
    leaf = EncodedLeaf(q=arr((2,) + shape, jnp.int8, P(None, *spec)),
                       scale=rep((2,)),
                       base=arr((1,) + shape, s=P(None, *spec)),
                       kidx=rep((2,), jnp.int32))

    def step(p, G, bv, gc, lr, B, dB):
        return _dequant_fused_update(p, G, 1, bv, gc, lr, B, dB, 1,
                                     "pallas", shards=(mesh, (ps,)))

    compiled = jax.jit(step).lower(arr(shape), leaf, arr(shape), arr(shape),
                                   rep(()), rep(()), rep(())).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-gather" not in text
