"""Sharding resolver rules + a small-scale multi-device dry-run.

The multi-device part runs in a SUBPROCESS so the forced host device count
never pollutes the main test process (smoke tests must see 1 device).
"""

import os
import subprocess
import sys
import textwrap

import pytest
from jax.sharding import PartitionSpec as P


class FakeMesh:
    axis_names = ("data", "model")
    class devices:  # noqa: D401
        shape = (16, 16)


def plan():
    from repro.dist.sharding import ShardingPlan
    return ShardingPlan(mesh=FakeMesh())


def spec(path, shape):
    from repro.dist.sharding import spec_for_leaf
    return spec_for_leaf(plan(), path, shape)


class TestResolverRules:
    def test_column_parallel(self):
        # stacked (n_units, d, H*dh): model on OUTPUT dim, data-FSDP on input
        assert spec("u0/mixer/wq", (24, 2048, 2048)) == P(None, "data", "model")
        assert spec("u0/mlp/w_up", (24, 2048, 8192)) == P(None, "data", "model")
        # non-stacked (shared/hybrid closure block)
        assert spec("shared/mixer/wq", (2048, 2048)) == P("data", "model")

    def test_row_parallel(self):
        assert spec("u0/mixer/wo", (24, 2048, 2048)) == P(None, "model", "data")
        assert spec("u0/mlp/w_down", (24, 8192, 2048)) == P(None, "model", "data")

    def test_stacked_layer_axis_never_sharded(self):
        s = spec("u0/mlp/w_up", (32, 2048, 8192))  # 32 divisible by 16!
        assert s == P(None, "data", "model")

    def test_non_divisible_replicates(self):
        # an output dim of 20 heads * 7 = 140 is not divisible by 16
        s = spec("u0/mixer/wq", (24, 2048, 140))
        assert s == P(None, "data", None)

    def test_embed_replicated_on_model(self):
        s = spec("embed", (92544, 2048))
        assert s == P("data", None)

    def test_norms_replicated(self):
        assert spec("u0/ln1/scale", (24, 2048)) == P(None, "data")
        assert spec("final_norm/scale", (2048,)) == P("data")

    def test_batch_pspec_fallbacks(self):
        from repro.dist.sharding import batch_pspec
        p = plan()
        assert batch_pspec(p, (256, 4096)) == P("data", None)
        assert batch_pspec(p, (1, 1)) == P(None, None)  # long_500k batch 1


class TestMoERules:
    def test_expert_parallel_when_divisible(self):
        from repro.dist.sharding import make_plan, spec_for_leaf
        from repro.configs.registry import get_config
        pl = make_plan(FakeMesh(), get_config("moonshot-v1-16b-a3b"))
        s = spec_for_leaf(pl, "u0/mlp/w_gate", (48, 64, 2048, 1408))
        assert s == P(None, "model", None, "data")

    def test_tp_fallback_when_not_divisible(self):
        from repro.dist.sharding import make_plan, spec_for_leaf
        from repro.configs.registry import get_config
        pl = make_plan(FakeMesh(), get_config("qwen2-moe-a2.7b"))
        s = spec_for_leaf(pl, "u0/mlp/w_gate", (24, 60, 2048, 1408))
        assert s == P(None, None, "data", "model")


SUBPROCESS_PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.configs.registry import get_config
    from repro.configs.base import ShapeConfig
    from repro.dist.sharding import (inputs_shardings, make_mesh, make_plan,
                                     params_shardings)
    from repro.models.registry import build

    mesh = make_mesh((4, 2), ("data", "model"))
    cfg = get_config("internlm2-1.8b").reduced(d_model=64, n_heads=4,
                                               n_kv_heads=2, d_ff=128,
                                               vocab=256, d_head=16)
    model = build(cfg)
    plan = make_plan(mesh, cfg)
    shape = ShapeConfig(name="t", seq_len=16, global_batch=8, kind="train")
    specs = model.input_specs(shape)
    params_specs = jax.eval_shape(lambda: model.init(0))
    p_shard = params_shardings(plan, params_specs)
    in_shard = inputs_shardings(plan, specs)

    def loss(p, b):
        return model.loss_fn(p, b, remat=False, loss_chunk=8)

    with mesh:
        lowered = jax.jit(jax.grad(loss),
                          in_shardings=(p_shard, in_shard)).lower(
            params_specs, specs)
        compiled = lowered.compile()
    from repro.roofline.analysis import cost_analysis_dict
    cost = cost_analysis_dict(compiled)
    assert float(cost.get("flops", 0)) > 0
    # actually execute on the 8 fake devices — numerics + shardings together
    params = jax.device_put(model.init(0), p_shard)
    batch = jax.device_put(model.sample_batch(shape), in_shard)
    g = jax.jit(jax.grad(loss), in_shardings=(p_shard, in_shard))(params, batch)
    total = sum(float(jnp.sum(jnp.abs(x))) for x in jax.tree.leaves(g))
    assert np.isfinite(total) and total > 0
    # compare against single-device execution
    g1 = jax.grad(loss)(model.init(0), model.sample_batch(shape))
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-2, atol=2e-2)
    print("MULTIDEVICE_OK")
""")


def test_multidevice_lower_compile_and_execute():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run([sys.executable, "-c", SUBPROCESS_PROG],
                         capture_output=True, text=True, env=env, timeout=500)
    assert out.returncode == 0, (out.stdout[-1000:], out.stderr[-3000:])
    assert "MULTIDEVICE_OK" in out.stdout
