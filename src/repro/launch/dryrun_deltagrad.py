import os
if "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ before any other import — jax locks the device count on first init.

"""Dry-run of the PAPER'S OWN hot path at LM scale: one DeltaGrad approx
step (Algorithm 1, non-explicit branch) for an assigned architecture on the
production mesh.

The step = grad over the r removed sequences present in the batch
(+ L-BFGS B·v over the full parameter pytree + the leave-r-out update),
with the history pair buffers sharded exactly like the parameters.  This is
the cell the §Perf log hillclimbs as "most representative of the paper's
technique":

    python -m repro.launch.dryrun_deltagrad --arch internlm2-1.8b
"""

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import get_config, get_shape
from repro.core.lbfgs import lbfgs_hvp_stacked_pytree
from repro.dist.sharding import inputs_shardings, make_plan, params_shardings
from repro.launch.mesh import make_production_mesh
from repro.models.registry import build, count_params
from repro.roofline.analysis import roofline_from_compiled
from repro.roofline.hw import TPU_V5E
from repro.roofline.model import analytic_cost
from repro.utils.tree import tree_sub

M_HISTORY = 2  # paper default
# removed sequences present in this step's minibatch, padded UP to the
# data-parallel degree: a removal buffer smaller than the `data` axis is
# unshardable -> replicated -> every device redundantly recomputes the
# removed-gradient AND its TP all-reduces go 16x (§Perf deltagrad-step
# iteration 2). The engine's DeltaGradConfig.removal_pad does the same.
R_SEQS = 16


def lower_deltagrad_cell(arch: str, multi_pod: bool = False,
                         variant: str = "baseline"):
    cfg = get_config(arch)
    shape = get_shape("train_4k")
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    n_dev = int(np.prod(mesh.devices.shape))
    plan = make_plan(mesh, cfg)
    model = build(cfg)

    params_specs = jax.eval_shape(lambda: model.init(0))
    p_shard = params_shardings(plan, params_specs)
    # ZeRO compute constraint (same lesson as §Perf iteration 3): gradients
    # must see model-only-sharded weights, or GSPMD contraction-splits the
    # data-FSDP dim and replicates the batch.
    compute_shard = params_shardings(make_plan(mesh, cfg, fsdp=False),
                                     params_specs)
    stacked_specs = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((M_HISTORY,) + s.shape, s.dtype),
        params_specs)
    # history pairs sharded like params (stack axis replicated)
    stk_shard = jax.tree.map(
        lambda ns: jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec(None, *ns.spec)), p_shard)
    rem_specs = {"tokens": jax.ShapeDtypeStruct((R_SEQS, shape.seq_len),
                                                jnp.int32)}
    rem_shard = inputs_shardings(plan, rem_specs)
    scalars = jax.ShapeDtypeStruct((), jnp.float32)

    def approx_step(params, w_t, g_t, dWs, dGs, rem_batch, lr, n_total, r):
        """Paper eq. (2): w -= lr/(n-r) [ n (g_t + B v) - r g_removed ]."""
        v = tree_sub(params, w_t)
        bv = lbfgs_hvp_stacked_pytree(dWs, dGs, v)
        params_c = jax.lax.with_sharding_constraint(params, compute_shard)
        g_removed = jax.grad(lambda p: model.loss_fn(p, rem_batch))(params_c)
        denom = jnp.maximum(n_total - r, 1.0)

        def upd(p, gt, b, gr):
            return p - lr * (n_total * (gt + b) - r * gr) / denom

        return jax.tree.map(upd, params, g_t, bv, g_removed)

    with mesh:
        lowered = jax.jit(
            approx_step,
            in_shardings=(p_shard, p_shard, p_shard, stk_shard, stk_shard,
                          rem_shard, None, None, None),
            donate_argnums=(0,),
        ).lower(params_specs, params_specs, params_specs, stacked_specs,
                stacked_specs, rem_specs, scalars, scalars, scalars)
        compiled = lowered.compile()

    # analytic cost: removed-seq grad (train-like on R_SEQS sequences)
    # + (4m+3) parameter-sized streams for hvp/update + Gram psums.
    n_params = count_params(cfg)
    import dataclasses
    sub_shape = dataclasses.replace(shape, global_batch=R_SEQS)
    ac_grad = analytic_cost(cfg, sub_shape, n_params=n_params)
    hvp_flops = (4 * M_HISTORY + 3) * n_params * 2
    hvp_bytes = (4 * M_HISTORY + 6) * n_params * 4.0
    flops = ac_grad.flops_global + hvp_flops
    bytes_ = ac_grad.breakdown.get("bytes_acts", 0) + \
        3 * R_SEQS * shape.seq_len * cfg.vocab * 4.0 + hvp_bytes

    report = roofline_from_compiled(
        compiled, hw=TPU_V5E, arch=f"deltagrad-step-{arch}",
        shape="train_4k",
        mesh_name=mesh_name, n_devices=n_dev,
        model_flops=6.0 * n_params * R_SEQS * shape.seq_len,
        analytic_flops=flops, analytic_bytes=bytes_, variant=variant,
        note=f"approx step, m={M_HISTORY}, r={R_SEQS} seqs in batch")
    return lowered, compiled, report


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default="benchmarks/artifacts")
    ap.add_argument("--variant", default="baseline")
    args = ap.parse_args()
    t0 = time.time()
    lowered, compiled, report = lower_deltagrad_cell(
        args.arch, args.multi_pod, args.variant)
    dt = time.time() - t0
    mem = str(compiled.memory_analysis())
    print(f"OK deltagrad-step {args.arch} compile={dt:.1f}s "
          f"dominant={report.dominant} t=({report.t_compute:.3e},"
          f"{report.t_memory:.3e},{report.t_collective:.3e})")
    print(f"   memory: {mem[:240]}")
    rec = json.loads(report.to_json())
    rec.update({"status": "ok", "compile_s": dt, "memory_analysis": mem})
    os.makedirs(args.out, exist_ok=True)
    tag = f"deltagrad-step-{args.arch}__train_4k__{report.mesh}__{args.variant}"
    with open(os.path.join(args.out, tag + ".json"), "w") as f:
        json.dump(rec, f, indent=1)


if __name__ == "__main__":
    main()
