import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import: jax locks the device count on first init.

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell with
ShapeDtypeStruct inputs and production NamedShardings, record
memory_analysis / cost_analysis / collective bytes for §Dry-run and
§Roofline of EXPERIMENTS.md.

Usage:
    python -m repro.launch.dryrun --arch qwen3-32b --shape train_4k
    python -m repro.launch.dryrun --all [--multi-pod-only|--single-pod-only]
    python -m repro.launch.dryrun --all --out benchmarks/artifacts

Skips (documented in DESIGN.md §6): long_500k for pure full-attention archs.
"""

import argparse
import json
import time
import traceback
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import all_archs, all_shapes, get_config, get_shape
from repro.dist.sharding import (
    caches_shardings,
    inputs_shardings,
    make_plan,
    params_shardings,
)
from repro.launch.mesh import make_production_mesh
from repro.models.registry import active_param_count, build, count_params
from repro.optim.optimizers import adamw
from repro.roofline.analysis import roofline_from_compiled
from repro.roofline.hw import TPU_V5E
from repro.roofline.model import analytic_cost
from repro.train.loop import make_train_step
from repro.train.state import TrainState

# long_500k only runs for sub-quadratic (SSM/hybrid) families.
LONG_OK_FAMILIES = ("ssm", "hybrid")

# gradient-accumulation factor per train shape (activation-memory fit)
GRAD_ACCUM = {"train_4k": 8}


def cell_is_skipped(arch: str, shape: str) -> Optional[str]:
    cfg = get_config(arch)
    if cfg.family == "simple":
        return "paper model (exercised via repro.core, not the LM dry-run)"
    sh = get_shape(shape)
    if sh.kind == "long_decode" and cfg.family not in LONG_OK_FAMILIES:
        return "long_500k needs sub-quadratic attention (full-attention arch)"
    return None


def model_flops(cfg, shape) -> float:
    n = active_param_count(cfg) if cfg.moe else count_params(cfg)
    if shape.kind == "train":
        return 6.0 * n * shape.seq_len * shape.global_batch
    if shape.kind == "prefill":
        return 2.0 * n * shape.seq_len * shape.global_batch
    # decode: one token per sequence
    return 2.0 * n * shape.global_batch


def lower_cell(arch: str, shape_name: str, multi_pod: bool, *,
               grad_accum: Optional[int] = None, variant: str = "baseline",
               plan_tweak=None):
    cfg = get_config(arch)
    if "moesort" in variant and cfg.moe is not None:
        import dataclasses
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, dispatch="sort"))
    shape = get_shape(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    n_dev = int(np.prod(mesh.devices.shape))
    plan = make_plan(mesh, cfg)
    if "dpzero" in variant:
        plan.batch_over_model = True  # pure DP: model axis carries batch
    if plan_tweak is not None:
        plan = plan_tweak(plan)
    model = build(cfg)

    specs = model.input_specs(shape)
    in_batch_shardings = inputs_shardings(plan, specs)

    def _serve_params():
        """Serving stores weights compute-ready: bf16, model-only sharding.
        FSDP(data)-sharded fp32 weights would be re-gathered EVERY decoded
        token (measured: 2 weight all-gathers per layer per step on
        minicpm3 decode_32k — §Perf decode iteration 1); there is no
        optimizer state to justify it."""
        sp = jax.eval_shape(lambda: model.init(0))
        sp = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(
                s.shape, jnp.bfloat16 if s.dtype == jnp.float32 else s.dtype),
            sp)
        serve_plan = make_plan(mesh, cfg, fsdp=False)
        return sp, params_shardings(serve_plan, sp)

    if shape.is_decode:
        if cfg.family == "audio":
            cache_specs = model.cache_specs(shape.global_batch, shape.seq_len,
                                            enc_len=1500)
        else:
            cache_specs = model.cache_specs(shape.global_batch, shape.seq_len)
        params_specs, p_shard = _serve_params()
        c_shard = caches_shardings(plan, cache_specs)

        def serve_step(params, batch, caches):
            return model.decode_fn(params, batch, caches)

        with mesh:
            lowered = jax.jit(
                serve_step,
                in_shardings=(p_shard, in_batch_shardings, c_shard),
                donate_argnums=(2,),
            ).lower(params_specs, specs, cache_specs)
            compiled = lowered.compile()
    elif shape.kind == "prefill":
        params_specs, p_shard = _serve_params()

        def prefill_step(params, batch):
            return model.prefill_fn(params, batch)

        with mesh:
            lowered = jax.jit(
                prefill_step,
                in_shardings=(p_shard, in_batch_shardings),
            ).lower(params_specs, specs)
            compiled = lowered.compile()
    else:
        accum = grad_accum if grad_accum is not None else GRAD_ACCUM.get(
            shape_name, 1)
        if "dpzero" in variant:
            accum = 1  # per-device batch is already global/256 sequences
        opt = adamw()
        loss_kwargs = {}
        if "seqpar" in variant:
            # sequence parallelism: residual stream sharded (dp, model, -)
            from jax.sharding import PartitionSpec as P
            sizes = plan.axis_sizes
            dp = tuple(a for a in ("pod", "data") if a in sizes)
            loss_kwargs["act_pspec"] = P(dp if len(dp) > 1 else dp[0],
                                         "model", None)
        loss = lambda p, b: model.loss_fn(p, b, **loss_kwargs)  # noqa: E731
        from repro.dist.sharding import batch_pspec

        def micro_shard(leaf):
            # microbatch leaves are (grad_accum, B/g, ...): batch is axis 1
            spec = batch_pspec(plan, leaf.shape, batch_axis=1)
            return plan.named(spec)

        if "dpzero" in variant:
            # pure DP: compute weights fully replicated (ZeRO gathers once)
            from repro.dist.sharding import replicated_shardings
            compute_shard = replicated_shardings(
                plan, jax.eval_shape(lambda: model.init(0)))
        else:
            compute_plan = make_plan(mesh, cfg, fsdp=False)
            if plan_tweak is not None:
                compute_plan = plan_tweak(compute_plan)
            compute_shard = params_shardings(
                compute_plan, jax.eval_shape(lambda: model.init(0)))
        compute_dtype = jnp.bfloat16 if "bf16zero" in variant else None
        params_specs = jax.eval_shape(lambda: model.init(0))
        step_fn = make_train_step(loss, opt, lambda s: jnp.float32(3e-4),
                                  grad_accum=accum,
                                  microbatch_sharding=micro_shard,
                                  compute_sharding=compute_shard,
                                  compute_dtype=compute_dtype,
                                  storage_sharding=params_shardings(
                                      plan, params_specs))
        opt_specs = jax.eval_shape(opt.init, params_specs)
        state_specs = TrainState(params_specs, opt_specs,
                                 jax.ShapeDtypeStruct((), jnp.int32))
        p_shard = params_shardings(plan, params_specs)
        o_shard = params_shardings(plan, opt_specs)
        s_shard = TrainState(p_shard, o_shard,
                             plan.named(jax.sharding.PartitionSpec()))
        with mesh:
            lowered = jax.jit(
                step_fn,
                in_shardings=(s_shard, in_batch_shardings),
                donate_argnums=(0,),
            ).lower(state_specs, specs)
            compiled = lowered.compile()

    ac = analytic_cost(cfg, shape,
                       grad_accum=(grad_accum or GRAD_ACCUM.get(shape_name, 1)),
                       n_params=count_params(cfg))
    report = roofline_from_compiled(
        compiled,
        hw=TPU_V5E,
        arch=arch,
        shape=shape_name,
        mesh_name=mesh_name,
        n_devices=n_dev,
        model_flops=model_flops(cfg, shape),
        variant=variant,
        analytic_flops=ac.flops_global,
        analytic_bytes=ac.bytes_global,
    )
    return lowered, compiled, report


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: Optional[str],
             verbose: bool = True, variant: str = "baseline"):
    skip = cell_is_skipped(arch, shape_name)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    if skip:
        if verbose:
            print(f"SKIP  {arch} x {shape_name} x {mesh_name}: {skip}")
        return {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                "status": "skipped", "reason": skip}
    t0 = time.time()
    try:
        lowered, compiled, report = lower_cell(arch, shape_name, multi_pod,
                                               variant=variant)
    except Exception as e:
        traceback.print_exc()
        return {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                "status": "failed", "error": f"{type(e).__name__}: {e}"}
    dt = time.time() - t0
    try:
        mem = compiled.memory_analysis()
        mem_str = str(mem)
    except Exception:
        mem_str = "n/a"
    if verbose:
        print(f"OK    {arch} x {shape_name} x {mesh_name}  "
              f"compile={dt:.1f}s dominant={report.dominant} "
              f"t=({report.t_compute:.3e},{report.t_memory:.3e},"
              f"{report.t_collective:.3e})s useful={report.usefulness:.3f}")
        print(f"      memory_analysis: {mem_str[:300]}")
        print(f"      cost_analysis: flops/dev="
              f"{report.flops_global / report.n_devices:.3e} "
              f"bytes/dev={report.bytes_global / report.n_devices:.3e}")
    rec = json.loads(report.to_json())
    rec.update({"status": "ok", "compile_s": dt, "memory_analysis": mem_str})
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        tag = f"{arch}__{shape_name}__{mesh_name}__{variant}".replace("/", "_")
        with open(os.path.join(out_dir, tag + ".json"), "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--single-pod-only", action="store_true")
    ap.add_argument("--multi-pod-only", action="store_true")
    ap.add_argument("--out", default="benchmarks/artifacts")
    ap.add_argument("--variant", default="baseline")
    args = ap.parse_args()

    meshes = [False, True]
    if args.single_pod_only:
        meshes = [False]
    if args.multi_pod_only:
        meshes = [True]
    if not args.all and args.multi_pod:
        meshes = [True]
    elif not args.all and not args.multi_pod:
        meshes = [False]

    results = []
    if args.all:
        archs = [a for a, c in all_archs().items() if c.family != "simple"]
        shapes = list(all_shapes().keys())
        for mp in meshes:
            for arch in archs:
                for shape in shapes:
                    results.append(run_cell(arch, shape, mp, args.out,
                                            variant=args.variant))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        for mp in meshes:
            results.append(run_cell(args.arch, args.shape, mp, args.out,
                                    variant=args.variant))

    ok = sum(1 for r in results if r["status"] == "ok")
    sk = sum(1 for r in results if r["status"] == "skipped")
    fail = [r for r in results if r["status"] == "failed"]
    print(f"\n=== dry-run summary: {ok} ok, {sk} skipped, {len(fail)} failed ===")
    for r in fail:
        print(f"FAILED {r['arch']} x {r['shape']} x {r['mesh']}: {r['error']}")
    if fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
