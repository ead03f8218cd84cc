"""Compile a cell's main programs at real size for a described TPU v5e,
with no chip attached, and print each program's `memory_analysis()`.

    JAX_PLATFORMS=cpu python3 bench/tools/compile_v5e.py --config paper-mlp

What the chip's compiler would refuse (a kernel's tiling, a program that
does not fit), it refuses here at no chip time.  Nothing runs.
"""

import argparse
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def _mem(compiled):
    m = compiled.memory_analysis()
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "generated_code_size_in_bytes")
    return {k: int(getattr(m, k)) for k in keys if hasattr(m, k)}


def mlp(cfg, dev):
    import jax
    import jax.numpy as jnp

    from bench.harness.core import load_module
    from repro.models.simple import mlp_objective
    from repro.kernels.fused_update.ops import update

    n, d, h, c = (int(cfg[k]) for k in ("n_rows", "d_in", "hidden",
                                         "classes"))
    S = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(shape, dt,
                                                          sharding=dev)
    params = {"w1": S((d, h)), "b1": S((h,)), "w2": S((h, c)), "b2": S((c,))}
    cols = {"x": S((n, d)), "y": S((n,), jnp.int32)}
    out = {}
    grad = mlp_objective(l2=float(cfg["l2"])).make_grad_fn()
    out["program: full-batch gradient (explicit step)"] = _mem(
        grad.lower(params, cols, S((n,))).compile())
    p = sum(x.shape[0] * (x.shape[1] if len(x.shape) > 1 else 1)
            for x in params.values())
    out["program: fused_update kernel"] = _mem(
        update.lower(S((p,)), S((p,)), S((p,)), S((p,)), 0.1, 10.0, 1.0,
                     1.0).compile())
    ref = load_module(os.path.join(ROOT, "bench", "configs",
                                   cfg["name"] + ".reference.py"))
    train = ref.make_train(cfg, "highest")
    out["reference: exact retraining, 40 steps"] = _mem(
        train.lower(params, cols["x"], cols["y"],
                    S((n,), jnp.bool_)).compile())
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    args = ap.parse_args()
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench.harness.core import load_json

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    dev = SingleDeviceSharding(topo.devices[0])
    cfg = load_json(ROOT, "bench", "configs", args.config + ".json")
    print(json.dumps(globals()[cfg["model"]](cfg, dev), indent=1))


if __name__ == "__main__":
    main()
