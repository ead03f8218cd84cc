"""LM-scale DeltaGrad: the flagship end-to-end benchmark.

Everything the MLP benches measure, on a multi-million-parameter
transformer LM (a reduced `--model` registry config — internlm2-1.8b
blocks: GQA + RoPE + SwiGLU — with the objective built by
`Objective.from_model`):

  * ``session``  — the user-facing path: `UnlearnerSession.from_config`
    train-with-cache wall, a coalesced guard-ON delete burst vs
    `baseline_retrain` (wall + unlearning distance ratio), snapshot /
    restore bitwise parity, an add request, all with the tracer live so
    every ``replay.scan`` span carries roofline pred-vs-measured cost
    (exported to ``--trace-out``);
  * ``variants`` — the storage story at LM pytree shape: resident
    stacked f32 (reference + per-step python-oracle parity), host-
    streamed f32 (EXACT parity with resident — bit-identical recorders),
    host-streamed ``delta_int8`` (per-device HBM high-water, encoded
    bytes, compression, quantization envelope vs the python oracle), and
    a sharded+streamed delta_int8 run in a subprocess with a forced
    host-device mesh (`ShardedStreamer` carrying per-layer LM leaves);
  * ``flash``    — the Pallas flash-attention kernel routed onto the
    replay forward (interpret-mode oracle off-TPU) vs the blockwise
    reference, loss + gradient;
  * ``roofline`` — span counts and predicted/measured ratio stats pulled
    from the live trace;
  * ``derived``  — the acceptance booleans CI gates
    (`check_bench --suite lm`): deltagrad replay beats retrain, streamed
    delta_int8 HBM high-water under resident f32, exact streamed parity.

    PYTHONPATH=src python benchmarks/bench_lm.py --quick \
        --trace-out BENCH_lm.trace.json
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import subprocess
import sys
import tempfile
import time

import numpy as np

# CI-sized: ~2.4M params (untied embed + lm_head at vocab 8192 dominate),
# small enough that CPU CI fits+replays in minutes
QUICK = dict(n_layers=2, d_model=128, n_heads=4, n_kv_heads=2, d_ff=256,
             vocab=8192, d_head=32, seq=32, docs=128, batch=32, steps=16,
             lr=0.02, burn_in=4, period=4, window=4, deletes=4)
# flagship: ~4.8M params, deeper stack, longer path.  lr is HALVED vs
# QUICK and burn_in stretched: at 4 layers the quick lr=0.02 recipe makes
# the L-BFGS correction blow past the guard clip (NaN parity, distance
# ratio ~0); 0.01/burn_in=6 replays clean (ratio ~2.9, zero fallbacks).
# docs stays 128 so the 4 deletes keep the same corpus density the
# distance-ratio claim was calibrated at — at 256 docs the baseline
# barely moves and the ratio is noise either way.
FULL = dict(n_layers=4, d_model=128, n_heads=4, n_kv_heads=2, d_ff=256,
            vocab=16384, d_head=32, seq=32, docs=128, batch=32, steps=20,
            lr=0.01, burn_in=6, period=4, window=4, deletes=4)

SHAPE_KEYS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "d_ff",
              "vocab", "d_head")


def _shape(p):
    return {k: p[k] for k in SHAPE_KEYS}


def build_problem(args):
    from repro.configs.registry import get_config
    from repro.core.deltagrad import DeltaGradConfig, Objective
    from repro.core.history import HistoryMeta
    from repro.data.synthetic import token_stream
    from repro.models.registry import build, count_params

    p = QUICK if args.quick else FULL
    model_cfg = get_config(args.model).reduced(**_shape(p))
    model = build(model_cfg)
    obj = Objective.from_model(model, loss_chunk=p["seq"])
    docs = token_stream(n_docs=p["docs"], seq_len=p["seq"],
                        vocab=model_cfg.vocab, seed=args.seed)
    meta = HistoryMeta(n=docs.n, batch_size=p["batch"], seed=5,
                       steps=p["steps"], lr_schedule=((0, p["lr"]),))
    cfg = DeltaGradConfig(period=p["period"], burn_in=p["burn_in"],
                          history_size=2, guard=True, curvature_eps=1e-8,
                          stream_window=p["window"])
    removed = np.linspace(3, docs.n - 8, p["deletes"]).astype(np.int64)
    n_params = count_params(model_cfg)
    return p, model_cfg, model, obj, docs, meta, cfg, removed, n_params


def _median_wall(fn, reps):
    import jax
    w = fn()  # warm-up: trace + compile
    walls = []
    for _ in range(reps):
        t0 = time.perf_counter()
        w = fn()
        jax.block_until_ready(w[0] if isinstance(w, tuple) else w)
        walls.append(time.perf_counter() - t0)
    return float(np.median(walls)), w


def run_variant(args, variant: str):
    """One storage variant, bench_shard-style; `sharded_delta` expects the
    host platform device count already forced (subprocess)."""
    import jax

    from repro.core.deltagrad import (deltagrad_retrain,
                                      sgd_train_with_cache)
    from repro.core.store import HistoryStore, PlacementPolicy
    from repro.utils.tree import tree_norm, tree_sub

    p, _, _, obj, docs, meta, cfg, removed, _ = build_problem(args)
    delta = variant in ("delta_streamed", "sharded_delta")
    codec = "delta_int8" if delta else "f32"
    tier = "stacked" if variant == "resident" else "host"

    t0 = time.perf_counter()
    _, hist = sgd_train_with_cache(obj, _init(args), docs, meta,
                                   tier=tier, codec=codec)
    jax.block_until_ready(hist.final_params)
    train_wall = time.perf_counter() - t0

    placement = PlacementPolicy.local(args.devices) \
        if variant == "sharded_delta" else None
    store = None
    if tier == "host":
        store = HistoryStore.create(hist, placement=placement,
                                    window=p["window"])

    wall, (w, st) = _median_wall(
        lambda: deltagrad_retrain(obj, hist, docs, removed, cfg,
                                  store=store), args.reps)
    out = {
        "variant": variant,
        "devices": args.devices if placement is not None else 1,
        "store": st.extra["store"],
        "train_cache_wall_s": train_wall,
        "replay_wall_s": wall,
        "hbm_high_water_bytes": int(st.extra["hbm_high_water"]),
        "history_bytes": int(hist.nbytes()),
        "approx_steps": st.approx_steps,
        "explicit_steps": st.explicit_steps,
        "guard_fallbacks": st.guard_fallbacks,
    }
    if variant == "resident":
        # the flagship wall comparison: corrected replay vs retraining
        # from scratch on the same shrunken dataset (both warm)
        from repro.core.deltagrad import baseline_retrain
        bwall, _ = _median_wall(
            lambda: baseline_retrain(obj, docs, meta, _init(args), removed),
            args.reps)
        out["baseline_retrain_wall_s"] = bwall
        w_py, _ = deltagrad_retrain(obj, hist, docs, removed,
                                    dataclasses.replace(cfg, impl="python"))
        out["parity_vs_python"] = float(tree_norm(tree_sub(w, w_py))) \
            / max(1e-12, float(tree_norm(w_py)))
    if variant == "streamed":
        # exact invariant: the host-streamed recorder is bit-identical to
        # the stacked one, so the replay must match to the last bit
        _, hist_res = sgd_train_with_cache(obj, _init(args), docs, meta,
                                           tier="stacked")
        w_res, _ = deltagrad_retrain(obj, hist_res, docs, removed, cfg)
        out["parity_vs_resident"] = float(tree_norm(tree_sub(w, w_res)))
    if delta:
        out["compression_ratio"] = float(store.compression_ratio)
        out["encoded_bytes_high"] = int(store.enc_bytes_high)
        w_py, _ = deltagrad_retrain(obj, hist, docs, removed,
                                    dataclasses.replace(cfg, impl="python"))
        out["parity_vs_python"] = float(tree_norm(tree_sub(w, w_py))) \
            / max(1e-12, float(tree_norm(w_py)))
    if variant == "sharded_delta":
        # mesh reduction reassociation only: vs the single-device streamed
        # replay of the SAME encoded history
        w_1, _ = deltagrad_retrain(obj, hist, docs, removed, cfg)
        out["sharded_vs_streamed"] = float(tree_norm(tree_sub(w, w_1))) \
            / max(1e-12, float(tree_norm(w_1)))
    return out


def _init(args):
    from repro.configs.registry import get_config
    from repro.models.registry import build
    p = QUICK if args.quick else FULL
    return build(get_config(args.model).reduced(**_shape(p))).init(1)


def run_session(args, trace_out):
    """The user-facing path, traced end to end."""
    import jax

    from repro.core.deltagrad import DeltaGradConfig
    from repro.core.session import UnlearnerConfig, UnlearnerSession
    from repro.data.synthetic import token_stream
    from repro.obs import trace as obs_trace
    from repro.utils.tree import tree_norm, tree_sub

    p, model_cfg, model, _, _, _, _, removed, n_params = build_problem(args)
    docs = token_stream(n_docs=p["docs"], seq_len=p["seq"],
                        vocab=model_cfg.vocab, seed=args.seed)
    sess = UnlearnerSession.from_config(
        args.model, docs, reduced=_shape(p),
        config=UnlearnerConfig(
            steps=p["steps"], batch_size=p["batch"], lr=p["lr"], seed=5,
            deltagrad=DeltaGradConfig(period=p["period"],
                                      burn_in=p["burn_in"], history_size=2,
                                      guard=True, curvature_eps=1e-8)),
        loss_chunk=p["seq"])

    t0 = time.perf_counter()
    w_star = sess.fit()
    jax.block_until_ready(w_star)
    fit_wall = time.perf_counter() - t0
    hist_bytes = int(sess.history.nbytes())

    with tempfile.TemporaryDirectory() as snap:
        sess.save(snap)

        t0 = time.perf_counter()
        w_u, _ = sess.baseline(removed.tolist())
        jax.block_until_ready(w_u)
        baseline_wall = time.perf_counter() - t0

        # coalesced guard-ON burst: two handles, one group replay.  CPU
        # runs price spans against v5e peaks, named explicitly: the ratio
        # then says how far a CPU run sits from that roofline, not a
        # device metric
        from repro.roofline.hw import TPU_V5E
        obs_trace.enable(obs_trace.Tracer(hw=TPU_V5E))
        k = len(removed) // 2
        t0 = time.perf_counter()
        h1 = sess.delete(removed[:k].tolist())
        h2 = sess.delete(removed[k:].tolist())
        resp = h1.result()
        jax.block_until_ready(resp.params)
        delete_wall = time.perf_counter() - t0
        h2.result()
        tracer = obs_trace.disable()
        w_i = resp.params

        d_ui = float(tree_norm(tree_sub(w_u, w_i)))
        d_us = float(tree_norm(tree_sub(w_u, w_star)))

        # restore must serve the SAME coalesced plan bitwise-identically
        restored = UnlearnerSession.restore(snap, sess.objective)
        r1 = restored.delete(removed[:k].tolist())
        restored.delete(removed[k:].tolist())
        w_r = r1.result().params
        restore_dist = float(tree_norm(tree_sub(w_i, w_r)))

    # add: two fresh documents through the serving surface
    rng = np.random.default_rng(args.seed + 1)
    new_docs = {"tokens": rng.integers(0, model_cfg.vocab,
                                       size=(2, p["seq"]), dtype=np.int32)}
    w_a = sess.add(data=new_docs).result().params
    add_served = bool(all(np.isfinite(np.asarray(x)).all()
                          for x in jax.tree.leaves(w_a)))

    session = {
        "fit_wall_s": fit_wall,
        "history_bytes_resident": hist_bytes,
        "delete_wall_s": delete_wall,
        "baseline_retrain_wall_s": baseline_wall,
        "coalesced_group_size": int(resp.group_size),
        "distance_deltagrad": d_ui,
        "distance_noop": d_us,
        "distance_ratio": d_us / max(d_ui, 1e-12),
        "guard_fallbacks": int(resp.stats[0].guard_fallbacks),
        "restore_parity": restore_dist,
        "add_served": add_served,
        "params": int(n_params),
    }
    return session, _roofline_stats(tracer, trace_out)


def _roofline_stats(tracer, trace_out):
    """Parse the exported Chrome trace: every replay.scan span must carry
    the roofline pred/measured annotations (cf. bench_obs)."""
    path = trace_out
    tmp = None
    if not path:
        tmp = tempfile.NamedTemporaryFile(suffix=".json", delete=False)
        path = tmp.name
        tmp.close()
    tracer.export_chrome(path)
    with open(path) as f:
        doc = json.load(f)
    if tmp is not None:
        os.unlink(tmp.name)
    scans = [e for e in doc.get("traceEvents", [])
             if e.get("ph") == "X" and e.get("name") == "replay.scan"]
    need = {"pred_s", "measured_s", "roofline_ratio"}
    annotated = bool(scans) and all(need <= set(e.get("args", {}))
                                    for e in scans)
    ratios = [float(e["args"]["roofline_ratio"]) for e in scans
              if need <= set(e.get("args", {}))]
    return {
        "replay_scan_spans": len(scans),
        "annotated": annotated,
        "ratio_min": float(np.min(ratios)) if ratios else 0.0,
        "ratio_median": float(np.median(ratios)) if ratios else 0.0,
        "ratio_max": float(np.max(ratios)) if ratios else 0.0,
    }


def run_flash(args):
    """Flash kernel routed on the LM objective vs the blockwise reference
    (loss + grad through jit/vmap/grad — the replay engine's drive)."""
    import jax
    import jax.numpy as jnp

    from repro.core.deltagrad import Objective
    from repro.data.synthetic import token_stream
    from repro.utils.tree import tree_norm, tree_sub
    from repro.configs.registry import get_config
    from repro.models.registry import build

    p = QUICK if args.quick else FULL
    model = build(get_config(args.model).reduced(**_shape(p)))
    docs = token_stream(n_docs=4, seq_len=p["seq"], vocab=p["vocab"],
                        seed=args.seed)
    batch = {"tokens": jnp.asarray(np.asarray(docs.columns["tokens"]))}
    params = model.init(1)
    w = jnp.ones((4,))

    obj_ref = Objective.from_model(model, loss_chunk=p["seq"])
    obj_fl = Objective.from_model(model, loss_chunk=p["seq"],
                                  attn_impl="flash")
    l_ref, g_ref = obj_ref.make_value_grad_fn()(params, batch, w)
    l_fl, g_fl = obj_fl.make_value_grad_fn()(params, batch, w)
    loss_abs = abs(float(l_ref) - float(l_fl))
    grad_rel = float(tree_norm(tree_sub(g_fl, g_ref))) \
        / max(1e-12, float(tree_norm(g_ref)))
    return {
        "impl": "interpret" if jax.default_backend() != "tpu" else "pallas",
        "loss_abs_diff": loss_abs,
        "grad_rel_err": grad_rel,
        # bf16 model dtype: kernel-vs-ref tolerance (tests/test_kernels.py)
        "parity_ok": bool(loss_abs < 5e-3 and grad_rel < 5e-2),
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--model", default="internlm2-1.8b",
                    help="configs.registry name the reduced config is "
                         "derived from")
    ap.add_argument("--quick", action="store_true",
                    help="CI-sized run (matches the committed baseline)")
    ap.add_argument("--devices", type=int, default=2,
                    help="forced host devices for the sharded variant")
    ap.add_argument("--reps", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="BENCH_lm.json")
    ap.add_argument("--trace-out", default="",
                    help="Chrome trace of the session delete burst")
    ap.add_argument("--role", default="main", choices=("main", "variant"),
                    help=argparse.SUPPRESS)
    ap.add_argument("--variant", default="", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    if args.role == "variant":
        # child process: one variant, JSON on the last stdout line
        print(json.dumps(run_variant(args, args.variant)))
        return

    # the sharded_delta variant runs in a child on forced host devices; a
    # TPU belongs to the process that first touched it — this one — so the
    # child would wait on the held chip forever.  Refuse before any work.
    import jax
    if jax.default_backend() == "tpu":
        raise SystemExit(
            "bench_lm's sharded_delta variant runs in a child process on "
            "forced host devices and cannot share the TPU this process "
            "would hold; run the sharded replay on chips with "
            "`python chip_smoke.py --chips 4`")

    from repro.models.registry import count_params
    from repro.configs.registry import get_config

    p = QUICK if args.quick else FULL
    n_params = count_params(get_config(args.model).reduced(**_shape(p)))

    session, roofline = run_session(args, args.trace_out)
    print(f"session: fit {session['fit_wall_s']:.1f}s  delete "
          f"{session['delete_wall_s']:.1f}s  baseline "
          f"{session['baseline_retrain_wall_s']:.1f}s  ratio "
          f"{session['distance_ratio']:.2f}  roofline spans "
          f"{roofline['replay_scan_spans']}")

    variants = {}
    for variant in ("resident", "streamed", "delta_streamed"):
        variants[variant] = run_variant(args, variant)
        v = variants[variant]
        print(f"{variant:14s} replay {v['replay_wall_s'] * 1e3:8.1f} ms  "
              f"hbm {v['hbm_high_water_bytes'] / 1e6:8.1f} MB  "
              f"store {v['store']}")

    # sharded+streamed delta: own subprocess so the host-platform device
    # count is forced before jax initializes (cf. bench_shard)
    flags = [f"--{k.replace('_', '-')}={v}" for k, v in vars(args).items()
             if k not in ("role", "variant", "quick", "out", "trace_out")]
    if args.quick:
        flags.append("--quick")
    env = dict(os.environ, PYTHONPATH="src")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count="
                        f"{args.devices}").strip()
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--role", "variant",
         "--variant", "sharded_delta"] + flags,
        capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    if proc.returncode != 0:
        print(proc.stdout)
        print(proc.stderr, file=sys.stderr)
        raise SystemExit("sharded_delta variant failed")
    variants["sharded_delta"] = json.loads(
        proc.stdout.strip().splitlines()[-1])
    v = variants["sharded_delta"]
    print(f"{'sharded_delta':14s} replay {v['replay_wall_s'] * 1e3:8.1f} ms  "
          f"hbm {v['hbm_high_water_bytes'] / 1e6:8.1f} MB/dev  "
          f"parity {v['sharded_vs_streamed']:.2e}")

    flash = run_flash(args)
    print(f"flash ({flash['impl']}): loss diff {flash['loss_abs_diff']:.1e}"
          f"  grad rel {flash['grad_rel_err']:.1e}  ok {flash['parity_ok']}")

    res_hbm = variants["resident"]["hbm_high_water_bytes"]
    delta_hbm = variants["delta_streamed"]["hbm_high_water_bytes"]
    results = {
        "config": {k: v for k, v in vars(args).items()
                   if k not in ("role", "variant", "out", "trace_out")},
        "model": {
            "name": args.model,
            "reduced": _shape(p),
            "params": int(n_params),
            "multi_million": bool(n_params >= 2_000_000),
        },
        "session": session,
        "roofline": roofline,
        "variants": variants,
        "flash": flash,
        "derived": {
            # the acceptance booleans (ISSUE 10)
            "replay_beats_retrain": bool(
                variants["resident"]["replay_wall_s"]
                < variants["resident"]["baseline_retrain_wall_s"]),
            "replay_speedup": variants["resident"]["baseline_retrain_wall_s"]
            / max(1e-12, variants["resident"]["replay_wall_s"]),
            "hbm_delta_lt_resident": bool(delta_hbm < res_hbm),
            "hbm_reduction_delta": res_hbm / max(1, delta_hbm),
            "history_bytes_reduction":
                variants["resident"]["history_bytes"]
                / max(1, variants["delta_streamed"]["history_bytes"]),
        },
    }
    with open(args.out, "w") as f:
        json.dump(results, f, indent=1)
    print(f"wrote {args.out}")
    d = results["derived"]
    print(f"replay_beats_retrain={d['replay_beats_retrain']} "
          f"(x{d['replay_speedup']:.2f})  "
          f"hbm_delta_lt_resident={d['hbm_delta_lt_resident']} "
          f"(x{d['hbm_reduction_delta']:.2f})  "
          f"history_bytes x{d['history_bytes_reduction']:.2f}")


if __name__ == "__main__":
    main()
