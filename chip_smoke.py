"""Bring-up smoke test on a TPU: the unlearning path at the paper MLP's width.

    python chip_smoke.py             # one chip: phases (b)-(d)
    python chip_smoke.py --chips 4   # four chips: phase (e) only

Phases, all in this one process (a TPU belongs to the process that first
touches it, so nothing here starts a child):

  (a) device check — exits non-zero, printing no result line, when JAX
      finds no TPU; there is no CPU fallback.
  (b) session: the paper's 784-300-10 ReLU MLP (238,510 parameters) on an
      MNIST-shaped synthetic set (60,000 x 784, 10 classes) with the
      paper's recipe (`configs/paper_mlp.py`).  `UnlearnerSession.fit()`
      caches the path on the stacked tier; the batch replay
      (`deltagrad_retrain`) must run the compiled scan with the Pallas
      fused update; a 1% delete burst goes in as two handles that coalesce
      into one group replay; one add follows; `session.baseline()` is the
      exact-retrain reference the DeltaGrad models must beat the trained
      model against.
  (c) streamed: the same problem with a `delta_int8` history on the host
      tier, replayed from encoded windows through the Pallas dequant
      kernels, within the codec's envelope of (b)'s replay.
  (d) serving: `repro.launch.serve.unlearn_main` in-process — a request
      stream, a coalesced burst and a seeded trace through the
      `ServingScheduler`; every request must be served.
  (e) four chips: a `delta_int8` host-tier replay sharded over a 4-device
      mesh against the single-device replay of the same history.

The script sets no JAX option of its own beyond the compile cache the
entry points also enable: it runs at the precision the program chooses.
The last line of stdout is the JSON result.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

D, HIDDEN, CLASSES = 784, 300, 10  # paper MLP (configs/paper_mlp.py)
N_ROWS = 60_000                    # MNIST's training-set size
STEPS = 40                         # lr 0.2 -> 0.1 at step 10; j0 = T/4
DELETE_FRAC = 0.01


def _check_device():
    import jax

    dev = jax.devices()[0]
    print(f"jax {jax.__version__}; device_kind {dev.device_kind!r}; "
          f"platform {dev.platform}; devices {len(jax.devices())}",
          flush=True)
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX platform is "
                 f"{dev.platform!r}); this smoke runs only on a chip")
    return dev


def _norm(tree) -> float:
    from repro.utils.tree import tree_norm
    return float(tree_norm(tree))


def _dist(a, b) -> float:
    from repro.utils.tree import tree_sub
    return _norm(tree_sub(a, b))


def _finite(tree) -> bool:
    import jax
    import numpy as np
    return all(bool(np.isfinite(np.asarray(x)).all())
               for x in jax.tree.leaves(tree))


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(f"chip_smoke: {what}")


def make_problem(n: int, seed: int):
    """(dataset, delete rows) — the dataset is regenerated from the seed."""
    import numpy as np

    from repro.data.synthetic import multiclass_classification

    ds = multiclass_classification(n=n, d=D, num_classes=CLASSES, seed=seed)
    rows = np.random.default_rng(seed + 1).choice(
        n, size=max(2, int(n * DELETE_FRAC)), replace=False)
    return ds, [int(r) for r in rows]


def make_session(ds, seed: int, codec: str = "f32"):
    """The paper-MLP session on a fresh view of `ds` (its own deletion
    bookkeeping): full-batch GD, l2 1e-3, lr 0.2 -> 0.1 at step 10,
    T0 = 2, j0 = T/4, Algorithm-4 guard on."""
    from repro.core.deltagrad import DeltaGradConfig
    from repro.core.session import UnlearnerConfig, UnlearnerSession
    from repro.data.dataset import Dataset
    from repro.models.simple import mlp_init, mlp_objective

    cfg = UnlearnerConfig(
        steps=STEPS, lr_schedule=((0, 0.2), (10, 0.1)), seed=seed,
        history_codec=codec,
        deltagrad=DeltaGradConfig(period=2, burn_in=STEPS // 4,
                                  history_size=2, guard=True,
                                  curvature_eps=1e-8))
    return UnlearnerSession(mlp_objective(l2=1e-3),
                            mlp_init(D, HIDDEN, CLASSES, seed=seed),
                            Dataset(ds.columns), cfg)


def _fit(sess, label: str):
    import jax

    t0 = time.perf_counter()
    w = sess.fit()
    jax.block_until_ready(w)
    print(f"  {label}: fit {STEPS} steps in "
          f"{time.perf_counter() - t0:.3f} s, history "
          f"{sess.history.nbytes() / 1e6:.1f} MB on tier "
          f"{sess.history.tier!r}", flush=True)
    return w


def _replay(sess, rows, placement=None, **cfg_changes):
    """The batch replay (Algorithm 1) of `sess`'s cached path."""
    import dataclasses

    from repro.core.deltagrad import deltagrad_retrain

    cfg = dataclasses.replace(sess.config.deltagrad, **cfg_changes)
    t0 = time.perf_counter()
    w, st = deltagrad_retrain(sess.objective, sess.history, sess.dataset,
                              rows, cfg, placement=placement)
    return w, st, time.perf_counter() - t0


def phase_session(ds, rows, seed: int) -> dict:
    """(b): fit, batch replay, coalesced two-handle delete, add, reference."""
    import jax
    import numpy as np

    from repro.models.simple import mlp_accuracy

    print("(b) session on the stacked tier", flush=True)
    sess = make_session(ds, seed)
    w_orig = _fit(sess, "stacked")

    w_replay, st, wall = _replay(sess, rows)
    print(f"  batch replay of {len(rows)} deletes: {wall:.3f} s, impl "
          f"{st.extra['impl']}, fused {st.extra['fused']}, store "
          f"{st.extra['store']}, {st.approx_steps} approx + "
          f"{st.explicit_steps} explicit steps, {st.guard_fallbacks} guard "
          f"fallbacks", flush=True)
    _check(st.extra["impl"] == "scan", "batch replay did not run the scan")
    _check(st.extra["fused"] == "pallas",
           "batch replay did not run the compiled Pallas update")

    k = len(rows) // 2
    t0 = time.perf_counter()
    h1 = sess.delete(rows[:k])
    h2 = sess.delete(rows[k:])
    r1, r2 = h1.result(), h2.result()
    del_wall = time.perf_counter() - t0
    w_dg = r2.params
    print(f"  session delete burst ({k} + {len(rows) - k} rows, two "
          f"handles): {del_wall:.3f} s, group size {r1.group_size}, "
          f"{r1.stats[0].approx_steps} approx + "
          f"{r1.stats[0].explicit_steps} explicit steps, store "
          f"{r1.stats[0].extra['store']}", flush=True)
    _check(r1.group_size == r2.group_size == len(rows),
           "the two delete handles did not coalesce into one replay")

    j = int(np.flatnonzero(~sess.dataset.removed)[0])
    add = {c: v[[j]] for c, v in ds.columns.items()}
    t0 = time.perf_counter()
    w_add = sess.add(data=add).result().params
    print(f"  session add (1 row): {time.perf_counter() - t0:.3f} s",
          flush=True)

    t0 = time.perf_counter()
    w_exact, _ = sess.baseline(rows)
    jax.block_until_ready(w_exact)
    print(f"  baseline retrain: {time.perf_counter() - t0:.3f} s",
          flush=True)

    for name, w in (("replay", w_replay), ("session delete", w_dg),
                    ("session add", w_add), ("baseline", w_exact)):
        _check(_finite(w), f"{name} params are not finite")
    d_orig = _dist(w_exact, w_orig)
    d_dg = _dist(w_exact, w_dg)
    d_replay = _dist(w_exact, w_replay)
    print(f"  ||w_exact - w_orig|| {d_orig!r}; ||w_exact - w_session|| "
          f"{d_dg!r} (ratio {d_orig / max(d_dg, 1e-30)!r}); "
          f"||w_exact - w_replay|| {d_replay!r} (ratio "
          f"{d_orig / max(d_replay, 1e-30)!r})", flush=True)
    print(f"  accuracy: trained {mlp_accuracy(w_orig, ds)!r}, after "
          f"delete {mlp_accuracy(w_dg, ds)!r}, exact "
          f"{mlp_accuracy(w_exact, ds)!r}", flush=True)
    _check(d_dg < d_orig, "session delete is no closer to the exact "
                          "retrain than the trained model")
    _check(d_replay < d_orig, "batch replay is no closer to the exact "
                              "retrain than the trained model")
    return {"w_replay": w_replay}


def phase_streamed(ds, rows, seed: int, w_ref) -> None:
    """(c): delta_int8 host tier, encoded windows, Pallas dequant kernels."""
    print("(c) streamed delta_int8 history on the host tier", flush=True)
    sess = make_session(ds, seed, codec="delta_int8")
    _fit(sess, "delta_int8")
    w, st, wall = _replay(sess, rows)
    print(f"  streamed replay: {wall:.3f} s, store {st.extra['store']}, "
          f"stream_decode {st.extra['stream_decode']}, fused "
          f"{st.extra['fused']}, {st.extra['windows']} windows, "
          f"compression {st.extra['compression_ratio']!r}, HBM high-water "
          f"{st.extra['hbm_high_water']} B", flush=True)
    _check(st.extra["store"] == "streamed", "history was not streamed")
    _check(st.extra["stream_decode"] == "kernel",
           "windows were not decoded in the kernels")
    _check(st.extra["fused"] == "pallas", "kernels did not run compiled")
    _check(_finite(w), "streamed replay params are not finite")
    # the decode-parity invariant: dequantizing inside the kernels gives
    # bitwise the replay that decodes each window on arrival
    w_f, st_f, wall_f = _replay(sess, rows, stream_decode="fetch")
    print(f"  fetch-decoded replay: {wall_f:.3f} s, HBM high-water "
          f"{st_f.extra['hbm_high_water']} B, ||w_kernel - w_fetch|| "
          f"{_dist(w, w_f)!r}", flush=True)
    _check(_dist(w, w_f) == 0.0,
           "kernel-decoded replay differs from the fetch-decoded one")
    # the codec's envelope (tests/test_delta.py): 5% of the f32 norm
    d, env = _dist(w, w_ref), 0.05 * max(_norm(w_ref), 1.0)
    print(f"  ||w_delta_int8 - w_f32|| {d!r} (envelope {env!r})",
          flush=True)
    _check(d <= env, "delta_int8 replay left the codec envelope")


def phase_serving() -> None:
    """(d): the serving CLI in-process; every ticket must be served."""
    from repro.launch.serve import unlearn_main

    print("(d) serving tier (repro.launch.serve unlearn)", flush=True)
    t0 = time.perf_counter()
    res = unlearn_main(["--requests", "8", "--burst", "4",
                        "--bench-out", ""])
    srv = res["serving"]
    failed = sum(c["failed"] for c in srv["per_class"].values())
    served = sum(c["served"] for c in srv["per_class"].values())
    admitted = srv["admission"]["admitted"]
    print(f"  serving: {admitted} admitted, {served} served, {failed} failed, "
          f"{srv['rejected']} rejected, lone request served "
          f"{srv['lone_request_served']}; coalesced burst parity vs "
          f"python {res['coalesce']['parity_vs_python']!r}; "
          f"{time.perf_counter() - t0:.3f} s", flush=True)
    _check(failed == 0, "serving requests failed")
    _check(served == admitted, "admitted requests were left unserved")
    _check(srv["rejected"] == 0, "serving requests were rejected")
    _check(srv["lone_request_served"], "the lone request was not served")


def phase_sharded(ds, rows, seed: int, chips: int) -> None:
    """(e): sharded streamed replay on `chips` devices vs one device."""
    import jax

    from repro.core.store import PlacementPolicy

    print(f"(e) delta_int8 host-tier replay sharded over {chips} chips",
          flush=True)
    sess = make_session(ds, seed, codec="delta_int8")
    w_orig = _fit(sess, "delta_int8")
    w1, s1, wall1 = _replay(sess, rows)
    wN, sN, wallN = _replay(sess, rows, PlacementPolicy.local(chips))
    w_exact, _ = sess.baseline(rows)
    for st, wall, label in ((s1, wall1, "1 chip"),
                            (sN, wallN, f"{chips} chips")):
        print(f"  {label}: {wall:.3f} s, store {st.extra['store']}, "
              f"stream_decode {st.extra['stream_decode']}, "
              f"{st.approx_steps} approx + {st.explicit_steps} explicit "
              f"steps, history HBM high-water {st.extra['hbm_high_water']}"
              " B/device", flush=True)
    for d in jax.devices():
        stats = d.memory_stats() or {}
        print(f"  device {d.id}: peak_bytes_in_use "
              f"{stats.get('peak_bytes_in_use', 'not reported')}",
              flush=True)
    _check(sN.extra["store"] == "sharded_streamed",
           "replay was not sharded")
    _check(_finite(wN), "sharded replay params are not finite")
    rel = _dist(wN, w1) / max(_norm(w1), 1e-30)
    d_orig = _dist(w_exact, w_orig)
    print(f"  ||w_{chips} - w_1|| / ||w_1|| {rel!r}; "
          f"||w_exact - w_orig|| {d_orig!r}, ||w_exact - w_1|| "
          f"{_dist(w_exact, w1)!r}, ||w_exact - w_{chips}|| "
          f"{_dist(w_exact, wN)!r}", flush=True)
    _check((s1.approx_steps, s1.explicit_steps)
           == (sN.approx_steps, sN.explicit_steps),
           "sharded and single-device replays took different steps")
    _check(rel <= 1e-4, "sharded replay disagrees with one device")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the sharded replay phase (e)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch.cache import enable_compile_cache
    cache = enable_compile_cache()
    dev = _check_device()
    import jax
    print(f"compile cache {cache}", flush=True)

    t0 = time.perf_counter()
    ds, rows = make_problem(N_ROWS, args.seed)
    print(f"data: {ds.n} x {D}, {CLASSES} classes, {len(rows)} deletes "
          f"({time.perf_counter() - t0:.3f} s)", flush=True)
    if args.chips == 4:
        _check(len(jax.devices()) >= 4, "--chips 4 needs four devices")
        phase_sharded(ds, rows, args.seed, 4)
    else:
        b = phase_session(ds, rows, args.seed)
        phase_streamed(ds, rows, args.seed, b["w_replay"])
        phase_serving()
    print(f"total {time.perf_counter() - t0:.3f} s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
