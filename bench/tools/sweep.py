"""Find the highest request rate a serving cell sustains, by one sweep.

    python3 bench/tools/sweep.py --workload <serve cell> --seed <n> \
        --seconds <s> --rates 5 10 20 40 ...

Sets the cell up once, then offers each rate in turn for `--seconds`
(open loop, the cell's own mix), on the same session; rows deleted by one
rate stay deleted for the next.  Prints, per rate, the forget latency p50
and p95, the requests finished, and whether the backlog grew (the last
quarter's requests waiting longer than the first quarter's).  The cell's
rate is set, once, at about four fifths of the highest rate whose p95 meets
the class deadline with no growing backlog.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))), "src"))
    import jax
    import numpy as np

    from bench.harness import loadgen
    from bench.harness.core import Run, check_device, find_cell, load_module
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cell = find_cell(args.workload)
    check_device(cell.chips)
    drv = load_module(os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "drivers",
        cell.traffic["driver"] + ".py"))
    r = Run(cell=cell, seed=args.seed, seconds=args.seconds,
            t_start=time.time())
    st = drv.setup(r)
    print(f"setup {time.time() - r.t_start:.3f} s", flush=True)
    sess = st["sess"]
    for rate in args.rates:
        tr = dict(cell.traffic, arrivals=dict(cell.traffic["arrivals"],
                                              rate_per_s=rate))
        reqs = loadgen.trace(tr, args.seconds, args.seed + int(rate))
        live = ~np.asarray(sess.dataset.removed, dtype=bool)
        loadgen.materialize(reqs, live, args.seed + int(rate))
        st["reqs"] = reqs
        drv.window(r, st)
        until = st["t_close"] + 30.0
        for _, tk, _ in st["sent"]:
            if tk is not None:
                tk.req.done.wait(max(0.0, until - time.monotonic()))
        t0 = st["t0"]
        lat, wait = [], []
        for rq, tk, _ in st["sent"]:
            q = tk.req if tk is not None else None
            ok = q is not None and q.done.is_set() and q.error is None
            lat.append(q.t_done - (t0 + rq.due_s) if ok else np.inf)
            wait.append(q.t_dispatch - (t0 + rq.due_s) if ok else np.inf)
        lat, wait = np.asarray(lat), np.asarray(wait)
        q = max(1, len(wait) // 4)
        batches = len({tk.req.batch_id for _, tk, _ in st["sent"]
                       if tk is not None})
        print(json.dumps({
            "rate": rate, "requests": len(lat),
            "finished": int(np.isfinite(lat).sum()),
            "p50_ms": float(np.percentile(lat, 50) * 1e3),
            "p95_ms": float(np.percentile(lat, 95) * 1e3),
            "wait_first_q_ms": float(np.median(wait[:q]) * 1e3),
            "wait_last_q_ms": float(np.median(wait[-q:]) * 1e3),
            "batches": batches,
            "rows_deleted": int(np.asarray(sess.dataset.removed).sum())}),
            flush=True)
    st["sched"].stop()


if __name__ == "__main__":
    main()
