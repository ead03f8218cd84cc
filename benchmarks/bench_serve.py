"""Continuous-batching serving benchmark → BENCH_serve.json.

Drives the serving tier (`repro.serve`) the way an inference bench drives
an LLM server: a seeded open-loop arrival trace at several OFFERED LOADS,
measuring the throughput-vs-p99 curve around the knee.  Sections:

  * the classic per-request / coalesced-burst / certificate sections come
    from `repro.launch.serve unlearn` (run in-process, merged in), so one
    JSON still carries the whole serve story;
  * ``continuous_batching`` — the new subsystem's numbers:
      - `service_ms`: measured serial service time (one delete replay,
        submit+flush+drain), the unit the offered loads are relative to;
      - `points[]`: for each relative rate in ``rates_rel`` (×1/service),
        a fresh session + `ServingScheduler` serves the same-seeded
        Poisson (or diurnal) multi-tenant delete/add trace open-loop —
        throughput, overall and per-class e2e p50/p95/p99, deadline
        misses, batch-size mean, cross-tenant batch count;
      - `interactive_misses_below_knee`: deadline misses for the
        interactive class summed over the points offered BELOW the knee
        (rate_rel < 1) — gated exactly 0;
      - serial ablation at the peak rate: the same trace through a
        ``max_batch=1`` scheduler (continuous batching off, everything
        else identical) — `p99_ratio_serial_over_cb` is the win, and
        `cb_beats_serial_at_peak` gates it as a hard boolean;
      - `parity_vs_python`: the same virtual-clock trace replayed inline
        through scan-impl and python-impl sessions forms IDENTICAL
        batches, so the coalesced group replays must agree exactly
        (0.0 on the full-batch CI config);
      - `add_capacity_retraces`: summed over every point — admission
        charges adds against the staged pow2 bucket, so this gates 0.

The SLA deadlines used here are the bench's own (generous) quick-mode
classes, recorded in the config section: CI boxes stall unpredictably,
and the gate is "zero misses below the knee", not "50 ms everywhere".

    PYTHONPATH=src python -m benchmarks.bench_serve --quick --trace poisson
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

QUICK = dict(n=800, d=32, steps=40, requests=6, burst=8, add_frac=0.25)
FULL = dict(n=4000, d=500, steps=80, requests=12, burst=8, add_frac=0.25)

RATES_REL = (0.5, 1.5, 4.0)     # offered load as a multiple of 1/service
TENANTS = {"tenant-a": 0.5, "tenant-b": 0.3, "tenant-c": 0.2}
CLASS_MIX = {"interactive": 0.5, "batch": 0.3, "bulk_gdpr": 0.2}


def _next_pow2_at_least(k: int) -> int:
    p = 1
    while p < k:
        p <<= 1
    return p


class VirtualClock:
    """Deterministic clock for the parity replay: every call advances a
    fixed tick, so two runs that make the same call sequence see the same
    timestamps — batch formation replays exactly."""

    def __init__(self, tick_s: float = 1e-3):
        self.t = 0.0
        self.tick_s = tick_s

    def __call__(self) -> float:
        self.t += self.tick_s
        return self.t


def _bench_classes():
    from repro.serve import SLAClass
    # generous quick-mode deadlines (see module docstring); holds still
    # differ per class so batching behavior is exercised
    return (SLAClass("interactive", deadline_s=0.5, hold_s=0.0),
            SLAClass("batch", deadline_s=2.0, hold_s=0.01),
            SLAClass("bulk_gdpr", deadline_s=8.0, hold_s=0.05))


def _build_session(size, seed):
    from repro.core.deltagrad import DeltaGradConfig
    from repro.core.session import UnlearnerConfig, UnlearnerSession
    from repro.data.synthetic import binary_classification
    from repro.models.simple import logreg_init, logreg_objective

    obj = logreg_objective(l2=5e-3)
    cfg = UnlearnerConfig(
        steps=size["steps"], batch_size=size.get("batch_size", 1024),
        lr=0.3, seed=seed,
        deltagrad=DeltaGradConfig(period=5, burn_in=10,
                                  impl=size.get("impl", "scan")))
    ds = binary_classification(n=size["n"], d=size["d"], seed=seed)
    sess = UnlearnerSession(obj, logreg_init(size["d"], seed=1), ds, cfg)
    sess.fit()
    return sess, ds


def _measure_service_s(size, seed) -> float:
    """Median wall for ONE single-delete replay (submit+flush+drain) —
    the serving-time unit the offered loads are relative to."""
    import jax
    sess, ds = _build_session(size, seed)
    sess.warmup([("delete", 1)])
    algo = sess.algorithm
    rng = np.random.default_rng(seed + 10)
    live = np.flatnonzero(algo.live[:size["n"]])
    rows = rng.choice(live, size=8, replace=False)
    walls = []
    for r in rows:
        t0 = time.perf_counter()
        sess.submit(op="delete", rows=[int(r)], coalesce=False)
        sess.flush()
        jax.block_until_ready(algo.params)
        walls.append(time.perf_counter() - t0)
    return float(sorted(walls)[len(walls) // 2])


def _make_trace(trace, rate, n_events, seed, add_frac):
    from repro.serve import diurnal_trace, poisson_trace
    if trace == "diurnal":
        return diurnal_trace(max(rate / 2, 1e-3), rate * 2,
                             period_s=max(0.25, n_events / rate),
                             n_events=n_events, seed=seed,
                             tenants=TENANTS, classes=CLASS_MIX,
                             add_frac=add_frac)
    return poisson_trace(rate, n_events, seed, tenants=TENANTS,
                         classes=CLASS_MIX, add_frac=add_frac)


def _run_point(size, seed, events, max_batch):
    """Serve one materialized trace open-loop; returns the point record."""
    from repro.obs import metrics as obs_metrics
    from repro.serve import (LoadGenerator, ServeConfig, ServingScheduler,
                             materialize)

    sess, ds = _build_session(size, seed)
    materialize(events, ds, seed=seed + 20)
    n_add_rows = sum(ev.n_rows for ev in events if ev.op == "add")
    sched = ServingScheduler(sess, ServeConfig(
        classes=_bench_classes(), max_batch=max_batch,
        add_capacity=max(1, n_add_rows)))
    # warm every pow2 batch bucket a dispatch could hit (both ops): an
    # unwarmed bucket's compile landing inside a measured point would
    # charge ~1s of tracing to that point's p99
    ks = [k for k in (1, 2, 4, 8, 16) if k <= max_batch]
    warm = [("delete", k) for k in ks]
    if n_add_rows:
        warm += [("add", k) for k in ks if k <= _next_pow2_at_least(
            n_add_rows)]
    # compile-time attribution: the warmup cost is its own metric, never
    # inside a measured point's latency (every bucket a dispatch can hit
    # is compiled before the open loop starts)
    compile_s = sess.warmup(warm)
    obs_metrics.get_registry().histogram(
        "bench.warmup_compile_s", unit="s",
        owner="benchmarks").observe(compile_s)
    sched.start()
    res = LoadGenerator(sched).open_loop(events)
    for tk in res.tickets:
        tk.wait(timeout=120.0)
    sched.stop()
    st = sched.stats()

    reqs = [tk.req for tk in res.tickets if tk.req.t_done is not None]
    h_e2e = obs_metrics.Histogram("bench.point_e2e_ms", unit="ms",
                                  owner="benchmarks")
    for q in reqs:
        h_e2e.observe(q.e2e_s * 1e3)
    e2e = h_e2e.summary()
    wall = (max(q.t_done for q in reqs) - min(q.t_enqueue for q in reqs)
            if reqs else 1e-9)
    return {
        "served": len(reqs),
        "rejected": res.rejected,
        "throughput_rps": len(reqs) / max(wall, 1e-9),
        "warmup_compile_s": compile_s,
        "e2e_ms": {"p50": e2e["p50"], "p95": e2e["p95"],
                   "p99": e2e["p99"], "max": e2e["max"]},
        "per_class": st["per_class"],
        "deadline_misses": st["deadline_misses_total"],
        "batch_size_mean": st["batches"]["size_mean"],
        "batch_size_max": st["batches"]["size_max"],
        "cross_tenant_batches": st["batches"]["cross_tenant"],
        "add_capacity_retraces": st["add_capacity_retraces"],
        "admission": st["admission"],
    }


def _parity_inline(size, seed, n_events):
    """Same virtual-clock trace through scan and python sessions, inline:
    identical batch formation, so the coalesced replays must agree."""
    from repro.serve import ServeConfig, ServingScheduler, materialize
    from repro.utils.tree import tree_norm, tree_sub

    trace_seed = seed + 30

    def run(impl):
        # full-batch GD: the scan and python backends are bitwise-identical
        # by construction, so the parity check isolates BATCH FORMATION
        # (mini-batch replays carry the engine suite's float tolerance)
        sess, ds = _build_session(
            {**size, "impl": impl, "batch_size": size["n"]}, seed)
        events = _make_trace("poisson", 100.0, n_events, trace_seed,
                             size["add_frac"])
        materialize(events, ds, seed=seed + 31)
        n_add_rows = sum(ev.n_rows for ev in events if ev.op == "add")
        sched = ServingScheduler(
            sess, ServeConfig(classes=_bench_classes(), max_batch=8,
                              add_capacity=max(1, n_add_rows)),
            clock=VirtualClock())
        for ev in events:
            sched.submit(op=ev.op, rows=ev.rows, data=ev.data,
                         tenant=ev.tenant, sla_class=ev.sla_class)
        while sched.pump(force=True):
            pass
        batches = [(b["op"], tuple(b["rows"])) for b in sched.batch_log]
        return sess.params, batches

    p_scan, batches_scan = run("scan")
    p_py, batches_py = run("python")
    return (float(tree_norm(tree_sub(p_scan, p_py))),
            batches_scan == batches_py)


def main(argv=()) -> None:
    # default () so benchmarks.run can call main() with module selectors
    # still in sys.argv; __main__ passes sys.argv[1:]
    ap = argparse.ArgumentParser(prog="bench_serve")
    ap.add_argument("--quick", action="store_true",
                    help="CI-sized problem (n=800, d=32, steps=40)")
    ap.add_argument("--trace", default="poisson",
                    choices=("poisson", "diurnal"),
                    help="arrival process for the load sweep")
    ap.add_argument("--events", type=int, default=0,
                    help="arrivals per sweep point (0: 24 quick / 80 full)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="BENCH_serve.json")
    ap.add_argument("--trace-out", default="",
                    help="enable the span tracer for the WHOLE bench run "
                         "and write a Chrome/Perfetto trace-event JSON "
                         "here ('' disables); the metrics registry lands "
                         "beside it as <path>.metrics.jsonl")
    args = ap.parse_args(list(argv))

    from repro.obs import metrics as obs_metrics
    from repro.obs import trace as obs_trace
    if args.trace_out:
        from repro.roofline.hw import TPU_V5E
        # priced against v5e peaks on any device (see bench_lm)
        obs_trace.enable(obs_trace.Tracer(hw=TPU_V5E))

    size = dict(QUICK if args.quick else FULL)
    n_events = args.events or (24 if args.quick else 80)

    # -- classic sections via the serve driver (in-process, merged) ----------
    from repro.launch import serve as serve_cli
    with tempfile.TemporaryDirectory() as td:
        tmp_out = os.path.join(td, "classic.json")
        serve_cli.unlearn_main([
            "--n", str(size["n"]), "--d", str(size["d"]),
            "--steps", str(size["steps"]),
            "--requests", str(size["requests"]),
            "--add-frac", str(size["add_frac"]),
            "--burst", str(size["burst"]),
            "--trace", args.trace if args.trace != "diurnal" else "poisson",
            "--seed", str(args.seed), "--bench-out", tmp_out])
        with open(tmp_out) as f:
            results = json.load(f)

    # -- the continuous-batching sweep ---------------------------------------
    service_s = _measure_service_s(size, args.seed)
    print(f"serial service time: {service_s * 1e3:.2f} ms/request")

    points = []
    for rel in RATES_REL:
        rate = rel / service_s
        events = _make_trace(args.trace, rate, n_events,
                             args.seed + 40, size["add_frac"])
        pt = _run_point(size, args.seed, events, max_batch=16)
        pt.update({"rate_rel": rel, "rate_rps": rate})
        points.append(pt)
        print(f"  load x{rel:>4}: {pt['throughput_rps']:8.1f} req/s, "
              f"e2e p99 {pt['e2e_ms']['p99']:8.1f} ms, "
              f"batch mean {pt['batch_size_mean']:.1f}, "
              f"{pt['cross_tenant_batches']} cross-tenant, "
              f"{pt['deadline_misses']} misses")

    # serial ablation at the PEAK rate: continuous batching off
    peak = points[-1]
    events = _make_trace(args.trace, peak["rate_rps"], n_events,
                         args.seed + 40, size["add_frac"])
    serial = _run_point(size, args.seed, events, max_batch=1)
    print(f"  serial@peak: e2e p99 {serial['e2e_ms']['p99']:.1f} ms vs "
          f"cb {peak['e2e_ms']['p99']:.1f} ms")

    parity, batches_equal = _parity_inline(
        size, args.seed, n_events=min(12, n_events))
    print(f"  coalesced-replay parity scan vs python: {parity:.2e} "
          f"(batch plans equal: {batches_equal})")

    misses_below_knee = sum(
        pt["per_class"].get("interactive", {}).get("deadline_misses", 0)
        for pt in points if pt["rate_rel"] < 1.0)
    retraces = (sum(pt["add_capacity_retraces"] for pt in points)
                + serial["add_capacity_retraces"])

    results["continuous_batching"] = {
        "trace": args.trace,
        "service_ms": service_s * 1e3,
        "rates_rel": list(RATES_REL),
        "events_per_point": n_events,
        "points": points,
        "interactive_misses_below_knee": int(misses_below_knee),
        "serial_p99_ms": serial["e2e_ms"]["p99"],
        "cb_p99_ms": peak["e2e_ms"]["p99"],
        "p99_ratio_serial_over_cb": (serial["e2e_ms"]["p99"]
                                     / max(peak["e2e_ms"]["p99"], 1e-9)),
        "cb_beats_serial_at_peak": bool(serial["e2e_ms"]["p99"]
                                        >= peak["e2e_ms"]["p99"]),
        "batch_size_mean_at_peak": peak["batch_size_mean"],
        "cross_tenant_batches_at_peak": peak["cross_tenant_batches"],
        "add_capacity_retraces": int(retraces),
        "parity_vs_python": parity,
        "batch_plans_equal": bool(batches_equal),
    }
    results["config"].update({
        "bench": "serve", "quick": bool(args.quick),
        "cb_trace": args.trace, "cb_rates_rel": list(RATES_REL),
        "cb_events": n_events, "cb_max_batch": 16,
        "cb_classes": [(c.name, c.deadline_s, c.hold_s)
                       for c in _bench_classes()],
    })

    with open(args.out, "w") as f:
        json.dump(results, f, indent=1)
    print(f"wrote {args.out}")

    if args.trace_out:
        tracer = obs_trace.disable()
        tracer.export_chrome(args.trace_out)
        obs_metrics.get_registry().to_jsonl(args.trace_out
                                            + ".metrics.jsonl")
        n_scan = sum(1 for e in tracer.events()
                     if e["name"] == "replay.scan")
        print(f"wrote {args.trace_out} ({len(tracer.events())} spans, "
              f"{n_scan} replay.scan) + {args.trace_out}.metrics.jsonl")

    # CSV rows for benchmarks.run
    cb = results["continuous_batching"]
    print(f"serve_cb_service,{service_s * 1e6:.1f},"
          f"p99_ratio_serial_over_cb={cb['p99_ratio_serial_over_cb']:.2f}"
          f"|parity={cb['parity_vs_python']:.2e}"
          f"|misses_below_knee={cb['interactive_misses_below_knee']}")


if __name__ == "__main__":
    main(sys.argv[1:])
