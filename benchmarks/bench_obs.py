"""Observability overhead gate: tracer-off replay wall vs a span-stubbed
baseline, measured in ONE process.

The ISSUE's acceptance bar is "tracer-off replay wall within 1% of
baseline".  A 1% gate on absolute wall clock is un-enforceable across CI
runners (machine-to-machine variance alone is >10%), so this bench makes
the gate runner-independent: it times the SAME warm online-delete stream
three ways in one process, with repeats interleaved so clock drift hits
every arm equally —

  * ``plain`` — ``repro.obs.trace.span`` monkey-patched to a stub that
    returns the no-op span without touching tracer state: the
    "instrumentation compiled out" floor;
  * ``off``   — the real ``span()`` with the tracer disabled: the shipped
    default;
  * ``on``    — a live ``Tracer`` recording every span.

``tracer_off_ratio = min(off walls) / min(plain walls)`` is what CI gates
at 1.01 against a committed baseline of 1.0 (`check_bench --suite obs`).
Min-of-repeats makes the ratio a noise floor comparison, not a mean.

The ``on`` arm's tracer is also exported to Chrome trace-event JSON and
validated structurally: the gate asserts the trace is Perfetto-loadable
("X" events with ts/dur/pid/tid) and that every ``replay.scan`` span
carries the roofline annotations (``pred_s`` / ``measured_s`` /
``roofline_ratio``) — the predicted-vs-measured accounting the obs layer
exists to provide.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import tempfile
import time

import numpy as np

from benchmarks.common import DG_CFG, emit
from repro.core.deltagrad import sgd_train_with_cache
from repro.core.history import HistoryMeta
from repro.core.online import online_deltagrad
from repro.data.synthetic import binary_classification
from repro.models.simple import logreg_init, logreg_objective
from repro.obs import trace as obs_trace
from repro.roofline.hw import TPU_V5E

# dispatch-bound shape: per-step dispatch dominates gradient FLOPs, which
# maximises the tracer's relative footprint — the adversarial regime for
# a <=1% overhead claim
QUICK = dict(n=1000, d=32, steps=120, batch=128, lr=0.3, l2=5e-3, seed=0,
             requests=12, repeats=5)
FULL = dict(n=2000, d=64, steps=200, batch=256, lr=0.3, l2=5e-3, seed=0,
            requests=16, repeats=7)

_REAL_SPAN = obs_trace.span


def _stub_span(*_args, **_kwargs):
    """`plain` arm: the span site costs one call + the shared no-op."""
    return obs_trace.NOOP_SPAN


def _run_stream(p, obj, mode):
    """One warm online delete stream; returns (warm wall, tracer|None).

    The history is rebuilt per run (streams rewrite it) from the shared
    Objective so the compiled grad_fn stays warm; ``warmup=True`` routes
    the trace/compile cost into ``compile_time_s``, keeping it out of the
    measured wall.
    """
    ds = binary_classification(n=p["n"], d=p["d"], seed=p["seed"])
    meta = HistoryMeta(n=p["n"], batch_size=p["batch"], seed=7,
                       steps=p["steps"], lr_schedule=((0, p["lr"]),))
    p0 = logreg_init(p["d"], seed=1)
    _, hist = sgd_train_with_cache(obj, p0, ds, meta, impl="scan")
    reqs = np.random.default_rng(11).choice(
        meta.n, p["requests"], replace=False).tolist()
    cfg = dataclasses.replace(DG_CFG, impl="scan")

    tracer = None
    obs_trace.disable()
    if mode == "plain":
        obs_trace.span = _stub_span
    elif mode == "on":
        # priced against v5e peaks on any device (see bench_lm)
        obs_trace.enable(obs_trace.Tracer(hw=TPU_V5E))
    try:
        _, ostats = online_deltagrad(obj, hist, ds, reqs, cfg,
                                     mode="delete", warmup=True)
    finally:
        obs_trace.span = _REAL_SPAN
        tracer = obs_trace.disable()
    return ostats.wall_time_s, tracer if mode == "on" else None


def _disabled_span_ns(iters: int = 200_000) -> float:
    """ns per `span()` call with the tracer disabled (kwargs included —
    that's what a real call site pays)."""
    obs_trace.disable()
    t0 = time.perf_counter()
    for _ in range(iters):
        obs_trace.span("bench.noop", t0=0, t1=1)
    return (time.perf_counter() - t0) / iters * 1e9


def _validate_chrome(tracer):
    """(valid, roofline_ok, n_events) from a round-tripped export."""
    if tracer is None:
        return False, False, 0
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "trace.json")
        tracer.export_chrome(path)
        with open(path) as f:
            doc = json.load(f)
    evs = doc.get("traceEvents", [])
    xs = [e for e in evs if e.get("ph") == "X"]
    need = {"name", "ts", "dur", "pid", "tid"}
    valid = bool(xs) and all(need <= set(e) for e in xs)
    scans = [e for e in xs if e.get("name") == "replay.scan"]
    roofline = bool(scans) and all(
        {"pred_s", "measured_s", "roofline_ratio"} <= set(e.get("args", {}))
        for e in scans)
    return valid, roofline, len(evs)


def run(quick: bool = False, out_json: str = "BENCH_obs.json"):
    p = QUICK if quick else FULL
    obj = logreg_objective(l2=p["l2"])

    walls = {"plain": [], "off": [], "on": []}
    tracer = None
    for _ in range(p["repeats"]):
        # interleave the arms so slow drift (thermal, noisy neighbours)
        # lands on all three equally instead of biasing the ratio
        for mode in ("plain", "off", "on"):
            wall, tr = _run_stream(p, obj, mode)
            walls[mode].append(wall)
            tracer = tr or tracer

    plain = min(walls["plain"])
    off = min(walls["off"])
    on = min(walls["on"])
    span_ns = _disabled_span_ns()
    valid, roofline, n_events = _validate_chrome(tracer)

    results = {
        "config": {"bench": "obs", "quick": bool(quick), "n": p["n"],
                   "d": p["d"], "steps": p["steps"], "batch": p["batch"],
                   "requests": p["requests"], "repeats": p["repeats"],
                   "seed": p["seed"]},
        "obs": {
            "replay_wall_plain_s": plain,
            "replay_wall_off_s": off,
            "tracer_off_ratio": off / max(plain, 1e-12),
            "replay_wall_on_s": on,
            "tracer_on_ratio": on / max(plain, 1e-12),
            "disabled_span_ns": span_ns,
            "trace_valid_chrome": valid,
            "replay_spans_have_roofline": roofline,
            "span_events": n_events,
        },
    }
    if out_json:
        path = out_json if os.path.isabs(out_json) else os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            out_json)
        with open(path, "w") as f:
            json.dump(results, f, indent=1)
    o = results["obs"]
    rows = [emit("obs_tracer_overhead", off,
                 {"tracer_off_ratio": f"{o['tracer_off_ratio']:.4f}",
                  "tracer_on_ratio": f"{o['tracer_on_ratio']:.4f}",
                  "disabled_span_ns": f"{span_ns:.0f}",
                  "span_events": n_events,
                  "trace_valid_chrome": valid,
                  "roofline_annotated": roofline})]
    return rows, results


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help="CI-sized run (matches the committed baseline)")
    ap.add_argument("--out", default="BENCH_obs.json")
    args = ap.parse_args(argv)
    rows, _ = run(quick=args.quick, out_json=args.out)
    return rows


if __name__ == "__main__":
    main()
