"""The benchmark's run: find the cell by name, build it, warm it, measure a
window, read the device, check the answers against the plain reference,
and print the result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own, found by name:

  BENCHMARK.json                       cells and metrics
  bench/configs/<config>.json          sizes, recipe, precision
  bench/configs/<config>.reference.py  the plain reference (``make_train``)
  bench/models/<model>.py              inputs from the seed, program session
  bench/traffic/<traffic>.json         the mix; ``driver`` names its driver
  bench/drivers/<driver>.py            the general driver of that kind
  bench/limits/<cell>.json             limits of the numbers compared
  bench/metrics/<metric>.py            one reader per per-layer metric
"""

from __future__ import annotations

import gc
import importlib.util
import json
import os
import sys
import tempfile
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


TRACE_SECONDS = 10.0


class NoChip(SystemExit):
    pass


def load_json(*parts) -> Dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(path: str):
    spec = importlib.util.spec_from_file_location(
        "bench_" + os.path.basename(path).replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def seed_key(seed: int):
    """A JAX PRNG key from any whole-number seed (64 bits and more)."""
    import jax
    import numpy as np

    s = np.random.SeedSequence(int(seed) % (1 << 128))
    return jax.random.PRNGKey(int(s.generate_state(1, np.uint32)[0]))


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    limits: Dict
    e2e: List[Dict]
    per_layer: List[Dict]
    model: Any
    reference: Any


def find_cell(name: str, overrides: Optional[Dict] = None) -> Cell:
    """The cell `name` of BENCHMARK.json with its files.  `overrides`
    replace top-level keys of the configuration and traffic (the tests'
    small sizes)."""
    bench = load_json(ROOT, "BENCHMARK.json")
    wl = {w["name"]: w for w in bench["workloads"]}
    if name not in wl:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(wl)}")
    w = wl[name]
    cfg = load_json(BENCH_DIR, "configs", w["config"] + ".json")
    traffic = load_json(BENCH_DIR, "traffic", w["traffic"] + ".json")
    traffic["name"] = w["traffic"]
    ov = overrides or {}
    cfg.update(ov.get("config", {}))
    traffic.update(ov.get("traffic", {}))
    limits = load_json(BENCH_DIR, "limits", name + ".json")
    reported = {m["name"] for m in bench["end_to_end"]
                if name in m.get("workloads", [name])}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in reported)]
    model = load_module(os.path.join(BENCH_DIR, "models",
                                     cfg["model"] + ".py"))
    reference = load_module(os.path.join(BENCH_DIR, "configs",
                                         w["config"] + ".reference.py"))
    return Cell(name=name, chips=int(w["chips"]), config=cfg,
                traffic=traffic, limits=limits,
                e2e=[m for m in bench["end_to_end"]
                     if m["name"] in reported],
                per_layer=per_layer, model=model, reference=reference)


@dataclass
class Run:
    """What one run hands from the driver to the metric readers and the
    correctness check."""

    cell: Cell
    seed: int
    seconds: float
    t_start: float                      # process start, host clock
    setup_s: float = 0.0
    e2e: Dict[str, float] = field(default_factory=dict)
    data: Dict[str, Any] = field(default_factory=dict)  # driver's readings
    answers: List = field(default_factory=list)  # [(removed rows, params)]
    attempted: int = 0
    failed: int = 0
    trace: Any = None                   # xplane.Trace of the window
    marks: List = field(default_factory=list)  # set-up phases, (name, t)

    def mark(self, name: str) -> None:
        """The end of a set-up phase, in seconds since process start."""
        self.marks.append((name, time.time() - self.t_start))


def check_device(chips: int, require_tpu: bool = True) -> Dict:
    import jax

    devs = jax.devices()
    d = devs[0]
    if require_tpu and (d.platform != "tpu" or len(devs) < chips):
        raise NoChip(f"bench: this cell needs {chips} TPU chip(s); JAX "
                     f"finds {len(devs)} {d.platform} device(s)")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": min(len(devs), chips)}


def memory_peak_bytes(chips: int) -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks)) if peaks else 0


class CompileCounter:
    """Counts programs traced or compiled while `active`."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.active = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.active and event in self.EVENTS:
            self.count += 1


def _profile_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # Python call tracing would slow the host
    opts.host_tracer_level = 2
    return opts


def run(workload: str, seed: int, seconds: float, trace: bool,
        t_start: Optional[float] = None, require_tpu: bool = True,
        overrides: Optional[Dict] = None, fault: Optional[str] = None,
        on_done=None) -> Dict:
    """One run of a cell; returns the result line as a dict.

    `require_tpu=False` and `overrides` are for the tests, which drive the
    rest of a run at a small size on the CPU; `fault` names a fault the
    test plants in the timed path (`bench/harness/faults.py`)."""
    t_start = time.time() if t_start is None else t_start
    cell = find_cell(workload, overrides=overrides)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax

    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    device = check_device(cell.chips, require_tpu)

    from bench.harness import check as checker
    from bench.harness import faults
    from bench.harness.hostwatch import HostWatch

    driver = load_module(os.path.join(
        BENCH_DIR, "drivers", cell.traffic["driver"] + ".py"))
    # a traced run traces at most TRACE_SECONDS of the window: the trace's
    # size and its reduction grow with its length
    r = Run(cell=cell, seed=int(seed),
            seconds=min(float(seconds), TRACE_SECONDS) if trace
            else float(seconds),
            t_start=t_start)
    r.data["device_kind"] = device["kind"]
    r.mark("jax and device")
    counter = CompileCounter()
    with faults.planted(fault):
        state = driver.setup(r)
        logdir = None
        if trace:
            logdir = tempfile.mkdtemp(prefix="bench-trace-")
            jax.profiler.start_trace(logdir,
                                     profiler_options=_profile_options())
        counter.active = True
        r.setup_s = time.time() - r.t_start
        with jax.profiler.TraceAnnotation("bench.window"), faults.armed(), \
                HostWatch() as host:
            driver.window(r, state)
        counter.active = False
        if trace:
            jax.profiler.stop_trace()
        driver.finish(r, state)
    if counter.count:
        print(f"bench: {counter.count} programs traced or compiled inside "
              "the window", file=sys.stderr, flush=True)
    print("bench: set-up phases ended at (s) "
          + ", ".join(f"{n} {t:.3f}" for n, t in r.marks)
          + f"; set-up {r.setup_s:.3f}", file=sys.stderr)
    print(f"bench: host during the window: {host.summary()}",
          file=sys.stderr, flush=True)
    device["memory_peak_bytes"] = memory_peak_bytes(cell.chips)
    if trace:
        from bench.harness import xplane

        r.trace = xplane.load(xplane.find_xplane(logdir))
        device["busy_s"] = xplane.mean_busy_s(r.trace)
        device["window_s"] = r.trace.window_s
        import shutil
        shutil.rmtree(logdir, ignore_errors=True)
    metrics = {}
    if not trace:
        r.e2e["setup_s"] = r.setup_s
        r.e2e["peak_hbm_gib"] = device["memory_peak_bytes"] / 2**30
        for m in cell.e2e:
            if m["name"] in r.e2e:
                metrics[m["name"]] = {"value": r.e2e[m["name"]],
                                      "unit": m["unit"]}
    else:
        for m in cell.per_layer:
            reader = load_module(os.path.join(BENCH_DIR, "metrics",
                                              m["name"] + ".py"))
            v = reader.read(r)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    # the program's state is freed by the driver's finish(); only the
    # sampled answers (host copies) remain for the reference
    state = None
    gc.collect()
    compared = checker.compare(r)
    if on_done is not None:
        on_done(r, compared)
    ok = all(c["ok"] for c in compared) and r.attempted > 0
    out = {"correct": bool(ok), "attempted": r.attempted,
           "failed": r.failed, "metrics": metrics, "device": device}
    if trace:
        from bench.harness import xplane
        out["breakdown"] = {"device_ops": xplane.top_ops(r.trace),
                            "idle_gaps": xplane.idle_gaps(r.trace)}
    out["compared"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                       for c in compared}
    for c in compared:
        print(f"compared {c['name']} {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['ok'] else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    return out
