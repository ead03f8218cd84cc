"""Reduce a profiler trace (``.xplane.pb``) to device busy time, per-op and
per-program device time, and idle gaps named by what the host was doing.

Read with ``jax.profiler.ProfileData`` alone.  Device planes are named
``/device:TPU:<n>``; on them the ``XLA Ops`` line holds one event per
executed operation (a Pallas kernel is one such event, named after its
kernel) and the ``XLA Modules`` line one event per executed program, named
after its jitted function (``jit__explicit_step(...)``).  Host planes hold
the Python thread's ``TraceAnnotation`` spans and the runtime's dispatch
events.  All timestamps are on one clock, in nanoseconds.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# host spans the benchmark itself opens (TraceAnnotation) start with this
BENCH_SPAN = "bench."


@dataclass
class Event:
    name: str
    start: float  # seconds, on the trace's clock
    dur: float    # seconds

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclass
class Trace:
    ops: Dict[int, List[Event]] = field(default_factory=dict)
    modules: Dict[int, List[Event]] = field(default_factory=dict)
    host: List[Event] = field(default_factory=list)
    window: Tuple[float, float] = (0.0, 0.0)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def devices(self) -> List[int]:
        return sorted(self.ops)


def find_xplane(logdir: str) -> str:
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return max(paths, key=os.path.getmtime)


def load(path: str) -> Trace:
    """Parse `path`.  The window is the host span `bench.window` (the
    benchmark opens it around the traced window); events outside it are
    clipped away."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    tr = Trace()
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            evs = [Event(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                   for e in line.events]
            if m:
                dev = int(m.group(1))
                if line.name == OPS_LINE:
                    tr.ops.setdefault(dev, []).extend(evs)
                elif line.name == MODULES_LINE:
                    tr.modules.setdefault(dev, []).extend(evs)
            elif plane.name.startswith("/host:"):
                tr.host.extend(evs)
    wins = [e for e in tr.host if e.name == "bench.window"]
    if wins:
        w = max(wins, key=lambda e: e.dur)
        tr.window = (w.start, w.end)
    else:
        allev = [e for evs in tr.ops.values() for e in evs]
        if allev:
            tr.window = (min(e.start for e in allev),
                         max(e.end for e in allev))
    lo, hi = tr.window
    clip = lambda evs: [Event(e.name, max(e.start, lo),
                              min(e.end, hi) - max(e.start, lo))
                        for e in evs if e.end > lo and e.start < hi]
    tr.ops = {d: clip(v) for d, v in tr.ops.items()}
    tr.modules = {d: clip(v) for d, v in tr.modules.items()}
    tr.host = clip(tr.host)
    return tr


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def busy_s(tr: Trace, dev: int) -> float:
    """Seconds in which some operation ran on device `dev`."""
    return sum(b - a for a, b in union([(e.start, e.end)
                                        for e in tr.ops.get(dev, [])]))


def mean_busy_s(tr: Trace) -> float:
    devs = tr.devices
    return sum(busy_s(tr, d) for d in devs) / len(devs) if devs else 0.0


def time_by_name(evs: List[Event], pattern: str) -> float:
    rx = re.compile(pattern)
    return sum(e.dur for e in evs if rx.search(e.name))


def count_by_name(evs: List[Event], pattern: str) -> int:
    rx = re.compile(pattern)
    return sum(1 for e in evs if rx.search(e.name))


def short_name(name: str) -> str:
    """An op event's name is its whole HLO instruction; keep the
    instruction's name and result shape (``%fusion.3 = f32[60000,784]``)."""
    head, sep, rest = name.partition(" = ")
    return head + sep + rest.split("{", 1)[0].split(" ", 1)[0] if sep \
        else name


def top_ops(tr: Trace, k: int = 10) -> List[List]:
    """The `k` device operations that took most time, summed over calls and
    averaged over devices: ``[[name, seconds], ...]``."""
    tot: Dict[str, float] = {}
    for evs in tr.ops.values():
        for e in evs:
            n = short_name(e.name)
            tot[n] = tot.get(n, 0.0) + e.dur
    n = max(1, len(tr.ops))
    return [[name, s / n] for name, s in
            sorted(tot.items(), key=lambda kv: -kv[1])[:k]]


def _host_label(tr: Trace, a: float, b: float) -> str:
    """What the host was doing in the gap (a, b): the innermost benchmark
    span covering its midpoint, with the innermost other host event
    covering it (a dispatch, a transfer, a program's span), if any.
    "no host span" means none was open: the host ran untraced Python (the
    profiler's Python tracer is off) or waited."""
    mid = 0.5 * (a + b)
    cover = [e for e in tr.host if e.start <= mid <= e.end]
    bench = [e for e in cover if e.name.startswith(BENCH_SPAN)
             and e.name != "bench.window"]
    other = [e for e in cover if not e.name.startswith(BENCH_SPAN)]
    parts = []
    if bench:
        parts.append(min(bench, key=lambda e: e.dur).name)
    if other:
        parts.append(min(other, key=lambda e: e.dur).name)
    return " / ".join(parts) if parts else "no host span"


def idle_gaps(tr: Trace, k: int = 10) -> List[List]:
    """The `k` longest idle gaps on the first device, named by what the
    host was doing: ``[[label, seconds], ...]``."""
    if not tr.devices:
        return []
    dev = tr.devices[0]
    lo, hi = tr.window
    busy = union([(e.start, e.end) for e in tr.ops.get(dev, [])])
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    return [[_host_label(tr, a, b), b - a] for a, b in gaps[:k]]
