"""The program's spans on the device trace's clock.

While the profiler traces, every span the program opens (`repro.obs.trace`:
``replay.*``, ``online.*``, ``session.*``, ``serve.*``) is a host event of
the ``.xplane.pb`` under its own name, beside the benchmark's own
``bench.*`` spans, on the clock of the device's operations.  These helpers
pick such spans out of an `xplane.Trace` and measure the device's idle time
inside them.
"""

from __future__ import annotations

from bisect import bisect_right
from typing import List, Sequence, Tuple

from bench.harness import xplane

# `xplane.load` clips events to the traced window, so a span cut by an edge
# starts or ends on it (to within float rounding of the window's bounds)
EDGE_S = 1e-6


def host_spans(tr: xplane.Trace, name: str) -> List[xplane.Event]:
    """The host events named `name`, in start order, leaving out any that
    touches an edge of the traced window: its time is not whole."""
    lo, hi = tr.window
    return sorted((e for e in tr.host if e.name == name
                   and e.start > lo + EDGE_S and e.end < hi - EDGE_S),
                  key=lambda e: e.start)


def inside(evs: Sequence[xplane.Event],
           outer: xplane.Event) -> List[xplane.Event]:
    """The events of `evs` that lie within `outer`'s interval."""
    return [e for e in evs if outer.start <= e.start and e.end <= outer.end]


def device_idle_s(tr: xplane.Trace,
                  intervals: Sequence[Tuple[float, float]]) -> List[float]:
    """For each interval ``(a, b)``, the seconds of it in which no operation
    ran on the first device (the union of its ``XLA Ops`` events)."""
    busy = xplane.union([(e.start, e.end)
                         for e in tr.ops.get(tr.devices[0], [])])
    starts = [x for x, _ in busy]
    out = []
    for a, b in intervals:
        i, covered = max(bisect_right(starts, a) - 1, 0), 0.0
        while i < len(busy) and busy[i][0] < b:
            x, y = busy[i]
            covered += max(0.0, min(y, b) - max(x, a))
            i += 1
        out.append((b - a) - covered)
    return out
