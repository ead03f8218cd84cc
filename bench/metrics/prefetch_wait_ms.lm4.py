"""Median, over the window's whole replays (the benchmark's
``bench.replay`` spans), of the device-idle time inside the replay's waits
for a prefetched history window (the program's ``store.prefetch_wait``
spans: the replay needs window s+1 before the staging thread has put it on
the devices), summed over the replay, in ms.  Moves ``rows_per_s``."""

import numpy as np

from bench.harness import spans


def read(run):
    tr = run.trace
    if tr is None or not tr.devices:
        return None
    replays = spans.host_spans(tr, "bench.replay")
    waits = spans.host_spans(tr, "store.prefetch_wait")
    if not replays or not waits:
        return None
    idle = [sum(spans.device_idle_s(tr, [(e.start, e.end)
                                         for e in spans.inside(waits, r)]))
            for r in replays]
    return float(np.median(idle) * 1e3)
