"""Median, over the window's whole replays (the benchmark's
``bench.replay`` spans), of the device-idle time inside the replay's host
syncs (the program's ``replay.host_sync`` spans: each explicit step's
admission scalars and each scanned segment's guard flags read back),
summed over the replay, in ms.  Moves ``rows_per_s``."""

import numpy as np

from bench.harness import spans


def read(run):
    tr = run.trace
    if tr is None or not tr.devices:
        return None
    replays = spans.host_spans(tr, "bench.replay")
    syncs = spans.host_spans(tr, "replay.host_sync")
    if not replays or not syncs:
        return None
    idle = [sum(spans.device_idle_s(tr, [(e.start, e.end)
                                         for e in spans.inside(syncs, r)]))
            for r in replays]
    return float(np.median(idle) * 1e3)
