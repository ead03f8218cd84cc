"""Median host time a leave-group-out replay spends building its schedule
and plan (the program's ``replay.schedule_build`` span, one a replay), in
ms.  The device has nothing queued while it runs.  Moves ``rows_per_s``."""

import numpy as np

from bench.harness import spans


def read(run):
    tr = run.trace
    if tr is None or not tr.devices:
        return None
    d = [e.dur for e in spans.host_spans(tr, "replay.schedule_build")]
    return float(np.median(d) * 1e3) if d else None
