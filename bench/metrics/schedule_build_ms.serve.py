"""Median, over the window's served batches (the program's ``serve.batch``
spans), of the host time the batch spent building its request groups'
schedules (the ``online.schedule_build`` spans inside it), in ms.  Moves
``forget_p50_ms``."""

import numpy as np

from bench.harness import spans


def read(run):
    tr = run.trace
    if tr is None or not tr.devices:
        return None
    batches = spans.host_spans(tr, "serve.batch")
    builds = spans.host_spans(tr, "online.schedule_build")
    if not batches or not builds:
        return None
    return float(np.median([sum(e.dur for e in spans.inside(builds, b))
                            for b in batches]) * 1e3)
