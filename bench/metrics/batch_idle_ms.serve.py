"""Median, over the window's served batches (the program's ``serve.batch``
spans, from the start of its service to its parameters' publication), of the
time inside the batch in which no operation ran on the device, in ms.
Moves ``forget_p50_ms``."""

import numpy as np

from bench.harness import spans


def read(run):
    tr = run.trace
    if tr is None or not tr.devices:
        return None
    batches = spans.host_spans(tr, "serve.batch")
    if not batches:
        return None
    return float(np.median(spans.device_idle_s(
        tr, [(b.start, b.end) for b in batches])) * 1e3)
