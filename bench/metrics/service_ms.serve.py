"""Median time from a batch's dispatch to its parameters' publication
(``t_done - t_dispatch`` on the program's `QueuedRequest`), in ms: the
session and online engine's share of a request.  Moves ``forget_p50_ms``."""

import numpy as np


def read(run):
    s = run.data.get("service_s")
    return float(np.median(s) * 1e3) if s else None
