"""Mean rows coalesced into one dispatched replay by the serving tier
(counted from the program's per-request batch ids).  Moves
``forget_p95_ms``: more rows a batch, fewer replays a request."""

import numpy as np


def read(run):
    b = run.data.get("batch_rows")
    return float(np.mean(b)) if b else None
