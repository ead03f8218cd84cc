"""95th percentile of the time a served request waited from when it was
due until its batch was dispatched (the executor's ``t_dispatch`` stamp on
the program's `QueuedRequest`), in ms.  Moves ``forget_p95_ms``."""

import numpy as np


def read(run):
    w = run.data.get("queue_wait_s")
    return float(np.percentile(w, 95) * 1e3) if w else None
