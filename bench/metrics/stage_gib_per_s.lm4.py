"""Rate at which the history store stages windows onto the devices, in
GiB/s a chip: the bytes the program's ``store.staged_bytes`` counter added
during the window (bytes put on each device; its value at the end of
set-up is taken by `bench/models/lm.py`), over the time the window's
``store.window_stage`` spans took (the staging threads stacking and
shipping the windows).  Moves ``rows_per_s``."""

from bench.harness import spans


def read(run):
    tr = run.trace
    if tr is None or not tr.devices or "staged_bytes_setup" not in run.data:
        return None
    from repro.obs import metrics

    found = [m for m in metrics.get_registry().metrics()
             if m.name == "store.staged_bytes"]
    stages = spans.host_spans(tr, "store.window_stage")
    t = sum(e.dur for e in stages)
    if not found or t <= 0:
        return None
    staged = found[0].value - run.data["staged_bytes_setup"]
    return staged / 2**30 / t
