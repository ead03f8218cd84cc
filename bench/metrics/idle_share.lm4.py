"""Share of the traced window in which no operation ran on a device:
1 - (union of busy intervals) / window, averaged over the cell's chips, in
percent.  Moves ``rows_per_s``."""

from bench.harness import xplane


def read(run):
    tr = run.trace
    if tr is None or not tr.devices or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - xplane.mean_busy_s(tr) / tr.window_s)
