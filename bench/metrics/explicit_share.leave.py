"""Share of the device's busy time spent in the replay's explicit-step
programs, against the scanned approximate segments and everything else,
in percent.  Programs are told apart by the names of their jitted
functions on the trace's ``XLA Modules`` line.  Moves ``rows_per_s``."""

from bench.harness import xplane

EXPLICIT = r"^jit__explicit_step"


def read(run):
    tr = run.trace
    if tr is None or not tr.devices:
        return None
    busy = xplane.mean_busy_s(tr)
    if busy <= 0:
        return None
    t = sum(xplane.time_by_name(tr.modules.get(d, []), EXPLICIT)
            for d in tr.devices) / len(tr.devices)
    if t <= 0:
        return None
    return 100.0 * t / busy
