"""Share of the devices' busy time spent in collectives, in percent: the
union of the intervals of all-reduce, all-gather, reduce-scatter,
collective-permute and all-to-all operations (their async start and done
halves included) on each device's ``XLA Ops`` line, over that device's
busy time, averaged over the cell's chips.  Moves ``rows_per_s``."""

import re

from bench.harness import xplane

COLLECTIVE = re.compile(
    r"^%(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)"
    r"[-.\w]* = ")


def read(run):
    tr = run.trace
    if tr is None or not tr.devices:
        return None
    shares = []
    for dev in tr.devices:
        busy = xplane.busy_s(tr, dev)
        if busy <= 0:
            continue
        coll = xplane.union([(e.start, e.end) for e in tr.ops[dev]
                             if COLLECTIVE.match(e.name)])
        shares.append(sum(b - a for a, b in coll) / busy)
    return 100.0 * sum(shares) / len(shares) if shares else None
