"""The Pallas ``dequant_update`` kernel's share of its roofline, in percent:
the least time the chips could take for the kernel's calls in the traced
window (each call's bytes over peak HBM bandwidth; it is memory bound, see
`bench/counts/lm.py`) over the kernel's device time in the trace.  Each
call runs on one device's block of one parameter leaf; its elements are
those of the result's shape on the trace.  Moves ``rows_per_s``."""

import re

from bench.counts import lm
from bench.harness import peaks

# the kernel's custom call on the `XLA Ops` line, named after its jitted
# wrapper, with its f32 result: (1, width) for a vector, (rows, last dim)
# for a leaf of rank 2 or more
KERNEL = re.compile(r"^%dequant_deltagrad_update(\.\d+)? = f32\[(\d+),(\d+)\]")


def read(run):
    tr = run.trace
    if tr is None or not tr.devices:
        return None
    base = run.cell.config["history"]["codec"].startswith("delta")
    pk = peaks.for_device(run)
    t_min = t = 0.0
    for dev in tr.devices:
        for e in tr.ops.get(dev, []):
            m = KERNEL.match(e.name)
            if m is None:
                continue
            c = lm.dequant_update(int(m.group(2)) * int(m.group(3)),
                                  base=base)
            t_min += max(c["bytes"] / pk["hbm_bytes_per_s"],
                         c["flops"] / pk["bf16_flops_per_s"])
            t += e.dur
    return 100.0 * t_min / t if t > 0 else None
