"""The Pallas ``fused_update`` kernel's share of its roofline, in percent:
the least time the chip could take for the kernel's calls in the traced
window (bytes over peak HBM bandwidth; it is memory bound, see
`bench/counts/kernels.py`) over the kernel's device time in the trace.
Moves ``rows_per_s``."""

from bench.counts import kernels
from bench.harness import peaks, xplane

# the kernel's custom call on the `XLA Ops` line, named after its pallas_call
KERNEL = r"^%deltagrad_update(\.\d+)? = "


def read(run):
    tr = run.trace
    if tr is None or not tr.devices:
        return None
    ops = [e for d in tr.devices for e in tr.ops.get(d, [])]
    calls = xplane.count_by_name(ops, KERNEL)
    t = xplane.time_by_name(ops, KERNEL)
    if calls == 0 or t <= 0:
        return None
    c = kernels.fused_update(run.data["n_params"])
    pk = peaks.for_device(run)
    t_min = calls * max(c["bytes"] / pk["hbm_bytes_per_s"],
                        c["flops"] / pk["bf16_flops_per_s"])
    return 100.0 * t_min / t
