"""Model FLOP utilization of the replays in the traced window, in percent:
the model FLOPs the window's replays needed, counted from the
configuration's schedule and shapes (`bench/counts/deltagrad.py` for the
gradients a replay needs, `bench/counts/mlp.py` for the FLOPs a row;
nothing the program counts or recomputes), over the traced window's
length times the chip's bf16 peak.  Moves ``rows_per_s``."""

from bench.counts import deltagrad
from bench.harness import peaks


def read(run):
    tr = run.trace
    d = run.data
    if tr is None or not tr.devices or not d.get("replay_rows") \
            or tr.window_s <= 0:
        return None
    rows = sum(deltagrad.replay_grad_rows(run.cell.config, d["n_rows"], r)
               for r in d["replay_rows"])
    flops = rows * d["grad_flops_per_row"]
    pk = peaks.for_device(run)
    return 100.0 * flops / (tr.window_s * pk["bf16_flops_per_s"]
                            * run.cell.chips)
