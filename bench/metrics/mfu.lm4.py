"""Model FLOP utilization of the LM replays in the traced window, in
percent: the model FLOPs the window's replays needed, counted from the
configuration's schedule and shapes (`bench/counts/deltagrad.py` for the
documents' gradients a replay needs, `bench/counts/lm.py` for the FLOPs of
a document's gradient; nothing the program counts or recomputes), over the
traced window's length times the bf16 peak of the cell's chips.  Moves
``rows_per_s``."""

from bench.counts import deltagrad, lm
from bench.harness import peaks


def read(run):
    tr = run.trace
    d = run.data
    if tr is None or not tr.devices or not d.get("replay_rows") \
            or tr.window_s <= 0:
        return None
    cfg = run.cell.config
    docs = sum(deltagrad.replay_grad_rows(cfg, d["n_rows"], r)
               for r in d["replay_rows"])
    flops = docs * lm.grad_flops_per_row(cfg)
    pk = peaks.for_device(run)
    return 100.0 * flops / (tr.window_s * pk["bf16_flops_per_s"]
                            * run.cell.chips)
