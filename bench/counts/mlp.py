"""Model FLOPs of the paper MLP (d -> h ReLU -> c, softmax cross-entropy).

A per-example gradient needs the forward pass (2dh + 2hc multiply-adds
counted as two operations each) and the backward pass to the weights
(dW2 and dh: 4hc; dW1: 2dh; the input needs no gradient): 4dh + 6hc
matmul operations a row.  Elementwise work (ReLU, softmax, biases) is
left out; it is under 0.5% at the paper's widths."""


def grad_flops_per_row(cfg) -> int:
    d, h, c = int(cfg["d_in"]), int(cfg["hidden"]), int(cfg["classes"])
    return 4 * d * h + 6 * h * c


def n_params(cfg) -> int:
    d, h, c = int(cfg["d_in"]), int(cfg["hidden"]), int(cfg["classes"])
    return d * h + h + h * c + c
