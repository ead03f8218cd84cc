"""The model work one DeltaGrad replay needs, from the configuration.

Step t of T is explicit while t <= j0 (``burn_in``) and every T0-th
(``period``) step after; the others are approximate (Wu et al., ICML 2020,
Algorithm 1).  An explicit step needs the gradient over the batch's rows
that remain, an approximate step over the batch's removed rows only.
"""


def steps(cfg):
    """(explicit, approximate) steps of one replay."""
    T = int(cfg["steps"])
    j0 = int(cfg["deltagrad"]["burn_in"])
    T0 = int(cfg["deltagrad"]["period"])
    explicit = sum(1 for t in range(T) if t <= j0 or (t - j0) % T0 == 0)
    return explicit, T - explicit


def replay_grad_rows(cfg, n_rows: int, removed: int) -> float:
    """Per-example gradients one replay removing `removed` of `n_rows`
    rows needs: a batch holds its share of the removed rows."""
    explicit, approx = steps(cfg)
    b = int(cfg["batch_size"])
    gone = b * removed / n_rows
    return explicit * (b - gone) + approx * gone
