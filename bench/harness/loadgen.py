"""Seeded open-loop request traces for the serving cells.

Adapted from the program's ``serve/loadgen.py`` (its Poisson/diurnal/fixed
traces and ``materialize``), kept here so that no program change can move
the yardstick, with two changes:

  * every request is timed from when it was DUE, not from when it was
    submitted, so a stall in the generator or the server counts against
    the requests behind it;
  * the driver records how late the generator ran (submit time minus due
    time) and prints it.

A traffic file (``bench/traffic/<name>.json``) holds the parameters.  Every
seed gets the same multiset of inter-arrival gaps, request sizes and tenant
shares, drawn as quantiles of their laws; the seed only permutes them.  So
two seeds offer the same work in another order, and the spread between
seeds measures the system, not the draw.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List

import numpy as np


@dataclass
class Request:
    due_s: float          # offset from window start
    tenant: str
    op: str
    n_rows: int
    rows: List[int] = None


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _zipf_law(lo: int, hi: int, a: float):
    ks = np.arange(lo, hi + 1)
    p = ks.astype(np.float64) ** -float(a)
    return ks, p / p.sum()


def _inverse_cdf(ks: np.ndarray, p: np.ndarray, u: np.ndarray) -> np.ndarray:
    cdf = np.cumsum(p)
    return ks[np.minimum(np.searchsorted(cdf, u, side="right"), ks.size - 1)]


def sizes(spec: Dict, n: int) -> np.ndarray:
    """`n` request sizes as quantiles of ``spec``'s law (unpermuted)."""
    if spec["dist"] == "fixed":
        return np.full(n, int(spec["rows"]), dtype=np.int64)
    if spec["dist"] == "zipf":
        ks, p = _zipf_law(int(spec["min"]), int(spec["max"]), spec["a"])
        return _inverse_cdf(ks, p, _quantiles(n)).astype(np.int64)
    raise ValueError(f"unknown size law {spec['dist']!r}")


def gaps(spec: Dict, n: int) -> np.ndarray:
    """`n` inter-arrival gaps (seconds) as quantiles of ``spec``'s law."""
    rate = float(spec["rate_per_s"])
    u = _quantiles(n)
    if spec["process"] == "poisson":
        return -np.log1p(-u) / rate
    if spec["process"] == "fixed":
        return np.full(n, 1.0 / rate)
    raise ValueError(f"unknown arrival process {spec['process']!r}")


def tenants(spec: Dict, n: int) -> np.ndarray:
    """Tenant names with Zipf-skewed shares: tenant k (1-based) gets a
    share proportional to k**-zipf_a."""
    ks, p = _zipf_law(1, int(spec["count"]), spec.get("zipf_a", 0.0))
    return np.asarray([f"t{k}" for k in _inverse_cdf(ks, p, _quantiles(n))])


def trace(traffic: Dict, seconds: float, seed: int) -> List[Request]:
    """The requests due in a window of `seconds`: ``rate * seconds`` of
    them, with the seed permuting gaps, sizes and tenants independently."""
    arr = traffic["arrivals"]
    n = max(1, int(round(float(arr["rate_per_s"]) * seconds)))
    ss = np.random.default_rng(np.random.SeedSequence([seed, 0x7A11C]))
    g = ss.permutation(gaps(arr, n))
    # rescale so that the last request is due just inside the window,
    # whatever the permutation: every seed offers the same rate
    due = np.cumsum(g)
    due *= (seconds * (n - 0.5) / n) / due[-1]
    sz = ss.permutation(sizes(traffic["request_rows"], n))
    tn = ss.permutation(tenants(traffic["tenants"], n))
    return [Request(due_s=float(d), tenant=str(t), op="delete",
                    n_rows=int(k)) for d, t, k in zip(due, tn, sz)]


def materialize(reqs: List[Request], live: np.ndarray, seed: int) -> int:
    """Bind rows: deletes consume DISJOINT rows of a seeded permutation of
    the live rows, so no batching order can conflict.  Returns the number
    of rows bound."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0x805]))
    perm = rng.permutation(np.flatnonzero(live))
    cursor = 0
    for r in reqs:
        if cursor + r.n_rows > perm.size:
            raise ValueError(f"the trace deletes more than the {perm.size} "
                             "live rows")
        r.rows = [int(x) for x in perm[cursor:cursor + r.n_rows]]
        cursor += r.n_rows
    return cursor
