"""The comparison that decides `correct`.

Each answer the timed path published (the parameters after a sampled
request or replay, with the rows it had removed by then) is compared with
the plain reference's exact retraining without those rows, computed at the
precision the configuration states.  The number compared is the unlearning
gap: the distance of the answer from that retraining, as a share of the
deletion's own effect (the distance between the reference trained on all
rows and on the rows that remain):

    gap = ||w_answer - w_ref(R)|| / ||w_ref(all) - w_ref(R)||

An answer that did not unlearn reads about 1; a sound DeltaGrad answer
reads far below.  The worst gap over the sampled answers is held to the
cell's limit (`bench/limits/<cell>.json`).  A request that was refused,
failed or never published is for `correct` too: their limit is 0.  The
reference runs after the window, once the program's state is freed, and is
not part of `setup_s`.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional

import numpy as np


def _norm(tree) -> float:
    import jax

    return math.sqrt(sum(float(np.sum(np.square(np.asarray(x, np.float64))))
                         for x in jax.tree.leaves(tree)))


def _sub(a, b):
    import jax

    return jax.tree.map(lambda x, y: np.asarray(x, np.float64)
                        - np.asarray(y, np.float64), a, b)


def reference_answers(run, removed_sets: List[np.ndarray],
                      precision: Optional[str] = None):
    """(w_ref(all), [w_ref(R) for R in removed_sets]) on the host."""
    import jax
    import jax.numpy as jnp

    cell = run.cell
    train = cell.reference.make_train(cell.config, precision or "highest")
    params0, *cols = run.data["ref_inputs"]
    n = run.data["n_rows"]

    def one(removed):
        live = np.ones(n, dtype=bool)
        live[np.asarray(removed, dtype=np.int64)] = False
        w = train(params0, *cols, jnp.asarray(live))
        return jax.tree.map(np.asarray, jax.block_until_ready(w))

    full = one(np.zeros(0, np.int64))
    return full, [one(r) for r in removed_sets]


def gaps(run, answers) -> List[float]:
    full, refs = reference_answers(run, [r for r, _ in answers])
    out = []
    for (_, w), ref in zip(answers, refs):
        eff = _norm(_sub(full, ref))
        d = _norm(_sub(w, ref))
        out.append(d / eff if eff > 0 and math.isfinite(d) else math.inf)
    return out


def control_answers(run, precision: str):
    """The control: the run's answers as the plain reference computes them
    at `precision` (the next lower one), put in the program's place."""
    _, ctl = reference_answers(run, [r for r, _ in run.answers], precision)
    return [(r, w) for (r, _), w in zip(run.answers, ctl)]


def compare(run, answers=None) -> List[Dict]:
    """Every number compared, each with its limit and whether it holds.
    `answers` replaces the run's own (the control's, in the tests)."""
    answers = run.answers if answers is None else answers
    failed = {"name": "failed_requests", "value": run.failed, "limit": 0,
              "ok": run.failed == 0}
    if not answers:
        return [failed, {"name": "answers_checked", "value": 0, "limit": 1,
                         "ok": False}]
    g = gaps(run, answers)
    worst = max(g)
    lim = float(run.cell.limits["unlearn_gap"]["limit"])
    return [
        failed,
        {"name": "answers_checked", "value": len(g), "limit": 1,
         "ok": len(g) >= 1},
        {"name": "unlearn_gap", "value": worst, "limit": lim,
         "ok": math.isfinite(worst) and worst <= lim},
    ]
