"""Closed-loop leave-group-out replays against one recorded path.

Set-up trains once with the path recorded (`UnlearnerSession.fit`).  The
window then runs back-to-back independent Algorithm-1 replays
(`deltagrad_retrain`, the program's batch entry point) through one reused
`HistoryStore`; each removes a group of rows drawn from the seed.  Replays
do not rewrite the path, so the data is never depleted.

``rows_per_s`` counts the rows of whole replays that finished and were
published (``block_until_ready``), over the time from the window's start to
the last replay's completion.  The last replay started inside the window
runs to its end.
"""

from __future__ import annotations

import sys
import time

import numpy as np


def _groups(run, n: int):
    rows = int(run.cell.traffic["group_rows"])
    rng = np.random.default_rng(np.random.SeedSequence([run.seed, 0x6209]))
    while True:
        yield np.sort(rng.choice(n, size=rows, replace=False))


def setup(run):
    import jax

    from bench.harness.core import seed_key
    from repro.core.store import HistoryStore

    cell = run.cell
    inputs = cell.model.make_inputs(cell.config, seed_key(run.seed))
    jax.block_until_ready(inputs)
    sess = cell.model.session(cell.config, inputs, run.seed)
    run.mark("inputs")
    jax.block_until_ready(sess.fit())
    run.mark("fit")
    cfg = sess.config.deltagrad
    store = HistoryStore.create(sess.history, window=cfg.stream_window,
                                decode=cfg.stream_decode)
    n = cell.model.n_rows(cell.config)
    groups = _groups(run, n)
    st = {"sess": sess, "store": store, "cfg": cfg, "groups": groups}
    # warm-up replay: compiles every program the window runs
    _replay(st, next(groups))
    run.mark("warm-up")
    run.data.update(ref_inputs=cell.model.reference_args(inputs),
                    **cell.model.shape_counts(cell.config), n_rows=n)
    return st


def _replay(st, rows):
    import jax

    from repro.core.deltagrad import deltagrad_retrain

    sess = st["sess"]
    w, stats = deltagrad_retrain(sess.objective, sess.history, sess.dataset,
                                 rows, st["cfg"], store=st["store"])
    jax.block_until_ready(w)
    return w, stats


def window(run, st):
    import jax

    k = int(run.cell.traffic.get("check", {}).get("samples", 3))
    rng = np.random.default_rng(np.random.SeedSequence([run.seed, 0xC4EC]))
    t0 = time.monotonic()
    done = []       # (t_end, rows, stats, wall_s, this thread's cpu_s)
    keep = []       # reservoir of (index, rows, params), k - 1 slots
    last = None
    i = 0
    while time.monotonic() < t0 + run.seconds:
        rows = next(st["groups"])
        ta, ca = time.monotonic(), time.thread_time()
        with jax.profiler.TraceAnnotation("bench.replay"):
            w, stats = _replay(st, rows)
        tb = time.monotonic()
        done.append((tb, len(rows), stats, tb - ta, time.thread_time() - ca))
        # seeded reservoir sample of earlier answers; the last is kept apart
        if last is not None:
            j = i - 1
            if len(keep) < k - 1:
                keep.append(last)
            elif (s := int(rng.integers(0, j + 1))) < k - 1:
                keep[s] = last
        last = (i, rows, w)
        i += 1
    st.update(t0=t0, done=done, keep=keep, last=last)


def finish(run, st):
    import jax

    done, t0 = st["done"], st["t0"]
    run.attempted = len(done)
    rows = sum(d[1] for d in done)
    run.e2e["rows_per_s"] = rows / (done[-1][0] - t0)
    stats = [d[2] for d in done]
    run.data.update(
        replays=len(done), rows=rows, window_s=done[-1][0] - t0,
        replay_rows=[d[1] for d in done],
        explicit_steps=sum(s.explicit_steps for s in stats),
        approx_steps=sum(s.approx_steps for s in stats),
        guard_fallbacks=sum(s.guard_fallbacks for s in stats))
    d = run.data
    wall = [x[3] for x in done]
    slow = max(range(len(done)), key=lambda i: wall[i])
    print(f"bench: {d['replays']} replays in {d['window_s']!r} s; "
          f"{d['explicit_steps']} explicit + {d['approx_steps']} approx "
          f"steps, {d['guard_fallbacks']} guard fallbacks; replay median "
          f"{float(np.median(wall))!r} s, slowest {wall[slow]!r} s (this "
          f"thread on the CPU {done[slow][4]!r} s of it, #{slow})",
          file=sys.stderr, flush=True)
    picked = sorted(st["keep"] + [st["last"]], key=lambda a: a[0])
    run.answers = [(r, jax.tree.map(np.asarray, w)) for _, r, w in picked]
    st.clear()
    jax.clear_caches()
