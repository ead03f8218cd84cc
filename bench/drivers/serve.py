"""Open-loop delete traffic through the program's serving tier.

Requests go to `repro.serve.ServingScheduler` (admission, SLA-class EDF
flushes, cross-tenant coalescing) over an `UnlearnerSession` whose
`OnlineEngine` serves each batch as one Algorithm-3 group replay and
rewrites the recorded path.  The generator runs in this thread and sleeps
until each request is due; the scheduler's executor thread serves.

Forget latency is timed from when a request was DUE until its batch's
parameters were published (the executor stamps ``t_done`` after
``block_until_ready``).  A request that was refused, failed, or did not
finish within DRAIN_S of the window's close counts as missing every
limit (an infinite latency).
"""

from __future__ import annotations

import sys
import time

import numpy as np

DRAIN_S = 60.0  # how long past the window's close a request may still finish


def _pow2_widths(limit: int):
    k, out = 1, []
    while k < limit:
        out.append(k)
        k *= 2
    out.append(k)
    return out


def setup(run):
    import jax

    from bench.harness import loadgen
    from bench.harness.core import seed_key
    from repro.serve import ServeConfig, ServingScheduler, SLAClass

    cell, tr = run.cell, run.cell.traffic
    inputs = cell.model.make_inputs(cell.config, seed_key(run.seed))
    jax.block_until_ready(inputs)
    sess = cell.model.session(cell.config, inputs, run.seed)
    run.mark("inputs")
    jax.block_until_ready(sess.fit())
    run.mark("fit")
    sla = tr["sla"]
    scfg = ServeConfig(
        classes=(SLAClass(sla["name"], deadline_s=float(sla["deadline_s"]),
                          hold_s=float(sla["hold_s"])),),
        max_batch=int(tr["serve"]["max_batch"]),
        max_depth=int(tr["serve"]["max_depth"]),
        tenant_max_pending=tr["serve"].get("tenant_max_pending"),
        on_full="reject")
    sched = ServingScheduler(sess, scfg)
    n = cell.model.n_rows(cell.config)
    # every pow2 group width a batch can form (the engine pads a group's
    # changed rows to the next pow2)
    widest = min(int(tr["serve"]["max_batch"])
                 * int(tr["request_rows"].get("max", tr["request_rows"]
                                              .get("rows", 1))), n)
    sess.warmup([("delete", k) for k in _pow2_widths(widest)])
    run.mark("group widths")
    reqs = loadgen.trace(tr, run.seconds, run.seed)
    live = ~np.asarray(sess.dataset.removed, dtype=bool)
    # the first rows of the permutation serve the warm-up request (it
    # compiles the path's commit), the rest the window
    warm = loadgen.Request(due_s=0.0, tenant="warmup", op="delete",
                           n_rows=1)
    loadgen.materialize([warm] + reqs, live, run.seed)
    sched.start()
    t = sched.submit(op="delete", rows=warm.rows, tenant=warm.tenant,
                     sla_class=sla["name"])
    t.wait(timeout=600)
    run.mark("warm-up")
    run.data.update(ref_inputs=cell.model.reference_args(inputs),
                    **cell.model.shape_counts(cell.config),
                    n_rows=n)
    # the requests whose published answers are checked: a seeded sample
    # across the whole window (the last answer joins them at the close)
    k = int(tr.get("check", {}).get("samples", 4))
    rng = np.random.default_rng(np.random.SeedSequence([run.seed, 0xC4EC]))
    pick = set(rng.choice(len(reqs), size=min(k - 1, len(reqs)),
                          replace=False).tolist())
    return {"sess": sess, "sched": sched, "reqs": reqs, "sla": sla["name"],
            "warm": t, "pick": pick}


def window(run, st):
    from repro.serve import RetryAfter

    sched, reqs, pick = st["sched"], st["reqs"], st["pick"]
    held, watch = {}, []
    t0 = time.monotonic()
    sent = []  # (request, ticket or None, submit time)
    for i, rq in enumerate(reqs):
        due = t0 + rq.due_s
        wait = due - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        ts = time.monotonic()
        try:
            tk = sched.submit(op="delete", rows=rq.rows, tenant=rq.tenant,
                              sla_class=st["sla"])
        except RetryAfter:
            tk = None
        sent.append((rq, tk, ts))
        if tk is not None and i in pick:
            watch.append(tk)
        if watch:
            _capture(st["sess"], watch, held)
    st.update(t0=t0, sent=sent, held=held, watch=watch,
              t_close=max(t0 + run.seconds, time.monotonic()))


def _capture(sess, watch, held):
    """Keep a reference to the published parameters of each watched
    request that has resolved, while the session still holds its response
    (it keeps its last ``max_responses``).  No copy, no sync."""
    for tk in [tk for tk in watch if tk.req.done.is_set()]:
        watch.remove(tk)
        params = _published(sess, tk.req)
        if params is not None:
            held[id(tk)] = params


def _published(sess, q):
    if q.error is not None or not q.rows:
        return None
    first = q.rows[0]  # every request's rows are disjoint from the others'
    for resp in reversed(list(sess._responses.values())):
        if resp.request.rows and resp.request.rows[0] == first:
            return resp.params
    return None


def finish(run, st):
    import jax

    sched, sess, sent, t0 = st["sched"], st["sess"], st["sent"], st["t0"]
    held, watch = st["held"], st["watch"]
    until = st["t_close"] + DRAIN_S
    for _, tk, _ in sent:
        if tk is None:
            continue
        while (not tk.req.done.wait(0.005)
               and time.monotonic() < until):
            _capture(sess, watch, held)
        _capture(sess, watch, held)
    sched.stop()
    lat, late, qwait = [], [], []
    for rq, tk, ts in sent:
        due = t0 + rq.due_s
        late.append(ts - due)
        q = tk.req if tk is not None else None
        if q is None or not q.done.is_set() or q.error is not None:
            lat.append(np.inf)
            run.failed += 1
            continue
        lat.append(q.t_done - due)
        qwait.append(q.t_dispatch - due)
    lat = np.asarray(lat)
    run.attempted = len(sent)
    run.e2e["forget_p50_ms"] = float(np.percentile(lat, 50) * 1e3)
    run.e2e["forget_p95_ms"] = float(np.percentile(lat, 95) * 1e3)
    # per batch: rows coalesced and dispatch -> published
    batches = {}
    for _, tk, _ in sent:
        if tk is not None and tk.req.batch_id is not None:
            b = batches.setdefault(tk.req.batch_id, [0, 0.0])
            b[0] += tk.req.n_rows
            b[1] = tk.req.t_done - tk.req.t_dispatch
    service = [b[1] for b in batches.values()]
    run.data.update(
        queue_wait_s=qwait, batch_rows=[b[0] for b in batches.values()],
        service_s=service)
    print(f"bench: generator lateness p50 {float(np.percentile(late, 50))!r}"
          f" s, max {float(max(late))!r} s over {len(late)} requests; "
          f"{len(batches)} batches, service median "
          f"{float(np.median(service)) if service else 0.0!r} s, max "
          f"{float(max(service, default=0.0))!r} s", file=sys.stderr,
          flush=True)
    _answers(run, st)
    # free the program's state before the reference runs
    st.clear()
    jax.clear_caches()


def _answers(run, st):
    """The sampled requests' published parameters, and the last batch's,
    each with the rows of every request the benchmark sent that was
    published by then (batches publish one at a time, in batch-id order)."""
    import jax

    sess, held = st["sess"], st["held"]
    served = [tk.req for tk in [st["warm"]] + [tk for _, tk, _ in st["sent"]]
              if tk is not None and tk.req.done.is_set()
              and tk.req.error is None and tk.req.batch_id is not None]
    if not served:
        return
    picked = [(tk.req, held[id(tk)]) for _, tk, _ in st["sent"]
              if tk is not None and id(tk) in held]
    last = max(served, key=lambda q: q.batch_id)
    if all(q.batch_id != last.batch_id for q, _ in picked):
        params = _published(sess, last)
        if params is not None:
            picked.append((last, params))
    missed = len(st["pick"]) - len(held)
    if missed > 0:
        print(f"bench: {missed} sampled answers were not held",
              file=sys.stderr, flush=True)
    answers = []
    for q, params in sorted(picked, key=lambda a: a[0].batch_id):
        rm = np.asarray([r for s in served if s.batch_id <= q.batch_id
                         for r in s.rows], dtype=np.int64)
        answers.append((rm, jax.tree.map(np.asarray, params)))
    run.answers = answers
