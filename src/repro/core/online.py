"""DeltaGrad online deletion/addition — paper Algorithm 3 (Appendix C.2).

Requests arrive one at a time (GDPR-style streams).  After each request the
optimization-path cache is REWRITTEN in place so the next request corrects
the *previous DeltaGrad path* rather than the original training run:

  explicit steps:  w_t <- w^I_t,  g_t <- exact mean gradient of the current
                   (post-request) objective at w^I_t;
  approx steps:    w_t <- w^I_t,  g_t <- g^a_t, the approximated gradient
                   (paper eq. (S62)) — this is what keeps per-request cost
                   independent of how many requests came before.

The minibatch schedule is always replayed against the ORIGINAL dataset
numbering; cumulative deletions shrink each batch's effective size
``B_t(k) = B - |batch_t ∩ R_k|`` (paper's n-k bookkeeping), and rows
appended by earlier ADDITION requests extend each batch through their
precomputed, prefix-stable join masks (``data.sampler``).  Heavy-ball
histories are supported: each request reconstructs the velocity from
``vel_0 = 0`` while replaying, so the cache keeps storing plain gradients.

`OnlineEngine` owns the stream state (liveness over original AND added
rows, added-row join masks, the request-invariant device schedule) and
serves every request flavor — delete or add, single row or a COALESCED
GROUP of rows (`request_group`, one replay for K requests — the
session planner's batching primitive), SGD or momentum — through
`core.engine.run_online_request`: approx segments execute under `lax.scan`
against the history served by a `core.store.HistoryStore` — fully resident
(stacked/device tiers, optionally mesh-sharded with psum-reduced
per-example gradients) or streamed per segment window from the offload
tiers (host/disk) — and rewrites land in batched flushes through
`store.commit` (an O(1) pointer swap for resident storage, a codec
write-back for streamed).  `impl="python"` selects
`_online_request_python`, a per-step oracle driving the SAME precomputed
`ReplaySchedule` through the same jitted step math, kept as the parity
reference.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.deltagrad import (DeltaGradConfig, Objective, RetrainStats,
                                  _next_pow2, _tree_zeros)
from repro.core.engine import (SKIP, EXPLICIT, _online_approx_step,
                               _online_explicit_math, _ring_append,
                               _scan_pred, build_plan, run_online_request)
from repro.core.history import TrainingHistory
from repro.core.store import (HistoryStore, PlacementPolicy,
                              make_psum_grad_fn)
from repro.data.dataset import Dataset
from repro.data.sampler import (ReplaySchedule, addition_mask_all,
                                batch_indices_all, build_online_schedule)
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace


@dataclass
class OnlineStats:
    per_request: List[RetrainStats] = field(default_factory=list)
    wall_time_s: float = 0.0
    # first-request trace/compile cost, measured by the engine's warm-up
    # request (0.0 when warm-up is off) — kept OUT of wall_time_s so stream
    # throughput numbers aren't dominated by tracing
    compile_time_s: float = 0.0

    @property
    def grad_examples(self) -> int:
        return sum(s.grad_examples for s in self.per_request)

    @property
    def grad_examples_baseline(self) -> int:
        return sum(s.grad_examples_baseline for s in self.per_request)

    @property
    def theoretical_speedup(self) -> float:
        return self.grad_examples_baseline / max(self.grad_examples, 1)


Request = Union[int, Tuple[str, int]]


class OnlineEngine:
    """Persistent Algorithm-3 request engine over one cached training run.

    Owns everything that outlives a single request: the replayed (T, B)
    index matrix (uploaded once), liveness over original and added rows,
    the added-row join masks (grown prefix-stably as adds arrive), and the
    extended index matrix whose add-columns pad to powers of two so the
    compiled segment shapes stay stable across a stream.  Both backends
    serve requests from the same `build_online_schedule` output, so they
    see identical per-step row sets, weights, and learning rates.
    """

    def __init__(self, objective: Objective, history: TrainingHistory,
                 ds: Dataset, cfg: DeltaGradConfig, warmup=False,
                 add_capacity: int = 0,
                 placement: Optional[PlacementPolicy] = None,
                 store: Optional[HistoryStore] = None):
        self.objective = objective
        self.history = history
        self.ds = ds
        self.cfg = cfg
        # preallocated add-column block: sizing it for the expected number
        # of additions up front keeps the extended-schedule width (and so
        # every compiled segment shape) constant across the stream
        self.add_capacity = int(add_capacity)
        self.grad_fn = objective.make_grad_fn()
        meta = history.meta
        # every tier runs the compiled path: offload tiers stream segment
        # windows through core.store.SegmentStreamer; only an explicit
        # impl="python" selects the per-step oracle
        self.impl = "python" if cfg.impl == "python" else "scan"
        self.idx_all = batch_indices_all(meta.seed, meta.steps, meta.n,
                                         meta.batch_size)
        # Rows already deleted (by an earlier online stream over this same
        # rewritten history, or by a batch replay) stay masked out of the
        # replayed batches: the schedule then matches the CURRENT dataset.
        # If the cache was not rewritten for some of those deletions (batch
        # `deltagrad_retrain` does not rewrite), the first requests' explicit
        # steps serve as catch-up corrections — they evaluate exact
        # current-objective gradients and rewrite the cache toward
        # consistency, which is how Algorithm 3 absorbs any cache/objective
        # mismatch.  Rows added by an EARLIER engine instance cannot be
        # recovered from the dataset alone; reuse one OnlineEngine per
        # rewritten history (as `core.api.Unlearner` does) to keep their
        # join columns alive.
        self.live = ~np.asarray(ds.removed, dtype=bool)
        self.added: List[int] = []
        self._joins = None  # (T, capacity) bool, prefix-stable columns
        self.params = history.final_params
        self.compile_time_s = 0.0
        # the last served request's L-BFGS pair ring — snapshot state only
        # (every request rebuilds its ring from the rewritten path)
        self.last_ring = None
        # pow2-bucketed device-row capacity: appends within the bucket keep
        # every compiled shape put; outgrowing it bumps to the next pow2,
        # so an addition stream re-traces O(log #adds) times, not per add
        self._base_n = ds.n
        self._row_cap = ds.n + (_next_pow2(self.add_capacity)
                                if self.add_capacity else 0)
        self.store: Optional[HistoryStore] = None
        self._seg_grad_fn = None
        if self.impl == "scan":
            self.store = store if store is not None else HistoryStore.create(
                history, placement=placement, window=cfg.stream_window,
                decode=cfg.stream_decode)
            runner = self.store.sharded_replay()
            if runner is not None:
                self._seg_grad_fn = make_psum_grad_fn(
                    objective, runner.placement.data_axis)
            self._lr_dev = jnp.asarray(
                [meta.lr_at(t) for t in range(meta.steps)], jnp.float32)
            self._idx_dev = None  # uploaded lazily, re-used across requests
            self._idx_ver = None  # (len(added), width) of the upload
            if warmup:  # True, or an iterable of op flavors to precompile
                self._warmup(("delete",) if warmup is True else tuple(warmup))

    # -- stream state ------------------------------------------------------

    @property
    def _add_pad(self) -> int:
        need = max(len(self.added), self.add_capacity)
        return _next_pow2(need) if need else 0

    def _ensure_joins(self, n_cols: int) -> None:
        if n_cols and (self._joins is None
                       or self._joins.shape[1] < n_cols):
            meta = self.history.meta
            self._joins = addition_mask_all(
                meta.seed, meta.steps, meta.n, meta.batch_size,
                _next_pow2(n_cols))

    def _schedule(self, op: str, rows: Sequence[int]) -> ReplaySchedule:
        meta = self.history.meta
        K = len(rows)
        self._ensure_joins(len(self.added) + (K if op == "add" else 0))
        if op == "delete":
            # per-step changed count is bounded by the minibatch overlap
            # (<= B original rows) plus the group's previously-added rows,
            # so cap the pad like the batch path's min(r, B) — a K >> B
            # group must not widen every step's changed block to K
            n_added_in = len(set(rows) & set(self.added)) if self.added \
                else 0
            r_eff = min(K, min(meta.batch_size, meta.n) + n_added_in)
        else:
            r_eff = K  # add groups carry all K rows in the changed block
        return build_online_schedule(
            meta.seed, meta.steps, meta.n, meta.batch_size, rows, op,
            meta.lr_at, self.live, np.asarray(self.added, np.int64),
            self._joins, self._add_pad, idx_all=self.idx_all,
            r_pad=_next_pow2(r_eff))

    def _cols(self):
        """Device columns at the bucketed row capacity (see `_row_cap`).

        The cap honors a RAISED ``add_capacity`` (e.g. `begin_plan` sizing
        a whole flush, or the serving tier pre-staging its admission
        budget) — not just rows already appended — so staging happens once
        up front instead of as a mid-flush retrace on the first add
        burst.  Admission-side accounting (`repro.serve`) counts pending
        adds against this same bucket, padding included."""
        need = max(len(self.added), self.add_capacity)
        cap = self._base_n + (_next_pow2(need) if need else 0)
        if cap > self._row_cap:
            self._row_cap = cap
        if self.ds.n > self._row_cap:
            self._row_cap = self._base_n + _next_pow2(self.ds.n
                                                      - self._base_n)
        return self.ds.device_columns(capacity=self._row_cap)

    def _static_dev(self, sched: ReplaySchedule):
        """(idx, lr) on device, re-uploaded only when the added set grows or
        the padded schedule width changes (e.g. add_capacity was raised)."""
        key = (len(self.added), sched.idx.shape[1])
        if self._idx_ver != key or self._idx_dev is None:
            self._idx_dev = jnp.asarray(sched.idx, jnp.int32)
            self._idx_ver = key
        return self._idx_dev, self._lr_dev

    def _warmup(self, ops=("delete",)) -> None:
        """Trace + compile the request programs on throwaway requests (one
        per flavor the stream will serve — the compiled programs key on the
        request sign AND the pow2-bucketed group width, so `ops` entries
        are op names or ``(op, group_size)`` pairs).

        `run_online_request` with ``commit=False`` never lands its rewrites,
        so discarding its outputs leaves no trace; the measured time is the
        first-request compile cost reported as
        `OnlineStats.compile_time_s`."""
        live_rows = np.flatnonzero(self.live[:self.history.meta.n])
        if live_rows.size == 0:
            return
        t0 = time.perf_counter()
        with obs_trace.span("online.warmup", ops=len(ops)):
            for spec in ops:
                op, k = spec if isinstance(spec, tuple) else (spec, 1)
                k = int(min(k, live_rows.size))
                # existing live rows stand in for appended ones in add mode:
                # the schedule only needs gatherable row ids + the next free
                # join-mask columns
                sched = self._schedule(op, [int(r) for r in live_rows[:k]])
                out = run_online_request(self.grad_fn, self.store,
                                         self._cols(), sched, self.cfg,
                                         static_dev=self._static_dev(sched),
                                         seg_grad_fn=self._seg_grad_fn,
                                         commit=False)
                jax.block_until_ready(out[0])
        self.compile_time_s = time.perf_counter() - t0
        obs_metrics.get_registry().gauge(
            "online.compile_time_s", unit="s",
            owner="core.online").set(self.compile_time_s)

    # -- request serving ---------------------------------------------------

    def request(self, op: str, row: int) -> RetrainStats:
        """Serve one delete/add request, rewriting history + bookkeeping."""
        return self.request_group(op, [int(row)])

    def request_group(self, op: str, rows: Sequence[int]) -> RetrainStats:
        """Serve a COALESCED group of same-op requests as ONE replay.

        Group deletion applies the paper's index-set semantics (Algorithm 1
        with R = `rows`) to the current rewritten path, rewriting history
        once; group addition joins every new row through its own mask
        column in the same single replay.  K sequential replays collapse to
        one — per-request cost drops ~Kx — at the price of a path that is
        the GROUP correction, not the composition of K single-request
        corrections (both approximate the same leave-R-out model; see
        core.session for the serving-semantics contract)."""
        assert op in ("delete", "add"), op
        rows = [int(r) for r in rows]
        assert len(rows) == len(set(rows)), f"duplicate rows in {rows}"
        if max(rows) >= len(self.live):  # dataset grew since construction
            grown = np.ones(self.ds.n, dtype=bool)
            grown[:len(self.live)] = self.live
            self.live = grown
        if op == "delete":
            for row in rows:
                assert self.live[row], f"row {row} already deleted"
        else:
            for row in rows:
                assert self.history.meta.n <= row < self.ds.n, (
                    "add requests name rows appended AFTER the cached "
                    f"training run (expected {self.history.meta.n} <= row < "
                    f"{self.ds.n}, got {row}) — an original row would be "
                    "double-counted")
                assert row not in self.added, f"row {row} already added"
        with obs_trace.span("online.schedule_build", op=op, k=len(rows)):
            sched = self._schedule(op, rows)

        # whole-replay roofline lower bound (None — and not computed —
        # while tracing is off); the tracer stamps the measured wall and
        # ratio onto the span at exit
        pred = _scan_pred(
            sum(x.size for x in jax.tree.leaves(self.params)),
            self.history.meta.steps, sched.r_pad, self.cfg.history_size,
            bool(self.history.meta.momentum)) if obs_trace.enabled() \
            else None
        with obs_trace.span("online.request", op=op, k=len(rows),
                            pred_s=pred):
            if self.impl == "scan":
                # the store commits the rewrites into the history per
                # request (O(1) pointer swap for resident storage, codec
                # write-back for streamed tiers) so dataset bookkeeping and
                # the rewritten cache never diverge even if a later request
                # dies mid-stream
                params, rstat = run_online_request(
                    self.grad_fn, self.store, self._cols(), sched, self.cfg,
                    static_dev=self._static_dev(sched),
                    seg_grad_fn=self._seg_grad_fn)
            else:
                params, rstat = _online_request_python(
                    self.grad_fn, self.history, self.ds, sched, self.cfg)
                self.history.finalize(params)
        ring = rstat.extra.pop("lbfgs_ring", None)
        if ring is not None:
            self.last_ring = ring

        if op == "delete":
            for row in rows:
                self.live[row] = False
                self.ds.removed[row] = True
        else:
            self.added.extend(rows)
        self.params = params
        return rstat

    # -- snapshot / restore (core.session.save/restore) --------------------

    def state_dict(self) -> Dict[str, Any]:
        """Stream state that cannot be rebuilt from the dataset alone:
        liveness over original AND added rows, the added-row arrival order
        (join-mask column assignment), staged capacities, and the last
        request's L-BFGS pair ring (recorded for completeness — rings are
        rebuilt from the rewritten path on every request, so restore does
        not feed it back into the math)."""
        state = {
            "live": np.asarray(self.live, dtype=bool).copy(),
            "added": list(self.added),
            "add_capacity": int(self.add_capacity),
            "base_n": int(self._base_n),
            "row_cap": int(self._row_cap),
            "lbfgs_ring": None,
        }
        if self.last_ring is not None:
            state["lbfgs_ring"] = jax.device_get(self.last_ring)
        return state

    def load_state(self, state: Dict[str, Any]) -> None:
        self.live = np.asarray(state["live"], dtype=bool).copy()
        self.added = list(state["added"])
        self.add_capacity = int(state["add_capacity"])
        self._base_n = int(state.get("base_n", self.ds.n))
        self._row_cap = max(int(state.get("row_cap", self.ds.n)), self.ds.n)
        ring = state.get("lbfgs_ring")
        self.last_ring = (jax.tree.map(jnp.asarray, ring)
                          if ring is not None else None)
        self._joins = None
        self._ensure_joins(len(self.added))
        if self.impl == "scan":
            self._idx_dev = self._idx_ver = None


def online_deltagrad(
    objective: Objective,
    history: TrainingHistory,
    ds: Dataset,
    requests: Sequence[Request],
    cfg: DeltaGradConfig,
    mode: str = "delete",
    warmup: bool = False,
    placement: Optional[PlacementPolicy] = None,
) -> Tuple[Any, OnlineStats]:
    """Process deletion/addition requests sequentially, rewriting history.

    `requests` is either a sequence of row indices (all treated as `mode`)
    or a sequence of ``(op, row)`` pairs for mixed delete/add streams.  For
    additions, rows must already be appended to `ds` (``ds.n`` >
    ``history.meta.n``); each joins the replayed batches through the
    deterministic `addition_mask` of `data.sampler`, matching the inclusion
    probability of original samples.  `warmup=True` runs (and times) a
    throwaway first request so `OnlineStats.compile_time_s` absorbs the
    trace/compile cost and `wall_time_s` measures the warm stream only.
    """
    assert mode in ("delete", "add")
    requests = list(requests)
    ops = [r[0] if isinstance(r, (tuple, list)) else mode for r in requests]
    n_adds = ops.count("add")
    engine = OnlineEngine(objective, history, ds, cfg,
                          warmup=sorted(set(ops)) if warmup else False,
                          add_capacity=n_adds, placement=placement)
    stats = OnlineStats(compile_time_s=engine.compile_time_s)
    t_start = time.perf_counter()
    for r in requests:
        op, row = r if isinstance(r, (tuple, list)) else (mode, r)
        t_req = time.perf_counter()
        rstat = engine.request(op, int(row))
        # host-side dispatch wall per request (no added device sync:
        # compile happens synchronously at trace time, so a cache-miss
        # first request shows up here and bench_online can report it
        # separately from the steady per-request cost)
        rstat.extra["dispatch_wall_s"] = time.perf_counter() - t_req
        stats.per_request.append(rstat)
    jax.block_until_ready(engine.params)
    stats.wall_time_s = time.perf_counter() - t_start
    return engine.params, stats


def _online_request_python(grad_fn, history, ds, sched: ReplaySchedule,
                           cfg) -> Tuple[Any, RetrainStats]:
    """Per-step oracle: one request driven from the host over the SAME
    precomputed schedule and jitted step math as the scan path (additions,
    momentum, offload tiers, and the parity reference)."""
    meta = history.meta
    op = sched.mode
    sign = 1 if op == "delete" else -1
    momentum = bool(meta.momentum)
    plan = build_plan(cfg, sched, online=True)
    params = history.params_at(0)
    vel = _tree_zeros(params) if momentum else None
    mom = jnp.float32(meta.momentum)
    clip = jnp.float32(cfg.guard_norm_clip)
    stats = RetrainStats()
    # zeros-initialized device pair ring, mirroring the scan path's
    # `_ring_append` / masked-solve semantics exactly (the same jitted
    # admission + compact solve, with slot occupancy derived FROM the ring,
    # so parity holds at ANY fill level — including a partially-filled
    # ring during burn-in)
    dWs = jax.tree.map(
        lambda x: jnp.zeros((cfg.history_size,) + x.shape, x.dtype), params)
    dGs = dWs
    ring_started = False
    eps = jnp.float32(cfg.curvature_eps)

    def changed_grad(t):
        has = jnp.float32(1.0 if sched.dB[t] > 0 else 0.0)
        g = grad_fn(params, ds.take(sched.changed_idx[t]),
                    jnp.asarray(sched.changed_w[t]))
        return jax.tree.map(lambda x: has * x, g)

    for t in range(meta.steps):
        code = plan[t]
        if code == SKIP:
            stats.skipped_steps += 1
            continue
        kept = jnp.float32(sched.kept[t])
        dB = jnp.float32(sched.dB[t])
        lr = jnp.float32(meta.lr_at(t))
        w_t, g_t = history.entry(t)
        explicit = code == EXPLICIT or not ring_started
        g_one = None

        if not explicit:
            g_one = changed_grad(t)
            stats.grad_examples += int(sched.dB[t])
            new_p, new_vel, g_new, ok = _online_approx_step(
                params, vel, w_t, g_t, dWs, dGs, g_one, lr, kept, dB, clip,
                mom, sign=sign, momentum=momentum)
            if cfg.guard and not bool(ok):
                stats.guard_fallbacks += 1
                explicit = True  # g_one is reused — true cost kept + dB
            else:
                history.overwrite(t, params, g_new)
                params, vel = new_p, new_vel
                stats.approx_steps += 1

        if explicit:
            g_base = grad_fn(params, ds.take(sched.idx[t]),
                             jnp.asarray(sched.kept_w[t]))
            if g_one is None:
                g_one = changed_grad(t)
                stats.grad_examples += int(sched.dB[t])
            stats.grad_examples += int(sched.kept[t])
            p_in = params
            params, vel, g_cur, dw, dg, admit = _online_explicit_math(
                params, vel, w_t, g_t, g_base, g_one, lr, kept, dB, mom,
                sign=sign, momentum=momentum)
            dWs, dGs = _ring_append(dWs, dGs, dw, dg, admit, eps)
            ring_started = True
            history.overwrite(t, p_in, g_cur)
            stats.explicit_steps += 1

    base = sched.kept.astype(np.int64)
    if op == "add":
        base = base + sched.dB.astype(np.int64)
    stats.grad_examples_baseline = int(base.sum())
    if ring_started:  # see run_online_request: snapshot state for sessions
        stats.extra["lbfgs_ring"] = (dWs, dGs)
    return params, stats
