"""UnlearnerSession — the request-plan serving surface for DeltaGrad.

The paper's headline use case is answering *streams* of deletion/addition
requests far cheaper than retraining; follow-up work (Descent-to-Delete,
Neel et al. 2020; Mahadevan & Mathioudakis 2021) frames unlearning
explicitly as an online service.  This module is that service's API:

    sess = UnlearnerSession(objective, params0, dataset, UnlearnerConfig())
    sess.fit()                              # train once, caching the path
    h = sess.delete([3, 17, 256])           # returns a lazy RequestHandle
    sess.add(data={"x": new_x, "y": new_y})
    h.result().stats                        # force: flush + block
    sess.save("ckpt/"); UnlearnerSession.restore("ckpt/", objective)

Design:

  * REQUEST PLAN.  `submit()` enqueues typed `UnlearnRequest`s and returns
    lightweight `RequestHandle`s that resolve lazily — nothing executes
    (and nothing host-syncs) until a handle is forced via `.result()` /
    `.params`, or `flush()` runs.  Batch and stream semantics are unified:
    every request — bursty or one-at-a-time — is served by the session's
    ONE `core.online.OnlineEngine`, which rewrites the cached path after
    each replay, so interleaving "batch" deletes with "online" streams is
    well-defined instead of silently discarding engine state (the
    pre-session `Unlearner` footgun).

  * COALESCING PLANNER.  At flush, maximal runs of adjacent same-op
    requests with ``coalesce=True`` merge into ONE engine replay using the
    paper's group-deletion semantics (Algorithm 1 with an index set,
    applied to the current rewritten path): K pending deletes cost one
    T-step replay instead of K.  Serving-semantics contract: the coalesced
    result is the GROUP correction for the K rows — it approximates the
    same leave-K-out model as K sequential Algorithm-3 single-request
    corrections, but is not bitwise the serial composition (both land
    within the method's approximation error of exact retraining; the
    serial path remains available via ``coalesce=False`` and the
    ``stream_*`` helpers, and scan-vs-python parity holds for either).
    Changed-row blocks pad to the next pow2 of the burst size, so burst
    sizes bucket into O(log) distinct compiled shapes.

  * BUCKETED ADD CAPACITY.  The engine uploads device columns at a
    pow2-bucketed row capacity (`Dataset.device_columns(capacity=...)`),
    so an addition stream that outgrows the staged pool re-traces O(log
    #adds) times instead of once per appended row.

  * SNAPSHOT/RESTORE.  `save()` writes params through `train/checkpoint`
    (sharded .npz + atomic manifest) with the `TrainingHistory` state (all
    tiers), dataset columns + deletion mask, the ALGORITHM DESCRIPTOR
    (name + algorithm state, e.g. the engine's liveness/added-row
    order/capacities/L-BFGS ring, or descent-to-delete's contraction
    bound) and the session PRNG key in the checkpoint's extra payload.
    `restore()` rebuilds a session that serves the next request — and the
    next certified `publish()` — with results identical to the
    uninterrupted one.  Objectives hold code, not state, so the caller
    passes the objective to `restore()`.

ALGORITHM SELECTION (``UnlearnerConfig.algorithm``) — every entry in
`core.algorithms`'s registry serves through this same session surface:

  * ``"deltagrad"`` (default) — the paper's Algorithm 3: L-BFGS-corrected
    replay of the cached path.  Per-request cost ~ the explicit steps'
    gradients only; answers track exact retraining to within the paper's
    approximation error.  Choose it when requests trickle in and the
    cached path is warm — it is the low-latency path this repo exists
    for.  Certificate: Laplace ε-approximate deletion from the §5.1 δ0
    bound (δ = 0); the bound needs r ≪ n and strong convexity, and
    `certificate()` raises once cumulative removals push δ0's
    denominator negative.
  * ``"descent_to_delete"`` — noisy projected fine-tuning (Neel et al.
    2020): I full-batch steps from the current params per request group,
    Gaussian noise at publication.  Cost is independent of the training
    length T, so it wins on wall-clock whenever T-step replay (or
    retraining) is the alternative and a weaker, (ε, δ)-style guarantee
    with contraction bound ρ^I(bound + Δ) suffices.  Needs strong
    convexity for the contraction (κ = L/μ finite).
  * ``"retrain_oracle"`` — exact retraining served through the same
    engine (all-explicit plan).  The reference everything else is
    certified against: ε = 0, bound = 0, publish is the identity.  Use
    it for ground truth, audits, and small problems where exactness is
    cheap.

  Certificate semantics: `certificate()` reports (mechanism, ε, δ,
  bound, noise_scale) where `bound` certifies ``||w_alg − w_retrain*||``
  under the algorithm's analysis; `publish()` draws the calibrated noise
  deterministically from the session-held PRNG key (split per call, so a
  restored session publishes bitwise-identically).  ALL bounds assume
  the strongly-convex regularized setting — for non-convex objectives
  the numbers are diagnostics, not guarantees (the paper's guard only
  protects the replay's stability, not the certificate).

SERVING TIER.  For multi-caller traffic — per-tenant admission control,
SLA-class deadlines instead of the single ``max_pending``/``max_delay_s``
pair, cross-tenant batching, and seeded load generation — put
`repro.serve.ServingScheduler` in front of the session (the serving guide
lives in ``repro/serve/__init__.py``).  The session-level auto-flush
policy remains for single-caller use; `AutoFlushTimer` is deprecated in
favor of `repro.serve.SessionFlushClock`.

`core.api.Unlearner` is a thin compatibility shim over this class.
"""

from __future__ import annotations

import threading
import time
import warnings
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.core.algorithms import (Certificate, DescentToDeleteConfig,
                                   UnlearningAlgorithm, get_algorithm)
from repro.core.deltagrad import (DeltaGradConfig, Objective, RetrainStats,
                                  baseline_retrain, sgd_train_with_cache)
from repro.core.history import HistoryMeta, TrainingHistory
from repro.core.online import OnlineEngine, OnlineStats
from repro.core.privacy import PrivacyConfig
from repro.core.store import PlacementPolicy
from repro.data.dataset import Dataset
from repro.obs import trace as obs_trace
from repro.train import checkpoint as ckpt


@dataclass
class UnlearnerConfig:
    steps: int = 100
    batch_size: int = 1 << 30  # default: deterministic full-batch GD
    lr: float = 0.1
    lr_schedule: Optional[Sequence] = None  # overrides lr if given
    seed: int = 0
    momentum: float = 0.0  # heavy-ball (beyond-paper; see HistoryMeta)
    deltagrad: DeltaGradConfig = field(default_factory=DeltaGradConfig)
    # None resolves to "stacked" (the engine's native tier, see core/engine),
    # or to "host" — the codec-honoring offload tier — when history_codec is
    # not "f32" (stacked storage is uncompressed by construction).  An
    # EXPLICIT "stacked" + lossy codec is rejected by TrainingHistory.
    # host/disk tiers are served to the compiled scan by
    # core.store.SegmentStreamer (device holds ~2 windows, never the path).
    history_tier: Optional[str] = None
    history_codec: str = "f32"
    spill_dir: Optional[str] = None
    # mesh placement for the cached path + replay (core.store.PlacementPolicy
    # — plain data, so save()/restore() round-trips it and the restoring
    # host rebuilds the mesh lazily); None = single-device
    placement: Optional["PlacementPolicy"] = None
    # auto-flush policy: bound how long a submitted request can sit pending
    # under continuous load.  max_pending: flush when that many requests are
    # queued (the coalescing planner then serves them as one burst);
    # max_delay_s: flush when the OLDEST pending request has waited this
    # long (checked at submit and via session.poll()).  None disables.
    max_pending: Optional[int] = None
    max_delay_s: Optional[float] = None
    # which registered unlearning algorithm serves requests — see the
    # module docstring's selection guide and core/algorithms.py
    algorithm: str = "deltagrad"
    # certified-deletion constants (ε/δ targets + regularity constants);
    # None resolves to PrivacyConfig() defaults at certificate time
    privacy: Optional[PrivacyConfig] = None
    # descent-to-delete knobs (finetune steps, lr, projection radius)
    descent: Optional[DescentToDeleteConfig] = None


@dataclass
class UnlearnRequest:
    """One typed unlearning request.

    op:       "delete" | "add".
    rows:     row ids — original or previously-added rows for delete;
              already-appended rows for add (filled in automatically when
              `data` is given).
    data:     add payload (dict of columns); appended to the dataset at
              submit time so later requests can reference the new rows.
    coalesce: True → the planner may merge this request with adjacent
              same-op requests into ONE group replay; False → serve each
              row as its own Algorithm-3 replay (paper-exact
              single-request semantics), never merged.
    """

    op: str
    rows: Optional[Sequence[int]] = None
    data: Optional[Dict[str, np.ndarray]] = None
    coalesce: bool = True


@dataclass
class UnlearnResponse:
    """Resolved outcome of one request.

    stats holds one `RetrainStats` per replay that served the request — a
    single entry when the request was coalesced into (or was itself) one
    group replay, len(rows) entries for a serial (`coalesce=False`)
    request.  `group_size` is the total number of rows the replay(s)
    coalesced (> len(request.rows) when neighbors merged in).
    `dispatch_s` is host dispatch time for the whole group; `params` is
    the post-request model (a device value — NOT host-synced; forcing a
    handle blocks on it).

    MIGRATION NOTE — ``stats.extra``: the untyped per-replay dict
    (``impl``, ``store``, ``windows``, ``hbm_high_water``, ...) remains
    for backward compatibility, but it is no longer the primary
    observability surface.  The engine, store, queue, and monitor now
    publish typed counters/gauges/histograms into the
    `repro.obs.metrics` registry (``get_registry().snapshot()``, JSONL
    and Prometheus exporters) and emit `repro.obs.trace` spans with
    roofline predicted-vs-measured cost — new consumers should read
    those (the full name contract is the table in ``repro/obs``)
    instead of string-keying into ``extra``."""

    request: UnlearnRequest
    stats: List[RetrainStats]
    group_size: int
    dispatch_s: float
    params: Any = None


class AutoFlushTimer:
    """DEPRECATED shim — the global auto-flush timer is superseded by the
    serving tier (`repro.serve`): `ServingScheduler` for per-SLA-class
    deadlines, or `SessionFlushClock` for the degenerate one-class case
    this timer implemented.  Constructing it warns and returns a
    `SessionFlushClock` (same ``ticks``/``last_error``/``interval_s``/
    ``stop()`` surface), so existing callers keep working."""

    def __new__(cls, session: "UnlearnerSession",
                interval_s: Optional[float] = None):
        warnings.warn(
            "core.session.AutoFlushTimer is deprecated; use "
            "repro.serve.SessionFlushClock (one default SLA class) or "
            "repro.serve.ServingScheduler (per-class deadlines)",
            DeprecationWarning, stacklevel=2)
        from repro.serve.scheduler import SessionFlushClock
        return SessionFlushClock(session, interval_s=interval_s)


class RequestHandle:
    """Lazy handle returned by `UnlearnerSession.submit`.

    Holding a handle costs nothing: the request executes when the session
    flushes (explicitly, or because some handle was forced).  `.result()`
    forces the flush and blocks until this request's post-request params
    are on host — the only sync point in the serving path."""

    def __init__(self, session: "UnlearnerSession", ticket: int,
                 request: UnlearnRequest):
        self._session = session
        self._ticket = ticket
        self.request = request

    @property
    def done(self) -> bool:
        """True once the request has been served (it may still be
        executing asynchronously on the device)."""
        return self._ticket in self._session._responses

    def result(self, block: bool = True) -> UnlearnResponse:
        resp = self._session._resolve(self._ticket)
        if block:
            jax.block_until_ready(resp.params)
        return resp

    @property
    def params(self):
        """Post-request model (forces resolution, blocks)."""
        return self.result().params

    @property
    def stats(self) -> List[RetrainStats]:
        return self.result(block=False).stats


def plan_requests(pending: List[Tuple[int, UnlearnRequest]]
                  ) -> List[List[Tuple[int, UnlearnRequest]]]:
    """The coalescing planner: partition pending requests, in submission
    order, into serving groups.  Maximal runs of adjacent same-op requests
    with ``coalesce=True`` merge into one group (one engine replay);
    ``coalesce=False`` requests form singleton groups and break runs, so
    an explicitly-serial request is never reordered past a burst."""
    groups: List[List[Tuple[int, UnlearnRequest]]] = []
    for ticket, req in pending:
        if (groups and req.coalesce
                and groups[-1][0][1].coalesce
                and groups[-1][0][1].op == req.op):
            groups[-1].append((ticket, req))
        else:
            groups.append([(ticket, req)])
    return groups


class UnlearnerSession:
    """Request-plan serving session over one cached training run."""

    def __init__(
        self,
        objective: Objective,
        params0: Any,
        dataset: Dataset,
        config: UnlearnerConfig,
    ):
        self.objective = objective
        self.params0 = params0
        self.dataset = dataset
        self.config = config
        self.history: Optional[TrainingHistory] = None
        self.log: List[Dict] = []
        self._trained_params: Any = params0
        self._algorithm: Optional[UnlearningAlgorithm] = None
        self._prng_key: Optional[jax.Array] = None
        self._pending: List[Tuple[int, UnlearnRequest]] = []
        self._responses: Dict[int, UnlearnResponse] = {}
        self._failed: Dict[int, Exception] = {}
        self._tickets = 0
        # responses pin their post-request params (a device pytree) so
        # handles can be forced later; bound how many stay live — beyond
        # this, the oldest resolve to a clear "evicted" error instead of
        # leaking device memory on fire-and-forget submitters
        self.max_responses = 256
        # auto-flush bookkeeping (config.max_pending / max_delay_s); the
        # lock serializes submit/flush/poll so an `AutoFlushTimer` thread
        # can drive the deadline next to a submitting foreground thread
        self._lock = threading.RLock()
        self._oldest_pending_ts: Optional[float] = None
        self._autoflush_timer: Optional[Any] = None  # SessionFlushClock
        self.autoflush_count = 0
        self.autoflush_reasons: Dict[str, int] = {"max_pending": 0,
                                                  "max_delay_s": 0}
        # set by from_config(): the registry Model backing this session's
        # objective (None when the objective was hand-built)
        self.model: Optional[Any] = None

    @classmethod
    def from_config(
        cls,
        name: str,
        dataset: Dataset,
        *,
        reduced: Optional[Dict[str, Any]] = None,
        config: Optional[UnlearnerConfig] = None,
        l2: float = 0.0,
        remat: bool = False,
        loss_chunk: Optional[int] = None,
        attn_impl: Optional[str] = None,
        init_seed: int = 1,
    ) -> "UnlearnerSession":
        """Build a session from a registry model name.

        ``name`` is a `configs.registry` key (e.g. ``"internlm2-1.8b"``);
        ``reduced`` — if given — is a dict of `ModelConfig.reduced`
        overrides producing a CI-sized variant of the same architecture.
        The model's loss becomes the session objective via
        `Objective.from_model` (remat / loss_chunk / attn_impl are
        forwarded), initial params come from ``model.init(init_seed)``,
        and the built `models.registry.Model` is kept on ``session.model``
        for scoring/decoding next to the unlearning surface.
        """
        from repro.configs.registry import get_config
        from repro.models.registry import build

        model_cfg = get_config(name)
        if reduced is not None:
            model_cfg = model_cfg.reduced(**reduced)
        model = build(model_cfg)
        objective = Objective.from_model(
            model, remat=remat, loss_chunk=loss_chunk, l2=l2,
            attn_impl=attn_impl)
        sess = cls(objective, model.init(init_seed), dataset,
                   config or UnlearnerConfig())
        sess.model = model
        return sess

    # -- phase 1: training with path caching --------------------------------

    def fit(self) -> Any:
        if self._pending:
            raise RuntimeError(
                "flush() or resolve pending requests before refitting")
        c = self.config
        tier = c.history_tier
        if tier is None:
            tier = "host" if c.history_codec != "f32" else "stacked"
        meta = HistoryMeta(
            n=self.dataset.n,
            batch_size=min(c.batch_size, self.dataset.n),
            seed=c.seed,
            steps=c.steps,
            lr_schedule=tuple(c.lr_schedule) if c.lr_schedule else ((0, c.lr),),
            l2=self.objective.l2,
            momentum=c.momentum,
        )
        self._trained_params, self.history = sgd_train_with_cache(
            self.objective,
            self.params0,
            self.dataset,
            meta,
            tier=tier,
            codec=c.history_codec,
            spill_dir=c.spill_dir,
            window=c.deltagrad.stream_window,
        )
        self._algorithm = None
        return self._trained_params

    def _require_fit(self):
        if self.history is None:
            raise RuntimeError("call fit() (or restore()) before serving")

    # -- algorithm / engine / current model ---------------------------------

    @property
    def algorithm(self) -> UnlearningAlgorithm:
        """The session's ONE serving algorithm (created lazily from
        ``config.algorithm`` via the `core.algorithms` registry, bound to
        the cached run by `prepare()`)."""
        self._require_fit()
        if self._algorithm is None:
            cls = get_algorithm(self.config.algorithm)
            self._algorithm = cls(self.objective, self.dataset, self.config)
            self._algorithm.prepare(self.history, self._trained_params,
                                    self.params0)
        return self._algorithm

    @property
    def _engine(self) -> Optional[OnlineEngine]:
        """The algorithm's online engine, when it has one (deltagrad /
        retrain_oracle); None before the first request and for
        engine-less algorithms.  Kept as a property because drivers and
        tests reach for the engine's liveness/added state directly."""
        if self._algorithm is None:
            return None
        return getattr(self._algorithm, "_engine", None)

    def engine(self, placement: Optional[PlacementPolicy] = None
               ) -> OnlineEngine:
        """The session's online engine (created lazily; owns liveness,
        added-row join columns, and the rewritten cached path — served
        through a `core.store.HistoryStore`).  Only engine-backed
        algorithms (deltagrad, retrain_oracle) have one.

        `placement` overrides ``config.placement`` for the engine's store
        on FIRST creation (mesh-sharded resident replay); after that the
        engine — and its placement — is fixed for the session's life."""
        algo = self.algorithm
        if not hasattr(algo, "engine"):
            raise RuntimeError(
                f"algorithm {algo.name!r} does not serve through an "
                "OnlineEngine; use session.algorithm directly")
        return algo.engine(placement=placement)

    def warmup(self, specs=("delete",)) -> float:
        """Pre-compile the request programs; `specs` entries are op names
        or ``(op, group_size)`` pairs (group sizes bucket to pow2, so warm
        the bucket the serving bursts will hit).  Returns compile time."""
        return self.algorithm.warmup(tuple(specs))

    @property
    def params(self):
        """Current model — forces every pending request and blocks."""
        self.flush()
        p = self._algorithm.params if self._algorithm is not None \
            else self._trained_params
        jax.block_until_ready(p)
        return p

    # -- certified publication ----------------------------------------------

    def _next_key(self) -> jax.Array:
        """Split one use-key off the session-held PRNG key (created from
        ``config.seed`` on first use; save()/restore() round-trips it, so
        a restored session's next publish is bitwise-identical)."""
        if self._prng_key is None:
            self._prng_key = jax.random.PRNGKey(self.config.seed)
        self._prng_key, key = jax.random.split(self._prng_key)
        return key

    def certificate(self, eps: Optional[float] = None,
                    delta: Optional[float] = None) -> Certificate:
        """The serving algorithm's current deletion certificate — no
        noise is drawn and no state changes."""
        self.flush()
        return self.algorithm.certificate(eps=eps, delta=delta)

    def publish(self, eps: Optional[float] = None,
                delta: Optional[float] = None):
        """(params, Certificate): certified release of the current model
        through the algorithm's mechanism, with noise drawn from the
        session PRNG key (one split per publish)."""
        with self._lock:
            params = self.params  # flush + block
            return self.algorithm.publish(self._next_key(), params,
                                          eps=eps, delta=delta)

    # -- phase 2: the request plan ------------------------------------------

    def submit(self, request: Optional[UnlearnRequest] = None, *,
               op: Optional[str] = None,
               rows: Optional[Sequence[int]] = None,
               data: Optional[Dict[str, np.ndarray]] = None,
               coalesce: bool = True) -> RequestHandle:
        """Enqueue one request; returns a lazy `RequestHandle`.

        Nothing executes until the session flushes.  Add payloads (`data`)
        ARE appended to the dataset here, so their row ids are assigned at
        submission time and later requests may delete them.  Serialized
        against `flush()`/`poll()` (and so against an `AutoFlushTimer`)
        by the session lock."""
        with self._lock:
            return self._submit_locked(request, op=op, rows=rows,
                                       data=data, coalesce=coalesce)

    def _submit_locked(self, request, *, op, rows, data,
                       coalesce) -> RequestHandle:
        self._require_fit()
        if request is None:
            request = UnlearnRequest(op=op, rows=rows, data=data,
                                     coalesce=coalesce)
        if request.op not in ("delete", "add"):
            raise ValueError(f"op must be 'delete' or 'add', got "
                             f"{request.op!r}")
        if request.op == "add" and request.data is not None \
                and request.rows is None:
            request.rows = self.dataset.append(request.data).tolist()
        if not request.rows:
            raise ValueError("request names no rows")
        request.rows = [int(r) for r in request.rows]
        if len(set(request.rows)) != len(request.rows):
            raise ValueError(f"duplicate rows in request: {request.rows}")
        if request.op == "delete":
            pending_del = {r for _, q in self._pending if q.op == "delete"
                           for r in q.rows}
            for r in request.rows:
                if not 0 <= r < self.dataset.n:
                    raise ValueError(f"row {r} out of range")
                if self.dataset.removed[r] or r in pending_del:
                    raise ValueError(f"row {r} already deleted (or has a "
                                     "pending delete)")
        else:
            pending_add = {r for _, q in self._pending if q.op == "add"
                           for r in q.rows}
            already = (set(self._algorithm.added)
                       if self._algorithm is not None else set())
            base_n = self.history.meta.n
            for r in request.rows:
                if not base_n <= r < self.dataset.n:
                    raise ValueError(
                        "add requests name rows appended AFTER the cached "
                        f"training run (expected {base_n} <= row < "
                        f"{self.dataset.n}, got {r}) — an original row "
                        "would be double-counted")
                if r in already or r in pending_add:
                    raise ValueError(f"row {r} already added (or has a "
                                     "pending add)")
        ticket = self._tickets
        self._tickets += 1
        if not self._pending:
            self._oldest_pending_ts = time.monotonic()
        self._pending.append((ticket, request))
        handle = RequestHandle(self, ticket, request)
        self._maybe_autoflush()
        return handle

    # -- deadline/size-triggered auto-flush ---------------------------------

    def _maybe_autoflush(self) -> bool:
        """Flush when the pending queue trips the configured size or
        staleness bound.  Size is checked on every submit; the deadline is
        checked at submit time AND via `poll()` (call it between arrivals
        — e.g. from the serving loop's idle tick) so a lull after a burst
        cannot park requests past ``max_delay_s``."""
        c = self.config
        reason = None
        if (c.max_pending is not None and c.max_pending > 0
                and len(self._pending) >= c.max_pending):
            reason = "max_pending"
        elif (c.max_delay_s is not None and self._pending
              and time.monotonic() - self._oldest_pending_ts
              >= c.max_delay_s):
            reason = "max_delay_s"
        if reason is None:
            return False
        self.autoflush_count += 1
        self.autoflush_reasons[reason] += 1
        try:
            self.flush()
        except Exception:
            # a POLICY-triggered flush must not propagate a failing
            # group's error out of submit() — the caller would lose the
            # handle for the request it just enqueued.  flush() already
            # recorded the failing tickets in _failed (their handles
            # resolve to the error) and requeued the groups behind them.
            pass
        return True

    def poll(self) -> bool:
        """Deadline tick for continuous-load serving: flushes (returning
        True) iff pending work has outstayed ``config.max_delay_s``.
        Call it from the load loop's idle tick, or let
        `start_autoflush_timer()` drive it from a daemon thread."""
        with self._lock:
            return self._maybe_autoflush()

    def start_autoflush_timer(self, interval_s: Optional[float] = None):
        """DEPRECATED: drive the ``max_delay_s`` deadline from a daemon
        tick thread.  This now routes through the serving tier — it
        returns a `repro.serve.SessionFlushClock` (one default SLA class
        whose deadline is ``max_delay_s``; same ``ticks``/``stop()``
        surface as the old `AutoFlushTimer`).  New code should construct
        `repro.serve.ServingScheduler` for per-class deadlines, admission
        control, and cross-tenant batching.  Starting a new clock stops
        the previous one."""
        warnings.warn(
            "session.start_autoflush_timer() is deprecated; serve through "
            "repro.serve.ServingScheduler (SLA-class deadlines) or create "
            "repro.serve.SessionFlushClock directly",
            DeprecationWarning, stacklevel=2)
        if self.config.max_delay_s is None:
            raise ValueError(
                "start_autoflush_timer() needs config.max_delay_s — there "
                "is no deadline for the timer to enforce")
        from repro.serve.scheduler import SessionFlushClock
        if self._autoflush_timer is not None:
            self._autoflush_timer.stop()
        self._autoflush_timer = SessionFlushClock(self, interval_s=interval_s)
        return self._autoflush_timer

    @property
    def pending_age_s(self) -> float:
        """Seconds the OLDEST pending request has been waiting (0 if none):
        the staleness the auto-flush policy bounds."""
        if not self._pending or self._oldest_pending_ts is None:
            return 0.0
        return time.monotonic() - self._oldest_pending_ts

    @property
    def pending_count(self) -> int:
        """Number of submitted-but-unserved requests (len is atomic under
        CPython, so this is safe to read without the lock — the serving
        executor polls it between flush rounds)."""
        return len(self._pending)

    def pending_requests(self) -> List[Tuple[int, UnlearnRequest]]:
        """Snapshot of the pending set as ``(ticket, request)`` pairs, in
        submission order — what the coalescing planner would group at the
        next flush.  The serving tier uses this (plus `pending_count`) to
        decide whether a snapshot can proceed and to account pending add
        rows against staged device capacity."""
        with self._lock:
            return list(self._pending)

    def try_flush(self) -> Optional[List[UnlearnResponse]]:
        """Non-blocking variant of `flush()`: serve the pending set IF
        the session lock is immediately available, else return None
        without waiting.  Part of the serving-tier session surface
        (alongside `poll` and `pending_requests`) for callers driving the
        session from their own event loop, where a flush attempt must
        never park behind a foreground submitter (or another flush)
        holding the lock.  The threaded serving path does not need it:
        `ServingScheduler`'s executor is the session's only writer there
        and uses plain `flush()`."""
        if not self._lock.acquire(blocking=False):
            return None
        try:
            return self._flush_locked()
        finally:
            self._lock.release()

    def delete(self, rows: Sequence[int], coalesce: bool = True
               ) -> RequestHandle:
        return self.submit(op="delete", rows=list(rows), coalesce=coalesce)

    def add(self, data: Optional[Dict[str, np.ndarray]] = None,
            rows: Optional[Sequence[int]] = None, coalesce: bool = True
            ) -> RequestHandle:
        return self.submit(op="add", rows=rows, data=data, coalesce=coalesce)

    def _resolve(self, ticket: int) -> UnlearnResponse:
        if ticket not in self._responses and ticket not in self._failed:
            self.flush()
        if ticket in self._failed:
            err = self._failed[ticket]
            raise RuntimeError(
                f"request {ticket} was not served: {err}") from err
        return self._responses[ticket]

    def _record(self, ticket: int, resp: UnlearnResponse) -> None:
        self._responses[ticket] = resp
        while len(self._responses) > self.max_responses:
            old = next(iter(self._responses))  # oldest (insertion order)
            del self._responses[old]
            self._failed[old] = RuntimeError(
                "response evicted (more than max_responses unread "
                "responses); force handles promptly or raise "
                "session.max_responses")

    def flush(self) -> List[UnlearnResponse]:
        """Serve every pending request through the coalescing planner.

        Replays are DISPATCHED, not synced: device work queues up and
        `dispatch_s` measures host time only; blocking happens when a
        handle (or `.params`) is forced."""
        with self._lock:
            return self._flush_locked()

    def _flush_locked(self) -> List[UnlearnResponse]:
        if not self._pending:
            return []
        algo = self.algorithm
        pending, self._pending = self._pending, []
        ts0, self._oldest_pending_ts = self._oldest_pending_ts, None
        # size the add-column block for the whole plan once so the padded
        # schedule width (and every compiled shape) stays put across it
        n_adds = sum(len(q.rows) for _, q in pending if q.op == "add")
        with obs_trace.span("session.plan", requests=len(pending)):
            algo.begin_plan(n_adds)
            groups = plan_requests(pending)
        out: List[UnlearnResponse] = []
        for gi, group in enumerate(groups):
            op = group[0][1].op
            rows = [r for _, q in group for r in q.rows]
            t0 = time.perf_counter()
            try:
                stats = algo.apply(op, rows,
                                   coalesce=group[0][1].coalesce)
            except Exception as e:
                # the failing group's handles resolve to this error; groups
                # after it go back on the queue (ahead of anything submitted
                # later) so their handles stay servable
                for ticket, _ in group:
                    self._failed[ticket] = e
                self._pending = [tr for g in groups[gi + 1:] for tr in g] \
                    + self._pending
                if self._pending:
                    # keep the ORIGINAL enqueue clock: requeued requests
                    # were already waiting, and restarting the clock would
                    # let them silently outstay max_delay_s
                    self._oldest_pending_ts = ts0 or time.monotonic()
                raise
            dispatch_s = time.perf_counter() - t0
            for ticket, req in group:
                resp = UnlearnResponse(request=req, stats=stats,
                                       group_size=len(rows),
                                       dispatch_s=dispatch_s,
                                       params=algo.params)
                self._record(ticket, resp)
                out.append(resp)
            self.log.append({"op": op, "rows": rows,
                             "coalesced": len(stats) == 1 and len(rows) > 1,
                             "stats": stats})
        return out

    # -- streams (serial Algorithm-3 semantics; the paper's request model) ---

    def serve_stream(self, ops: Sequence[Tuple[str, int]]) -> OnlineStats:
        """Serve ``(op, row)`` pairs one replay per row (never coalesced),
        returning aggregate `OnlineStats`; wall_time_s covers dispatch +
        the final device sync, with compile cost reported separately."""
        self._require_fit()
        self.flush()  # drain older pending work outside this stream's timer
        algo = self.algorithm
        handles = [self.submit(op=op, rows=[int(row)], coalesce=False)
                   for op, row in ops]
        stats = OnlineStats(compile_time_s=algo.compile_time_s)
        t0 = time.perf_counter()
        self.flush()
        jax.block_until_ready(algo.params)
        stats.wall_time_s = time.perf_counter() - t0
        for h in handles:
            stats.per_request.extend(h.stats)
        return stats

    def stream_delete(self, rows: Sequence[int]) -> OnlineStats:
        return self.serve_stream([("delete", int(r)) for r in rows])

    def stream_add(self, data: Dict[str, np.ndarray]) -> OnlineStats:
        new_idx = self.dataset.append(data)
        return self.serve_stream([("add", int(r)) for r in new_idx])

    # -- reference: exact retraining (BaseL) ---------------------------------

    def baseline(self, indices, mode: str = "delete"):
        self._require_fit()
        idx = np.asarray(list(indices), dtype=np.int64)
        return baseline_retrain(
            self.objective, self.dataset, self.history.meta, self.params0,
            idx, mode)

    # -- snapshot / restore --------------------------------------------------

    def save(self, directory: str, step: Optional[int] = None,
             pending: str = "drain") -> str:
        """Write a restorable snapshot through `train/checkpoint`.

        ``pending`` picks the snapshot-under-load semantics, and both
        choices are deterministic: ``"drain"`` (default) flushes every
        pending request first, so the snapshot is always a consistent
        between-requests state — restoring it and serving the remainder of
        a request stream is identical to the uninterrupted session;
        ``"refuse"`` raises `RuntimeError` while anything is pending, for
        callers that must not absorb the drain latency inside save().
        Params ride as the checkpoint's sharded pytree; `TrainingHistory`
        (any tier), the dataset (columns + deletion mask), and the
        algorithm descriptor (e.g. the engine's liveness/added-row
        order/capacities/L-BFGS ring) ride in the extra payload.  Returns
        the step dir.  Holds the session lock for the whole write so a
        concurrent submitter or flush clock cannot mutate state between
        the flush and the state_dict reads."""
        if pending not in ("drain", "refuse"):
            raise ValueError(
                f"pending must be 'drain' or 'refuse', got {pending!r}")
        with self._lock:
            if pending == "refuse" and self._pending:
                raise RuntimeError(
                    f"save(pending='refuse') with {len(self._pending)} "
                    "pending request(s); flush() first or use "
                    "pending='drain'")
            return self._save_locked(directory, step)

    def _save_locked(self, directory: str, step: Optional[int]) -> str:
        self._require_fit()
        self.flush()
        params = self._algorithm.params if self._algorithm is not None \
            else self._trained_params
        jax.block_until_ready(params)
        step = self._tickets if step is None else int(step)
        extra = {
            "format": 2,
            "config": self.config,
            "params0": jax.device_get(self.params0),
            "history": self.history.state_dict(),
            "dataset": {
                "columns": {k: np.asarray(v)
                            for k, v in self.dataset.columns.items()},
                "removed": np.asarray(self.dataset.removed, dtype=bool).copy(),
            },
            # the algorithm descriptor: which algorithm served this
            # session plus its full serving state, so restore() rebuilds
            # the SAME algorithm mid-stream (format 1 snapshots carried a
            # bare deltagrad engine state under "engine")
            "algorithm": ({
                "name": self._algorithm.name,
                "state": self._algorithm.state_dict(),
            } if self._algorithm is not None else None),
            "prng_key": (np.asarray(jax.device_get(self._prng_key))
                         if self._prng_key is not None else None),
            "tickets": self._tickets,
        }
        return ckpt.save(directory, step, params, extra=extra)

    @classmethod
    def restore(cls, directory: str, objective: Objective,
                step: Optional[int] = None,
                spill_dir: Optional[str] = None) -> "UnlearnerSession":
        """Rebuild a session from `save()` output; the next request served
        is identical to what the uninterrupted session would have served.
        `objective` is code, not state — pass the same objective the saved
        session was built with."""
        if step is None:
            step = ckpt.latest_step(directory)
            if step is None:
                raise FileNotFoundError(
                    f"no complete checkpoint under {directory}")
        extra = ckpt.restore_extra(directory, step)
        history = TrainingHistory.from_state_dict(extra["history"],
                                                  spill_dir=spill_dir)
        ds = Dataset(extra["dataset"]["columns"])
        ds.removed = np.asarray(extra["dataset"]["removed"],
                                dtype=bool).copy()
        params = ckpt.restore(directory, step, like=history.final_params)
        params0 = extra.get("params0")
        if params0 is not None:
            params0 = jax.tree.map(jax.numpy.asarray, params0)
        sess = cls(objective, params0=params0, dataset=ds,
                   config=extra["config"])
        sess.history = history
        sess._trained_params = params
        sess._tickets = int(extra.get("tickets", 0))
        key = extra.get("prng_key")
        if key is not None:
            sess._prng_key = jax.numpy.asarray(np.asarray(key))
        algo_desc = extra.get("algorithm")
        if algo_desc is not None:
            if algo_desc["name"] != sess.config.algorithm:
                raise ValueError(
                    f"snapshot was served by {algo_desc['name']!r} but the "
                    f"restored config selects {sess.config.algorithm!r}")
            sess.algorithm.load_state(algo_desc["state"], params)
        elif extra.get("engine") is not None:  # format 1 (pre-registry)
            engine = sess.engine()
            engine.load_state(extra["engine"])
            engine.params = params
        return sess
