"""Unified compiled replay engine — the DeltaGrad hot path as one program.

Architecture (mapping to Wu et al., ICML 2020):

  Phase 0  SCHEDULE      `data.sampler.build_schedule` precomputes the whole
                         minibatch replay plan — (T, B) batch indices,
                         removal/addition overlap masks, per-step learning
                         rates — in one vectorized pass, then uploads it to
                         the device once.  This is the paper's "replay the
                         same minibatch sequence" assumption (§A.1.2) made a
                         data structure.  Full-batch GD's schedule is the
                         identity on every step (`batch_in_place`), so its
                         steps read the batch as a prefix of the device
                         columns instead of gathering it.

  Phase 1  RECORD        `run_training` — Algorithm 1's original SGD run,
                         executed as a single `jax.lax.scan`; the scan's
                         stacked outputs (w_t, g_t) ARE the optimization-path
                         cache (TrainingHistory's ``stacked`` tier), so
                         caching costs one device buffer instead of T host
                         round-trips.

  Phase 2  REPLAY        `run_replay` — Algorithm 1's retraining loop.
                         Explicit steps (t <= j0, or every T0) stay host-
                         driven because they mutate the L-BFGS pair buffer
                         with curvature admission (Algorithm 4's check).
                         Every maximal run of approx steps between two
                         explicit steps executes as ONE `lax.scan` whose body
                         reads (w_t, g_t) from the stacked history with
                         `lax.dynamic_slice`, evaluates gradients only on the
                         <= r changed rows present in B_t (the paper's eq.
                         (2)/(S7) update), applies the quasi-Hessian
                         correction B_t(w^I_t - w_t) via the compact L-BFGS
                         operator (Algorithm 2), and resolves the Algorithm-4
                         guard on-device with `lax.cond` — guard outcomes
                         come back as one stacked flag vector read once at
                         the end, never as a per-step blocking `bool()`.

  Phase 2' ONLINE        `run_online_request` — Algorithm 3 (Appendix C.2)
                         for BOTH request flavors (single-sample deletion and
                         addition) and both optimizers (plain SGD and
                         heavy-ball, whose velocity is reconstructed per
                         request inside the scan carry from vel_0 = 0): the
                         same segment scan additionally emits the rewritten
                         (w_t <- w^I_t, g_t <- g^a_t) pairs.  Rewrites —
                         including the explicit steps' — defer to ONE jitted
                         assembly + `lax.dynamic_update_slice` per contiguous
                         region per request, and once the L-BFGS buffer fills
                         the pair ring lives on device (where-gated
                         shift-append), so a steady request runs with zero
                         mid-request host syncs and per-request cost stays
                         independent of how many requests came before.
                         Addition requests extend the replayed batch with one
                         precomputed join-mask column per added row
                         (`data.sampler.build_online_schedule`); join
                         decisions are device arrays, never per-step host
                         calls.

  Phase 3  KERNEL        The non-momentum approx update is routed through
                         the Pallas ``kernels/fused_update`` op on TPU (one
                         HBM pass over the four parameter-sized operands);
                         CPU and tests use the numerically identical
                         ``ref.py`` oracle (or the kernel's interpret mode)
                         on the same flattened operands.

Where the history bytes live is `core.store`'s concern: stacked/device
tiers replay fully resident (optionally sharded across a mesh, the
segment scans then plain jits over the sharded state, `PlacedReplay`),
host/disk tiers stream double-buffered segment windows to the same
compiled scans — and the two COMPOSE: a mesh-placed host/disk tier
streams per-shard encoded window segments (`ShardedStreamer`), consumed
exactly like the resident sharded path.
Execution backends: ``impl="scan"`` (this
module's compiled path, all tiers) and ``impl="python"`` (the pre-refactor
per-step loop, kept as the parity oracle).  Numerics and counters
are identical between the two backends, guard ON or OFF.  The two
divergences documented after the engine refactor are resolved: (1) a scanned
segment that reports a guard fallback is re-run split at the first fallback
step, which then executes as a host explicit step and ADMITS its L-BFGS pair
exactly like the python loop (the cost is one host sync per scanned segment
when the guard is enabled — guard-off runs still sync nothing until the end);
(2) fallback steps charge their true `grad_examples` cost kept+dB in both
backends — the python loop now reuses the changed-row gradient it computed
in the rejected approx attempt instead of re-evaluating (and re-charging)
it in the explicit branch.

Frontends: `core.deltagrad.{sgd_train_with_cache, baseline_retrain,
deltagrad_retrain}` and `core.online.online_deltagrad` are thin wrappers
over this module.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree

from repro.core.history import HistoryMeta, TrainingHistory
from repro.core.lbfgs import LbfgsBuffer, lbfgs_hvp_stacked_pytree
from repro.core.store import (EncodedLeaf, HistoryStore, auto_window,
                              constrain, entry_at, is_encoded_window,
                              per_shard)
from repro.data.dataset import Dataset
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.roofline.hw import local_hw
from repro.roofline.replay import scan_segment_cost
from repro.data.sampler import (ReplaySchedule, addition_mask,
                                batch_indices, batch_indices_all,
                                build_schedule)
from repro.utils.tree import (tree_all_finite, tree_norm, tree_sub,
                              tree_vdot)


# --------------------------------------------------------------------------
# Config / stats (the public dataclasses; re-exported by core.deltagrad)
# --------------------------------------------------------------------------


@dataclass
class DeltaGradConfig:
    period: int = 5  # T0 — explicit gradient every T0 steps
    burn_in: int = 10  # j0 — initial explicit steps
    history_size: int = 2  # m — L-BFGS memory
    curvature_eps: float = 0.0  # pair admission threshold (Alg. 4 guard)
    guard: bool = False  # enable non-convex fallback checks
    guard_norm_clip: float = 1e4  # fallback if ||Bv|| > clip * ||v||
    removal_pad: int = 0  # 0 → auto (next pow2 of max per-batch overlap)
    impl: str = "scan"  # "scan" (compiled engine) | "python" (legacy loop)
    fused: str = "auto"  # "auto" | "pallas" | "interpret" | "ref"
    # steps per device-resident window when the history lives on an offload
    # tier (served by core.store.SegmentStreamer); 0 → auto
    stream_window: int = 0
    # streamed-window read path: "kernel" keeps windows ENCODED on device
    # and the scan dequantizes per step, "fetch" decodes each window to
    # f32 on arrival, "auto" → kernel for every non-f32 codec
    stream_decode: str = "auto"

    def is_explicit(self, t: int) -> bool:
        if t <= self.burn_in:
            return True
        return (t - self.burn_in) % self.period == 0


@dataclass
class RetrainStats:
    explicit_steps: int = 0
    approx_steps: int = 0
    guard_fallbacks: int = 0
    skipped_steps: int = 0  # empty effective batch (paper: no update)
    pairs_rejected: int = 0
    grad_examples: int = 0  # per-example gradient evaluations (DeltaGrad)
    grad_examples_baseline: int = 0  # what BaseL would have paid
    wall_time_s: float = 0.0
    extra: Dict[str, Any] = field(default_factory=dict)

    @property
    def theoretical_speedup(self) -> float:
        return self.grad_examples_baseline / max(self.grad_examples, 1)


def _scan_pred(n_params: int, steps: int, r: int, m: int,
               momentum: bool) -> Optional[float]:
    """Roofline-predicted cost (seconds) for a scanned replay segment —
    attached as ``pred_s`` to ``replay.scan`` spans so the exported trace
    carries measured-vs-roofline ratios, priced for the device that runs
    the segment unless the tracer names a spec.  Returns None (and
    computes nothing) while tracing is disabled, keeping the tracer-off
    hot path free of the prediction arithmetic."""
    tracer = obs_trace.get_tracer()
    if tracer is None:
        return None
    return scan_segment_cost(n_params, steps, r, m, momentum=momentum,
                             hw=tracer.hw or local_hw()).pred_s


def _publish_replay_metrics(stats: "RetrainStats", store,
                            in_place: bool) -> None:
    """Publish one finished replay's counters into the process-wide
    `repro.obs.metrics` registry (see the contract table in `repro.obs`).
    `in_place`: every explicit step read its batch in place."""
    reg = obs_metrics.get_registry()
    own = "core.engine"
    reg.counter("engine.replays", owner=own).inc()
    reg.counter("engine.explicit_steps", owner=own).inc(stats.explicit_steps)
    reg.counter("engine.explicit_in_place", owner=own).inc(
        stats.explicit_steps if in_place else 0)
    reg.counter("engine.approx_steps", owner=own).inc(stats.approx_steps)
    reg.counter("engine.guard_fallbacks",
                owner=own).inc(stats.guard_fallbacks)
    reg.counter("engine.grad_examples", owner=own).inc(stats.grad_examples)
    hw = store.hbm_high_water() if store is not None else 0
    if hw:
        reg.gauge("store.hbm_high_water_bytes", unit="B",
                  owner="core.store").set_max(hw)


# --------------------------------------------------------------------------
# Step plan
# --------------------------------------------------------------------------

SKIP, EXPLICIT, APPROX = 0, 1, 2


def _next_pow2(x: int) -> int:
    return 1 << max(0, (x - 1)).bit_length()


def build_plan(cfg: DeltaGradConfig, sched: ReplaySchedule,
               online: bool = False) -> np.ndarray:
    """Per-step execution codes.  SKIP (empty effective batch, paper §3)
    takes precedence over the explicit/approx cadence.  Batch mode skips any
    emptied batch; online mode mirrors Algorithm 3's condition exactly — skip
    only when the REQUEST row sits in a batch whose other rows are all gone
    (kept == 0 and dB > 0); request-absent empty batches still execute, as
    degenerate no-op/l2-only steps, matching the python oracle."""
    T = sched.steps
    codes = np.full(T, APPROX, dtype=np.int8)
    for t in range(T):
        if cfg.is_explicit(t):
            codes[t] = EXPLICIT
    if sched.mode == "delete":
        empty = sched.kept <= 0
        codes[empty & (sched.dB > 0) if online else empty] = SKIP
    return codes


class DeviceSchedule(NamedTuple):
    """`ReplaySchedule` uploaded to the device once per retraining run."""

    idx: jax.Array  # (T, B) i32
    kept_w: jax.Array  # (T, B) f32
    changed_idx: jax.Array  # (T, R) i32
    changed_w: jax.Array  # (T, R) f32
    dB: jax.Array  # (T,) f32
    kept: jax.Array  # (T,) f32
    lr: jax.Array  # (T,) f32


def to_device(sched: ReplaySchedule, idx=None, lr=None) -> DeviceSchedule:
    """Upload a schedule; pass already-uploaded `idx`/`lr` to reuse them
    (they are request-invariant across an online stream)."""
    return DeviceSchedule(
        idx=jnp.asarray(sched.idx, dtype=jnp.int32) if idx is None else idx,
        kept_w=jnp.asarray(sched.kept_w),
        changed_idx=jnp.asarray(sched.changed_idx, dtype=jnp.int32),
        changed_w=jnp.asarray(sched.changed_w),
        dB=jnp.asarray(sched.dB),
        kept=jnp.asarray(sched.kept),
        lr=jnp.asarray(sched.lr) if lr is None else lr,
    )


def _gather(cols, rows):
    return {k: c[rows] for k, c in cols.items()}


def batch_in_place(idx: np.ndarray, width: Optional[int] = None) -> bool:
    """Whether a step can read its scheduled batch in place: every row of the
    host (T, B) index matrix is ``arange(B)`` (full-batch GD) and the device
    reads it at that width (``width``: the uploaded schedule's).  Checked
    once per schedule; the answer is a static argument of the jitted
    steps."""
    B = idx.shape[1]
    return (B == (B if width is None else width)
            and bool((idx == np.arange(B)).all()))


def _read_batch(cols, rows, in_place: bool):
    """A step's scheduled batch: the columns gathered at `rows`, or, for an
    identity schedule (`batch_in_place`), their first ``len(rows)`` rows as a
    static slice, which XLA reads without a copy.  Device columns may be
    padded past the schedule's width (`Dataset.device_columns(capacity)`)."""
    if not in_place:
        return _gather(cols, rows)
    B = rows.shape[0]
    return {k: c if c.shape[0] == B else c[:B] for k, c in cols.items()}


# --------------------------------------------------------------------------
# Update math (shared by scan bodies, host explicit steps and the python
# oracle — one definition, identical numerics everywhere)
# --------------------------------------------------------------------------


def _sgd_math(p, g, lr):
    return jax.tree.map(lambda a, b: a - lr * b, p, g)


def _momentum_math(p, vel, g, lr, mom):
    """Heavy-ball: vel <- mom*vel + g; p <- p - lr*vel."""
    vel = jax.tree.map(lambda v, b: mom * v + b, vel, g)
    return jax.tree.map(lambda a, v: a - lr * v, p, vel), vel


@jax.jit
def _sgd_apply(p, g, lr):
    return _sgd_math(p, g, lr)


@jax.jit
def _momentum_apply(p, vel, g, lr, mom):
    return _momentum_math(p, vel, g, lr, mom)


@jax.jit
def _tree_zeros(p):
    return jax.tree.map(jnp.zeros_like, p)


def _resolve_fused(fused: str) -> str:
    assert fused in ("auto", "pallas", "interpret", "ref"), fused
    if fused == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    return fused


def _run_fused(w, g, b, c, lr, B, dB, s, fused: str):
    from repro.kernels.fused_update.ops import update as fused_op
    from repro.kernels.fused_update.ref import deltagrad_update_ref

    if fused == "pallas":
        return fused_op(w, g, b, c, lr, B, dB, s)
    if fused == "interpret":
        return fused_op(w, g, b, c, lr, B, dB, s, interpret=True)
    return deltagrad_update_ref(w, g, b, c, lr, B, dB, s)


def _flat_fused_update(params, g_t, bv, g_changed, lr, B, dB, sign: int,
                       fused: str, shards=None):
    """Paper eq. (2)/(S7) on the FLATTENED parameter vector, through the
    Pallas fused kernel (TPU), its interpret mode, or the jnp reference —
    all three compute w - lr/(B - sign*dB) * (B*(g_t + Bv) - sign*dB*g_c).

    With the parameters placed on a mesh (`shards`,
    `core.store.PlacedReplay.kernel_shards`) nothing is flattened across
    leaves: each leaf's kernel runs on every device's block of it."""
    s = jnp.float32(sign)
    if shards is not None:
        leaves, tdef = jax.tree.flatten(params)
        ops = zip(leaves, jax.tree.leaves(g_t), jax.tree.leaves(bv),
                  jax.tree.leaves(g_changed))
        flat = lambda x: x.reshape(-1)
        return jax.tree.unflatten(tdef, [
            per_shard(lambda lr, B, dB, w, g, b, c: _run_fused(
                flat(w), flat(g), flat(b), flat(c), lr, B, dB, s,
                fused).reshape(w.shape), shards, i, (lr, B, dB), xs)
            for i, xs in enumerate(ops)])
    w, unravel = ravel_pytree(params)
    g, _ = ravel_pytree(g_t)
    b, _ = ravel_pytree(bv)
    c, _ = ravel_pytree(g_changed)
    return unravel(_run_fused(w, g, b, c, lr, B, dB, s, fused))


def _enc_slice_args(leaf: EncodedLeaf, i):
    """(q, scale, base) of step ``i`` of one encoded window leaf, in the
    leaf's shape, for the `kernels.dequant_update` ops (scale is per
    (leaf, step), which is why the fused dequant kernels route PER LEAF)."""
    q = leaf.q[i]
    scale = leaf.scale[i] if leaf.scale is not None else jnp.float32(1.0)
    base = None if leaf.base is None else leaf.base[leaf.kidx[i]]
    return q, scale, base


def _leafwise(fn, params, *trees):
    """``fn(k, p, *xs)`` over the parameter leaves, `k` the leaf's index
    (`W`/`G` windows flatten their `EncodedLeaf` leaves whole)."""
    leaves, tdef = jax.tree.flatten(params)
    cols = [jax.tree.leaves(t, is_leaf=lambda x: isinstance(x, EncodedLeaf))
            for t in trees]
    return jax.tree.unflatten(tdef, [fn(k, p, *xs) for k, (p, *xs)
                                     in enumerate(zip(leaves, *cols))])


def _dequant_sub_tree(params, W, i, fused: str, shards=None):
    """``v = params - w_t`` with the cached parameter operand consumed
    ENCODED — the `dequant_sub` Pallas kernel dequantizes in registers, so
    no f32 copy of w_t is ever materialized.  `shards` routes each leaf's
    call per device (`core.store.per_shard`)."""
    from repro.kernels.dequant_update.ops import dequant_sub

    def one(k, p, leaf):
        if not isinstance(leaf, EncodedLeaf):
            return p - leaf[i]
        q, scale, base = _enc_slice_args(leaf, i)
        kern = lambda scale, p, q, *base: dequant_sub(
            p, q, scale, *base, interpret=fused == "interpret")
        return per_shard(kern, shards, k, (scale,),
                         (p, q) + (() if base is None else (base,)))

    return _leafwise(one, params, W)


def _dequant_fused_update(params, G, i, bv, g_changed, lr, B, dB, sign: int,
                          fused: str, shards=None):
    """The non-momentum approx update with the cached gradient operand
    consumed ENCODED — `dequant_update` fuses the dequant with the
    leave-r-out step, per leaf (per-leaf scales), and per device with
    `shards`."""
    from repro.kernels.dequant_update.ops import dequant_update

    def one(k, p, leaf, b, c):
        if not isinstance(leaf, EncodedLeaf):
            denom = jnp.maximum(B - sign * dB, 1.0)
            return p - lr * (B * (leaf[i] + b) - sign * dB * c) / denom
        q, scale, base = _enc_slice_args(leaf, i)
        kern = lambda lr, B, dB, scale, p, q, b, c, *base: dequant_update(
            p, q, b, c, lr, B, dB, sign, scale, *base,
            interpret=fused == "interpret")
        return per_shard(kern, shards, k, (lr, B, dB, scale),
                         (p, q, b, c) + (() if base is None else (base,)))

    return _leafwise(one, params, G, bv, g_changed)


def _approx_math(g_t, bv, g_changed, B, dB, sign: int):
    """The paper's eq. (2)/(S7) leave-r-out (add-r) gradient estimate
    g^a = (B*(g_t + Bv) - sign*dB*g_c) / max(B - sign*dB, 1) — the ONE
    definition shared by the python oracle, both scan bodies, and the online
    rewrite (there with B = B_t(k), dB = 1{req in batch})."""
    denom = jnp.maximum(B - sign * dB, 1.0)
    return jax.tree.map(
        lambda gt, b, gc: (B * (gt + b) - sign * dB * gc) / denom,
        g_t, bv, g_changed)


@partial(jax.jit, static_argnames=("sign",))
def _approx_update(params, w_t, g_t, dWs, dGs, g_changed, lr, B, dB, clip,
                   sign: int):
    """Legacy tree-math approx step (python oracle path)."""
    v = tree_sub(params, w_t)
    bv = lbfgs_hvp_stacked_pytree(dWs, dGs, v)
    g_est = _approx_math(g_t, bv, g_changed, B, dB, sign)
    new = jax.tree.map(lambda p, g: p - lr * g, params, g_est)
    bn = tree_norm(bv)
    vn = tree_norm(v)
    ok = jnp.logical_and(tree_all_finite(new), bn <= clip * vn)
    return new, ok


@partial(jax.jit, static_argnames=("sign",))
def _approx_gradient(params, w_t, g_t, dWs, dGs, g_changed, B, dB, clip,
                     sign: int):
    """The leave-r-out gradient ESTIMATE (eq. (2) numerator/denominator)
    without applying it — the momentum extension needs the gradient."""
    v = tree_sub(params, w_t)
    bv = lbfgs_hvp_stacked_pytree(dWs, dGs, v)
    g_est = _approx_math(g_t, bv, g_changed, B, dB, sign)
    ok = jnp.logical_and(tree_all_finite(g_est),
                         tree_norm(bv) <= clip * tree_norm(v))
    return g_est, ok


@partial(jax.jit, static_argnames=("sign",))
def _combine_explicit(g_kept, g_changed, k, dB, B, sign: int):
    """(g_full, g_step): the pair-definition gradient over the ORIGINAL
    batch and the leave-r-out / add-r update gradient (paper §A.1.2)."""
    if sign > 0:  # delete
        g_full = jax.tree.map(lambda a, b: (k * a + dB * b) / B,
                              g_kept, g_changed)
        g_step = g_kept
    else:  # add
        g_full = g_kept
        g_step = jax.tree.map(lambda a, b: (B * a + dB * b) / (B + dB),
                              g_kept, g_changed)
    return g_full, g_step


# --------------------------------------------------------------------------
# Phase 1: RECORD — original training as one scan
# --------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("grad_fn", "momentum", "in_place", "pin"))
def _train_scan(params0, vel0, cols, idx, lr, w_ones, mom, *, grad_fn,
                momentum: bool, in_place: bool, pin=None):
    def body(carry, xs):
        params, vel = carry
        rows, lr_t = xs
        g = constrain(grad_fn(params, _read_batch(cols, rows, in_place),
                              w_ones), pin)
        if momentum:
            new_p, new_vel = _momentum_math(params, vel, g, lr_t, mom)
        else:
            new_p, new_vel = _sgd_math(params, g, lr_t), vel
        return (constrain(new_p, pin), new_vel), (params, g)

    (pT, velT), (Ws, Gs) = jax.lax.scan(body, (params0, vel0), (idx, lr))
    return pT, velT, Ws, Gs


def run_training(
    objective,
    params0,
    ds: Dataset,
    meta: HistoryMeta,
    tier: str = "device",
    codec: str = "f32",
    spill_dir: Optional[str] = None,
    impl: str = "scan",
    window: int = 0,
    spill_window: Optional[int] = None,
    placement=None,
) -> Tuple[Any, TrainingHistory]:
    """Train w_t by plain SGD (the paper's optimizer), caching (w_t, g_t).

    ``window`` bounds the recorder's device high-water on offload tiers
    (steps scanned per spill; 0 → the same auto default
    `core.store.SegmentStreamer` uses on the read path).  On the disk
    tier, spills batch ONE .npz per ``spill_window`` steps (None → match
    the stream window; 1 → the legacy one-file-per-step layout, which
    stays readable either way).

    ``placement`` (a `core.store.PlacementPolicy`) is recorded on the
    history, so stores built for it take its mesh; a mesh that shards the
    model's state (`PlacementPolicy.shards_state`) also trains with the
    parameters, gradients and recorded windows sharded."""
    grad_fn = objective.make_grad_fn()
    momentum = bool(meta.momentum)
    vel = _tree_zeros(params0) if momentum else None
    B = min(meta.batch_size, meta.n)
    if spill_window is None:
        spill_window = auto_window(meta.steps, window) if tier == "disk" \
            else 0
    history = TrainingHistory(meta, tier=tier, codec=codec,
                              spill_dir=spill_dir, spill_window=spill_window)
    history.placement = placement
    pin = None
    if placement is not None and placement.shards_state and impl != "python":
        shardings = placement.param_shardings(params0)
        params0 = jax.device_put(params0, shardings)
        pin = tuple(jax.tree.leaves(shardings))

    if impl == "python":
        ones = np.ones(B, dtype=np.float32)
        params = params0
        for t in range(meta.steps):
            idx = batch_indices(meta.seed, t, meta.n, meta.batch_size)
            g = grad_fn(params, ds.take(idx), ones)
            history.append(params, g)
            if momentum:
                params, vel = _momentum_apply(params, vel, g,
                                              jnp.float32(meta.lr_at(t)),
                                              jnp.float32(meta.momentum))
            else:
                params = _sgd_apply(params, g, jnp.float32(meta.lr_at(t)))
        history.finalize(params)
        return params, history

    idx_all = batch_indices_all(meta.seed, meta.steps, meta.n, meta.batch_size)
    lrs = np.asarray([meta.lr_at(t) for t in range(meta.steps)], np.float32)
    cols = ds.device_columns()
    idx_dev = jnp.asarray(idx_all, jnp.int32)
    lr_dev = jnp.asarray(lrs)
    ones = jnp.ones((B,), jnp.float32)
    mom = jnp.float32(meta.momentum)
    in_place = batch_in_place(idx_all)

    if tier in ("host", "disk"):
        # offload tiers keep the full path OUT of device memory, but the
        # recorder still runs compiled: scan one WINDOW of steps at a
        # time and spill each window's (Ws, Gs) through the codec — the
        # device never holds more than one window of the path (the read
        # path mirrors this via core.store.SegmentStreamer)
        L = auto_window(meta.steps, window)
        params = params0
        pending = None
        for a in range(0, meta.steps, L):
            b = min(meta.steps, a + L)
            params, vel, Ws, Gs = _train_scan(
                params, vel, cols, idx_dev[a:b], lr_dev[a:b], ones, mom,
                grad_fn=grad_fn, momentum=momentum, in_place=in_place,
                pin=pin)
            # the previous window's encoded entries cross to the host
            # while this one trains
            history.land_window(pending)
            pending = history.encode_window(Ws, Gs)
            del Ws, Gs
        history.land_window(pending)
        history.finalize(params)
        return params, history

    params, _, Ws, Gs = _train_scan(
        params0, vel, cols, idx_dev, lr_dev, ones, mom, grad_fn=grad_fn,
        momentum=momentum, in_place=in_place, pin=pin)
    history.set_stacked(Ws, Gs, final_params=params)
    return params, history


# --------------------------------------------------------------------------
# BaseL: exact retraining from scratch, also one scan
# --------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("grad_fn", "momentum", "mode",
                                   "in_place"))
def _baseline_scan(params0, vel0, cols, sd: DeviceSchedule, mom, *, grad_fn,
                   momentum: bool, mode: str, in_place: bool):
    def body(carry, t):
        params, vel = carry
        batch = _read_batch(cols, sd.idx[t], in_place)
        w = sd.kept_w[t]
        if mode == "add":
            joined = _gather(cols, sd.changed_idx[t])
            batch = {k: jnp.concatenate([c, joined[k]])
                     for k, c in batch.items()}
            w = jnp.concatenate([w, sd.changed_w[t]])
        g = grad_fn(params, batch, w)
        if momentum:
            new_p, new_vel = _momentum_math(params, vel, g, sd.lr[t], mom)
        else:
            new_p, new_vel = _sgd_math(params, g, sd.lr[t]), vel
        upd = sd.kept[t] > 0 if mode == "delete" else jnp.bool_(True)
        new_p = jax.tree.map(lambda n, o: jnp.where(upd, n, o), new_p, params)
        if momentum:
            new_vel = jax.tree.map(lambda n, o: jnp.where(upd, n, o),
                                   new_vel, vel)
        return (new_p, new_vel), None

    T = sd.idx.shape[0]
    (pT, _), _ = jax.lax.scan(body, (params0, vel0), jnp.arange(T))
    return pT


def run_baseline(
    objective,
    ds: Dataset,
    meta: HistoryMeta,
    params0,
    changed_idx: np.ndarray,
    mode: str = "delete",
    impl: str = "scan",
) -> Tuple[Any, RetrainStats]:
    """BaseL: exact retraining on the modified dataset, replaying the
    original schedule (paper eq. (1) / (S6))."""
    assert mode in ("delete", "add")
    changed_idx = np.asarray(changed_idx, dtype=np.int64)
    grad_fn = objective.make_grad_fn()
    momentum = bool(meta.momentum)
    stats = RetrainStats()
    t0 = time.perf_counter()
    r_pad = _next_pow2(max(1, len(changed_idx)))
    sched = build_schedule(meta.seed, meta.steps, meta.n, meta.batch_size,
                           changed_idx, mode, r_pad, meta.lr_at)

    eff = sched.kept.astype(np.int64) \
        + (sched.dB.astype(np.int64) if mode == "add" else 0)
    nonskip = eff > 0
    stats.grad_examples = int(eff[nonskip].sum())
    stats.skipped_steps = int((~nonskip).sum())
    stats.explicit_steps = meta.steps

    if impl == "python":
        params = params0
        vel = _tree_zeros(params0) if momentum else None
        B = min(meta.batch_size, meta.n)
        n_add = len(changed_idx) if mode == "add" else 0
        pad_to = B + n_add
        for t in range(meta.steps):
            idx = batch_indices(meta.seed, t, meta.n, meta.batch_size)
            if mode == "delete":
                eff_t = idx[~np.isin(idx, changed_idx)]
            else:
                joins = addition_mask(meta.seed, t, meta.n, meta.batch_size,
                                      n_add)
                eff_t = np.concatenate([idx, changed_idx[joins]])
            if len(eff_t) == 0:
                continue
            batch, weights = ds.padded_batch(eff_t, pad_to)
            g = grad_fn(params, batch, weights)
            if momentum:
                params, vel = _momentum_apply(params, vel, g,
                                              jnp.float32(meta.lr_at(t)),
                                              jnp.float32(meta.momentum))
            else:
                params = _sgd_apply(params, g, jnp.float32(meta.lr_at(t)))
        stats.wall_time_s = time.perf_counter() - t0
        return params, stats

    vel = _tree_zeros(params0) if momentum else None
    params = _baseline_scan(params0, vel, ds.device_columns(),
                            to_device(sched), jnp.float32(meta.momentum),
                            grad_fn=grad_fn, momentum=momentum, mode=mode,
                            in_place=batch_in_place(sched.idx))
    jax.block_until_ready(params)
    stats.wall_time_s = time.perf_counter() - t0
    return params, stats


# --------------------------------------------------------------------------
# Phase 2: REPLAY — Algorithm 1 with scanned approx segments
# --------------------------------------------------------------------------


def _changed_grad(grad_fn, params, cols, sd: DeviceSchedule, t):
    """The gradient over step `t`'s changed rows, or zeros when the batch
    holds none of them: the padded changed-row batch is only computed when
    it carries weight (at LM scale it costs as much as the step's own
    batch)."""
    return jax.lax.cond(
        sd.dB[t] > 0,
        lambda p: grad_fn(p, _gather(cols, sd.changed_idx[t]),
                          sd.changed_w[t]),
        lambda p: jax.tree.map(jnp.zeros_like, p), params)


def _replay_segment_impl(params, vel, t0, off, W, G, cols,
                         sd: DeviceSchedule, dWs, dGs, B, clip, mom, *,
                         grad_fn, sign: int, momentum: bool, fused: str,
                         span: int, shards=None):
    """One approx segment [t0, t0+span) as a single scan.

    Per step: dynamic-slice (w_t, g_t) out of the stacked history WINDOW
    (leaves indexed ``t - off``; ``off`` is 0 for a fully resident path and
    the window start for a streamed one — see `core.store`), gradient on
    the <= R changed rows only, compact L-BFGS correction, fused update.
    The Algorithm-4 guard verdict is DETECTION-only here: the stacked `oks`
    output flags failing steps, and the caller re-runs the segment split at
    the first failure so that step executes as a host explicit step (which
    admits its L-BFGS pair — see `run_replay`).  Steps after a failed guard
    may therefore carry garbage; the caller discards them.

    Under `core.store.PlacedReplay` it is a plain jit over sharded state:
    `shards` routes each leaf's kernel call per device.

    ENCODED windows (`EncodedLeaf` leaves — the streamers' kernel decode
    mode) dequantize per step inside this scan.  On the default jnp path
    `entry_at` slice-decodes (XLA fuses the elementwise dequant); the
    non-momentum Pallas path instead routes the encoded leaves
    straight into `kernels.dequant_update` — dequant fused with the
    subtract (v = w - w_t) and with the approx update in registers, no
    f32 window copy anywhere."""
    use_dq = (is_encoded_window(W) and not momentum
              and fused in ("pallas", "interpret"))

    def body(carry, t):
        params, vel = carry
        lr, dB, kept = sd.lr[t], sd.dB[t], sd.kept[t]
        g_changed = _changed_grad(grad_fn, params, cols, sd, t)
        if use_dq:
            v = _dequant_sub_tree(params, W, t - off, fused, shards)
        else:
            v = tree_sub(params, entry_at(W, t, off))
        bv = lbfgs_hvp_stacked_pytree(dWs, dGs, v)
        guard_ok = tree_norm(bv) <= clip * tree_norm(v)
        if momentum:
            g_t = entry_at(G, t, off)
            g_est = _approx_math(g_t, bv, g_changed, B, dB, sign)
            ok = jnp.logical_and(tree_all_finite(g_est), guard_ok)
            new_p, new_vel = _momentum_math(params, vel, g_est, lr, mom)
        elif use_dq:
            new_p = _dequant_fused_update(params, G, t - off, bv, g_changed,
                                          lr, B, dB, sign, fused, shards)
            ok = jnp.logical_and(tree_all_finite(new_p), guard_ok)
            new_vel = vel
        else:
            g_t = entry_at(G, t, off)
            new_p = _flat_fused_update(params, g_t, bv, g_changed, lr, B, dB,
                                       sign, fused, shards=shards)
            ok = jnp.logical_and(tree_all_finite(new_p), guard_ok)
            new_vel = vel

        upd = kept > 0 if sign > 0 else jnp.bool_(True)
        new_p = jax.tree.map(lambda n, o: jnp.where(upd, n, o), new_p, params)
        new_vel = jax.tree.map(lambda n, o: jnp.where(upd, n, o), new_vel, vel)
        return (new_p, new_vel), ok

    (params, vel), oks = jax.lax.scan(body, (params, vel),
                                      t0 + jnp.arange(span))
    return params, vel, oks


_replay_segment = partial(jax.jit, static_argnames=(
    "grad_fn", "sign", "momentum", "fused", "span", "shards"))(
        _replay_segment_impl)


def run_replay(
    objective,
    history: TrainingHistory,
    ds: Dataset,
    changed_idx: np.ndarray,
    cfg: DeltaGradConfig,
    mode: str = "delete",
    params0=None,
    placement=None,
    store: Optional[HistoryStore] = None,
) -> Tuple[Any, RetrainStats]:
    """Algorithm 1 (GD + SGD unified; GD == SGD with batch_size >= n).

    Where the history bytes live is `core.store.HistoryStore`'s problem:
    stacked/device tiers replay fully resident (optionally mesh-sharded —
    pass a `PlacementPolicy` or a prebuilt store), host/disk tiers stream
    device-resident segment windows with prefetch.  Only
    ``cfg.impl="python"`` still selects the per-step oracle loop."""
    assert mode in ("delete", "add")
    if cfg.impl == "python":
        return _run_replay_python(objective, history, ds, changed_idx, cfg,
                                  mode, params0)
    if store is None:
        store = HistoryStore.create(history, placement=placement,
                                    window=cfg.stream_window,
                                    decode=cfg.stream_decode)

    meta = history.meta
    changed_idx = np.asarray(changed_idx, dtype=np.int64)
    r = len(changed_idx)
    B = min(meta.batch_size, meta.n)
    grad_fn = objective.make_grad_fn()
    momentum = bool(meta.momentum)
    sign = 1 if mode == "delete" else -1
    fused = _resolve_fused(cfg.fused)
    r_pad = cfg.removal_pad or _next_pow2(max(1, min(r, B)))
    runner = store.sharded_replay()

    t_start = time.perf_counter()
    with obs_trace.span("replay.schedule_build", steps=meta.steps, r=r):
        sched = build_schedule(meta.seed, meta.steps, meta.n,
                               meta.batch_size, changed_idx, mode, r_pad,
                               meta.lr_at)
        plan = build_plan(cfg, sched)
        sd = to_device(sched)
    # explicit steps donate their parameters: never the caller's
    params = (jax.tree.map(jnp.copy, params0) if params0 is not None
              else store.params0())
    pin = shards = None
    if runner is not None:
        params = runner.place(params)
        pin, shards = runner.pin(params), runner.kernel_shards(params)
    in_place = batch_in_place(sched.idx, sd.idx.shape[1])
    cols = ds.device_columns()
    buffer = LbfgsBuffer(cfg.history_size, curvature_eps=cfg.curvature_eps)

    vel = _tree_zeros(params) if momentum else None
    Bf = jnp.float32(B)
    clip = jnp.float32(cfg.guard_norm_clip)
    mom = jnp.float32(meta.momentum)
    stats = RetrainStats()
    T = meta.steps
    seg_oks: List[Tuple[int, int, Any]] = []  # (t0, t1, device flags)

    n_params = (sum(x.size for x in jax.tree.leaves(params))
                if obs_trace.enabled() else 0)

    def scan_segment(p, v, a, b):
        with obs_trace.span(
                "replay.scan", t0=a, t1=b,
                pred_s=_scan_pred(n_params, b - a, r_pad,
                                  cfg.history_size, momentum)):
            W, G, off = store.window(a, b)
            if runner is not None:
                fn = runner.wrap(
                    partial(_replay_segment_impl, grad_fn=grad_fn,
                            sign=sign, momentum=momentum, fused=fused,
                            span=b - a, shards=shards),
                    key=("replay", b - a, sign, momentum, fused),
                    n_outputs=3)
                return fn(p, v, jnp.int32(a), jnp.int32(off), W, G, cols,
                          sd, dWs, dGs, Bf, clip, mom)
            return _replay_segment(
                p, v, jnp.int32(a), jnp.int32(off), W, G, cols, sd, dWs,
                dGs, Bf, clip, mom, grad_fn=grad_fn, sign=sign,
                momentum=momentum, fused=fused, span=b - a)

    def explicit_step(p, v, tt):
        with obs_trace.span("replay.explicit", t0=tt, steps=1):
            return _host_explicit_step(
                grad_fn, buffer, p, v, tt, store, cols, sd,
                float(sched.kept[tt]), float(sched.dB[tt]), Bf, mom, sign,
                momentum, in_place, pin, stats)

    t = 0
    while t < T:
        code = plan[t]
        if code == EXPLICIT or (code == APPROX and len(buffer) == 0):
            params, vel = explicit_step(params, vel, t)
            t += 1
        elif code == SKIP and len(buffer) == 0:
            t += 1
        else:
            t2 = t
            while t2 < T and plan[t2] != EXPLICIT:
                t2 += 1
            while t < t2:
                # a streamed store may cap the scan at its window boundary;
                # resident stores always run the whole segment at once
                b = store.span_end(t, t2)
                dWs, dGs = buffer.stacked()
                p_in, v_in = params, vel
                params, vel, oks = scan_segment(p_in, v_in, t, b)
                if cfg.guard:
                    # segment-splitting retry: one host sync per scanned
                    # segment (guard ON only); if any step tripped the
                    # Algorithm-4 guard, keep the all-ok prefix, run the
                    # tripped step as a host explicit step (admitting its
                    # L-BFGS pair like the python loop), and rescan the rest
                    # with the enlarged buffer.  Split spans stay below the
                    # explicit period, so at most period-2 extra scan
                    # compilations exist per stream — the prefix re-run is
                    # the real cost when fallbacks are dense (ROADMAP: a
                    # lax.while_loop formulation could keep this on device).
                    with obs_trace.span("replay.host_sync", kind="guard"):
                        ok = np.asarray(oks)
                    fell = np.flatnonzero((plan[t:b] != SKIP) & ~ok)
                    if fell.size:
                        tf = t + int(fell[0])
                        with obs_trace.span("replay.guard_retry", t=tf,
                                            prefix=tf - t):
                            if tf > t:
                                params, vel, oks_p = scan_segment(
                                    p_in, v_in, t, tf)
                                seg_oks.append((t, tf, oks_p))
                            else:
                                params, vel = p_in, v_in
                            stats.guard_fallbacks += 1
                            params, vel = explicit_step(params, vel, tf)
                        t = tf + 1
                        continue
                seg_oks.append((t, b, oks))
                t = b

    # counters resolved once at the end — no per-step host syncs (with the
    # guard enabled, recorded segments are all-ok by construction: fallback
    # steps were peeled off and accounted as host explicit steps above)
    for t0_, t1_, oks in seg_oks:
        nonskip = plan[t0_:t1_] != SKIP
        dB_i = sched.dB[t0_:t1_].astype(np.int64)
        if cfg.guard:
            stats.approx_steps += int((nonskip & np.asarray(oks)).sum())
        else:
            stats.approx_steps += int(nonskip.sum())
        stats.grad_examples += int(dB_i[nonskip].sum())
    stats.skipped_steps = int((plan == SKIP).sum())
    base = sched.kept.astype(np.int64) if mode == "delete" \
        else sched.kept.astype(np.int64) + sched.dB.astype(np.int64)
    stats.grad_examples_baseline = int(base.sum())
    jax.block_until_ready(params)
    stats.wall_time_s = time.perf_counter() - t_start
    stats.extra["buffer_admitted"] = buffer.admitted
    stats.extra["buffer_rejected"] = buffer.rejected
    stats.extra["impl"] = "scan"
    stats.extra["fused"] = fused
    stats.extra["store"] = store.kind
    stats.extra["hbm_high_water"] = store.hbm_high_water()
    stats.extra["segments"] = max(1, len(seg_oks))
    if getattr(store, "windows_fetched", 0):
        stats.extra["windows"] = store.windows_fetched
        stats.extra["host_wait_s"] = store.host_wait_s
        stats.extra["prefetch_depth"] = store.depth_used
        stats.extra["host_stage_high"] = store.host_stage_high
        stats.extra["stream_decode"] = store.decode_mode
        stats.extra["encoded_bytes_high"] = store.enc_bytes_high
        stats.extra["compression_ratio"] = store.compression_ratio
    if history.io_read_s or history.io_write_s:
        # disk-tier spill IO (cumulative; windowed spills batch one .npz
        # per window — see TrainingHistory)
        stats.extra["spill_io_read_s"] = history.io_read_s
        stats.extra["spill_io_write_s"] = history.io_write_s
    if runner is not None:
        stats.extra["mesh"] = runner.placement.describe()
    _publish_replay_metrics(stats, store, in_place)
    return params, stats


@partial(jax.jit, static_argnames=("grad_fn", "sign", "momentum",
                                   "in_place", "pin"),
         donate_argnames=("params",))
def _explicit_step(params, vel, t, W, G, i, cols, sd: DeviceSchedule, B,
                   mom, *, grad_fn, sign: int, momentum: bool,
                   in_place: bool, pin=None):
    """The whole explicit step as ONE program: kept + changed gradients
    against the history entry (w_t, g_t) — read at index ``i`` of the
    store's window (W, G) and decoded here, so no decoded copy of it is
    ever stored — pair construction (with the Algorithm-4 admission inner
    products), and the parameter update.  The host only syncs the two
    admission scalars — one round-trip per explicit step.  `pin`
    (`core.store.PlacedReplay.pin`) keeps the parameter-shaped outputs on
    the parameters' placement.  `params` is donated: the new parameters
    take its buffers (a parameter-sized tree less at an LM step's peak)."""
    k, dB, lr = sd.kept[t], sd.dB[t], sd.lr[t]
    g_kept = grad_fn(params, _read_batch(cols, sd.idx[t], in_place),
                     sd.kept_w[t])
    g_changed = _changed_grad(grad_fn, params, cols, sd, t)
    g_full, g_step = _combine_explicit(g_kept, g_changed, k, dB, B, sign)
    dw = constrain(tree_sub(params, entry_at(W, i, 0)), pin)
    dg = constrain(tree_sub(g_full, entry_at(G, i, 0)), pin)
    admit = jnp.stack([tree_vdot(dg, dw), tree_vdot(dw, dw)])
    if momentum:
        new_p, new_vel = _momentum_math(params, vel, g_step, lr, mom)
    else:
        new_p, new_vel = _sgd_math(params, g_step, lr), vel
    return constrain(new_p, pin), new_vel, dw, dg, admit


def _host_explicit_step(grad_fn, buffer, params, vel, t, store, cols, sd,
                        k, dB, Bf, mom, sign, momentum, in_place, pin,
                        stats):
    """One explicit step (host-driven: it mutates the L-BFGS buffer)."""
    W, G, i = store.entry_window(t)
    params, vel, dw, dg, admit = _explicit_step(
        params, vel, t, W, G, i, cols, sd, Bf, mom, grad_fn=grad_fn,
        sign=sign, momentum=momentum, in_place=in_place, pin=pin)
    with obs_trace.span("replay.host_sync", kind="admit"):
        curv, ss = np.asarray(admit)
    if not buffer.add_pair(dw, dg, float(curv), float(ss)):
        stats.pairs_rejected += 1
    stats.grad_examples += int(k + dB)
    stats.explicit_steps += 1
    return params, vel


def _run_replay_python(objective, history, ds, changed_idx, cfg, mode,
                       params0):
    """The pre-refactor per-step loop, verbatim — parity oracle + disk tier."""
    meta = history.meta
    changed_idx = np.asarray(changed_idx, dtype=np.int64)
    r = len(changed_idx)
    n, B = meta.n, min(meta.batch_size, meta.n)
    grad_fn = objective.make_grad_fn()
    buffer = LbfgsBuffer(cfg.history_size, curvature_eps=cfg.curvature_eps)

    r_pad = cfg.removal_pad or _next_pow2(max(1, min(r, B)))
    n_add = r if mode == "add" else 0
    clip = jnp.float32(cfg.guard_norm_clip)
    mom = jnp.float32(meta.momentum) if meta.momentum else None

    params = params0 if params0 is not None else history.params_at(0)
    vel = _tree_zeros(params) if meta.momentum else None
    stats = RetrainStats()
    t0 = time.perf_counter()

    for t in range(meta.steps):
        idx = batch_indices(meta.seed, t, n, meta.batch_size)
        if mode == "delete":
            kept_idx, changed_in = ds.split_batch(idx, removed_set=changed_idx)
        else:
            joins = addition_mask(meta.seed, t, n, meta.batch_size, n_add)
            kept_idx, changed_in = idx, changed_idx[joins]
        dB = len(changed_in)
        k = len(kept_idx)
        lr = jnp.float32(meta.lr_at(t))
        stats.grad_examples_baseline += (k if mode == "delete" else k + dB)

        if mode == "delete" and k == 0:
            stats.skipped_steps += 1  # paper §3: B - dB_t == 0 → no update
            continue

        explicit = cfg.is_explicit(t)
        w_t, g_t = history.entry(t)
        g_changed = None  # set by the approx attempt; reused on fallback

        if not explicit and len(buffer) == 0:
            explicit = True  # nothing to approximate with yet

        if not explicit:
            # ---- approx step: gradients only on the changed samples --------
            if dB > 0:
                cb, cw = ds.padded_batch(changed_in, r_pad)
                g_changed = grad_fn(params, cb, cw)
                stats.grad_examples += dB
            else:
                g_changed = _tree_zeros(params)
            dWs, dGs = buffer.stacked()
            sign = 1 if mode == "delete" else -1
            if mom is not None:
                g_est, ok = _approx_gradient(
                    params, w_t, g_t, dWs, dGs, g_changed,
                    jnp.float32(B), jnp.float32(dB), clip, sign)
                if cfg.guard and not bool(ok):
                    stats.guard_fallbacks += 1
                    explicit = True
                else:
                    params, vel = _momentum_apply(params, vel, g_est, lr, mom)
                    stats.approx_steps += 1
            else:
                new_params, ok = _approx_update(
                    params, w_t, g_t, dWs, dGs, g_changed, lr,
                    jnp.float32(B), jnp.float32(dB), clip, sign
                )
                if cfg.guard and not bool(ok):
                    stats.guard_fallbacks += 1
                    explicit = True  # fall through to the explicit branch
                else:
                    params = new_params
                    stats.approx_steps += 1

        if explicit:
            # ---- explicit step: full-batch gradient at w^I_t ---------------
            kb, kw = ds.padded_batch(kept_idx,
                                     B if mode == "delete" else B + n_add)
            g_kept = grad_fn(params, kb, kw)
            if g_changed is None:
                # regular explicit step — the changed-row gradient was not
                # evaluated yet; a guard fallback already computed (and
                # charged) it at these same params, so reuse it there and
                # charge this step its true cost k + dB either way.
                if dB > 0:
                    cb, cw = ds.padded_batch(changed_in, r_pad)
                    g_changed = grad_fn(params, cb, cw)
                else:
                    g_changed = _tree_zeros(params)
                stats.grad_examples += dB
            stats.grad_examples += k

            if mode == "delete":
                # mean over the ORIGINAL batch (pair definition, §A.1.2)
                g_full = jax.tree.map(
                    lambda a, b: (k * a + dB * b) / float(B), g_kept, g_changed
                )
                g_step = g_kept  # mean over kept == leave-r-out update
            else:
                g_full = g_kept  # original batch == kept in add mode
                g_step = jax.tree.map(
                    lambda a, b: (B * a + dB * b) / float(B + dB),
                    g_kept, g_changed
                )

            dw = tree_sub(params, w_t)
            dg = tree_sub(g_full, g_t)
            if not buffer.add(dw, dg):
                stats.pairs_rejected += 1
            if mom is not None:
                params, vel = _momentum_apply(params, vel, g_step, lr, mom)
            else:
                params = _sgd_apply(params, g_step, lr)
            stats.explicit_steps += 1

    stats.wall_time_s = time.perf_counter() - t0
    stats.extra["buffer_admitted"] = buffer.admitted
    stats.extra["buffer_rejected"] = buffer.rejected
    stats.extra["impl"] = "python"
    return params, stats


# --------------------------------------------------------------------------
# Phase 2': ONLINE — Algorithm 3 (delete AND add, SGD AND heavy-ball) with
# history rewrite in the scan
# --------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("sign", "momentum"))
def _online_approx_step(params, vel, w_t, g_t, dWs, dGs, g_one, lr, kept, dB,
                        clip, mom, *, sign: int, momentum: bool):
    """One Algorithm-3 approx step — the quasi-Hessian-corrected gradient of
    the post-request objective at params (eq. (S62), with the per-step
    PRE-request batch size kept+dB for deletes / kept for adds), the
    resulting SGD or heavy-ball update, and the guard verdict.

    The pair ring is the zeros-initialized device ring and may be PARTIALLY
    filled during burn-in: the masked compact solve derives slot occupancy
    from the ring itself (`lbfgs.ring_valid_mask`) and is bitwise identical
    to the unmasked solve once the ring is full.

    This is the ONE definition shared verbatim by the scan body and the
    per-step python oracle (`core.online`), which is what makes
    scan-vs-python parity hold to float32 round-off."""
    b_prev = kept + dB if sign > 0 else kept
    v = tree_sub(params, w_t)
    bv = lbfgs_hvp_stacked_pytree(dWs, dGs, v, masked=True)
    g_new = _approx_math(g_t, bv, g_one, b_prev, dB, sign)
    if momentum:
        new_p, new_vel = _momentum_math(params, vel, g_new, lr, mom)
    else:
        new_p, new_vel = _sgd_math(params, g_new, lr), vel
    ok = jnp.logical_and(tree_all_finite(new_p),
                         tree_norm(bv) <= clip * tree_norm(v))
    return new_p, new_vel, g_new, ok


@partial(jax.jit, static_argnames=("sign", "momentum"))
def _online_explicit_math(params, vel, w_t, g_t, g_base, g_one, lr, kept, dB,
                          mom, *, sign: int, momentum: bool):
    """Online explicit-step math shared by the device step and the oracle.

    `g_base` is the gradient over the scheduled kept rows — the POST-request
    batch for deletes, the PRE-request batch for adds; mixing in the request
    row's `g_one` yields the other one.  Returns the updated (params, vel),
    the post-request gradient `g_cur` (the cache rewrite value), and the
    L-BFGS pair built against the PRE-request gradient (paper §A.1.2 pair
    definition carried over to the rewritten path)."""
    has = dB > 0
    denom = jnp.maximum(kept + dB, 1.0)
    mix = jax.tree.map(
        lambda a, b: jnp.where(has, (kept * a + dB * b) / denom, a),
        g_base, g_one)
    g_cur, g_prev = (g_base, mix) if sign > 0 else (mix, g_base)
    dw = tree_sub(params, w_t)
    dg = tree_sub(g_prev, g_t)
    admit = jnp.stack([tree_vdot(dg, dw), tree_vdot(dw, dw)])
    if momentum:
        new_p, new_vel = _momentum_math(params, vel, g_cur, lr, mom)
    else:
        new_p, new_vel = _sgd_math(params, g_cur, lr), vel
    return new_p, new_vel, g_cur, dw, dg, admit


def _online_segment_impl(params, vel, t0, off, W, G, cols,
                         sd: DeviceSchedule, dWs, dGs, clip, mom, *,
                         grad_fn, sign: int, momentum: bool, span: int):
    """Online approx segment: like `_replay_segment` but with the per-step
    effective batch size (paper's n-k bookkeeping), the velocity carried in
    the scan state for heavy-ball histories, and the rewrite pairs
    (w_t <- w^I_t, g_t <- g^a_t, eq. (S62)) emitted as stacked scan outputs.
    Guard verdicts are detection-only, as in `_replay_segment`.  History
    leaves are indexed ``t - off`` (window offset for streamed stores)."""

    def body(carry, t):
        params, vel = carry
        w_t = entry_at(W, t, off)
        g_t = entry_at(G, t, off)
        lr, dB, kept = sd.lr[t], sd.dB[t], sd.kept[t]
        g_one = _changed_grad(grad_fn, params, cols, sd, t)
        new_p, new_vel, g_new, ok = _online_approx_step(
            params, vel, w_t, g_t, dWs, dGs, g_one, lr, kept, dB, clip, mom,
            sign=sign, momentum=momentum)

        if sign > 0:  # Algorithm 3's skip: request emptied the whole batch
            skip = jnp.logical_and(kept <= 0, dB > 0)
        else:
            skip = jnp.bool_(False)
        new_p = jax.tree.map(lambda n, o: jnp.where(skip, o, n), new_p,
                             params)
        new_vel = jax.tree.map(lambda n, o: jnp.where(skip, o, n), new_vel,
                               vel)
        w_wr = jax.tree.map(lambda n, o: jnp.where(skip, o, n), params, w_t)
        g_wr = jax.tree.map(lambda n, o: jnp.where(skip, o, n), g_new, g_t)
        return (new_p, new_vel), (w_wr, g_wr, ok)

    (params, vel), (w_writes, g_writes, oks) = jax.lax.scan(
        body, (params, vel), t0 + jnp.arange(span))
    return params, vel, w_writes, g_writes, oks


_online_segment = partial(jax.jit, static_argnames=(
    "grad_fn", "sign", "momentum", "span"))(_online_segment_impl)


@partial(jax.jit, static_argnames=("grad_fn", "sign", "momentum",
                                   "in_place"))
def _online_explicit_step(params, vel, t, w_t, g_t, cols,
                          sd: DeviceSchedule, mom, *, grad_fn, sign: int,
                          momentum: bool, in_place: bool):
    """Online explicit step fused into one program: kept and changed-row
    gradients against the store-served history entry, the pre/post-request
    gradient pair, and the update.  Only the two L-BFGS admission scalars
    return to the host; the cache rewrite value `g_cur` is handed back so
    the caller can batch it into the end-of-request flush instead of
    scattering per step."""
    kept, dB, lr = sd.kept[t], sd.dB[t], sd.lr[t]
    g_base = grad_fn(params, _read_batch(cols, sd.idx[t], in_place),
                     sd.kept_w[t])
    g_one = _changed_grad(grad_fn, params, cols, sd, t)
    return _online_explicit_math(params, vel, w_t, g_t, g_base, g_one, lr,
                                 kept, dB, mom, sign=sign, momentum=momentum)


@jax.jit
def _ring_append(dWs, dGs, dw, dg, admit, eps):
    """Where-gated shift-append of the stacked (m, ...) pair ring with the
    admission rule `<dg, dw> >= eps * <dw, dw>` resolved ON DEVICE.  The
    ring starts as exact zeros, so the masked compact solve
    (`lbfgs.compact_coeffs_masked` via `ring_valid_mask`) can consume it at
    ANY fill level — burn-in no longer needs a host-side buffer phase.
    Shared by the fused device step and the python oracle so admission is
    one definition."""
    ok = jnp.logical_and(admit[1] > 0.0, admit[0] >= eps * admit[1])
    dWs = jax.tree.map(
        lambda b, n: jnp.where(
            ok, jnp.concatenate([b[1:], n[None].astype(b.dtype)]), b),
        dWs, dw)
    dGs = jax.tree.map(
        lambda b, n: jnp.where(
            ok, jnp.concatenate([b[1:], n[None].astype(b.dtype)]), b),
        dGs, dg)
    return dWs, dGs


@partial(jax.jit, static_argnames=("grad_fn", "sign", "momentum",
                                   "in_place"))
def _online_explicit_fused(params, vel, t, w_t, g_t, cols,
                           sd: DeviceSchedule, dWs, dGs, eps, mom, *,
                           grad_fn, sign: int, momentum: bool,
                           in_place: bool):
    """`_online_explicit_step` with the Algorithm-4 pair admission resolved
    ON DEVICE via `_ring_append` — every explicit step (burn-in included)
    runs this fused program against the zeros-initialized ring, so an
    online request has ZERO mid-request host syncs (guard off).  No fill
    count crosses this program's boundary: occupancy is derived from the
    ring by the masked solve, which keeps this program — and so the
    full-ring replay results — bitwise identical to the pre-masking
    engine."""
    new_p, new_vel, g_cur, dw, dg, admit = _online_explicit_step(
        params, vel, t, w_t, g_t, cols, sd, mom, grad_fn=grad_fn, sign=sign,
        momentum=momentum, in_place=in_place)
    dWs, dGs = _ring_append(dWs, dGs, dw, dg, admit, eps)
    return new_p, new_vel, g_cur, dWs, dGs


def run_online_request(
    grad_fn,
    store: HistoryStore,
    cols,
    sched: ReplaySchedule,
    cfg: DeltaGradConfig,
    static_dev: Optional[Tuple[jax.Array, jax.Array]] = None,
    commit: bool = True,
) -> Tuple[Any, RetrainStats]:
    """One online request — a single row or a coalesced GROUP of rows
    (delete or add — `sched.mode`, width `sched.r_pad`) — against the
    current cached path, served through a `core.store.HistoryStore`
    (resident — optionally mesh-sharded — or streamed from an offload
    tier).  Returns (params, stats); rewrites are committed into the store
    (and through it into the history) before returning.

    `sched` comes from `data.sampler.build_online_schedule` (the caller owns
    the stream state: liveness, added rows, join masks).  `static_dev` is
    the request-invariant (idx, lr) pair already on device — pass it so a
    stream uploads the (T, B [+pad]) schedule once, not per request.

    History rewrites are fully deferred: explicit steps hand their (w, g)
    rewrite back instead of scattering per step, segment outputs stay as
    stacked chunks, and each maximal contiguous region of rewrites lands in
    ONE jitted assembly + scatter (resident) or codec write-back (streamed)
    in `store.commit` (sound because every step is visited once and reads
    only its original entry).  Momentum-trained histories replay with the
    heavy-ball velocity reconstructed from vel_0 = 0 in the scan carry; the
    cache keeps storing plain gradients, so each request's reconstruction
    is self-contained (Algorithm 3 with momentum)."""
    meta = store.meta
    op = sched.mode
    sign = 1 if op == "delete" else -1
    momentum = bool(meta.momentum)
    plan = build_plan(cfg, sched, online=True)
    sd = to_device(sched, *(static_dev or (None, None)))
    runner = store.sharded_replay()
    in_place = batch_in_place(sched.idx, sd.idx.shape[1])
    params = store.params0()  # w_0 is never rewritten
    n_params = (sum(x.size for x in jax.tree.leaves(params))
                if obs_trace.enabled() else 0)
    vel = _tree_zeros(params) if momentum else None
    clip = jnp.float32(cfg.guard_norm_clip)
    mom = jnp.float32(meta.momentum)
    stats = RetrainStats()
    T = meta.steps
    seg_oks: List[Tuple[int, int, Any]] = []

    # Deferred history rewrites.  Every step t is visited exactly once per
    # request and only ever READS the original entry at t, so nothing needs
    # to land in (W, G) before the request completes: rewrites accumulate as
    # contiguous chunks — explicit-step runs and scanned-segment outputs —
    # and ONE jitted assembly per contiguous region scatters them all
    # (`store.commit`; steady streams compile it once).
    regions: List[Tuple[int, List[str], List, List]] = []
    write_end = -1

    def _region(t):
        if not regions or t != write_end:
            regions.append((t, [], [], []))
        return regions[-1]

    def note_single(t, w, g):
        nonlocal write_end
        _, kinds, pw, pg = _region(t)
        if not kinds or kinds[-1] != "run":
            kinds.append("run")
            pw.append([])
            pg.append([])
        pw[-1].append(w)
        pg[-1].append(g)
        write_end = t + 1

    def note_seg(t, span, w, g):
        nonlocal write_end
        _, kinds, pw, pg = _region(t)
        kinds.append("seg")
        pw.append(w)
        pg.append(g)
        write_end = t + span

    # The L-BFGS pair ring lives ON DEVICE from step 0: a zeros-initialized
    # stacked (m, ...) ring plus an admitted-pair `count`, appended to by the
    # where-gated `_ring_append` inside every fused explicit step and read by
    # scanned segments through the MASKED compact solve
    # (`lbfgs.compact_coeffs_masked` — exact at any fill level, bitwise
    # identical to the unmasked solve once the ring is full).  Burn-in no
    # longer runs a host-side buffer phase, so a request has zero
    # mid-request host syncs even before the ring fills (guard off).
    dWs = jax.tree.map(
        lambda x: jnp.zeros((cfg.history_size,) + x.shape, x.dtype), params)
    dGs = dWs
    ring_started = False  # True once any explicit step ran (plan invariant:
    #                       the first non-skipped step is always explicit)
    eps = jnp.float32(cfg.curvature_eps)

    def do_explicit(params, vel, t, r2):
        nonlocal dWs, dGs, ring_started
        with obs_trace.span("replay.explicit", t0=t, steps=r2 - t):
            for tt in range(t, r2):
                p_in = params
                w_t, g_t = store.entry(tt)
                params, vel, g_cur, dWs, dGs = _online_explicit_fused(
                    params, vel, tt, w_t, g_t, cols, sd, dWs, dGs, eps,
                    mom, grad_fn=grad_fn, sign=sign, momentum=momentum,
                    in_place=in_place)
                note_single(tt, p_in, g_cur)
        ring_started = True
        stats.grad_examples += int(
            (sched.kept[t:r2] + sched.dB[t:r2]).sum())
        stats.explicit_steps += r2 - t
        return params, vel

    t = 0
    while t < T:
        code = plan[t]
        if code == EXPLICIT or (code == APPROX and not ring_started):
            r2 = t + 1
            if code == EXPLICIT:
                while r2 < T and plan[r2] == EXPLICIT:
                    r2 += 1
            params, vel = do_explicit(params, vel, t, r2)
            t = r2
        elif code == SKIP and not ring_started:
            t += 1  # entry stays as-is; the write region simply breaks here
        else:
            t2 = t
            while t2 < T and plan[t2] != EXPLICIT:
                t2 += 1

            def scan_segment(p, v, a, b, pW, pG):
                with obs_trace.span(
                        "replay.scan", t0=a, t1=b,
                        pred_s=_scan_pred(n_params, b - a, sched.r_pad,
                                          cfg.history_size, momentum)):
                    Wd, Gd, off = store.window(a, b)
                    if runner is not None:
                        fn = runner.wrap(
                            partial(_online_segment_impl,
                                    grad_fn=grad_fn, sign=sign,
                                    momentum=momentum, span=b - a),
                            key=("online", b - a, sign, momentum),
                            n_outputs=5)
                        return fn(p, v, jnp.int32(a), jnp.int32(off), Wd,
                                  Gd, cols, sd, pW, pG, clip, mom)
                    return _online_segment(
                        p, v, jnp.int32(a), jnp.int32(off), Wd, Gd, cols,
                        sd, pW, pG, clip, mom, grad_fn=grad_fn,
                        sign=sign, momentum=momentum, span=b - a)

            while t < t2:
                b = store.span_end(t, t2)
                pW, pG = dWs, dGs
                p_in, v_in = params, vel
                params, vel, w_wr, g_wr, oks = scan_segment(
                    p_in, v_in, t, b, pW, pG)
                if cfg.guard:
                    # segment-splitting retry (see run_replay): the tripped
                    # step becomes an explicit step that admits its pair and
                    # rewrites the exact post-request gradient; the failed
                    # segment's outputs are never noted, so they are simply
                    # dropped from the flush.
                    with obs_trace.span("replay.host_sync", kind="guard"):
                        ok = np.asarray(oks)
                    fell = np.flatnonzero((plan[t:b] != SKIP) & ~ok)
                    if fell.size:
                        tf = t + int(fell[0])
                        with obs_trace.span("replay.guard_retry", t=tf,
                                            prefix=tf - t):
                            if tf > t:
                                params, vel, w_wr, g_wr, oks_p = \
                                    scan_segment(p_in, v_in, t, tf, pW, pG)
                                note_seg(t, tf - t, w_wr, g_wr)
                                seg_oks.append((t, tf, oks_p))
                            else:
                                params, vel = p_in, v_in
                            stats.guard_fallbacks += 1
                            params, vel = do_explicit(params, vel, tf,
                                                      tf + 1)
                        t = tf + 1
                        continue
                note_seg(t, b - t, w_wr, g_wr)
                seg_oks.append((t, b, oks))
                t = b

    if commit:
        with obs_trace.span("replay.commit", regions=len(regions)):
            store.commit(regions, final_params=params)

    for t0_, t1_, oks in seg_oks:
        nonskip = plan[t0_:t1_] != SKIP
        if cfg.guard:
            stats.approx_steps += int((nonskip & np.asarray(oks)).sum())
        else:
            stats.approx_steps += int(nonskip.sum())
        stats.grad_examples += int(
            sched.dB[t0_:t1_].astype(np.int64)[nonskip].sum())
    stats.skipped_steps = int((plan == SKIP).sum())
    base = sched.kept.astype(np.int64)
    if op == "add":
        base = base + sched.dB.astype(np.int64)
    stats.grad_examples_baseline = int(base.sum())
    stats.extra["store"] = store.kind
    stats.extra["hbm_high_water"] = store.hbm_high_water()
    if getattr(store, "windows_fetched", 0):
        stats.extra["windows"] = store.windows_fetched
        stats.extra["prefetch_depth"] = store.depth_used
    if runner is not None:
        stats.extra["mesh"] = runner.placement.describe()
    # the end-of-request pair ring, for session snapshots (the ring is
    # rebuilt from the rewritten path on every request, so this is state
    # a snapshot records rather than state the next request consumes);
    # the engine pops it off extra so logged stats stay device-array-free
    if ring_started:
        stats.extra["lbfgs_ring"] = (dWs, dGs)
    _publish_replay_metrics(stats, store, in_place)
    return params, stats
