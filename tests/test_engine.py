"""Scan-engine parity vs the legacy per-step loop (core/engine.py).

The compiled replay engine must be a pure performance refactor: for every
mode x optimizer combination, final parameters from the `lax.scan` path must
match the pre-refactor python loop (kept as `impl="python"`) to <= 1e-5, and
the RetrainStats counters must agree exactly.
"""

import dataclasses

import jax
import numpy as np
import pytest

from repro.core import engine
from repro.core.deltagrad import (
    DeltaGradConfig,
    baseline_retrain,
    deltagrad_retrain,
    sgd_train_with_cache,
)
from repro.core.engine import (_next_pow2, batch_in_place,
                               run_online_request)
from repro.core.history import HistoryMeta, TrainingHistory
from repro.core.online import OnlineEngine, online_deltagrad
from repro.data.sampler import (addition_mask_all, build_online_schedule,
                                build_schedule)
from repro.data.synthetic import (binary_classification,
                                  multiclass_classification)
from repro.models.simple import (logreg_init, logreg_objective, mlp_init,
                                 mlp_objective)
from repro.utils.tree import tree_norm, tree_sub

TOL = 1e-5


def _problem(n=1200, d=12, steps=60, batch=256, momentum=0.0, seed=0):
    ds = binary_classification(n=n, d=d, seed=seed)
    obj = logreg_objective(l2=5e-3)
    meta = HistoryMeta(n=ds.n, batch_size=batch, seed=7, steps=steps,
                       lr_schedule=((0, 0.3),), momentum=momentum)
    p0 = logreg_init(d, seed=seed + 1)
    return ds, obj, meta, p0


def _dist(a, b):
    return float(tree_norm(tree_sub(a, b)))


CFG = DeltaGradConfig(period=5, burn_in=8, history_size=2)
CFG_PY = dataclasses.replace(CFG, impl="python")


class TestTrainingParity:
    @pytest.mark.parametrize("momentum", [0.0, 0.9])
    def test_record_scan_matches_loop(self, momentum):
        ds, obj, meta, p0 = _problem(momentum=momentum)
        w_s, h_s = sgd_train_with_cache(obj, p0, ds, meta, impl="scan")
        w_p, h_p = sgd_train_with_cache(obj, p0, ds, meta, impl="python")
        assert _dist(w_s, w_p) < TOL
        for t in (0, meta.steps // 2, meta.steps - 1):
            es, ep = h_s.entry(t), h_p.entry(t)
            assert _dist(es[0], ep[0]) < TOL
            assert _dist(es[1], ep[1]) < TOL


class TestBaselineParity:
    @pytest.mark.parametrize("mode", ["delete", "add"])
    @pytest.mark.parametrize("batch", [256, 1 << 30])
    def test_baseline_scan_matches_loop(self, mode, batch):
        ds, obj, meta, p0 = _problem(batch=batch)
        changed = np.random.default_rng(3).choice(meta.n, 12, replace=False)
        if mode == "add":
            changed = ds.append({k: v[changed] for k, v in ds.columns.items()})
        w_s, _ = baseline_retrain(obj, ds, meta, p0, changed, mode, impl="scan")
        w_p, _ = baseline_retrain(obj, ds, meta, p0, changed, mode,
                                  impl="python")
        assert _dist(w_s, w_p) < TOL


class TestReplayParity:
    @pytest.mark.parametrize("mode", ["delete", "add"])
    @pytest.mark.parametrize("batch", [256, 1 << 30])  # SGD and GD
    @pytest.mark.parametrize("momentum", [0.0, 0.9])
    def test_replay_scan_matches_loop(self, mode, batch, momentum):
        ds, obj, meta, p0 = _problem(batch=batch, momentum=momentum)
        w_star, hist = sgd_train_with_cache(obj, p0, ds, meta)
        changed = np.random.default_rng(4).choice(meta.n, 10, replace=False)
        if mode == "add":
            changed = ds.append({k: v[changed] for k, v in ds.columns.items()})
        w_s, st_s = deltagrad_retrain(obj, hist, ds, changed, CFG, mode=mode)
        w_p, st_p = deltagrad_retrain(obj, hist, ds, changed, CFG_PY,
                                      mode=mode)
        assert _dist(w_s, w_p) < TOL, (mode, batch, momentum)
        assert st_s.extra["impl"] == "scan" and st_p.extra["impl"] == "python"
        for f in ("explicit_steps", "approx_steps", "guard_fallbacks",
                  "skipped_steps", "grad_examples", "grad_examples_baseline"):
            assert getattr(st_s, f) == getattr(st_p, f), f

    def test_skip_steps_counted_identically(self):
        ds, obj, meta, p0 = _problem(n=40, d=5, steps=10, batch=8)
        _, hist = sgd_train_with_cache(obj, p0, ds, meta)
        from repro.data.sampler import batch_indices
        batch0 = batch_indices(meta.seed, 0, 40, 8)
        cfg = dataclasses.replace(CFG, period=3, burn_in=2)
        w_s, st_s = deltagrad_retrain(obj, hist, ds, batch0, cfg)
        w_p, st_p = deltagrad_retrain(
            obj, hist, ds, batch0, dataclasses.replace(cfg, impl="python"))
        assert st_s.skipped_steps == st_p.skipped_steps >= 1
        assert _dist(w_s, w_p) < TOL

    def test_guard_fallback_counters_on_device(self):
        """guard_norm_clip=0 trips the guard on every approx step; the
        segment-splitting retry must turn each into an explicit step (one
        host sync per scanned segment, never per step)."""
        ds, obj, meta, p0 = _problem()
        _, hist = sgd_train_with_cache(obj, p0, ds, meta)
        changed = np.arange(10)
        cfg = dataclasses.replace(CFG, guard=True, guard_norm_clip=0.0)
        w, st = deltagrad_retrain(obj, hist, ds, changed, cfg)
        assert st.approx_steps == 0
        assert st.guard_fallbacks > 0
        assert st.explicit_steps == meta.steps - st.skipped_steps
        assert np.isfinite(_dist(w, p0))

    @pytest.mark.parametrize("clip", [0.2, 0.0])
    def test_guard_retry_full_stats_parity(self, clip):
        """The two documented scan/python divergences are gone: fallback
        steps admit their L-BFGS pair mid-segment (segment-splitting retry)
        and both backends charge the true grad_examples cost kept + dB, so
        with the guard ON the scan path matches the oracle exactly —
        parameters AND every counter."""
        ds, obj, meta, p0 = _problem()
        _, hist = sgd_train_with_cache(obj, p0, ds, meta)
        changed = np.random.default_rng(4).choice(meta.n, 10, replace=False)
        cfg = dataclasses.replace(CFG, guard=True, guard_norm_clip=clip)
        w_s, st_s = deltagrad_retrain(obj, hist, ds, changed, cfg)
        w_p, st_p = deltagrad_retrain(obj, hist, ds, changed,
                                      dataclasses.replace(cfg, impl="python"))
        assert st_p.guard_fallbacks > 0  # the regime under test
        assert _dist(w_s, w_p) < TOL
        for f in ("explicit_steps", "approx_steps", "guard_fallbacks",
                  "skipped_steps", "grad_examples", "grad_examples_baseline",
                  "pairs_rejected"):
            assert getattr(st_s, f) == getattr(st_p, f), f


ONLINE_TOL = 1.5e-7  # both backends share the per-step math verbatim


def _assert_request_stats_equal(st_s, st_p):
    assert len(st_s.per_request) == len(st_p.per_request)
    for a, b in zip(st_s.per_request, st_p.per_request):
        for f in ("explicit_steps", "approx_steps", "guard_fallbacks",
                  "skipped_steps", "grad_examples",
                  "grad_examples_baseline"):
            assert getattr(a, f) == getattr(b, f), f


class TestOnlineParity:
    def test_online_delete_scan_matches_loop(self):
        reqs = [3, 17, 101]
        ds1, obj, meta, p0 = _problem()
        _, h1 = sgd_train_with_cache(obj, p0, ds1, meta)
        w_s, st_s = online_deltagrad(obj, h1, ds1, reqs, CFG, mode="delete")
        ds2, _, _, _ = _problem()
        _, h2 = sgd_train_with_cache(obj, p0, ds2, meta)
        w_p, st_p = online_deltagrad(obj, h2, ds2, reqs, CFG_PY,
                                     mode="delete")
        assert _dist(w_s, w_p) < ONLINE_TOL
        assert len(st_s.per_request) == len(reqs)
        _assert_request_stats_equal(st_s, st_p)
        # the rewritten caches must agree too (they seed the NEXT request)
        for t in (0, meta.steps - 1):
            assert _dist(h1.entry(t)[0], h2.entry(t)[0]) < TOL
            assert _dist(h1.entry(t)[1], h2.entry(t)[1]) < TOL

    @pytest.mark.parametrize("momentum", [0.0, 0.9])
    def test_online_add_scan_matches_loop(self, momentum):
        """Addition streams must run the scanned path (no python fallback)
        and agree with the per-step oracle in params, rewritten cache, and
        every counter."""

        def run(cfg):
            ds, obj, meta, p0 = _problem(momentum=momentum)
            _, h = sgd_train_with_cache(obj, p0, ds, meta)
            src = np.arange(4)
            new = ds.append({k: v[src] for k, v in ds.columns.items()})
            w, st = online_deltagrad(obj, h, ds, new.tolist(), cfg,
                                     mode="add")
            return w, st, h, meta

        w_s, st_s, h1, meta = run(CFG)
        w_p, st_p, h2, _ = run(CFG_PY)
        assert _dist(w_s, w_p) < ONLINE_TOL, momentum
        _assert_request_stats_equal(st_s, st_p)
        for t in (0, meta.steps // 2, meta.steps - 1):
            assert _dist(h1.entry(t)[0], h2.entry(t)[0]) < TOL
            assert _dist(h1.entry(t)[1], h2.entry(t)[1]) < TOL

    def test_online_momentum_delete_scan_matches_loop(self):
        """Heavy-ball histories are no longer rejected: the velocity is
        reconstructed per request inside the scan carry."""
        reqs = [3, 17, 101, 640]

        def run(cfg):
            ds, obj, meta, p0 = _problem(momentum=0.9)
            _, h = sgd_train_with_cache(obj, p0, ds, meta)
            return online_deltagrad(obj, h, ds, reqs, cfg, mode="delete")

        w_s, st_s = run(CFG)
        w_p, st_p = run(CFG_PY)
        assert _dist(w_s, w_p) < ONLINE_TOL
        _assert_request_stats_equal(st_s, st_p)

    def test_online_mixed_stream_scan_matches_loop(self):
        """Interleaved delete/add requests — including deletion of a row
        added earlier in the same stream."""

        def run(cfg):
            ds, obj, meta, p0 = _problem()
            _, h = sgd_train_with_cache(obj, p0, ds, meta)
            new = ds.append({k: v[10:13] for k, v in ds.columns.items()})
            reqs = [("delete", 3), ("add", int(new[0])), ("delete", 17),
                    ("add", int(new[1])), ("delete", int(new[0])),
                    ("add", int(new[2])), ("delete", 101)]
            return online_deltagrad(obj, h, ds, reqs, cfg)

        w_s, st_s = run(CFG)
        w_p, st_p = run(CFG_PY)
        assert _dist(w_s, w_p) < ONLINE_TOL
        _assert_request_stats_equal(st_s, st_p)

    def test_online_guard_retry_matches_loop(self):
        """Online guard fallbacks admit their L-BFGS pair via the
        segment-splitting retry, so the scan path tracks the oracle even
        when the Algorithm-4 guard trips repeatedly."""
        cfg = dataclasses.replace(CFG, guard=True, guard_norm_clip=0.1)

        def run(c):
            ds, obj, meta, p0 = _problem()
            _, h = sgd_train_with_cache(obj, p0, ds, meta)
            return online_deltagrad(obj, h, ds, [3, 17, 101], c)

        w_s, st_s = run(cfg)
        w_p, st_p = run(dataclasses.replace(cfg, impl="python"))
        assert sum(s.guard_fallbacks for s in st_p.per_request) > 0
        assert _dist(w_s, w_p) < ONLINE_TOL
        _assert_request_stats_equal(st_s, st_p)

    def test_online_fully_deleted_batch_matches_loop(self):
        """Degenerate Algorithm-3 case: earlier requests empty a whole batch,
        then a later request replays it with kept == 0 and the request row
        absent — the scan path must execute (not skip) those steps exactly
        like the python oracle."""
        from repro.data.sampler import batch_indices

        def make():
            ds = binary_classification(n=40, d=5, seed=9)
            obj = logreg_objective(l2=5e-3)
            meta = HistoryMeta(n=40, batch_size=4, seed=1, steps=12,
                               lr_schedule=((0, 0.1),))
            p0 = logreg_init(5, seed=2)
            _, h = sgd_train_with_cache(obj, p0, ds, meta)
            return ds, obj, meta, h

        ds1, obj, meta, h1 = make()
        batch3 = batch_indices(meta.seed, 3, meta.n, meta.batch_size)
        outside = next(i for i in range(meta.n) if i not in set(batch3))
        reqs = [int(i) for i in batch3] + [outside]
        cfg = dataclasses.replace(CFG, burn_in=2, period=4)
        w_s, st_s = online_deltagrad(obj, h1, ds1, reqs, cfg, mode="delete")
        ds2, _, _, h2 = make()
        w_p, st_p = online_deltagrad(
            obj, h2, ds2, reqs, dataclasses.replace(cfg, impl="python"),
            mode="delete")
        assert _dist(w_s, w_p) < TOL
        for a, b in zip(st_s.per_request, st_p.per_request):
            assert a.skipped_steps == b.skipped_steps
            assert a.approx_steps == b.approx_steps
        for t in (3, meta.steps - 1):
            assert _dist(h1.entry(t)[1], h2.entry(t)[1]) < TOL


_STAT_FIELDS = ("explicit_steps", "approx_steps", "guard_fallbacks",
                "skipped_steps", "pairs_rejected", "grad_examples",
                "grad_examples_baseline")


def _full_batch_mlp(n=256, d=12, steps=30):
    """The paper's model family (ReLU MLP) under full-batch GD, tiny.  Its
    matmuls compile to the same library dot on the CPU whether their rows
    were gathered or read in place; a matrix-VECTOR model (binary logreg)
    instead gets a loop-fused dot on the gather path, equal to round-off
    only."""
    ds = multiclass_classification(n=n, d=d, num_classes=4, seed=0)
    meta = HistoryMeta(n=n, batch_size=1 << 30, seed=7, steps=steps,
                       lr_schedule=((0, 0.3), (10, 0.1)))
    return ds, mlp_objective(), meta, mlp_init(d, 16, 4, seed=1)


def _in_place_case(case):
    """(the engine's identity check for the case's schedule, and for an
    identity schedule a run returning (params, RetrainStats))."""
    ds, obj, meta, p0 = _full_batch_mlp()
    rows = np.arange(5, 15)
    cfg = dataclasses.replace(CFG, guard=True)
    sched = build_schedule(meta.seed, meta.steps, meta.n, meta.batch_size,
                           rows, "delete", 16, meta.lr_at)
    if case == "record":
        return (batch_in_place(sched.idx),
                lambda: sgd_train_with_cache(obj, p0, ds, meta))
    if case == "baseline":
        return (batch_in_place(sched.idx),
                lambda: baseline_retrain(obj, ds, meta, p0, rows))
    _, hist = sgd_train_with_cache(obj, p0, ds, meta)
    if case == "delete":
        return (batch_in_place(sched.idx),
                lambda: deltagrad_retrain(obj, hist, ds, rows, cfg))
    if case == "add":
        # the appended rows widen the device columns past the schedule's
        # width: the in-place read is a prefix slice
        new = ds.append({k: v[rows] for k, v in ds.columns.items()})
        add = build_schedule(meta.seed, meta.steps, meta.n, meta.batch_size,
                             new, "add", 16, meta.lr_at)
        return (batch_in_place(add.idx),
                lambda: deltagrad_retrain(obj, hist, ds, new, cfg,
                                          mode="add"))
    eng = OnlineEngine(obj, hist, ds, cfg)
    if case == "online-group":
        group = eng._schedule("delete", rows.tolist())

        def serve():  # a request rewrites the path: serve a fresh one
            ds_, obj_, _, _ = _full_batch_mlp()
            _, hist_ = sgd_train_with_cache(obj_, p0, ds_, meta)
            fresh = OnlineEngine(obj_, hist_, ds_, cfg)
            st = fresh.request_group("delete", rows.tolist())
            return fresh.params, st

        return batch_in_place(group.idx), serve
    if case == "online-pow2-columns":
        group = eng._schedule("delete", rows.tolist())
        cols = ds.device_columns(capacity=_next_pow2(ds.n + 1))
        assert next(iter(cols.values())).shape[0] > group.batch
        return (batch_in_place(group.idx),
                lambda: run_online_request(
                    eng.grad_fn, eng.store, cols, group, cfg,
                    static_dev=eng._static_dev(group), commit=False))
    if case == "minibatch":
        mini = build_schedule(meta.seed, meta.steps, meta.n, 64, rows,
                              "delete", 16, meta.lr_at)
        return batch_in_place(mini.idx), None
    if case == "add-join-columns":
        # three earlier additions ride a pow2 block of 4 join columns, the
        # last of them padding that points at row 0
        ds.append({k: v[:4] for k, v in ds.columns.items()})
        live = np.ones(ds.n, dtype=bool)
        joins = addition_mask_all(meta.seed, meta.steps, meta.n,
                                  meta.batch_size, 4)
        ext = build_online_schedule(
            meta.seed, meta.steps, meta.n, meta.batch_size, [meta.n + 3],
            "add", meta.lr_at, live, np.arange(meta.n, meta.n + 3), joins,
            4)
        return batch_in_place(ext.idx), None
    assert case == "shard-padded", case
    # a device schedule read wider than the batch (256 columns padded to
    # 259 with row-0 columns) is not the identity
    return batch_in_place(sched.idx, sched.idx.shape[1] + 3), None


class TestInPlaceBatch:
    @pytest.mark.parametrize("case, identity", [
        ("record", True), ("baseline", True), ("delete", True),
        ("add", True), ("online-group", True),
        ("online-pow2-columns", True), ("minibatch", False),
        ("add-join-columns", False), ("shard-padded", False)])
    def test_identity_schedule_reads_batch_in_place(self, case, identity,
                                                    monkeypatch):
        """Full-batch GD's schedule is the identity on every step, so each
        step reads the first B rows of the columns in place instead of
        gathering them; the numbers must be bitwise the gather path's, and
        every other schedule keeps the gather."""
        check, run = _in_place_case(case)
        assert check is identity
        if run is None:
            return
        w_in, st_in = run()
        monkeypatch.setattr(engine, "batch_in_place",
                            lambda idx, width=None: False)
        w_g, st_g = run()
        if isinstance(st_in, TrainingHistory):  # the recorded path too
            w_in = (w_in, st_in.stacked_view())
            w_g = (w_g, st_g.stacked_view())
        else:
            for f in _STAT_FIELDS:
                assert getattr(st_in, f) == getattr(st_g, f), f
        for a, b in zip(jax.tree.leaves(w_in), jax.tree.leaves(w_g)):
            assert np.array_equal(np.asarray(a), np.asarray(b)), case


class TestStackedTier:
    def test_stacked_history_roundtrip_and_overwrite(self):
        ds, obj, meta, p0 = _problem(steps=20)
        _, h = sgd_train_with_cache(obj, p0, ds, meta, tier="stacked")
        _, h2 = sgd_train_with_cache(obj, p0, ds, meta, tier="device")
        assert len(h) == meta.steps
        for t in (0, 7, 19):
            assert _dist(h.entry(t)[0], h2.entry(t)[0]) < 1e-7
        w5, g5 = h.entry(5)
        marked = {k: v + 1.0 for k, v in w5.items()}
        h.overwrite(5, marked, g5)
        assert _dist(h.entry(5)[0], marked) < 1e-7
        assert _dist(h.entry(4)[0], h2.entry(4)[0]) < 1e-7
        state = h.state_dict()
        h3 = TrainingHistory.from_state_dict(state)
        assert _dist(h3.entry(5)[0], marked) < 1e-7

    def test_replay_works_from_every_memory_tier(self):
        changed = np.arange(8)
        ds, obj, meta, p0 = _problem(steps=30)
        ref_w = None
        for tier, want_store in (("stacked", "resident"),
                                 ("device", "resident"),
                                 ("host", "streamed")):
            _, h = sgd_train_with_cache(obj, p0, ds, meta, tier=tier)
            w, st = deltagrad_retrain(obj, h, ds, changed, CFG)
            # every tier runs the compiled scan; offload tiers are not
            # stacked onto the device — they stream segment windows
            # (core.store.SegmentStreamer), never the whole path
            assert st.extra["impl"] == "scan", tier
            assert st.extra["store"] == want_store, tier
            ref_w = w if ref_w is None else ref_w
            assert _dist(w, ref_w) < TOL, tier

    def test_device_tier_records_without_duplicating(self):
        """set_stacked must not keep per-entry slice copies next to the
        stacked arrays (2x HBM)."""
        ds, obj, meta, p0 = _problem(steps=10)
        _, h = sgd_train_with_cache(obj, p0, ds, meta, tier="device")
        leaves = sum(x.nbytes for x in
                     __import__("jax").tree.leaves(h.stacked_view()))
        assert h.nbytes() <= leaves * 1.01


class TestFusedKernelRouting:
    def test_interpret_mode_matches_ref(self):
        """The Pallas fused_update wiring, exercised end-to-end through the
        engine in interpret mode (CPU stand-in for the TPU kernel path)."""
        ds, obj, meta, p0 = _problem(steps=30)
        _, hist = sgd_train_with_cache(obj, p0, ds, meta)
        changed = np.arange(6)
        w_ref, st_ref = deltagrad_retrain(
            obj, hist, ds, changed,
            dataclasses.replace(CFG, fused="ref"))
        w_int, st_int = deltagrad_retrain(
            obj, hist, ds, changed,
            dataclasses.replace(CFG, fused="interpret"))
        assert st_ref.extra["fused"] == "ref"
        assert st_int.extra["fused"] == "interpret"
        assert _dist(w_ref, w_int) < TOL
