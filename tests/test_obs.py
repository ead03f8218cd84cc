"""repro.obs — span tracer, shared metrics registry, roofline accounting.

The observability layer's contract (see `repro.obs`'s docstring tables):

  * disabled tracing is near-free and allocation-shared (`NOOP_SPAN`);
  * spans nest per thread, record on any thread, and export as
    Chrome/Perfetto trace-event JSON — deterministic under an injected
    virtual clock;
  * a span opened with ``pred_s`` closes with ``measured_s`` and
    ``roofline_ratio`` (the predicted-vs-measured hook the replay engine
    uses);
  * `Histogram` quantiles track `np.percentile` within one log-bucket
    width, and `ServeMonitor` + `launch/serve.py` both serve their
    percentiles from it — the repo's ONE quantile code path;
  * JSONL and Prometheus exports round-trip the registry;
  * a real scan replay under a live tracer emits roofline-annotated
    ``replay.scan`` spans (the BENCH_obs acceptance invariant);
  * while the JAX profiler traces, spans land on the ``.xplane.pb`` host
    plane under their own names, with or without a tracer, carrying only
    their int and str args (never ``pred_s``).
"""

import json
import os
import subprocess
import sys
import threading
import time
import warnings

import numpy as np
import pytest

from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.obs.metrics import Histogram, MetricsRegistry, read_jsonl
from repro.obs.trace import NOOP_SPAN, Tracer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _tracer_clean():
    """Never leak an enabled tracer into other tests (or from them)."""
    obs_trace.disable()
    yield
    obs_trace.disable()


class _VirtualClock:
    """Monotonic fake: every read advances by `step` seconds."""

    def __init__(self, start=100.0, step=1.0):
        self.t = start
        self.step = step

    def __call__(self):
        self.t += self.step
        return self.t


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------


class TestTracer:
    def test_disabled_span_is_shared_noop(self):
        assert not obs_trace.enabled()
        s = obs_trace.span("x", a=1)
        assert s is NOOP_SPAN
        with s as inner:
            assert inner.set(b=2) is NOOP_SPAN

    def test_disabled_overhead_bound(self):
        """The disabled call is an attr load + None check; bound it VERY
        loosely (20µs vs the ~0.2µs measured) so slow CI never flakes."""
        obs_trace.disable()
        iters = 50_000
        t0 = time.perf_counter()
        for _ in range(iters):
            obs_trace.span("replay.scan", t0=0, t1=8)
        per_call = (time.perf_counter() - t0) / iters
        assert per_call < 20e-6

    def test_enable_disable_roundtrip(self):
        tr = obs_trace.enable()
        assert obs_trace.enabled() and obs_trace.get_tracer() is tr
        assert obs_trace.enable() is tr  # idempotent reuse
        assert obs_trace.disable() is tr
        assert not obs_trace.enabled()
        assert obs_trace.disable() is None

    def test_virtual_clock_deterministic_export(self):
        """Nested spans under a +1s-per-read clock: exact ts/dur/parent."""
        tr = obs_trace.enable(Tracer(clock=_VirtualClock()))
        # epoch read = 101; outer enter = 102, inner enter = 103,
        # inner exit = 104, outer exit = 105
        with obs_trace.span("outer", k=1):
            with obs_trace.span("inner"):
                pass
        obs_trace.disable()
        inner, outer = tr.events()
        assert inner["name"] == "inner" and outer["name"] == "outer"
        assert inner["ts"] == pytest.approx(2e6)
        assert inner["dur"] == pytest.approx(1e6)
        assert inner["args"]["parent"] == "outer"
        assert outer["ts"] == pytest.approx(1e6)
        assert outer["dur"] == pytest.approx(3e6)
        assert "parent" not in outer["args"]

    def test_roofline_hook_on_exit(self):
        tr = obs_trace.enable(Tracer(clock=_VirtualClock()))
        with obs_trace.span("replay.scan", pred_s=2.0):
            pass  # dur = exactly 1.0s of virtual time
        obs_trace.disable()
        (ev,) = tr.events()
        assert ev["args"]["measured_s"] == pytest.approx(1.0)
        assert ev["args"]["roofline_ratio"] == pytest.approx(0.5)

    def test_cross_thread_spans_get_own_track(self):
        """A span on a worker thread must not nest under the main thread's
        open span — stacks are per-thread, tids are distinct."""
        tr = obs_trace.enable(Tracer())
        started, release = threading.Event(), threading.Event()

        def worker():
            with obs_trace.span("store.window_stage", wid=3):
                started.set()
                release.wait(timeout=5)

        th = threading.Thread(target=worker, name="staging-0")
        with obs_trace.span("replay.scan"):
            th.start()
            assert started.wait(timeout=5)
            release.set()
            th.join(timeout=5)
        obs_trace.disable()
        by_name = {e["name"]: e for e in tr.events()}
        stage = by_name["store.window_stage"]
        scan = by_name["replay.scan"]
        assert stage["tid"] != scan["tid"]
        assert "parent" not in stage["args"]
        names = {m["args"]["name"]
                 for m in tr.to_chrome()["traceEvents"]
                 if m.get("ph") == "M"}
        assert "staging-0" in names

    def test_chrome_export_roundtrip(self, tmp_path):
        tr = obs_trace.enable(Tracer(clock=_VirtualClock()))
        with obs_trace.span("serve.batch", size=4,
                            dtype=np.float32(1.5), err=ValueError("x")):
            pass
        obs_trace.disable()
        path = tr.export_chrome(str(tmp_path / "trace.json"))
        with open(path) as f:
            doc = json.load(f)  # must be strictly valid JSON
        xs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
        assert len(xs) == 1 and xs[0]["name"] == "serve.batch"
        # non-JSON arg values fall back to float/str, never crash export
        assert xs[0]["args"]["dtype"] == pytest.approx(1.5)
        assert "x" in xs[0]["args"]["err"]
        assert doc["displayTimeUnit"] == "ms"

    def test_max_events_drops_not_grows(self):
        tr = obs_trace.enable(Tracer(max_events=3))
        for i in range(5):
            with obs_trace.span(f"s{i}"):
                pass
        obs_trace.disable()
        assert len(tr.events()) == 3
        assert tr.dropped == 2


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------


class TestMetrics:
    def test_counter_gauge_basics(self):
        reg = MetricsRegistry()
        c = reg.counter("engine.replays", owner="core.engine")
        c.inc()
        c.inc(3)
        assert reg.counter("engine.replays").value == 4
        g = reg.gauge("store.hbm_high_water_bytes", unit="B")
        g.set_max(100)
        g.set_max(40)  # raise-only
        assert g.value == 100 and g.high == 100
        g.set(10)
        assert g.value == 10 and g.high == 100

    def test_kind_mismatch_raises(self):
        reg = MetricsRegistry()
        reg.counter("m")
        with pytest.raises(TypeError):
            reg.histogram("m")

    def test_labels_key_distinct_metrics(self):
        reg = MetricsRegistry()
        a = reg.counter("serve.served", labels={"class": "interactive"})
        b = reg.counter("serve.served", labels={"class": "batch"})
        a.inc()
        assert b.value == 0
        assert len(reg.metrics()) == 2

    def test_histogram_tracks_np_percentile(self):
        """Quantile error is bounded by one 4% log bucket; exact fields
        (count/mean/min/max) are exact."""
        rng = np.random.default_rng(0)
        sample = rng.lognormal(mean=2.0, sigma=1.2, size=5000)
        h = Histogram("lat", unit="ms")
        for v in sample:
            h.observe(float(v))
        s = h.summary()
        assert s["count"] == 5000
        assert s["mean"] == pytest.approx(float(np.mean(sample)))
        assert s["max"] == pytest.approx(float(np.max(sample)))
        for key, q in (("p50", 50), ("p95", 95), ("p99", 99)):
            exact = float(np.percentile(sample, q))
            assert abs(s[key] - exact) / exact < 0.05, (key, s[key], exact)

    def test_histogram_clamps_and_edges(self):
        h = Histogram("x")
        for v in (0.0, 1e-9, 5.0, 1e12):  # underflow, tiny, mid, overflow
            h.observe(v)
        assert h.min == 0.0 and h.max == 1e12
        assert 0.0 <= h.quantile(0.01) <= 1e12
        assert h.quantile(0.999) <= h.max  # clamped to observed max

    def test_empty_histogram_summary(self):
        assert Histogram("x").summary() == {"count": 0}

    def test_jsonl_roundtrip(self, tmp_path):
        reg = MetricsRegistry()
        reg.counter("queue.admitted", owner="serve.queue").inc(7)
        reg.gauge("online.compile_time_s", unit="s").set(1.25)
        h = reg.histogram("launch.dispatch_ms", unit="ms")
        for v in (1.0, 2.0, 3.0):
            h.observe(v)
        path = reg.to_jsonl(str(tmp_path / "metrics.jsonl"))
        snaps = read_jsonl(path)
        assert snaps == reg.snapshot()
        by_name = {s["name"]: s for s in snaps}
        assert by_name["queue.admitted"]["value"] == 7
        assert by_name["launch.dispatch_ms"]["count"] == 3

    def test_prometheus_exposition(self):
        reg = MetricsRegistry()
        reg.counter("queue.admitted", owner="serve.queue").inc(2)
        reg.histogram("serve.e2e_ms", unit="ms",
                      labels={"class": "interactive"}).observe(10.0)
        text = reg.to_prometheus()
        assert "# TYPE queue_admitted counter" in text
        assert "queue_admitted 2" in text
        assert "# TYPE serve_e2e_ms summary" in text
        assert 'serve_e2e_ms{class="interactive",quantile="0.5"}' in text
        assert 'serve_e2e_ms_count{class="interactive"} 1' in text
        assert text.endswith("\n")

    def test_default_registry_swap(self):
        old = obs_metrics.get_registry()
        try:
            fresh = obs_metrics.set_registry(MetricsRegistry())
            assert obs_metrics.get_registry() is fresh
        finally:
            obs_metrics.set_registry(old)


# ---------------------------------------------------------------------------
# one quantile code path (the dedup satellite)
# ---------------------------------------------------------------------------


class TestOneQuantilePath:
    SAMPLE = [3.0, 1.0, 40.0, 7.5, 0.4, 12.0, 12.0, 95.0, 2.2, 6.1]

    def test_monitor_quantiles_equal_shared_histogram(self):
        """ServeMonitor's per-class dispatch quantiles are EXACTLY the
        shared Histogram's on the same sample — same code, same buckets."""
        from repro.serve.monitor import ServeMonitor
        from repro.serve.queue import QueuedRequest

        mon = ServeMonitor()
        for i, ms in enumerate(self.SAMPLE):
            q = QueuedRequest(tenant="t0", sla_class="interactive",
                              op="delete", rows=[1], data=None,
                              coalesce=True, t_enqueue=0.0, deadline=1e9,
                              seq=i, t_dispatch=ms / 1e3, t_done=ms / 1e3)
            mon.observe_request(q)
        ref = Histogram("ref", unit="ms")
        for ms in self.SAMPLE:
            ref.observe(ms)
        got = mon.snapshot()["per_class"]["interactive"]["dispatch_ms"]
        want = ref.summary()
        assert got == want

    def test_no_private_percentile_helpers_remain(self):
        """The two pre-obs `_pcts` implementations are gone for good."""
        import repro.launch.serve as launch_serve
        import repro.serve.monitor as serve_monitor

        assert not hasattr(serve_monitor, "_pcts")
        assert not hasattr(launch_serve, "_pcts")


# ---------------------------------------------------------------------------
# the instrumented replay path + the CI gate
# ---------------------------------------------------------------------------


class TestReplayInstrumentation:
    def test_scan_replay_emits_roofline_spans(self):
        """A real (tiny) online delete under a live tracer produces
        ``replay.scan`` spans whose args carry the roofline annotations —
        the BENCH_obs acceptance invariant, in-process."""
        import dataclasses

        from repro.core.deltagrad import (DeltaGradConfig,
                                          sgd_train_with_cache)
        from repro.core.history import HistoryMeta
        from repro.core.online import online_deltagrad
        from repro.data.synthetic import binary_classification
        from repro.models.simple import logreg_init, logreg_objective

        n, d, steps = 200, 8, 30
        ds = binary_classification(n=n, d=d, seed=0)
        obj = logreg_objective(l2=5e-3)
        meta = HistoryMeta(n=n, batch_size=32, seed=7, steps=steps,
                           lr_schedule=((0, 0.3),))
        _, hist = sgd_train_with_cache(obj, logreg_init(d, seed=1), ds,
                                       meta, impl="scan")
        cfg = dataclasses.replace(
            DeltaGradConfig(period=5, burn_in=5, history_size=2),
            impl="scan")
        from repro.roofline.hw import TPU_V5E

        # the CPU has no peak rates: price against the v5e spec explicitly
        tr = obs_trace.enable(Tracer(hw=TPU_V5E))
        try:
            online_deltagrad(obj, hist, ds, [3, 11], cfg, mode="delete")
        finally:
            obs_trace.disable()
        scans = [e for e in tr.events() if e["name"] == "replay.scan"]
        assert scans, "no replay.scan spans recorded"
        for ev in scans:
            args = ev["args"]
            assert args["pred_s"] > 0.0
            assert args["measured_s"] >= 0.0
            assert args["roofline_ratio"] == pytest.approx(
                args["measured_s"] / args["pred_s"])
        # the commit span closes out every online replay
        assert any(e["name"] == "replay.commit" for e in tr.events())

    @pytest.mark.parametrize("batch", [200, 32])
    def test_explicit_in_place_counts_identity_schedules(self, batch):
        """``engine.explicit_in_place`` counts every explicit step of a
        full-batch replay (batch >= n: each step's batch is read in place)
        and none of a minibatch replay, on the batch and online paths."""
        import dataclasses

        from repro.core.deltagrad import (DeltaGradConfig, deltagrad_retrain,
                                          sgd_train_with_cache)
        from repro.core.history import HistoryMeta
        from repro.core.online import online_deltagrad
        from repro.data.synthetic import binary_classification
        from repro.models.simple import logreg_init, logreg_objective

        n, d = 200, 8
        ds = binary_classification(n=n, d=d, seed=0)
        obj = logreg_objective(l2=5e-3)
        meta = HistoryMeta(n=n, batch_size=batch, seed=7, steps=30,
                           lr_schedule=((0, 0.3),))
        _, hist = sgd_train_with_cache(obj, logreg_init(d, seed=1), ds, meta)
        cfg = dataclasses.replace(
            DeltaGradConfig(period=5, burn_in=5, history_size=2,
                            guard=True), impl="scan")
        old = obs_metrics.get_registry()
        reg = obs_metrics.set_registry(MetricsRegistry())
        try:
            _, st = deltagrad_retrain(obj, hist, ds, np.array([3, 11]), cfg)
            _, ost = online_deltagrad(obj, hist, ds, [5, 17], cfg,
                                      mode="delete")
        finally:
            obs_metrics.set_registry(old)
        explicit = st.explicit_steps + sum(
            r.explicit_steps for r in ost.per_request)
        assert explicit > 0
        assert reg.counter("engine.explicit_steps").value == explicit
        assert reg.counter("engine.explicit_in_place").value == (
            explicit if batch >= n else 0)

    def test_serve_trace_out_refuses_a_device_without_peaks(self, tmp_path):
        """`serve unlearn --trace-out` prices replay spans for the device
        that runs them; on one with no peak rates (the CPU) it stops at
        startup, before any session is built, with no tracer left on."""
        from repro.launch.serve import unlearn_main

        with pytest.raises(KeyError, match="no peak rates"):
            unlearn_main(["--trace-out", str(tmp_path / "t.json"),
                          "--bench-out", ""])
        assert not obs_trace.enabled()
        assert not (tmp_path / "t.json").exists()

    def test_committed_obs_baseline_passes_against_itself(self):
        """`check_bench --suite obs` must accept its own committed
        baseline, or the first CI run after merge is red by
        construction."""
        path = os.path.join(REPO, "benchmarks", "baselines",
                            "BENCH_obs.ci.json")
        tool = os.path.join(REPO, "tools", "check_bench.py")
        proc = subprocess.run(
            [sys.executable, tool, "--suite", "obs", "--current", path,
             "--baseline", path],
            capture_output=True, text=True,
            env={k: v for k, v in os.environ.items()
                 if k != "GITHUB_STEP_SUMMARY"}, cwd=REPO)
        assert proc.returncode == 0, proc.stdout + proc.stderr


# ---------------------------------------------------------------------------
# the profiler sink: spans on the device trace's clock
# ---------------------------------------------------------------------------


def _profiled(logdir, fn):
    """Run `fn` under a JAX profiler trace; return its host-plane events as
    ``(name, start_ns, end_ns, stats)``."""
    import glob

    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(logdir))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(logdir), "**", "*.xplane.pb"),
                        recursive=True)
    with warnings.catch_warnings():
        # reading an event's stats warns about the binding's type name
        warnings.simplefilter("ignore", DeprecationWarning)
        return [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                 dict(e.stats))
                for plane in ProfileData.from_file(path).planes
                if plane.name.startswith("/host:")
                for line in plane.lines for e in line.events]


def _named(events, name):
    return [e for e in events if e[0] == name]


def _within(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def _logreg_history(n=200, d=8, steps=30):
    from repro.core.deltagrad import sgd_train_with_cache
    from repro.core.history import HistoryMeta
    from repro.data.synthetic import binary_classification
    from repro.models.simple import logreg_init, logreg_objective

    ds = binary_classification(n=n, d=d, seed=0)
    obj = logreg_objective(l2=5e-3)
    meta = HistoryMeta(n=n, batch_size=32, seed=7, steps=steps,
                       lr_schedule=((0, 0.3),))
    _, hist = sgd_train_with_cache(obj, logreg_init(d, seed=1), ds, meta,
                                   impl="scan")
    return obj, hist, ds


class TestProfilerSink:
    def test_spans_land_on_the_host_plane_without_a_tracer(self, tmp_path):
        """No tracer installed: spans are profiler events under their own
        names, nested, with their int/str args as stats; other args (a
        device array, a float, None) are left off, never formatted."""
        import jax.numpy as jnp

        arr = jnp.ones(3)

        def work():
            assert not obs_trace.enabled()
            with obs_trace.span("serve.batch", batch=7, size=3, op="delete",
                                arr=arr, pred_s=None, frac=0.5) as s:
                assert s is not NOOP_SPAN
                assert s.set(late=1) is s
                with obs_trace.span("online.schedule_build", op="delete",
                                    k=3):
                    time.sleep(0.001)

        evs = _profiled(tmp_path, work)
        (batch,) = _named(evs, "serve.batch")
        (build,) = _named(evs, "online.schedule_build")
        assert _within(build, batch)
        assert build[2] - build[1] >= 1e6
        assert batch[3] == {"batch": 7, "size": 3, "op": "delete"}
        assert build[3] == {"op": "delete", "k": 3}

    def test_noop_again_once_the_profiler_stops(self, tmp_path):
        _profiled(tmp_path, lambda: None)
        assert not obs_trace.enabled()
        assert obs_trace.span("replay.scan", t0=0, t1=8) is NOOP_SPAN

    def test_tracer_and_profiler_both_record(self, tmp_path):
        """With a tracer installed the Chrome record is as before (roofline
        hook included) and the profiler gets the event too, without the
        float ``pred_s``."""
        tr = obs_trace.enable(Tracer(clock=_VirtualClock()))

        def work():
            with obs_trace.span("replay.scan", t0=0, t1=8, pred_s=2.0):
                pass

        evs = _profiled(tmp_path, work)
        obs_trace.disable()
        (ev,) = tr.events()
        assert ev["args"]["roofline_ratio"] == pytest.approx(0.5)
        (scan,) = _named(evs, "replay.scan")
        assert scan[3] == {"t0": 0, "t1": 8}

    def test_replays_under_the_profiler_emit_program_spans(self, tmp_path):
        """A tiny batch replay and online delete, profiled with no tracer:
        the schedule builds and host syncs are on the host plane, each sync
        inside an explicit step or a guarded segment's replay, and no event
        carries the roofline hook's args."""
        import dataclasses

        from repro.core.deltagrad import DeltaGradConfig, deltagrad_retrain
        from repro.core.online import online_deltagrad

        obj, hist, ds = _logreg_history()
        cfg = dataclasses.replace(
            DeltaGradConfig(period=5, burn_in=5, history_size=2,
                            guard=True), impl="scan")

        def work():
            deltagrad_retrain(obj, hist, ds, np.array([3, 11]), cfg)
            online_deltagrad(obj, hist, ds, [5, 17], cfg, mode="delete")

        evs = _profiled(tmp_path, work)
        assert len(_named(evs, "replay.schedule_build")) == 1
        assert len(_named(evs, "online.schedule_build")) == 2
        syncs = _named(evs, "replay.host_sync")
        kinds = {e[3]["kind"] for e in syncs}
        assert kinds == {"admit", "guard"}
        explicit = _named(evs, "replay.explicit")
        assert all(any(_within(s, x) for x in explicit)
                   for s in syncs if s[3]["kind"] == "admit")
        for name in ("online.request", "replay.scan", "replay.commit"):
            assert _named(evs, name), name
        assert not any({"pred_s", "measured_s", "roofline_ratio"}
                       & set(e[3]) for e in evs)
        assert not obs_trace.enabled()

    def test_served_batch_spans_share_the_batch_id(self, tmp_path):
        """A batch served through the scheduler: ``serve.batch`` carries the
        requests' `batch_id`, and the batch's submit, plan, schedule build
        and publish spans nest inside it."""
        from repro.core.session import UnlearnerConfig, UnlearnerSession
        from repro.core.deltagrad import DeltaGradConfig
        from repro.data.synthetic import binary_classification
        from repro.models.simple import logreg_init, logreg_objective
        from repro.serve import ServeConfig, ServingScheduler

        ds = binary_classification(n=200, d=8, seed=0)
        sess = UnlearnerSession(
            logreg_objective(l2=1e-3), logreg_init(8, seed=1), ds,
            UnlearnerConfig(steps=20, batch_size=64, lr=0.2, seed=0,
                            deltagrad=DeltaGradConfig(
                                period=5, burn_in=5, history_size=2)))
        sess.fit()
        sched = ServingScheduler(sess, ServeConfig())
        tickets = []

        def work():
            for rows in ([1, 2], [7]):
                tickets.append(sched.submit("delete", rows=rows))
            sched.drain()

        evs = _profiled(tmp_path, work)
        ids = {tk.req.batch_id for tk in tickets}
        assert len(ids) == 1 and None not in ids
        (batch,) = _named(evs, "serve.batch")
        assert batch[3]["batch"] == ids.pop()
        assert batch[3]["size"] == 2
        for name in ("serve.submit", "session.plan",
                     "online.schedule_build", "online.request",
                     "serve.publish"):
            (inner,) = _named(evs, name)
            assert _within(inner, batch), name
