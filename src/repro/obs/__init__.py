"""Unified observability layer: span tracing + the shared metrics registry.

This package is the ONE instrumentation contract for the repo — it
replaces the three ad-hoc stats paths that used to coexist
(`ServeMonitor`'s private percentile helper, `launch/serve.py`'s private
percentile helper, and untyped `RetrainStats.extra` dicts as the only
window into engine/store behavior):

  * `obs.trace`   — thread-safe monotonic span tracer with Chrome/Perfetto
                    trace-event export, and a sink into the JAX profiler's
                    trace; near-zero cost while both are off.
  * `obs.metrics` — counters / gauges / fixed-bucket histograms in one
                    registry, with JSONL and Prometheus-text exporters.

Spans reach two sinks.  While a JAX profiler trace runs
(``jax.profiler.start_trace``; the on-chip benchmark's ``--trace 1``),
every span is also a ``TraceAnnotation`` on the ``.xplane.pb`` host plane,
on the device trace's clock, under its own name with its int and str args
as event stats; no tracer is needed.  Install a `Tracer` with
``repro.obs.trace.enable()`` for the Chrome export (the serve CLI's
``--trace-out`` flag and ``benchmarks/bench_serve.py --trace-out`` do this
and export the trace); metrics publish unconditionally — read them with
``repro.obs.metrics.get_registry().snapshot()`` or either exporter.

SPAN CONTRACT — every span name, where it is emitted, and its args:

    span                    owner module        args
    ----------------------- ------------------- ---------------------------
    replay.schedule_build   core.engine         steps, r
    replay.scan             core.engine         t0, t1, pred_s, measured_s,
                                                roofline_ratio
    replay.explicit         core.engine         t0, steps
    replay.host_sync        core.engine         kind: "admit" (an explicit
                                                step's admission scalars)
                                                or "guard" (a segment's
                                                guard flags) read back
    replay.guard_retry      core.engine         t, prefix
    replay.commit           core.engine         regions
    online.warmup           core.online         ops
    online.schedule_build   core.online         op, k  (host-side schedule
                                                of a request group)
    online.request          core.online         op, k, pred_s, measured_s,
                                                roofline_ratio
    session.plan            core.session        requests  (begin_plan and
                                                plan_requests of a flush)
    store.window_stage      core.store          wid  (staging-pool thread)
    store.prefetch_wait     core.store          wid
    store.window            core.store          wid, hit
    serve.admit             serve.scheduler     op, tenant, cls
    serve.batch             serve.executor      batch, size, op  (batch is
                                                `QueuedRequest.batch_id` of
                                                the batch's requests)
    serve.submit            serve.executor      requests  (session.submit
                                                of each request of a batch)
    serve.publish           serve.executor      (block_until_ready on the
                                                published parameters)
    serve.hold              serve.executor      (the executor waiting while
                                                the flush policy holds)

    The spans of one served request share its batch id: they nest, on the
    executor thread, inside the ``serve.batch`` span that carries it.

    ``pred_s`` is the roofline-predicted span cost attached by
    `repro.roofline.replay`; the tracer stamps ``measured_s`` and
    ``roofline_ratio`` (measured / predicted) on span exit, so every
    replay span in a `Tracer`'s Chrome export carries predicted-vs-
    measured cost.  The hook serves that export and the CPU benchmark's
    gates only: ``pred_s`` is computed only while a `Tracer` is installed
    (`enabled()`), never for a profiler-only span, and it is a float, so
    it never reaches the profiler's event.

METRIC CONTRACT — every metric name, its type/unit, and the owner that
publishes it:

    metric                       type       unit  owner
    ---------------------------- ---------- ----- ---------------------
    engine.replays               counter    1     core.engine
    engine.explicit_steps        counter    1     core.engine
    engine.approx_steps          counter    1     core.engine
    engine.guard_fallbacks       counter    1     core.engine
    engine.grad_examples         counter    1     core.engine
    online.compile_time_s        gauge      s     core.online
    store.hbm_high_water_bytes   gauge      B     core.store
    store.windows_fetched        counter    1     core.store
    store.prefetch_hits          counter    1     core.store
    store.host_wait_s            counter    s     core.store
    queue.admitted               counter    1     serve.queue
    queue.rejected_depth         counter    1     serve.queue
    queue.rejected_tenant        counter    1     serve.queue
    queue.rejected_add_capacity  counter    1     serve.queue
    queue.blocked_admissions     counter    1     serve.queue
    serve.dispatch_ms{class}     histogram  ms    serve.monitor
    serve.e2e_ms{class}          histogram  ms    serve.monitor
    serve.queue_depth            histogram  1     serve.monitor
    serve.batch_size             histogram  1     serve.monitor
    serve.served{class}          counter    1     serve.monitor
    serve.failed{class}          counter    1     serve.monitor
    serve.deadline_misses{class} counter    1     serve.monitor
    serve.add_capacity_retraces  counter    1     serve.monitor
    launch.dispatch_ms           histogram  ms    launch.serve
    launch.blocked_ms            histogram  ms    launch.serve
    bench.warmup_compile_s       histogram  s     benchmarks

    `ServeMonitor` keeps one PRIVATE registry per instance by default
    (bench sweeps build a monitor per point; snapshots must not
    accumulate across points) — pass ``registry=get_registry()`` to
    publish a single serving stack into the process-wide surface, as the
    serve CLI does.  Structured per-replay facts remain available on
    `RetrainStats.extra` for backward compatibility, but new consumers
    should read this registry (see the migration note in
    `core/session.py`).
"""

from repro.obs import metrics, trace
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               get_registry, read_jsonl, set_registry)
from repro.obs.trace import (Span, Tracer, disable, enable, enabled,
                             get_tracer, span)

__all__ = [
    "metrics", "trace",
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "get_registry",
    "set_registry", "read_jsonl",
    "Span", "Tracer", "span", "enable", "disable", "enabled", "get_tracer",
]
