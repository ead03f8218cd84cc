"""Serving drivers.

Two entry points share this module:

  * ``unlearn`` — the DeltaGrad request server (ROADMAP serve-path item),
    built on ``core.session.UnlearnerSession``: trains with path caching,
    answers a stream of online delete/add requests (one lazy `submit()`
    per request — DISPATCH latency is what the server's queue sees, and is
    reported separately from BLOCKED latency, the device-drained time a
    per-request sync would pay), serves a burst of ``--burst`` deletes
    both serially and COALESCED into one group replay, then drives a
    seeded multi-tenant trace (``--trace poisson|diurnal|fixed``, mixed
    SLA classes) through `repro.serve.ServingScheduler` — admission,
    EDF flush, cross-tenant batching, and the lone-tail deadline tick.
    Summary percentiles include p99; a machine-readable
    ``BENCH_serve.json`` is written to ``--bench-out`` (the full
    continuous-batching load sweep lives in ``benchmarks/bench_serve.py``,
    which runs this driver in-process).

        PYTHONPATH=src python -m repro.launch.serve unlearn \
            --n 4000 --d 500 --steps 80 --requests 12 --add-frac 0.25 \
            --trace poisson --rate 200

    ``--model <name>`` swaps the default logreg problem for a reduced
    registry LM (`UnlearnerSession.from_config`): the dataset becomes a
    synthetic token stream (``--n`` docs of ``--seq-len`` tokens) and the
    reported score is an exp(-loss) proxy instead of accuracy — the rest
    of the surface (latency loop, coalesced burst, scheduler trace) is
    model-agnostic:

        PYTHONPATH=src python -m repro.launch.serve unlearn \
            --model internlm2-1.8b --n 256 --steps 40 --batch 64 \
            --lr 0.02 --requests 8 --rate 20

  * batched decode (default, backwards-compatible flags): prefill a prompt
    batch, then step the KV caches.

        PYTHONPATH=src python -m repro.launch.serve --arch internlm2-1.8b \
            --reduced --batch 4 --prompt-len 32 --gen 16
"""

from __future__ import annotations

import argparse
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import get_config
from repro.launch.cache import enable_compile_cache
from repro.models.registry import build
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace


def unlearn_main(argv) -> dict:
    """Stand up the online unlearning service and drive a request stream;
    returns the results dict that ``--bench-out`` writes.  Raises if any
    request fails or is not served within its wait."""
    import json

    from repro.core.deltagrad import DeltaGradConfig
    from repro.core.privacy import PrivacyConfig
    from repro.core.session import UnlearnerConfig, UnlearnerSession
    from repro.data.synthetic import binary_classification
    from repro.models.simple import (logreg_accuracy, logreg_init,
                                     logreg_objective)
    from repro.utils.tree import tree_norm, tree_sub

    ap = argparse.ArgumentParser(prog="serve unlearn")
    ap.add_argument("--model", default="",
                    help="configs.registry name — serve a reduced LM "
                         "instead of the default logreg problem "
                         "(UnlearnerSession.from_config); --n becomes the "
                         "document count")
    ap.add_argument("--seq-len", type=int, default=32,
                    help="tokens per synthetic document (with --model)")
    ap.add_argument("--n", type=int, default=4000)
    ap.add_argument("--d", type=int, default=500)
    ap.add_argument("--steps", type=int, default=80)
    ap.add_argument("--batch", type=int, default=1024)
    ap.add_argument("--lr", type=float, default=0.3)
    ap.add_argument("--l2", type=float, default=5e-3)
    ap.add_argument("--momentum", type=float, default=0.0)
    ap.add_argument("--period", type=int, default=5)
    ap.add_argument("--burn-in", type=int, default=10)
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--add-frac", type=float, default=0.25,
                    help="fraction of requests that are additions")
    ap.add_argument("--impl", default="scan", choices=("scan", "python"))
    ap.add_argument("--algorithm", default="deltagrad",
                    help="registered unlearning algorithm serving the "
                         "stream (core.algorithms registry)")
    ap.add_argument("--eps", type=float, default=1.0,
                    help="certified-deletion epsilon for the published "
                         "model / certificate report")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--burst", type=int, default=8,
                    help="K for the coalesced-vs-serial delete burst")
    ap.add_argument("--trace", default="poisson",
                    choices=("poisson", "diurnal", "fixed"),
                    help="arrival process for the continuous-serving "
                         "section (seeded; 'fixed' is the deterministic "
                         "equal-spacing mode driven by --arrival-ms)")
    ap.add_argument("--rate", type=float, default=0.0,
                    help="offered load in requests/s for poisson/diurnal "
                         "traces (0 derives it from --arrival-ms)")
    ap.add_argument("--arrival-ms", type=float, default=2.0,
                    help="inter-arrival gap for --trace fixed (and the "
                         "rate fallback for the seeded traces)")
    ap.add_argument("--sla-class", default="mixed",
                    choices=("mixed", "interactive", "batch", "bulk_gdpr"),
                    help="SLA class for generated requests ('mixed' draws "
                         "from all three)")
    ap.add_argument("--bench-out", default="BENCH_serve.json",
                    help="machine-readable results path ('' disables)")
    ap.add_argument("--trace-out", default="",
                    help="enable the span tracer and write a Chrome/"
                         "Perfetto trace-event JSON here ('' disables); "
                         "the metrics registry lands beside it as "
                         "<path>.metrics.jsonl; needs a device with peak "
                         "rates in repro.roofline.hw")
    ap.add_argument("--profile-dir", default="",
                    help="capture a jax.profiler device trace into this "
                         "directory ('' disables) — opt-in, for XLA-level "
                         "drill-down under the obs spans")
    args = ap.parse_args(argv)

    if args.trace_out:
        # replay spans are priced for this device; one with no peak rates
        # in roofline/hw.py is refused here, before any work is done
        from repro.roofline.hw import local_hw
        obs_trace.enable(obs_trace.Tracer(hw=local_hw()))
    if args.profile_dir:
        jax.profiler.start_trace(args.profile_dir)

    # the logreg-scale lr/batch defaults destroy a transformer (the
    # L-BFGS correction blows past the guard clip at lr=0.3): when
    # --model is set and the user left them at the logreg defaults,
    # swap in the LM recipe examples/unlearn_lm.py is calibrated at
    if args.model:
        if args.lr == ap.get_default("lr"):
            args.lr = 0.02
        if args.batch == ap.get_default("batch"):
            args.batch = 64

    cfg = UnlearnerConfig(
        steps=args.steps, batch_size=args.batch, lr=args.lr, seed=args.seed,
        momentum=args.momentum, algorithm=args.algorithm,
        privacy=PrivacyConfig(eps=args.eps, mu=0.5, L=1.0, c0=0.1, c2=0.1),
        # non-convex models need the Algorithm-4 curvature guard (the
        # paper's DNN recipe); the convex logreg path keeps it off
        deltagrad=DeltaGradConfig(period=args.period, burn_in=args.burn_in,
                                  impl=args.impl, guard=bool(args.model),
                                  curvature_eps=1e-8 if args.model else 0.0))

    # CI-sized LM reduction (matches examples/unlearn_lm.py); the serve
    # surface downstream is model-agnostic
    lm_reduced = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                      d_ff=128, vocab=128, d_head=16)
    obj = None if args.model else logreg_objective(l2=args.l2)

    def build_session(config=cfg):
        if args.model:
            from repro.data.synthetic import token_stream
            ds = token_stream(n_docs=args.n, seq_len=args.seq_len,
                              vocab=lm_reduced["vocab"], seed=args.seed)
            sess = UnlearnerSession.from_config(
                args.model, ds, reduced=lm_reduced, config=config,
                loss_chunk=args.seq_len)
        else:
            ds = binary_classification(n=args.n, d=args.d, seed=args.seed)
            sess = UnlearnerSession(obj, logreg_init(args.d, seed=1), ds,
                                    config)
        sess.fit()
        return sess, ds

    def score(sess, params, ds) -> float:
        """Accuracy for logreg; an exp(-token-CE) proxy for an LM."""
        if not args.model:
            return float(logreg_accuracy(params, ds))
        toks = jnp.asarray(np.asarray(ds.columns["tokens"][:64]))
        loss = sess.model.loss_fn(params, {"tokens": toks}, remat=False,
                                  loss_chunk=args.seq_len)
        return float(jnp.exp(-loss))

    t0 = time.perf_counter()
    sess, ds = build_session()
    jax.block_until_ready(sess.params)
    print(f"trained {args.steps} steps "
          f"(n={ds.n}, {'model=' + args.model if args.model else 'd=%d' % args.d}) "
          f"with path cache in {time.perf_counter() - t0:.2f}s; "
          f"score {score(sess, sess.params, ds):.4f}")

    # additions are served from a pre-appended row pool; with the engine's
    # pow2-bucketed row capacity a stream MAY outgrow the pool at O(log)
    # retrace cost, but staging the expected count keeps steady-state
    # latency clean of re-uploads entirely
    rng = np.random.default_rng(args.seed + 1)
    pool_src = rng.integers(0, args.n, size=args.requests)
    add_pool = list(ds.append({k: v[pool_src] for k, v in ds.columns.items()}))
    algo = sess.algorithm
    algo.begin_plan(args.requests)

    warm = [("delete", 1)] + ([("add", 1)] if args.add_frac > 0 else [])
    compile_s = sess.warmup(warm)
    print(f"session up (algorithm={algo.name}); first-request compile "
          f"{compile_s * 1e3:.0f} ms")

    # -- latency loop: dispatch (what the request queue sees) vs blocked
    # (dispatch + device drain) measured separately — timing a forced
    # jax.block_until_ready inside the per-request loop conflates the two.
    # Percentiles come from the shared obs.metrics histogram (the same
    # implementation ServeMonitor quantiles use).
    reg = obs_metrics.get_registry()
    reg.gauge("online.compile_time_s", unit="s",
              owner="core.online").set(compile_s)
    h_disp = reg.histogram("launch.dispatch_ms", unit="ms",
                           owner="launch.serve")
    h_block = reg.histogram("launch.blocked_ms", unit="ms",
                            owner="launch.serve")
    for i in range(args.requests):
        if add_pool and rng.random() < args.add_frac:
            op, row = "add", int(add_pool.pop(0))
        else:
            live = np.flatnonzero(algo.live[:args.n])
            op, row = "delete", int(rng.choice(live))
        t0 = time.perf_counter()
        h = sess.submit(op=op, rows=[row], coalesce=False)
        sess.flush()
        t_disp = time.perf_counter() - t0
        jax.block_until_ready(algo.params)
        t_block = time.perf_counter() - t0
        h_disp.observe(t_disp * 1e3)
        h_block.observe(t_block * 1e3)
        st = h.stats[0]
        print(f"  request {i:3d} {op:6s} row {row:5d}: dispatch "
              f"{t_disp * 1e3:7.1f} ms, blocked {t_block * 1e3:7.1f} ms  "
              f"(approx {st.approx_steps}, explicit {st.explicit_steps}, "
              f"grad-eval speedup x{st.theoretical_speedup:.1f})")
    dp, bp = h_disp.summary(), h_block.summary()
    print(f"served {args.requests} requests: dispatch p50 {dp['p50']:.1f} / "
          f"p95 {dp['p95']:.1f} / p99 {dp['p99']:.1f} ms, blocked p50 "
          f"{bp['p50']:.1f} / p95 {bp['p95']:.1f} / p99 {bp['p99']:.1f} ms; "
          f"score {score(sess, sess.params, ds):.4f}")

    # -- certified release: the certificate the stream's cumulative
    # deletions buy at --eps (publishes through the session PRNG key)
    published, cert = sess.publish(eps=args.eps)
    print(f"certificate: algorithm={cert.algorithm} "
          f"mechanism={cert.mechanism} eps={cert.eps:g} "
          f"delta={cert.delta:g} bound={cert.bound:.3e} "
          f"noise_scale={cert.noise_scale:.3e} removals={cert.removals}")

    # -- coalesced burst: K deletes as ONE group replay vs the serial path
    K = args.burst
    results = {
        "config": {"n": args.n, "d": args.d, "steps": args.steps,
                   "batch": args.batch, "requests": args.requests,
                   "add_frac": args.add_frac, "impl": args.impl,
                   "momentum": args.momentum, "burst": K,
                   "algorithm": args.algorithm, "eps": args.eps,
                   "trace": args.trace, "sla_class": args.sla_class,
                   "arrival_ms": args.arrival_ms},
        "compile_s": compile_s,
        "latency_ms": {"dispatch": dp, "blocked": bp},
        "accuracy": score(sess, sess.params, ds),
        "certificate": cert.as_dict(),
        "published_accuracy": score(sess, published, ds),
    }
    if args.model:
        # only stamped for LM runs — the logreg config must keep matching
        # the committed serve baseline (check_bench compares config dicts)
        results["config"]["model"] = args.model
        results["config"]["seq_len"] = args.seq_len
    if K > 0 and args.algorithm == "deltagrad":
        burst_rows = np.random.default_rng(args.seed + 2).choice(
            args.n, size=K, replace=False).tolist()

        sess_a, _ = build_session()          # serial Algorithm-3 stream
        sess_a.warmup([("delete", 1)])
        t0 = time.perf_counter()
        sess_a.stream_delete(burst_rows)
        t_serial = time.perf_counter() - t0

        sess_b, ds_b = build_session()       # ONE coalesced group replay
        sess_b.warmup([("delete", K)])
        t0 = time.perf_counter()
        hb = sess_b.delete(burst_rows)
        jax.block_until_ready(hb.params)
        t_coal = time.perf_counter() - t0

        # parity of the coalesced replay vs the python oracle
        import dataclasses
        cfg_py = dataclasses.replace(
            cfg, deltagrad=dataclasses.replace(cfg.deltagrad, impl="python"))
        sess_c, _ = build_session(cfg_py)
        sess_c.delete(burst_rows).result()
        parity = float(tree_norm(tree_sub(sess_b.params, sess_c.params)))
        drift = float(tree_norm(tree_sub(sess_b.params, sess_a.params)))
        results["coalesce"] = {
            "k": K,
            "serial_ms_per_req": t_serial / K * 1e3,
            "coalesced_ms_per_req": t_coal / K * 1e3,
            "per_request_speedup": t_serial / max(t_coal, 1e-9),
            "parity_vs_python": parity,
            "serial_vs_coalesced_dist": drift,
        }
        print(f"burst K={K}: serial {t_serial / K * 1e3:.1f} ms/req, "
              f"coalesced {t_coal / K * 1e3:.1f} ms/req "
              f"(x{t_serial / max(t_coal, 1e-9):.1f}); parity vs python "
              f"{parity:.2e}; serial-vs-coalesced dist {drift:.2e}")

    # -- continuous serving: a seeded open-loop trace through the serving
    # tier (repro.serve) — admission control, SLA-class deadlines, EDF
    # flush, cross-tenant batching, one replay in flight.  This replaces
    # the old session-global auto-flush load loop (and its hand-rolled
    # drain logic); the session-level max_pending/max_delay_s policy still
    # exists for embedded use, but the serving CLI routes everything
    # through the scheduler.  The lone tail request at the end proves the
    # deadline holds with ZERO further arrivals — the executor's idle tick
    # serves it, no timer thread and no extra poll() calls.
    if args.requests > 0:
        from repro.serve import (LoadGenerator, ServeConfig,
                                 ServingScheduler, diurnal_trace,
                                 fixed_trace, materialize, poisson_trace)

        sess_f, ds_f = build_session()
        rate = args.rate or (1e3 / args.arrival_ms if args.arrival_ms
                             else 200.0)
        class_mix = ({"interactive": 0.5, "batch": 0.3, "bulk_gdpr": 0.2}
                     if args.sla_class == "mixed" else (args.sla_class,))
        tenants = {"tenant-a": 0.6, "tenant-b": 0.4}
        if args.trace == "poisson":
            events = poisson_trace(rate, args.requests, args.seed + 3,
                                   tenants=tenants, classes=class_mix,
                                   add_frac=args.add_frac)
        elif args.trace == "diurnal":
            events = diurnal_trace(
                max(rate / 2, 1e-3), rate * 2,
                period_s=max(0.25, args.requests / rate),
                n_events=args.requests, seed=args.seed + 3,
                tenants=tenants, classes=class_mix,
                add_frac=args.add_frac)
        else:
            events = fixed_trace((args.arrival_ms or 2.0) / 1e3,
                                 args.requests, args.seed + 3,
                                 tenants=tenants, classes=class_mix,
                                 add_frac=args.add_frac)
        materialize(events, ds_f, seed=args.seed + 4)
        n_add_rows = sum(ev.n_rows for ev in events if ev.op == "add")
        # one serving stack per CLI run — publish its monitor into the
        # process-wide registry so --trace-out exports queue + serve
        # metrics alongside the engine/store ones
        from repro.serve.monitor import ServeMonitor
        sched = ServingScheduler(
            sess_f, ServeConfig(add_capacity=max(1, n_add_rows)),
            monitor=ServeMonitor(registry=reg))
        warm = [("delete", k) for k in (1, 2, 4, 8)]
        if n_add_rows:
            warm += [("add", k) for k in (1, 2, 4)]
        sess_f.warmup(warm)
        sched.start()
        res = LoadGenerator(sched).open_loop(events)
        for tk in res.tickets:
            if not tk.wait(timeout=60.0):  # wait() raises a failed request
                raise RuntimeError(
                    f"request {tk.req.seq} was not served within 60 s")
        # lone tail, then silence: only the executor's deadline tick fires
        used = {r for ev in events if ev.rows for r in ev.rows}
        live = np.flatnonzero(sess_f.algorithm.live[:args.n])
        lone_row = next(int(r) for r in live if int(r) not in used)
        lone = sched.submit("delete", rows=[lone_row],
                            sla_class=("interactive"
                                       if args.sla_class == "mixed"
                                       else args.sla_class))
        lone_ok = lone.wait(timeout=10.0)
        sched.stop()
        st = sched.stats()
        results["serving"] = {
            "trace": args.trace,
            "rate_rps": rate,
            "arrival_ms": args.arrival_ms,
            "sla_class": args.sla_class,
            "rejected": res.rejected,
            "lone_request_served": bool(lone_ok),
            "lone_missed_deadline": bool(lone.missed_deadline),
            **st,
        }
        bt = st["batches"]
        miss = st["deadline_misses_total"]
        print(f"serving: {st['admission']['admitted']} admitted "
              f"({res.rejected} rejected), {bt['count']} batches "
              f"(mean {bt['size_mean']:.1f} rows, {bt['cross_tenant']} "
              f"cross-tenant), {miss} deadline misses, "
              f"{st['add_capacity_retraces']} capacity retraces; lone "
              f"tail served by deadline tick: {lone_ok}")

    if args.bench_out:
        with open(args.bench_out, "w") as f:
            json.dump(results, f, indent=1)
        print(f"wrote {args.bench_out}")

    if args.profile_dir:
        jax.profiler.stop_trace()
        print(f"wrote jax profiler trace under {args.profile_dir}")
    if args.trace_out:
        tracer = obs_trace.disable()
        tracer.export_chrome(args.trace_out)
        reg.to_jsonl(args.trace_out + ".metrics.jsonl")
        n_scan = sum(1 for e in tracer.events()
                     if e["name"] == "replay.scan")
        print(f"wrote {args.trace_out} ({len(tracer.events())} spans, "
              f"{n_scan} replay.scan) + {args.trace_out}.metrics.jsonl")
    return results


def decode_main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build(cfg)
    params = model.init(args.seed)
    max_len = args.prompt_len + args.gen
    if cfg.family == "audio":
        caches = model.cache_init(args.batch, max_len, enc_len=64)
    else:
        caches = model.cache_init(args.batch, max_len)

    decode = jax.jit(lambda p, b, c: model.decode_fn(p, b, c),
                     donate_argnums=(2,))

    rng = np.random.default_rng(args.seed)
    prompt = rng.integers(0, cfg.vocab, size=(args.batch, args.prompt_len),
                          dtype=np.int32)

    # prefill by stepping (simple driver; the prefill graph is exercised by
    # the dry-run / tests)
    t0 = time.perf_counter()
    logits = None
    for t in range(args.prompt_len):
        logits, caches = decode(params, {"tokens": jnp.asarray(prompt[:, t:t + 1])},
                                caches)
    t_prefill = time.perf_counter() - t0

    key = jax.random.PRNGKey(args.seed)
    out_tokens = []
    t0 = time.perf_counter()
    for t in range(args.gen):
        if args.temperature > 0:
            key, sub = jax.random.split(key)
            nxt = jax.random.categorical(sub, logits / args.temperature, axis=-1)
        else:
            nxt = jnp.argmax(logits, axis=-1)
        nxt = nxt.astype(jnp.int32)[:, None]
        out_tokens.append(np.asarray(nxt))
        logits, caches = decode(params, {"tokens": nxt}, caches)
    t_gen = time.perf_counter() - t0

    gen = np.concatenate(out_tokens, axis=1)
    tok_s = args.batch * args.gen / max(t_gen, 1e-9)
    print(f"prefill {args.prompt_len} tok x {args.batch} in {t_prefill:.2f}s; "
          f"generated {args.gen} tok x {args.batch} in {t_gen:.2f}s "
          f"({tok_s:.1f} tok/s)")
    print("sample row 0:", gen[0].tolist())


def main() -> None:
    enable_compile_cache()
    if len(sys.argv) > 1 and sys.argv[1] == "unlearn":
        unlearn_main(sys.argv[2:])
    else:
        decode_main()


if __name__ == "__main__":
    main()
