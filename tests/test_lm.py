"""Transformer-LM unlearning: the model→Objective API end-to-end.

Tier-1 coverage for the LM integration path: `Objective.from_model` /
`UnlearnerSession.from_config` on a reduced-config transformer,
guard-ON deltagrad vs exact retrain, snapshot/restore bitwise parity,
the streamed + delta_int8 history path on a per-layer pytree, and the
flash-attention routing (interpret-mode kernel on CPU) against the
blockwise reference.  Shapes are toy; the architecture (GQA + RoPE +
SwiGLU, stacked per-layer leaves) is the real one.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.registry import get_config
from repro.core.deltagrad import (DeltaGradConfig, Objective,
                                  deltagrad_retrain, sgd_train_with_cache)
from repro.core.history import HistoryMeta
from repro.core.session import UnlearnerConfig, UnlearnerSession
from repro.core.store import HistoryStore
from repro.data.synthetic import token_stream
from repro.models.registry import build
from repro.utils.tree import tree_norm, tree_sub

REDUCED = dict(n_layers=2, d_model=32, n_heads=4, n_kv_heads=2, d_ff=64,
               vocab=64, d_head=8)
N_DOCS, SEQ, STEPS, BATCH = 48, 16, 18, 16
REMOVED = [3, 11, 25, 40]

# the paper's DNN recipe (§4.1): small T0, long burn-in, guard on
DG = DeltaGradConfig(period=2, burn_in=10, history_size=2, guard=True,
                     curvature_eps=1e-8)


def leaves_equal(a, b) -> bool:
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(la, lb))


@pytest.fixture(scope="module")
def docs():
    return token_stream(n_docs=N_DOCS, seq_len=SEQ, vocab=REDUCED["vocab"],
                        seed=0)


# the end-to-end distance assertion needs a deletion small relative to the
# corpus (4/256 docs) and enough SGD path for the correction to pay off —
# the tiny parity shapes above are too noisy for the quality claim
E2E = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
           vocab=128, d_head=16)
E2E_DOCS, E2E_SEQ, E2E_STEPS = 256, 32, 40


def make_lm_session(docs):
    return UnlearnerSession.from_config(
        "internlm2-1.8b", docs, reduced=E2E,
        config=UnlearnerConfig(steps=E2E_STEPS, batch_size=64, lr=0.02,
                               seed=5, deltagrad=DG),
        loss_chunk=E2E_SEQ)


# -- Objective.from_model ---------------------------------------------------


def test_from_model_matches_handrolled_vmap_bitwise(docs):
    """`Objective.from_model`'s per-example loss must equal the inline
    vmap every LM caller used to hand-roll — bitwise, not approximately:
    both trace the identical per-row program."""
    cfg = get_config("internlm2-1.8b").reduced(**REDUCED)
    model = build(cfg)
    params = model.init(1)
    batch = {"tokens": jnp.asarray(np.asarray(docs.columns["tokens"]))}

    obj = Objective.from_model(model, loss_chunk=SEQ)

    def handrolled(params, batch):
        def one(row):
            return model.loss_fn(params, {"tokens": row[None]},
                                 remat=False, loss_chunk=SEQ)
        return jax.vmap(one)(batch["tokens"])

    a = np.asarray(obj.per_example_loss(params, batch))
    b = np.asarray(handrolled(params, batch))
    assert a.shape == (docs.n,)
    assert (a == b).all()


def test_model_objective_convenience(docs):
    """`build(cfg).objective()` is the same bridge as Objective.from_model."""
    cfg = get_config("internlm2-1.8b").reduced(**REDUCED)
    model = build(cfg)
    obj = model.objective(loss_chunk=SEQ)
    assert isinstance(obj, Objective)
    batch = {"tokens": jnp.asarray(np.asarray(docs.columns["tokens"][:4]))}
    losses = np.asarray(obj.per_example_loss(model.init(1), batch))
    assert losses.shape == (4,) and np.isfinite(losses).all()


# -- end-to-end session surface on the LM -----------------------------------


def test_lm_session_end_to_end(tmp_path):
    """train-with-cache → snapshot → guard-ON delete vs exact retrain →
    restore → identical delete is bitwise → add resolves.

    One fit, the whole request surface: this is the ISSUE's acceptance
    path on a reduced transformer."""
    docs = token_stream(n_docs=E2E_DOCS, seq_len=E2E_SEQ,
                        vocab=E2E["vocab"], seed=0)
    sess = make_lm_session(docs)
    w_star = sess.fit()
    assert len(sess.history) == E2E_STEPS

    sess.save(str(tmp_path))

    w_u, _ = sess.baseline(REMOVED)              # exact retrain reference
    resp = sess.delete(REMOVED).result()
    w_i, stats = resp.params, resp.stats[0]

    d_ui = float(tree_norm(tree_sub(w_u, w_i)))
    d_us = float(tree_norm(tree_sub(w_u, w_star)))
    # DeltaGrad must land closer to the exact leave-K-out model than the
    # original params (the paper's Fig-style distance claim, non-convex)
    assert d_ui < d_us, (d_ui, d_us)
    assert stats.guard_fallbacks >= 0           # guard path exercised

    # restore serves the SAME plan bitwise-identically
    restored = UnlearnerSession.restore(str(tmp_path), sess.objective)
    w_r = restored.delete(REMOVED).result().params
    assert leaves_equal(w_i, w_r)

    # add: append two new documents, engine must serve them on the LM
    rng = np.random.default_rng(9)
    new_docs = {"tokens": rng.integers(
        0, E2E["vocab"], size=(2, E2E_SEQ), dtype=np.int32)}
    w_a = restored.add(data=new_docs).result().params
    assert all(np.isfinite(np.asarray(x)).all()
               for x in jax.tree.leaves(w_a))


# -- streamed + delta_int8 history on the LM pytree -------------------------


def test_lm_streamed_history_replay_parity(docs):
    """The tentpole storage claim at LM shape: (a) host-streamed f32
    replay is EXACTLY the resident replay (bit-identical recorders), and
    (b) the delta_int8 encoded path stays within the quantization
    envelope of the per-step python oracle on the same encoded history."""
    cfg_m = get_config("internlm2-1.8b").reduced(**REDUCED)
    model = build(cfg_m)
    obj = Objective.from_model(model, loss_chunk=SEQ)
    p0 = model.init(1)
    meta = HistoryMeta(n=docs.n, batch_size=BATCH, seed=5, steps=STEPS,
                       lr_schedule=((0, 0.05),))
    changed = np.asarray(REMOVED, dtype=np.int64)
    window = 4
    cfg = dataclasses.replace(DG, stream_window=window)

    # resident reference
    _, hist_res = sgd_train_with_cache(obj, p0, docs, meta, tier="stacked")
    w_res, _ = deltagrad_retrain(obj, hist_res, docs, changed, cfg)

    # (a) streamed f32: exact
    _, hist_f32 = sgd_train_with_cache(obj, p0, docs, meta, tier="host")
    store = HistoryStore.create(hist_f32, window=window)
    w_st, st = deltagrad_retrain(obj, hist_f32, docs, changed, cfg,
                                 store=store)
    assert st.extra["store"] == "streamed"
    assert float(tree_norm(tree_sub(w_st, w_res))) == 0.0

    # (b) delta_int8: within quantization envelope of the python oracle
    _, hist_d = sgd_train_with_cache(obj, p0, docs, meta, tier="host",
                                     codec="delta_int8")
    store_d = HistoryStore.create(hist_d, window=window)
    w_d, st_d = deltagrad_retrain(obj, hist_d, docs, changed, cfg,
                                  store=store_d)
    assert st_d.extra["store"] == "streamed"
    w_py, _ = deltagrad_retrain(obj, hist_d, docs, changed,
                                dataclasses.replace(cfg, impl="python"))
    rel = float(tree_norm(tree_sub(w_d, w_py))) \
        / max(1e-12, float(tree_norm(w_py)))
    assert rel < 5e-2, rel
    # the encoded path must actually compress the f32 rows (the margin is
    # modest here: at 18 steps the f32 keyframes dominate the encoded
    # bytes — bench_lm gates the amortized ratio on longer histories)
    assert store_d.compression_ratio > 1.2


# -- flash-attention routing on the replay forward --------------------------


def test_flash_routing_parity(docs):
    """An objective pinned to the flash kernel (interpret-mode on CPU)
    must match the blockwise reference to kernel tolerance — loss and
    gradient — through jit + vmap + grad, i.e. exactly how the replay
    engine drives it."""
    cfg = get_config("internlm2-1.8b").reduced(**REDUCED)
    model = build(cfg)
    p = model.init(1)
    batch = {"tokens": jnp.asarray(np.asarray(docs.columns["tokens"][:8]))}
    w = jnp.ones((8,))

    obj_ref = Objective.from_model(model, loss_chunk=SEQ)
    obj_fl = Objective.from_model(model, loss_chunk=SEQ, attn_impl="flash")

    l_ref, g_ref = obj_ref.make_value_grad_fn()(p, batch, w)
    l_fl, g_fl = obj_fl.make_value_grad_fn()(p, batch, w)

    # bf16 model dtype: kernel-vs-ref tolerance, not exactness
    assert abs(float(l_ref) - float(l_fl)) < 5e-3
    rel = float(tree_norm(tree_sub(g_fl, g_ref))) \
        / max(1e-12, float(tree_norm(g_ref)))
    assert rel < 5e-2, rel


def test_attention_impl_switch_validates():
    from repro.models.attention_config import (attention_impl,
                                               set_attention_impl,
                                               use_attention_impl)
    assert attention_impl() == "blockwise"
    with pytest.raises(ValueError):
        set_attention_impl("nope")
    with use_attention_impl("flash_interpret"):
        assert attention_impl() == "flash_interpret"
    assert attention_impl() == "blockwise"
    with use_attention_impl(None):
        assert attention_impl() == "blockwise"


# -- InternLM2 at the bench cell's recipe: reference, sharding, staging -----

ROOT = __import__("os").path.dirname(__import__("os").path.dirname(
    __import__("os").path.abspath(__file__)))
REF_PATH = ROOT + "/bench/configs/internlm2-1.8b-8l.reference.py"
# a tiny InternLM2: 2 layers, GQA 4/2 heads, SwiGLU FFN 2x hidden
TINY = dict(n_layers=2, d_model=32, n_heads=4, n_kv_heads=2, d_ff=64,
            vocab=64, d_head=8)


def _reference():
    import importlib.util
    spec = importlib.util.spec_from_file_location("lm_reference", REF_PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _ref_cfg(mc):
    return {"n_heads": mc.n_heads, "n_kv_heads": mc.n_kv_heads,
            "head_dim": mc.head_dim, "rope_theta": mc.rope_theta,
            "norm_eps": mc.norm_eps}


@pytest.mark.parametrize("what", ["loss", "grad"])
def test_bench_reference_matches_program_model(docs, what):
    """The benchmark's plain InternLM2 (bench/configs/...reference.py) and
    the program's transformer give the same per-document loss and
    gradients on seeded random weights.  Both are f32 at HIGHEST; they
    differ only in summation order (online-softmax blockwise attention
    against a plain softmax, chunked against whole-sequence loss), so
    they agree to f32 round-off: within 1e-5 relative."""
    ref = _reference()
    mc = get_config("internlm2-1.8b").reduced(**TINY)
    model = build(mc)
    obj = Objective.from_model(model, loss_chunk=SEQ, dtype=jnp.float32,
                               precision="highest")
    p = model.init(3)
    toks = jnp.asarray(np.asarray(docs.columns["tokens"][:3]))
    w = jnp.asarray([1.0, 0.0, 1.0])

    def ref_loss(p):
        per = jnp.stack([ref.doc_loss(p, t, _ref_cfg(mc), "highest")
                         for t in toks])
        return jnp.sum(per * w) / jnp.sum(w), per

    (want, per_ref), g_ref = jax.value_and_grad(ref_loss, has_aux=True)(p)
    if what == "loss":
        got = obj.per_example_loss(p, {"tokens": toks})
        np.testing.assert_allclose(np.asarray(got), np.asarray(per_ref),
                                   rtol=1e-5)
        return
    g = obj.make_grad_fn()(p, {"tokens": toks}, w)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(g_ref)):
        rel = float(jnp.linalg.norm(a - b)) / max(
            1e-30, float(jnp.linalg.norm(b)))
        assert rel <= 1e-5, rel


def test_bench_reference_imports_nothing_of_the_program():
    """The reference is independent of the code it checks: loading it and
    building its training function imports no `repro` module."""
    import subprocess
    import sys
    code = (
        "import importlib.util, sys\n"
        f"spec = importlib.util.spec_from_file_location('r', {REF_PATH!r})\n"
        "m = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(m)\n"
        "m.make_train({'lr_schedule': [[0, 0.01]], 'l2': 0.0,"
        " 'batch_size': 4, 'steps': 2})\n"
        "bad = [k for k in sys.modules if k == 'repro'"
        " or k.startswith('repro.')]\n"
        "assert not bad, bad\n"
        "print('REF_OK')\n")
    proc = subprocess.run([sys.executable, "-c", code], text=True,
                          capture_output=True, cwd=ROOT,
                          env={**__import__("os").environ,
                               "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0 and "REF_OK" in proc.stdout, \
        proc.stdout + proc.stderr


def test_model_sharded_minibatch_replay_subprocess():
    """On a (data, model) = (1, 4) mesh of forced CPU devices, a minibatch
    replay of a delta_int8 host-tier history keeps parameters, the
    explicit steps' pairs (dw, dg) and new parameters, and the L-BFGS ring
    on their `spec_for_leaf` placements, and publishes what the
    one-device replay of the same history publishes, to f32 round-off:
    within 1e-6 relative when every step is explicit, and within 1e-4 with
    approximate steps, which multiply a step's round-off by the L-BFGS
    operator's norm (up to ~10^3 on this non-convex minibatch LM)."""
    import os
    import subprocess
    import sys
    import textwrap
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
            " --xla_force_host_platform_device_count=4")
        import dataclasses
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.configs.registry import get_config
        from repro.core import engine, lbfgs
        from repro.core.deltagrad import (DeltaGradConfig, Objective,
            deltagrad_retrain, sgd_train_with_cache)
        from repro.core.history import HistoryMeta
        from repro.core.store import HistoryStore, PlacementPolicy
        from repro.data.synthetic import token_stream
        from repro.dist.sharding import params_shardings
        from repro.models.registry import build
        from repro.utils.tree import tree_norm, tree_sub
        mc = get_config("internlm2-1.8b").reduced(
            n_layers=2, d_model=32, n_heads=4, n_kv_heads=2, d_ff=64,
            vocab=64, d_head=8)
        model = build(mc)
        obj = Objective.from_model(model, remat=True, loss_chunk=16,
                                   dtype=jnp.float32, precision="highest")
        docs = token_stream(n_docs=32, seq_len=16, vocab=64, seed=0)
        meta = HistoryMeta(n=32, batch_size=4, seed=5, steps=14,
                           lr_schedule=((0, 0.01),))
        _, h = sgd_train_with_cache(obj, model.init(1), docs, meta,
                                    tier="host", codec="delta_int8",
                                    window=2)
        cfg = DeltaGradConfig(period=2, burn_in=4, history_size=2,
                              guard=True, curvature_eps=1e-8,
                              stream_window=2)
        removed = np.array([1, 5, 9, 20])
        w1, s1 = deltagrad_retrain(obj, h, docs, removed, cfg)
        pol = PlacementPolicy((1, 4), ("data", "model"))
        def norm(spec):  # P(None, 'model') == P(None, 'model', None)
            t = tuple(spec)
            while t and t[-1] is None:
                t = t[:-1]
            return t
        want = [norm(s.spec) for s in jax.tree.leaves(
            params_shardings(pol.plan(), w1))]
        assert any(any(a is not None for a in s) for s in want), want
        seen = {"pairs": [], "ring": []}
        step, add = engine._explicit_step, lbfgs.LbfgsBuffer.add_pair
        def spy_step(*a, **kw):
            out = step(*a, **kw)
            seen["pairs"].append([norm(x.sharding.spec) for t in (out[0],
                                  out[2], out[3])
                                  for x in jax.tree.leaves(t)])
            return out
        def spy_add(self, *a, **kw):
            ok = add(self, *a, **kw)
            if self._ring is not None:
                seen["ring"].append([norm(x.sharding.spec) for x in
                                     jax.tree.leaves(self._ring)])
            return ok
        engine._explicit_step, lbfgs.LbfgsBuffer.add_pair = spy_step, spy_add
        store = HistoryStore.create(h, placement=pol, window=2)
        w4, s4 = deltagrad_retrain(obj, h, docs, removed, cfg, store=store)
        engine._explicit_step, lbfgs.LbfgsBuffer.add_pair = step, add
        assert s4.extra["store"] == "sharded_streamed", s4.extra
        assert (s1.explicit_steps, s1.approx_steps) == \\
            (s4.explicit_steps, s4.approx_steps)
        assert s4.approx_steps > 0
        got = [norm(x.sharding.spec) for x in jax.tree.leaves(w4)]
        assert got == want, (got, want)
        assert seen["pairs"] and all(p == want * 3 for p in seen["pairs"])
        ring = [norm(P(None, *s)) for s in want] * 2
        assert seen["ring"] and all(r == ring for r in seen["ring"]), \\
            seen["ring"][-1]
        rel = float(tree_norm(tree_sub(w1, w4))) / float(tree_norm(w1))
        assert rel < 1e-4, rel
        exact = dataclasses.replace(cfg, burn_in=meta.steps)
        e1, _ = deltagrad_retrain(obj, h, docs, removed, exact)
        e4, _ = deltagrad_retrain(obj, h, docs, removed, exact, store=store)
        rel_e = float(tree_norm(tree_sub(e1, e4))) / float(tree_norm(e1))
        assert rel_e < 1e-6, rel_e
        print("MODEL_SHARD_OK", rel, rel_e)
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", code], text=True,
                          capture_output=True, env=env, cwd=ROOT)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "MODEL_SHARD_OK" in proc.stdout


def test_sharded_recording_keeps_device_blocks_subprocess():
    """Recorded on a (data, model) = (1, 4) mesh of forced CPU devices, a
    delta_int8 host-tier history keeps each sharded payload and keyframe as
    the per-device blocks it was fetched in (`HostShards`: nothing
    assembled, whole arrays still read back through ``np.asarray``), and a
    replay whose update runs through the per-device Pallas dequant kernels
    (interpret mode, each leaf in its own (rows, last dim) layout) publishes
    bitwise what the jnp update publishes.  The streamer holds one keyframe
    pair at a time: a window of the next key window never stages beside a
    window of the current one."""
    import os
    import subprocess
    import sys
    import textwrap
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
            " --xla_force_host_platform_device_count=4")
        import dataclasses
        import numpy as np, jax, jax.numpy as jnp
        from repro.configs.registry import get_config
        from repro.core.deltagrad import (DeltaGradConfig, Objective,
            deltagrad_retrain, sgd_train_with_cache)
        from repro.core.history import HistoryMeta, HostShards
        from repro.core.store import HistoryStore, PlacementPolicy
        from repro.data.synthetic import token_stream
        from repro.models.registry import build
        mc = get_config("internlm2-1.8b").reduced(
            n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
            vocab=256, d_head=16)
        model = build(mc)
        obj = Objective.from_model(model, remat=True, loss_chunk=16,
                                   dtype=jnp.float32, precision="highest")
        docs = token_stream(n_docs=32, seq_len=16, vocab=256, seed=0)
        meta = HistoryMeta(n=32, batch_size=4, seed=5, steps=20,
                           lr_schedule=((0, 0.01),))
        pol = PlacementPolicy((1, 4), ("data", "model"))
        _, h = sgd_train_with_cache(obj, model.init(1), docs, meta,
                                    tier="host", codec="delta_int8",
                                    window=1, placement=pol)
        assert h.key_interval == 16  # two key windows
        is_hs = lambda x: isinstance(x, HostShards)
        p3, _ = h.encoded_entry(3)
        blocks = [x for x in jax.tree.leaves(p3, is_leaf=is_hs) if is_hs(x)]
        assert blocks and all(len(x.pieces) == 4 for x in blocks)
        bw, _ = h.base_entry(0)
        assert any(is_hs(x) for x in jax.tree.leaves(bw, is_leaf=is_hs))
        for x in blocks:
            assert np.asarray(x).shape == x.shape
        cfg = DeltaGradConfig(period=2, burn_in=4, history_size=2,
                              guard=True, curvature_eps=1e-8,
                              stream_window=1)
        removed = np.array([1, 5, 9, 20])
        out = {}
        for fused in ("ref", "interpret"):
            store = HistoryStore.create(h, window=1)
            assert store.kind == "sharded_streamed"
            stage, mixed = store._stage_window, []
            def spy(wid, store=store, stage=stage):
                held = set(store._buf) | set(store._inflight)
                mixed.extend(o for o in held if store._key_windows(o)
                             != store._key_windows(wid))
                return stage(wid)
            store._stage_window = spy
            w, s = deltagrad_retrain(obj, h, docs, removed,
                                     dataclasses.replace(cfg, fused=fused),
                                     store=store)
            assert s.extra["fused"] == fused and s.approx_steps > 0
            assert not mixed and len(store._base_cache) == 1, mixed
            out[fused] = w
        for a, b in zip(jax.tree.leaves(out["ref"]),
                        jax.tree.leaves(out["interpret"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        print("DEVICE_BLOCKS_OK")
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", code], text=True,
                          capture_output=True, env=env, cwd=ROOT)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "DEVICE_BLOCKS_OK" in proc.stdout


def test_staged_bytes_count_a_window(docs):
    """``store.staged_bytes`` grows by what a window put on the device:
    the encoded payload, its scales and its keyframes, on a one-device
    streamer (the counter counts bytes a chip)."""
    from repro.core.store import SegmentStreamer, tree_device_nbytes
    from repro.obs import metrics as obs_metrics

    mc = get_config("internlm2-1.8b").reduced(**TINY)
    model = build(mc)
    obj = Objective.from_model(model, loss_chunk=SEQ)
    meta = HistoryMeta(n=docs.n, batch_size=BATCH, seed=5, steps=8,
                       lr_schedule=((0, 0.05),))
    _, h = sgd_train_with_cache(obj, model.init(1), docs, meta, tier="host",
                                codec="delta_int8")
    prev = obs_metrics.set_registry(obs_metrics.MetricsRegistry())
    try:
        # no prefetch: the next window would stage on another thread
        store = SegmentStreamer(h, window=4, prefetch=False)
        W, G, _ = store.window(0, 4)
        staged = obs_metrics.get_registry().counter("store.staged_bytes")
        assert staged.value == tree_device_nbytes((W, G)) > 0
    finally:
        obs_metrics.set_registry(prev)
