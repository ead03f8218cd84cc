"""Time-axis delta codecs + decode-in-kernel streamed replay.

Contract under test (see core/history.py `DeltaCodec` and
core/store.py `EncodedLeaf`):

  * entry t is stored as ``inner(x_t - base)`` against the immutable f32
    keyframe of key window ``t // key_interval`` — keyframe entries decode
    EXACTLY (residual 0 -> int8 absmax 0 -> scale 1.0, q zeros);
  * overwrites re-encode against the SAME base, so online rewrites never
    ripple into neighbouring entries;
  * the streamed scan path can keep windows ENCODED on device
    (``stream_decode="kernel"``) and dequantize inside the update — the
    endpoint must be bitwise identical to decode-on-fetch, and within the
    repo parity envelope of the per-step python oracle;
  * the disk tier batches one ``win_*.npz`` per stream window, stays
    readable next to the legacy per-step layout, and survives a
    state_dict round-trip mid-stream.
"""

import dataclasses
import os

import jax
import numpy as np
import pytest

from repro.core.deltagrad import (DeltaGradConfig, deltagrad_retrain,
                                  sgd_train_with_cache)
from repro.core.history import (CODECS, DeltaInt8Codec, HistoryMeta,
                                TrainingHistory)
from repro.core.online import online_deltagrad
from repro.core.store import (SegmentStreamer, entry_at, is_encoded_window,
                              tree_nbytes)
from repro.data.synthetic import binary_classification
from repro.models.simple import logreg_init, logreg_objective
from repro.utils.tree import tree_norm, tree_sub

TOL = 1.5e-7
CFG = DeltaGradConfig(period=5, burn_in=10, history_size=2)
META = dict(n=200, batch_size=64, seed=0, steps=30,
            lr_schedule=((0, 0.2),), l2=1e-3)


def _problem():
    ds = binary_classification(n=META["n"], d=16, seed=0)
    obj = logreg_objective(l2=META["l2"])
    return ds, obj, HistoryMeta(**META), logreg_init(16, seed=1)


def _dist(a, b):
    return float(tree_norm(tree_sub(a, b)))


def _tree(seed, scale=1.0):
    rng = np.random.RandomState(seed)
    return {"w": rng.randn(6, 4).astype(np.float32) * scale,
            "b": rng.randn(4).astype(np.float32) * scale}


# --------------------------------------------------------------------------
# Codec-level contracts
# --------------------------------------------------------------------------


class TestDeltaCodec:
    def test_roundtrip_within_residual_quant_error(self):
        codec = DeltaInt8Codec()
        base = codec.make_base(_tree(0))
        x = jax.tree.map(lambda b: b + np.float32(0.01) *
                         np.random.RandomState(1).randn(*b.shape)
                         .astype(np.float32), base)
        out = codec.decode_delta(codec.encode_delta(x, base), base)
        # int8 residual error <= absmax/127 per leaf; residual absmax~0.03
        for k in x:
            err = np.max(np.abs(np.asarray(out[k]) - x[k]))
            bound = np.max(np.abs(x[k] - base[k])) / 127.0
            assert err <= bound + 1e-7

    def test_keyframe_entry_decodes_exactly(self):
        """Residual 0 -> int8 absmax 0 -> scale fallback 1.0, q all-zero:
        the keyframe itself round-trips bitwise."""
        codec = DeltaInt8Codec()
        base = codec.make_base(_tree(2))
        stored = codec.encode_delta(_tree(2), base)
        for k in ("w", "b"):
            assert stored[k]["q"].dtype == np.int8
            assert not stored[k]["q"].any()
            assert float(stored[k]["scale"]) == 1.0
        out = codec.decode_delta(stored, base)
        assert _dist(out, jax.tree.map(np.asarray, base)) == 0.0

    def test_absmax_zero_leaf_no_nan(self):
        codec = CODECS["int8"]()
        z = {"w": np.zeros((3, 3), np.float32)}
        dec = codec.decode(codec.encode(z))
        assert np.all(np.asarray(dec["w"]) == 0.0)

    def test_codec_without_base_raises_actionably(self):
        codec = DeltaInt8Codec()
        with pytest.raises(ValueError, match="encode_delta"):
            codec.encode(_tree(0))
        with pytest.raises(ValueError, match="TrainingHistory"):
            codec.decode({"q": None})

    @pytest.mark.parametrize("codec", ["delta_int8", "delta_bf16"])
    def test_history_entries_within_quant_envelope(self, codec):
        ds, obj, meta, p0 = _problem()
        _, h32 = sgd_train_with_cache(obj, p0, ds, meta, tier="host")
        _, h = sgd_train_with_cache(obj, p0, ds, meta, tier="host",
                                    codec=codec)
        K = h.codec.key_interval
        for t in (0, K - 1, K, K + 1, meta.steps - 1):
            w32, g32 = h32.entry(t)
            w, g = h.entry(t)
            ref = float(tree_norm(w32))
            assert _dist(w, w32) <= 0.05 * max(ref, 1.0)
            assert _dist(g, g32) <= 0.05 * max(float(tree_norm(g32)), 1.0)
        # keyframe entries are exact: residual quantizes to all-zero
        w0, g0 = h.entry(K)
        w0_32, _ = h32.entry(K)
        assert _dist(w0, w0_32) == 0.0

    def test_overwrite_does_not_ripple(self):
        """Rewriting entry t re-encodes against the SAME keyframe: every
        other entry's decoded value is untouched, as is the base."""
        ds, obj, meta, p0 = _problem()
        _, h = sgd_train_with_cache(obj, p0, ds, meta, tier="host",
                                    codec="delta_int8")
        before = [h.entry(t) for t in range(meta.steps)]
        base_before = jax.tree.map(np.copy, h.base_entry(0)[0])
        new_w = jax.tree.map(lambda x: x * 1.5, before[5][0])
        h.overwrite(5, new_w, before[5][1])
        assert _dist(h.base_entry(0)[0], base_before) == 0.0
        for t in range(meta.steps):
            if t == 5:
                continue
            assert _dist(h.entry(t)[0], before[t][0]) == 0.0
            assert _dist(h.entry(t)[1], before[t][1]) == 0.0

    def test_delta_bytes_beat_f32(self):
        ds, obj, meta, p0 = _problem()
        _, h32 = sgd_train_with_cache(obj, p0, ds, meta, tier="host")
        _, h = sgd_train_with_cache(obj, p0, ds, meta, tier="host",
                                    codec="delta_int8")
        # ~2.5 bytes/param/step (int8 residual + base amortized over K=16)
        assert h.nbytes() < 0.45 * h32.nbytes()


# --------------------------------------------------------------------------
# Streamed replay: encoded windows, kernel-vs-fetch, python oracle
# --------------------------------------------------------------------------


class TestDeltaStreamedReplay:
    @pytest.mark.parametrize("codec", ["delta_int8", "delta_bf16"])
    def test_kernel_vs_fetch_bitwise(self, codec):
        """Keeping windows encoded on device and decoding in-scan must be
        BITWISE identical to decode-on-fetch: both decode paths run the
        same `q*scale + base`, and the codec's scale makes the product
        exact, so whether XLA contracts the multiply-add cannot matter."""
        ds, obj, meta, p0 = _problem()
        changed = np.arange(6)
        _, h = sgd_train_with_cache(obj, p0, ds, meta, tier="host",
                                    codec=codec)
        cfg_k = dataclasses.replace(CFG, stream_window=8,
                                    stream_decode="kernel")
        w_k, st_k = deltagrad_retrain(obj, h, ds, changed, cfg_k)
        assert st_k.extra["stream_decode"] == "kernel"
        assert st_k.extra["encoded_bytes_high"] > 0
        # the tiny logreg leaves carry proportionally large scale/kidx/base
        # overhead, so only require strictly-smaller-than-decoded here; the
        # shard bench (64x64 MLP leaves) gates the real ratio
        assert st_k.extra["compression_ratio"] > 1.2
        cfg_f = dataclasses.replace(CFG, stream_window=8,
                                    stream_decode="fetch")
        w_f, st_f = deltagrad_retrain(obj, h, ds, changed, cfg_f)
        assert st_f.extra["stream_decode"] == "fetch"
        assert _dist(w_k, w_f) == 0.0
        # encoded windows keep the device high-water below decoded windows
        assert st_k.extra["hbm_high_water"] < st_f.extra["hbm_high_water"]

    @pytest.mark.parametrize("codec", ["delta_int8", "int8", "bf16"])
    def test_kernel_mode_matches_python_oracle(self, codec):
        ds, obj, meta, p0 = _problem()
        changed = np.arange(6)
        _, h = sgd_train_with_cache(obj, p0, ds, meta, tier="host",
                                    codec=codec)
        cfg = dataclasses.replace(CFG, stream_window=8,
                                  stream_decode="kernel")
        w_k, _ = deltagrad_retrain(obj, h, ds, changed, cfg)
        w_p, _ = deltagrad_retrain(obj, h, ds, changed,
                                   dataclasses.replace(CFG, impl="python"))
        assert _dist(w_k, w_p) <= TOL

    def test_f32_forces_fetch(self):
        ds, obj, meta, p0 = _problem()
        _, h = sgd_train_with_cache(obj, p0, ds, meta, tier="host")
        store = SegmentStreamer(h, window=8)  # decode="auto"
        assert store.decode_mode == "fetch"
        W, _, off = store.window(0, 8)
        assert not is_encoded_window(W)

    def test_unknown_decode_mode_raises(self):
        ds, obj, meta, p0 = _problem()
        _, h = sgd_train_with_cache(obj, p0, ds, meta, tier="host")
        with pytest.raises(ValueError, match="kernel"):
            SegmentStreamer(h, window=8, decode="gpu")

    def test_encoded_window_slice_decode_matches_entry(self):
        """`entry_at` on an ENCODED window (the in-scan decode the engine
        uses outside the Pallas route) agrees with the store's own decoded
        entry bitwise — both run the decode expression under jit."""
        ds, obj, meta, p0 = _problem()
        _, h = sgd_train_with_cache(obj, p0, ds, meta, tier="host",
                                    codec="delta_int8")
        store = SegmentStreamer(h, window=8, decode="kernel")
        W, G, off = store.window(8, 16)
        assert is_encoded_window(W)
        slice_jit = jax.jit(lambda w, t: entry_at(w, t, off))
        for t in (8, 12, 15):
            w_ref, g_ref = store.entry(t)
            assert _dist(slice_jit(W, t), w_ref) == 0.0
            assert _dist(slice_jit(G, t), g_ref) == 0.0

    def test_interpret_kernel_replay_matches_ref(self):
        """The fused dequant Pallas kernels (interpret mode on CPU) take
        over the encoded-window update and agree with the jnp path."""
        ds, obj, meta, p0 = _problem()
        changed = np.arange(6)
        _, h = sgd_train_with_cache(obj, p0, ds, meta, tier="host",
                                    codec="delta_int8")
        cfg = dataclasses.replace(CFG, stream_window=8,
                                  stream_decode="kernel")
        w_ref, _ = deltagrad_retrain(obj, h, ds, changed, cfg)
        w_pl, st = deltagrad_retrain(
            obj, h, ds, changed,
            dataclasses.replace(cfg, fused="interpret"))
        assert st.extra["fused"] == "interpret"
        assert _dist(w_pl, w_ref) <= TOL

    def test_momentum_replay_falls_back_to_jnp_decode(self):
        """Momentum replays have no dequant kernel; encoded windows still
        work via the in-scan slice decode."""
        ds = binary_classification(n=META["n"], d=16, seed=0)
        obj = logreg_objective(l2=META["l2"])
        meta = HistoryMeta(**{**META, "momentum": 0.9})
        _, h = sgd_train_with_cache(obj, logreg_init(16, seed=1), ds, meta,
                                    tier="host", codec="delta_int8")
        cfg = dataclasses.replace(CFG, stream_window=8,
                                  stream_decode="kernel")
        w_k, _ = deltagrad_retrain(obj, h, ds, np.arange(6), cfg)
        w_f, _ = deltagrad_retrain(
            obj, h, ds, np.arange(6),
            dataclasses.replace(cfg, stream_decode="fetch"))
        assert _dist(w_k, w_f) == 0.0
        # vs the eager python oracle the momentum recursion compounds the
        # per-decode 1-ulp FMA difference, so the envelope is looser
        w_p, _ = deltagrad_retrain(obj, h, ds, np.arange(6),
                                   dataclasses.replace(CFG, impl="python"))
        assert _dist(w_k, w_p) <= 4 * TOL

    def test_online_rewrites_committed_through_delta(self):
        """Streamed online requests under the delta codec: rewrites commit
        back through encode_delta against the ORIGINAL keyframes, and a
        fresh engine resumes bit-identically to the uninterrupted run."""
        reqs_all = [("delete", 3), ("delete", 17)]

        def mk():
            ds = binary_classification(n=META["n"], d=16, seed=0)
            obj = logreg_objective(l2=META["l2"])
            _, h = sgd_train_with_cache(obj, logreg_init(16, seed=1), ds,
                                        HistoryMeta(**META), tier="host",
                                        codec="delta_int8")
            return ds, obj, h

        ds1, obj1, h1 = mk()
        w_ref, _ = online_deltagrad(obj1, h1, ds1, reqs_all, CFG)
        ds2, obj2, h2 = mk()
        online_deltagrad(obj2, h2, ds2, reqs_all[:1], CFG)
        ds2.removed[3] = True
        w_resume, _ = online_deltagrad(obj2, h2, ds2, reqs_all[1:], CFG)
        assert _dist(w_resume, w_ref) <= TOL


# --------------------------------------------------------------------------
# Windowed disk spill
# --------------------------------------------------------------------------


class TestWindowedSpill:
    def _train(self, tmp_path, codec="f32", spill_window=None, sub="d"):
        ds, obj, meta, p0 = _problem()
        d = tmp_path / sub
        w, h = sgd_train_with_cache(obj, p0, ds, meta, tier="disk",
                                    codec=codec, spill_dir=str(d),
                                    spill_window=spill_window)
        return ds, obj, meta, w, h, d

    def test_one_npz_per_stream_window(self, tmp_path):
        _, _, meta, _, h, d = self._train(tmp_path, spill_window=8)
        wins = sorted(f for f in os.listdir(d) if f.startswith("win_"))
        assert len(wins) == -(-meta.steps // 8)
        assert not [f for f in os.listdir(d) if f.startswith("step_")]
        assert h.io_write_s > 0.0

    def test_windowed_matches_host_tier_bitwise(self, tmp_path):
        ds, obj, meta, _, h, _ = self._train(tmp_path, spill_window=8)
        _, h_host = sgd_train_with_cache(obj, logreg_init(16, seed=1), ds,
                                         meta, tier="host")
        for t in (0, 7, 8, 15, meta.steps - 1):
            assert _dist(h.entry(t)[0], h_host.entry(t)[0]) == 0.0
            assert _dist(h.entry(t)[1], h_host.entry(t)[1]) == 0.0
        assert h.io_read_s >= 0.0

    def test_legacy_per_step_layout_still_written_and_read(self, tmp_path):
        """spill_window=1 keeps the old step_*.npz files; entries agree
        with the windowed layout bitwise."""
        _, _, meta, _, h1, d1 = self._train(tmp_path, spill_window=1,
                                            sub="legacy")
        _, _, _, _, h8, _ = self._train(tmp_path, spill_window=8, sub="win")
        steps = [f for f in os.listdir(d1) if f.startswith("step_")]
        assert len(steps) == meta.steps
        for t in (0, 13, meta.steps - 1):
            assert _dist(h1.entry(t)[0], h8.entry(t)[0]) == 0.0

    def test_disk_default_spill_window_matches_stream_window(self, tmp_path):
        _, _, meta, _, h, d = self._train(tmp_path)  # spill_window=None
        assert h.spill_window > 1
        assert [f for f in os.listdir(d) if f.startswith("win_")]

    def test_replay_from_windowed_delta_spill(self, tmp_path):
        ds, obj, meta, _, h, _ = self._train(tmp_path, codec="delta_int8",
                                             spill_window=8)
        cfg = dataclasses.replace(CFG, stream_window=8,
                                  stream_decode="kernel")
        w_k, st = deltagrad_retrain(obj, h, ds, np.arange(6), cfg)
        assert st.extra["spill_io_read_s"] >= 0.0
        w_p, _ = deltagrad_retrain(obj, h, ds, np.arange(6),
                                   dataclasses.replace(CFG, impl="python"))
        assert _dist(w_k, w_p) <= TOL

    def test_state_dict_roundtrip_windowed_delta(self, tmp_path):
        ds, obj, meta, _, h, d = self._train(tmp_path, codec="delta_int8",
                                             spill_window=8)
        state = h.state_dict()
        h2 = TrainingHistory.from_state_dict(state, spill_dir=str(d))
        for t in (0, 9, meta.steps - 1):
            assert _dist(h.entry(t)[0], h2.entry(t)[0]) == 0.0
            assert _dist(h.entry(t)[1], h2.entry(t)[1]) == 0.0

    def test_overwrite_through_windowed_spill(self, tmp_path):
        ds, obj, meta, _, h, _ = self._train(tmp_path, codec="delta_int8",
                                             spill_window=8)
        before = [h.entry(t) for t in range(meta.steps)]
        new_w = jax.tree.map(lambda x: x * 1.5, before[9][0])
        h.overwrite(9, new_w, before[9][1])
        for t in range(meta.steps):
            if t == 9:
                continue
            assert _dist(h.entry(t)[0], before[t][0]) == 0.0

    def test_delta_disk_bytes_reported(self, tmp_path):
        _, _, _, _, h, _ = self._train(tmp_path, codec="delta_int8",
                                       spill_window=8)
        assert h.disk_nbytes() > 0
