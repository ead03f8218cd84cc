"""Optimization-path cache — the information DeltaGrad records during training.

DeltaGrad needs, for every original training step ``t``:
  * the parameters ``w_t``,
  * the (mini-)batch mean gradient ``g_t = (1/|B_t|) sum_{i in B_t} grad F_i(w_t)``,
  * enough metadata to *replay the exact minibatch schedule* (seed, batch
    size, dataset size, learning-rate schedule).

Storage tiers (per-entry, selectable):
  * ``stacked`` — ONE device pytree per quantity with a leading time axis
    (``w[t] == Ws_leaf[t]``).  This is the replay engine's native format:
    approx segments run under ``jax.lax.scan`` and read entries with
    ``lax.dynamic_slice`` without any host round-trip (see core/engine.py),
  * ``device`` — per-entry JAX arrays (sharded exactly like the live
    parameters; right choice on a TPU mesh where each host holds 1/N of
    every entry),
  * ``host``   — entries are pulled to host numpy (paper's choice; frees HBM),
  * ``disk``   — chunked ``.npz`` spill with an in-memory LRU window (long
    training runs; participates in checkpoint/restart).

Any tier can produce the stacked view on demand via ``stacked_view()``
(cached; invalidated by ``append``/``overwrite``) and be bulk-rewritten from
it via ``replace_from_stacked`` — the online engine edits the stacked arrays
functionally during a request and flushes after each request.

Optional compression codecs trade cache size for a tiny, quantifiable
perturbation of the cached path (bf16: 2x; int8 + per-leaf scale: ~4x) —
DeltaGrad's correction is first-order in the cache error, and the
``bench_hyperparams`` benchmark measures the effect.

Choosing a tier — the HBM math
------------------------------

The cache stores TWO pytrees per step (w_t and g_t), so with ``P`` model
bytes (f32 params) and ``T`` recorded steps:

  =========  =======================  ==================================
  tier       device bytes             when to pick it
  =========  =======================  ==================================
  stacked    ``2*T*P``                default — replay runs fastest; fits
                                      whenever 2*T*P is small next to HBM
                                      (1k steps of a 10M-param model =
                                      80 GB… too big; of a 100k-param
                                      model = 800 MB… fine)
  stacked    ``2*T*P / mesh``         same, placed on a mesh via
  + mesh                              `core.store.PlacementPolicy`: each
                                      device keeps 1/mesh of every sharded
                                      leaf, gathered one step at a time
  device     ``2*T*P``                per-entry arrays; only when entries
                                      must keep a custom per-leaf sharding
  host       ``~2*L*P`` (window)      paper's choice — frees HBM; served
                                      to the compiled scan in ``L``-step
                                      double-buffered windows by
                                      `core.store.SegmentStreamer`
                                      (host RAM pays ``2*T*P / ratio``,
                                      codec ratio 1/2/4 for f32/bf16/int8)
  host       ``~2*L*P / mesh``        the COMPOSED tier
  + mesh     (shard window)           (`core.store.ShardedStreamer`) — the
                                      only fit when the path exceeds any
                                      single host's HBM *and* any single
                                      device: each mesh shard streams only
                                      its `stacked_spec_for_leaf` slice of
                                      every window, so per-DEVICE bytes
                                      are ~2 windows of the shard and
                                      per-HOST RAM is the encoded path
                                      (``2*T*P / ratio``) plus one window
                                      of staged slices; parameters,
                                      gradients and L-BFGS pairs keep
                                      their placements too
                                      (`core.store.PlacedReplay`)
  disk       ``~2*L*P`` (window)      longest runs; host RAM ~0, entries
                                      spill to ``spill_dir`` .npz
                                      (``spill_dir="auto"`` → a fresh
                                      tempdir, removed with the process;
                                      ``spill_window=L`` batches one .npz
                                      per stream window so a window costs
                                      one IO burst instead of L);
                                      also composes with a mesh placement
                                      exactly like host + mesh
  host/disk  ``~2*L*P / ratio``       delta+int8 codec (``delta_int8``):
  + delta    (encoded window)         entry t is stored as an int8
                                      residual against an immutable
                                      per-key-window keyframe base, so
                                      the slowly-drifting path costs
                                      ~2.5 B/param/step instead of 8
                                      (f32) or ~2 (plain int8) — and the
                                      residuals quantize far better
                                      because DeltaGrad's own premise
                                      (w_t, g_t change slowly) makes
                                      them small
  decode-in  encoded bytes stay       ``stream_decode="kernel"`` (auto
  -kernel    resident; dequant runs   for lossy codecs): the streamers
             in registers             ship ENCODED windows to device and
                                      the replay scan dequantizes per
                                      step in registers (Pallas
                                      ``kernels/dequant_update`` on TPU,
                                      XLA-fused jnp elsewhere) — HBM
                                      high-water drops by the codec
                                      ratio and no f32 window copy is
                                      ever materialized
  =========  =======================  ==================================

Bytes per param per step, both quantities (w_t and g_t) included:

  ==========  ==============================================
  codec       bytes/param/step (stored form)
  ==========  ==============================================
  f32         8
  bf16        4
  int8        ~2   (+ one f32 scale per leaf per entry)
  delta_bf16  ~4   (+ 8/key_interval for keyframe bases)
  delta_int8  ~2   + 8/key_interval ≈ 2.5 at key_interval=16
  ==========  ==============================================

Codecs apply to host/disk (re-encoded per entry); ``stacked`` rejects
lossy codecs by construction (it stores what the engine produced).

At transformer-LM scale the table stops being hypothetical.  Worked rows
(``models.registry.count_params`` gives P exactly):

  ==========================  ========  ===================================
  model                       P         bytes/step — f32 8 B vs delta ~2.5
  ==========================  ========  ===================================
  bench_lm --quick (2 layers  2.4 M     19 MB/step f32 → a 16-step path is
  of internlm2-1.8b blocks,             306 MB resident; delta_int8 holds
  vocab 8k, d_model 128)                it at ~77 MB with streamed windows
  internlm2-1.8b (full)       1.9 B     15 GB/step f32 — a 1k-step path is
                                        ~15 TB: no single tier fits, only
                                        host+mesh (`ShardedStreamer`) with
                                        ``delta_int8`` (~4.7 TB host RAM
                                        across the fleet, ~2 encoded shard
                                        windows per device) is in range
  ==========================  ========  ===================================

`benchmarks/bench_lm.py` measures the quick row end to end (HBM
high-water, encoded bytes, exact streamed-vs-resident parity) on per-layer
pytree histories; `examples/unlearn_lm.py` is the API quickstart.

Delta encoding (``delta_int8`` / ``delta_bf16``) uses a FIXED per-window
keyframe base rather than chaining t against t-1: entry ``t`` stores a
quantized residual against the first entry of its key window
(``t // key_interval``), captured once and immutable afterwards.  Chained
deltas would ripple on every online rewrite and lose O(1) random access
(the replay needs arbitrary entries every explicit step); a fixed base
keeps windows independently decodable, keeps overwrites local to one
entry, and still captures the time-axis redundancy DeltaGrad guarantees.
"""

from __future__ import annotations

import os
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


# --------------------------------------------------------------------------
# Host copies of sharded arrays
# --------------------------------------------------------------------------


def _index_key(index, shape) -> Tuple[Tuple[int, int], ...]:
    """A shard's index (a tuple of slices) as (start, stop) per dim."""
    return tuple(s.indices(d)[:2] for s, d in zip(index, shape))


class HostShards:
    """A host array kept as the per-device blocks of the sharded device
    array it was fetched from.  Fetching it copies each block once and
    assembles nothing; a mesh-placed store sends each block back to its
    device as it is (`core.store.ShardedStreamer`), so a stream window
    costs no host copy either.  Every other reader gets the whole array
    from ``np.asarray``."""

    __slots__ = ("shape", "dtype", "pieces")

    def __init__(self, shape, dtype, pieces):
        self.shape = tuple(shape)
        self.dtype = np.dtype(dtype)
        self.pieces = pieces  # {_index_key: ndarray}

    @property
    def ndim(self) -> int:
        return len(self.shape)

    @property
    def nbytes(self) -> int:
        return sum(p.nbytes for p in self.pieces.values())

    def __array__(self, dtype=None, copy=None):
        out = np.empty(self.shape, self.dtype)
        for key, p in self.pieces.items():
            out[tuple(slice(a, b) for a, b in key)] = p
        return out if dtype is None else out.astype(dtype)

    def __getitem__(self, i: int) -> "HostShards":
        """Entry `i` of the leading axis, which no placement shards."""
        return HostShards(self.shape[1:], self.dtype,
                          {k[1:]: p[i] for k, p in self.pieces.items()})

    def block(self, index) -> np.ndarray:
        """The block at `index` (a tuple of slices): the stored piece when
        the placement asking for it is the one it was fetched under."""
        p = self.pieces.get(_index_key(index, self.shape))
        return p if p is not None else np.asarray(self)[tuple(index)]


_FETCH_THREADS = 8
_fetch_pool: Optional[ThreadPoolExecutor] = None


def to_host(tree):
    """`jax.device_get` that keeps sharded leaves as `HostShards`: every
    transfer is started before any is waited for, the blocks land on
    `_FETCH_THREADS` threads, nothing is assembled, and leaves already on
    the host pass through."""
    global _fetch_pool

    def blocks(x):
        if isinstance(x, jax.Array) and not x.is_fully_replicated:
            out = {}
            for s in x.addressable_shards:
                out.setdefault(_index_key(s.index, x.shape), s.data)
            return out
        return {None: x} if isinstance(x, jax.Array) else None

    leaves, tdef = jax.tree.flatten(tree)
    shards = [blocks(x) for x in leaves]
    arrays = [d for b in shards if b is not None for d in b.values()]
    for d in arrays:
        d.copy_to_host_async()
    if _fetch_pool is None:
        _fetch_pool = ThreadPoolExecutor(max_workers=_FETCH_THREADS)
    got = iter(_fetch_pool.map(np.asarray, arrays))
    out = []
    for x, b in zip(leaves, shards):
        if b is None:
            out.append(x)
        elif None in b:
            out.append(next(got))
        else:
            out.append(HostShards(x.shape, x.dtype,
                                  {k: next(got) for k in b}))
    return jax.tree.unflatten(tdef, out)


def host_block(x, index) -> np.ndarray:
    """The block of host array `x` at `index` (a tuple of slices)."""
    if isinstance(x, HostShards):
        return x.block(index)
    return np.asarray(x)[tuple(index)]


# --------------------------------------------------------------------------
# Codecs
# --------------------------------------------------------------------------


class Codec:
    name = "f32"

    def encode(self, tree):
        return jax.tree.map(np.asarray, jax.device_get(tree))

    def decode(self, stored):
        return jax.tree.map(jnp.asarray, stored)

    def decode_stacked(self, stored):
        """Decode a WINDOW of encoded entries stacked along a leading axis
        (one upload per window — `core.store.SegmentStreamer`'s read path).
        Must agree elementwise with per-entry `decode`."""
        return jax.tree.map(jnp.asarray, stored)


class F32Codec(Codec):
    name = "f32"


class BF16Codec(Codec):
    name = "bf16"

    def encode(self, tree):
        tree = jax.device_get(tree)
        return jax.tree.map(lambda x: np.asarray(x, dtype=jnp.bfloat16), tree)

    def decode(self, stored):
        return jax.tree.map(lambda x: jnp.asarray(x, dtype=jnp.float32), stored)

    def decode_stacked(self, stored):
        return jax.tree.map(lambda x: jnp.asarray(x, dtype=jnp.float32),
                            stored)


class Int8Codec(Codec):
    """Symmetric per-leaf absmax int8 quantization."""

    name = "int8"

    def encode(self, tree):
        window = jax.tree.map(lambda x: jnp.asarray(x, jnp.float32)[None],
                              tree)
        return _int8_entries(_int8_encode_window(window, None, None))[0]

    def decode(self, stored):
        def dec(d):
            return jnp.asarray(d["q"], dtype=jnp.float32) * d["scale"]

        return jax.tree.map(dec, stored, is_leaf=lambda x: isinstance(x, dict) and "q" in x)

    def decode_stacked(self, stored):
        """Stacked window form: q is (L, ...) int8, scale is (L,) — one
        per-entry scale broadcast over the entry's dims."""

        def dec(d):
            q = jnp.asarray(d["q"], dtype=jnp.float32)
            scale = jnp.asarray(d["scale"], dtype=jnp.float32)
            return q * scale.reshape((-1,) + (1,) * (q.ndim - 1))

        return jax.tree.map(dec, stored,
                            is_leaf=lambda x: isinstance(x, dict) and "q" in x)


@jax.jit
def _int8_encode_window(Xs, bases, kidx):
    """The int8 codec (of the residual against keyframe ``kidx[i]`` when
    `bases`, a tuple of keyframe trees, is given: `DeltaCodec`; they are
    stacked inside the program) applied to each step of stacked (L, ...)
    leaves, on the device: {"q": (L, ...) int8, "scale": (L,) f32} per leaf.  A
    step's scale is its absmax / 127 rounded UP to 17 significant bits (an
    all-zero step scales by 1): |q| <= 127 has 7, so every decoded
    ``q * scale`` is exact in f32 and ``q * scale + base`` rounds once
    whether or not the compiler contracts it into a fused multiply-add —
    which XLA decides per fusion, so without this the in-scan and
    whole-window decodes could differ in the last bit.  Sharded leaves
    reduce their absmax shard-locally."""

    def enc(x, *bs):
        r = x.astype(jnp.float32)
        if bs:
            r = r - jnp.stack(bs)[kidx]
        lead = (-1,) + (1,) * (r.ndim - 1)
        amax = jnp.max(jnp.abs(r), axis=tuple(range(1, r.ndim)),
                       initial=0.0)
        m, e = jnp.frexp(amax / 127.0)
        scale = jnp.ldexp(jnp.ceil(m * 2.0**17) / 2.0**17, e)
        scale = jnp.where(amax > 0, scale, 1.0)
        q = jnp.clip(jnp.round(r / scale.reshape(lead)), -127, 127)
        return {"q": q.astype(jnp.int8), "scale": scale}

    if bases is None:
        return jax.tree.map(enc, Xs)
    return jax.tree.map(enc, Xs, *bases)


def _int8_entries(window):
    """A device-encoded window as per-entry stored trees on the host:
    [{"q": (...) int8, "scale": f32} per leaf, one a step]."""
    host = to_host(window)
    is_enc = lambda x: isinstance(x, dict) and "q" in x
    L = jax.tree.leaves(host)[0].shape[0]
    return [jax.tree.map(lambda d: {"q": d["q"][i],
                                    "scale": np.float32(d["scale"][i])},
                         host, is_leaf=is_enc) for i in range(L)]


class DeltaCodec(Codec):
    """Time-axis delta wrapper: store entry t as ``inner(x_t - base)``.

    ``base`` is the f32 keyframe of entry t's key window (the first entry
    written in window ``t // key_interval``), captured once by
    `TrainingHistory` and immutable afterwards — overwrites re-encode
    against the SAME base, so rewrites never ripple and any entry decodes
    in O(1) from (residual, base).  The decode contract is exactly

        x_t == inner_decode(residual) + base     (elementwise, f32)

    which `core.store` reuses verbatim for stacked windows and in-kernel
    dequant, so per-entry, windowed, and fused-kernel reads are bitwise
    identical.  The base lives OUTSIDE the stored entry (the history and
    the streamers pass it in), so encode/decode without a base raise."""

    inner_cls: type = Int8Codec
    name = "delta_int8"
    key_interval = 16

    def __init__(self):
        self.inner = self.inner_cls()

    def _need_base(self, op):
        raise ValueError(
            f"codec {self.name!r} stores residuals against a per-key-window "
            f"keyframe base; {op} needs the base passed explicitly (use "
            "encode_delta/decode_delta, or go through TrainingHistory which "
            "manages the bases)")

    def encode(self, tree):
        self._need_base("encode()")

    def decode(self, stored):
        self._need_base("decode()")

    def decode_stacked(self, stored):
        self._need_base("decode_stacked()")

    def make_base(self, tree):
        """Immutable f32 host copy used as the key window's keyframe
        (sharded leaves stay in their per-device blocks: `HostShards`)."""
        return jax.tree.map(
            lambda x: x if isinstance(x, HostShards)
            else np.array(x, dtype=np.float32), to_host(tree))

    def encode_delta(self, tree, base):
        tree = jax.device_get(tree)
        resid = jax.tree.map(
            lambda x, b: np.asarray(x, dtype=np.float32) - b, tree, base)
        return self.inner.encode(resid)

    def decode_delta(self, stored, base):
        resid = self.inner.decode(stored)
        return jax.tree.map(lambda r, b: r + jnp.asarray(b), resid, base)


class DeltaInt8Codec(DeltaCodec):
    inner_cls = Int8Codec
    name = "delta_int8"


class DeltaBF16Codec(DeltaCodec):
    inner_cls = BF16Codec
    name = "delta_bf16"


CODECS = {"f32": F32Codec, "bf16": BF16Codec, "int8": Int8Codec,
          "delta_int8": DeltaInt8Codec, "delta_bf16": DeltaBF16Codec}


# --------------------------------------------------------------------------
# History
# --------------------------------------------------------------------------


@dataclass
class HistoryMeta:
    """Everything needed to replay the original training run."""

    n: int  # dataset size during original training
    batch_size: int  # B (== n for deterministic GD)
    seed: int  # sampler seed
    steps: int  # T
    lr_schedule: Tuple[Tuple[int, float], ...]  # piecewise-constant (from_step, lr)
    l2: float = 0.0
    # beyond-paper: heavy-ball momentum (paper covers plain SGD; with
    # momentum every replay — batch or online — reconstructs its own
    # velocity from vel_0 = 0 using the corrected gradients, so the cache
    # stores plain gradients only — see core/engine.py and tests)
    momentum: float = 0.0
    extra: Dict[str, Any] = field(default_factory=dict)

    def lr_at(self, t: int) -> float:
        lr = self.lr_schedule[0][1]
        for start, value in self.lr_schedule:
            if t >= start:
                lr = value
        return lr


class TrainingHistory:
    """Per-step (w_t, g_t) cache with tiered storage."""

    def __init__(
        self,
        meta: HistoryMeta,
        tier: str = "device",
        codec: str = "f32",
        spill_dir: Optional[str] = None,
        lru_window: int = 64,
        spill_window: int = 0,
    ):
        if tier not in ("stacked", "device", "host", "disk"):
            raise ValueError(
                f"unknown history tier {tier!r}; pick one of 'stacked' "
                "(device-resident, fastest replay), 'device' (per-entry "
                "arrays), 'host' (entries offloaded to host RAM, streamed "
                "to the scan per segment), or 'disk' (.npz spill under "
                "spill_dir) — see the tier-selection guide in "
                "repro/core/history.py")
        if codec not in CODECS:
            raise ValueError(f"unknown codec {codec!r}; pick one of "
                             f"{sorted(CODECS)}")
        # compression codecs apply where entries are re-encoded (host/disk);
        # stacked storage keeps what the engine produced, uncompressed
        # (the pre-existing device tier also ignores codecs, kept permissive
        # for backwards compatibility)
        if codec != "f32" and tier == "stacked":
            raise ValueError(
                f"codec={codec!r} has no effect on tier='stacked': stacked "
                "storage keeps the exact arrays the recording scan "
                "produced.  Use tier='host' (or 'disk') to store the path "
                f"{codec}-compressed — the SegmentStreamer still serves it "
                "to the compiled scan — or drop the codec")
        self.meta = meta
        self.tier = tier
        self.codec: Codec = CODECS[codec]()
        self.lru_window = lru_window
        self._params: List[Any] = []
        self._grads: List[Any] = []
        self._disk_paths: List[Optional[str]] = []
        self._stacked: Optional[Tuple[Any, Any]] = None  # (Ws, Gs), T leading
        self._stacked_len: int = 0
        # overwrite()s against stacked storage buffered here (t -> (w, g));
        # folded into ONE batched scatter on the next stacked read, so a
        # per-step rewrite loop costs O(T*P) total, not O(T^2*P)
        self._pending_over: Dict[int, Tuple[Any, Any]] = {}
        self.final_params = None
        if tier == "disk":
            if spill_dir is None:
                raise ValueError(
                    "tier='disk' spills every history entry to .npz files "
                    "and needs somewhere to put them: pass "
                    "spill_dir=<directory> (created if missing), or "
                    "spill_dir='auto' to opt into a fresh temporary "
                    "directory (removed when the process exits)")
            if spill_dir == "auto":
                import atexit
                import shutil
                import tempfile
                spill_dir = tempfile.mkdtemp(prefix="repro_history_")
                atexit.register(shutil.rmtree, spill_dir,
                                ignore_errors=True)
            os.makedirs(spill_dir, exist_ok=True)
        self.spill_dir = spill_dir
        # delta codecs: immutable f32 keyframes, kwid -> (base_w, base_g)
        self._bases: Dict[int, Tuple[Any, Any]] = {}
        # disk tier, windowed spill: one .npz per spill_window steps
        self.spill_window = max(0, int(spill_window)) if tier == "disk" else 0
        self._win_paths: List[str] = []
        self._spill_buf: List[Tuple[Any, Any]] = []  # not-yet-flushed entries
        self._spill_flushed = 0  # steps already on disk
        self._win_cache: Optional[Tuple[int, List[Tuple[Any, Any]]]] = None
        self._win_dirty = False
        self.io_read_s = 0.0  # cumulative spill IO wall time
        self.io_write_s = 0.0
        # the mesh the path was recorded under (`core.store.PlacementPolicy`,
        # set by `core.engine.run_training`): stores built for this history
        # are placed on it unless told otherwise
        self.placement = None
        # delta codecs, device-side recording: the current key window's
        # keyframe on the device, (kwid, base_w, base_g)
        self._dev_base: Optional[Tuple[int, Any, Any]] = None

    def __len__(self) -> int:
        return self._stacked_len + len(self._params)

    # -- delta-codec keyframe bases ------------------------------------------

    @property
    def is_delta(self) -> bool:
        return isinstance(self.codec, DeltaCodec)

    @property
    def key_interval(self) -> int:
        return self.codec.key_interval if self.is_delta else 0

    def base_entry(self, kwid: int) -> Tuple[Any, Any]:
        """(base_w, base_g) f32 keyframes of key window `kwid`."""
        return self._bases[kwid]

    def _base_for(self, t: int, params=None, grad=None) -> Tuple[Any, Any]:
        kwid = t // self.codec.key_interval
        if kwid not in self._bases:
            if params is None:
                raise KeyError(
                    f"no keyframe base for key window {kwid} (entry {t})")
            self._bases[kwid] = (self.codec.make_base(params),
                                 self.codec.make_base(grad))
        return self._bases[kwid]

    def _encode_pair(self, t: int, params, grad):
        if self.is_delta:
            bp, bg = self._base_for(t, params, grad)
            return (self.codec.encode_delta(params, bp),
                    self.codec.encode_delta(grad, bg))
        return self.codec.encode(params), self.codec.encode(grad)

    def _decode_pair(self, t: int, enc_p, enc_g):
        if self.is_delta:
            bp, bg = self._base_for(t)
            return (self.codec.decode_delta(enc_p, bp),
                    self.codec.decode_delta(enc_g, bg))
        return self.codec.decode(enc_p), self.codec.decode(enc_g)

    # -- write path --------------------------------------------------------

    def append(self, params, grad) -> None:
        t = len(self._params)
        if self._stacked_is_storage:
            # buffered; merged into the stacked arrays on the next read
            self._params.append(params)
            self._grads.append(grad)
        elif self.tier == "device":
            self._params.append(params)
            self._grads.append(grad)
            self._stacked = None
        else:
            self._store_encoded(t, *self._encode_pair(t, params, grad))

    def encode_window(self, Ws, Gs):
        """Append the entries of one recorded window, stacked (L, ...) on
        the device, in two halves.  On the offload tiers the int8 codecs
        encode it there (`_int8_encode_window`), so only the int8 payload,
        the per-entry scales and each key window's keyframe cross to the
        host; this half dispatches that encode and returns what
        `land_window` stores.  Nothing here waits for the device, so a
        recorder can dispatch its next window before landing this one and
        the transfer to the host overlaps that window's compute.  Other
        codecs and tiers store the window here, entry by entry, and
        return None."""
        inner = self.codec.inner if self.is_delta else self.codec
        if self.tier not in ("host", "disk") \
                or not isinstance(inner, Int8Codec):
            host_w, host_g = jax.device_get((Ws, Gs))
            for i in range(jax.tree.leaves(host_w)[0].shape[0]):
                self.append(jax.tree.map(lambda x: x[i], host_w),
                            jax.tree.map(lambda x: x[i], host_g))
            return None
        t0 = len(self._params)
        L = jax.tree.leaves(Ws)[0].shape[0]
        bases, kidx, new_keys = None, None, []
        if self.is_delta:
            K = self.codec.key_interval
            kwids = range(t0 // K, (t0 + L - 1) // K + 1)
            pairs = []
            for k in kwids:
                if self._dev_base is None or self._dev_base[0] != k:
                    i0 = k * K - t0
                    if i0 >= 0:  # key window k starts in this window
                        bw, bg = (jax.tree.map(lambda x: x[i0], Ws),
                                  jax.tree.map(lambda x: x[i0], Gs))
                        new_keys.append((k, bw, bg))
                    else:  # its keyframe came in through append()
                        bw, bg = jax.tree.map(jnp.asarray, self._bases[k])
                    self._dev_base = (k, bw, bg)
                pairs.append(self._dev_base[1:])
            bases = (tuple(p for p, _ in pairs), tuple(g for _, g in pairs))
            kidx = jnp.asarray([(t0 + i) // K - kwids[0] for i in range(L)],
                               jnp.int32)
        enc_w = _int8_encode_window(Ws, None if bases is None else bases[0],
                                    kidx)
        enc_g = _int8_encode_window(Gs, None if bases is None else bases[1],
                                    kidx)
        return t0, enc_w, enc_g, new_keys

    def land_window(self, pending) -> None:
        """The host half of `encode_window`: bring its result (and the
        keyframes its window opened) to the host and store its entries
        (nothing for None)."""
        if pending is None:
            return
        t0, enc_w, enc_g, new_keys = pending
        assert t0 == len(self._params), (t0, len(self._params))
        # one fetch: every transfer of the window starts before any waits
        keys, enc_w, enc_g = to_host(
            ([(bw, bg) for _, bw, bg in new_keys], enc_w, enc_g))
        for (k, _, _), (bw, bg) in zip(new_keys, keys):
            self._bases[k] = (self.codec.make_base(bw),
                              self.codec.make_base(bg))
        enc_w, enc_g = _int8_entries(enc_w), _int8_entries(enc_g)
        for i in range(len(enc_w)):
            self._store_encoded(t0 + i, enc_w[i], enc_g[i])

    def _store_encoded(self, t: int, enc_p, enc_g) -> None:
        """Keep entry `t` in stored form on the host or disk tier."""
        self._stacked = None
        if self.tier == "host":
            self._params.append(enc_p)
            self._grads.append(enc_g)
        elif self.spill_window > 1:  # disk, one .npz per window
            flat_p, tdef = jax.tree.flatten(enc_p)
            self._treedef = tdef
            self._params.append(None)
            self._grads.append(None)
            self._spill_buf.append((enc_p, enc_g))
            self._flush_spill()  # no-op until a window is complete
        else:  # disk, legacy one .npz per step
            path = os.path.join(self.spill_dir, f"step_{t:07d}.npz")
            flat_p, tdef = jax.tree.flatten(enc_p)
            flat_g, _ = jax.tree.flatten(enc_g)
            t0 = time.perf_counter()
            np.savez(path, n_p=len(flat_p), *flat_p, *flat_g)
            self.io_write_s += time.perf_counter() - t0
            self._params.append(None)
            self._grads.append(None)
            self._treedef = tdef
            self._disk_paths.append(path)

    # -- windowed disk spill (one .npz per spill_window steps) ---------------

    def _win_path(self, wid: int) -> str:
        return os.path.join(self.spill_dir, f"win_{wid:07d}.npz")

    def _write_win(self, wid: int, entries: List[Tuple[Any, Any]]) -> None:
        per_entry: List[List[Any]] = []
        n_p = 0
        for enc_p, enc_g in entries:
            flat_p, _ = jax.tree.flatten(enc_p)
            flat_g, _ = jax.tree.flatten(enc_g)
            n_p = len(flat_p)
            per_entry.append(flat_p + flat_g)
        # one member per LEAF stacked over the window's steps, not one per
        # leaf per step: npz overhead (zip entry + .npy header) is per
        # member, and encoded trees double the leaf count (q + scale) —
        # per-step members would cost more than the int8 payload saves
        stacked = [np.stack([np.asarray(row[i]) for row in per_entry])
                   for i in range(2 * n_p)]
        t0 = time.perf_counter()
        np.savez(self._win_path(wid), n_p=n_p,
                 t0=wid * self.spill_window, steps=len(entries), *stacked)
        self.io_write_s += time.perf_counter() - t0

    def _flush_spill(self, everything: bool = False) -> None:
        """Write buffered appends as window files — complete windows only,
        unless `everything` (finalize) also flushes the partial tail.  A
        partial window rewritten later (appends resumed after finalize)
        merges with the entries already on disk."""
        W = self.spill_window
        while self._spill_buf:
            wid = self._spill_flushed // W
            off = self._spill_flushed % W
            take = min(W - off, len(self._spill_buf))
            if not everything and off + take < W:
                return  # keep the partial tail buffered
            entries = (list(self._load_win(wid)) if off else []) \
                + self._spill_buf[:take]
            self._write_win(wid, entries)
            if wid >= len(self._win_paths):
                self._win_paths.append(self._win_path(wid))
            self._win_cache = (wid, entries)
            self._win_dirty = False
            self._spill_flushed += take
            self._spill_buf = self._spill_buf[take:]

    def _flush_win_cache(self) -> None:
        """Write back a dirty cached window (deferred overwrite commit)."""
        if self._win_cache is not None and self._win_dirty:
            wid, entries = self._win_cache
            self._write_win(wid, entries)
        self._win_dirty = False

    def _load_win(self, wid: int) -> List[Tuple[Any, Any]]:
        if self._win_cache is not None and self._win_cache[0] == wid:
            return self._win_cache[1]
        self._flush_win_cache()
        t0 = time.perf_counter()
        with np.load(self._win_paths[wid]) as data:
            n_p = int(data["n_p"])
            steps = int(data["steps"])
            stacked = [data[f"arr_{i}"] for i in range(2 * n_p)]
        self.io_read_s += time.perf_counter() - t0
        entries = []
        for e in range(steps):
            flat = [s[e] for s in stacked]
            entries.append((jax.tree.unflatten(self._treedef, flat[:n_p]),
                            jax.tree.unflatten(self._treedef, flat[n_p:])))
        self._win_cache = (wid, entries)
        self._win_dirty = False
        return entries

    def finalize(self, final_params) -> None:
        self.final_params = final_params
        self._dev_base = None  # recording is over: free the device keyframe
        # drain buffered writes (one batched scatter) so the pending dict
        # never outlives the run/request that produced it
        self._merge_pending()
        if self.spill_window > 1:
            self._flush_spill(everything=True)
            self._flush_win_cache()

    # -- stacked tier / view -------------------------------------------------

    def set_stacked(self, Ws, Gs, final_params=None) -> None:
        """Adopt (Ws, Gs) — pytrees with a leading time axis — as the cache.

        This is the zero-copy hand-off from the engine's recording scan: the
        arrays the scan collected ARE the history.  For the ``stacked`` and
        ``device`` tiers the stacked arrays become the storage (one device
        buffer — no per-entry slice copies); host/disk re-encode per entry."""
        T = jax.tree.leaves(Ws)[0].shape[0]
        if self.tier in ("stacked", "device"):
            self._stacked = (Ws, Gs)
            self._stacked_len = T
            self._params, self._grads = [], []
            self._pending_over = {}
        else:
            for i in range(T):
                self.append(jax.tree.map(lambda x: x[i], Ws),
                            jax.tree.map(lambda x: x[i], Gs))
        if final_params is not None:
            self.finalize(final_params)

    @property
    def _stacked_is_storage(self) -> bool:
        """True when `_stacked` IS the backing store (the stacked tier, or a
        device-tier history adopted via set_stacked/replace_from_stacked) —
        as opposed to the derived cache other tiers hold transiently."""
        return self.tier == "stacked" or self._stacked_len > 0

    def _merge_pending(self) -> None:
        """Stacked storage: fold buffered append()s and overwrite()s into the
        stacked arrays (one concatenate + one batched scatter)."""
        if not self._stacked_is_storage:
            return
        if self._params:
            new_w = jax.tree.map(lambda *xs: jnp.stack(xs), *self._params)
            new_g = jax.tree.map(lambda *xs: jnp.stack(xs), *self._grads)
            if self._stacked is None:
                self._stacked = (new_w, new_g)
            else:
                Ws, Gs = self._stacked
                self._stacked = (
                    jax.tree.map(lambda a, b: jnp.concatenate([a, b]), Ws, new_w),
                    jax.tree.map(lambda a, b: jnp.concatenate([a, b]), Gs, new_g),
                )
            self._stacked_len += len(self._params)
            self._params, self._grads = [], []
        if self._pending_over:
            ts = jnp.asarray(list(self._pending_over.keys()))
            vals = list(self._pending_over.values())
            up_w = jax.tree.map(lambda *xs: jnp.stack(xs), *[v[0] for v in vals])
            up_g = jax.tree.map(lambda *xs: jnp.stack(xs), *[v[1] for v in vals])
            Ws, Gs = self._stacked
            self._stacked = (
                jax.tree.map(lambda x, u: x.at[ts].set(u), Ws, up_w),
                jax.tree.map(lambda x, u: x.at[ts].set(u), Gs, up_g),
            )
            self._pending_over = {}

    def stacked_view(self):
        """(Ws, Gs) with every leaf stacked along a leading time axis.

        Free for the stacked tier; built once and cached for the others
        (invalidated by append/overwrite)."""
        if self._stacked_is_storage:
            self._merge_pending()
            if self._stacked is None:
                raise ValueError("stacked_view() on an empty history")
            return self._stacked
        if self._stacked is None:
            T = len(self)
            entries = [self.entry(t) for t in range(T)]
            Ws = jax.tree.map(lambda *xs: jnp.stack(xs), *[e[0] for e in entries])
            Gs = jax.tree.map(lambda *xs: jnp.stack(xs), *[e[1] for e in entries])
            if self.tier == "device" and not self._multi_device():
                # adopt as storage: keeping the per-entry arrays alongside
                # would double device memory for the whole path.  Skipped on
                # a mesh — the device tier's contract is entries sharded like
                # the live params, and jnp.stack'd copies would not be.
                self.set_stacked(Ws, Gs)
            else:
                self._stacked = (Ws, Gs)
        return self._stacked

    def _multi_device(self) -> bool:
        for tree in self._params[:1]:
            for leaf in jax.tree.leaves(tree):
                sharding = getattr(leaf, "sharding", None)
                if sharding is not None and len(getattr(
                        sharding, "device_set", ())) > 1:
                    return True
        return False

    def replace_from_stacked(self, Ws, Gs, final_params=None) -> None:
        """Bulk-rewrite the whole cache from edited stacked arrays (the online
        engine's end-of-request flush); pass `final_params` to finalize the
        post-request model in the same call."""
        if self.tier == "stacked" or (self.tier == "device"
                                      and not self._multi_device()):
            self._params, self._grads = [], []
            self._stacked = (Ws, Gs)
            self._stacked_len = jax.tree.leaves(Ws)[0].shape[0]
            self._pending_over = {}
        else:
            T = len(self)
            self._stacked = None
            for t in range(T):
                self.overwrite(t, jax.tree.map(lambda x: x[t], Ws),
                               jax.tree.map(lambda x: x[t], Gs))
            # do NOT cache (Ws, Gs) here: under a lossy codec the raw arrays
            # would diverge from what entry() decodes back; let stacked_view()
            # rebuild from the encoded entries so both read paths agree
        if final_params is not None:
            self.finalize(final_params)

    # -- read path ----------------------------------------------------------

    def _load_disk(self, t: int):
        if self.spill_window > 1:
            if t >= self._spill_flushed:  # still buffered, not yet on disk
                return self._spill_buf[t - self._spill_flushed]
            wid, off = divmod(t, self.spill_window)
            return self._load_win(wid)[off]
        t0 = time.perf_counter()
        with np.load(self._disk_paths[t]) as data:
            n_p = int(data["n_p"])
            arrays = [data[f"arr_{i}"] for i in range(2 * n_p)]
        self.io_read_s += time.perf_counter() - t0
        p = jax.tree.unflatten(self._treedef, arrays[:n_p])
        g = jax.tree.unflatten(self._treedef, arrays[n_p:])
        return p, g

    def entry(self, t: int):
        """(w_t, g_t) decoded back to device arrays."""
        if self._stacked_is_storage:
            if t in self._pending_over:  # not yet scattered — serve directly
                return self._pending_over[t]
            if self._params:
                self._merge_pending()
            if self._stacked is None or not 0 <= t < self._stacked_len:
                raise IndexError(f"history entry {t} of {len(self)}")
            Ws, Gs = self._stacked
            return (jax.tree.map(lambda x: x[t], Ws),
                    jax.tree.map(lambda x: x[t], Gs))
        if self.tier == "device":
            return self._params[t], self._grads[t]
        if self.tier == "host":
            return self._decode_pair(t, self._params[t], self._grads[t])
        p, g = self._load_disk(t)
        return self._decode_pair(t, p, g)

    def encoded_entry(self, t: int):
        """(w_t, g_t) in STORED form — no codec decode, no device upload.

        Offload tiers only: this is `core.store.SegmentStreamer`'s read
        path (it stacks a whole window of encoded entries, ships them in
        one copy, and decodes on device)."""
        assert self.tier in ("host", "disk"), self.tier
        if self.tier == "host":
            return self._params[t], self._grads[t]
        return self._load_disk(t)

    def params_at(self, t: int):
        return self.entry(t)[0]

    def grad_at(self, t: int):
        return self.entry(t)[1]

    # -- in-place rewrite (online deletion, Algorithm 3) --------------------

    def overwrite(self, t: int, params, grad) -> None:
        if self._stacked_is_storage:
            if self._params:
                self._merge_pending()  # appends first, to fix the length
            if self._stacked is None or not 0 <= t < self._stacked_len:
                raise IndexError(f"history entry {t} of {len(self)}")
            self._pending_over[t] = (params, grad)
            return
        self._stacked = None
        if self.tier == "device":
            self._params[t] = params
            self._grads[t] = grad
            return
        if self.tier == "host":
            self._params[t], self._grads[t] = self._encode_pair(t, params,
                                                                grad)
            return
        # disk: re-encode against the same (immutable) base — a delta
        # rewrite stays local to this entry, no ripple into neighbours
        enc_p, enc_g = self._encode_pair(t, params, grad)
        if self.spill_window > 1:
            if t >= self._spill_flushed:
                self._spill_buf[t - self._spill_flushed] = (enc_p, enc_g)
                return
            wid, off = divmod(t, self.spill_window)
            entries = self._load_win(wid)
            entries[off] = (enc_p, enc_g)
            self._win_dirty = True  # written back on window change/finalize
            return
        flat_p, _ = jax.tree.flatten(enc_p)
        flat_g, _ = jax.tree.flatten(enc_g)
        t0 = time.perf_counter()
        np.savez(self._disk_paths[t], n_p=len(flat_p), *flat_p, *flat_g)
        self.io_write_s += time.perf_counter() - t0

    # -- checkpoint integration ---------------------------------------------

    def state_dict(self) -> Dict[str, Any]:
        if self.spill_window > 1:
            self._flush_spill(everything=True)
            self._flush_win_cache()
        state = {
            "meta": self.meta,
            "tier": self.tier,
            "codec": self.codec.name,
            "params": [jax.device_get(p) for p in self._params],
            "grads": [jax.device_get(g) for g in self._grads],
            "final_params": jax.device_get(self.final_params),
            "disk_paths": list(self._disk_paths),
        }
        if self._bases:
            state["bases"] = dict(self._bases)
        if self.spill_window > 1:
            state["spill_window"] = self.spill_window
            state["win_paths"] = list(self._win_paths)
            state["spill_flushed"] = self._spill_flushed
        if self._stacked_is_storage and self._stacked is not None:
            self._merge_pending()
            state["params"], state["grads"] = [], []
            state["stacked"] = jax.device_get(self._stacked)
        return state

    @classmethod
    def from_state_dict(cls, state: Dict[str, Any], spill_dir: Optional[str] = None):
        h = cls(state["meta"], tier=state["tier"], codec=state["codec"],
                spill_dir=spill_dir or "/tmp/repro_history",
                spill_window=state.get("spill_window", 0))
        h._params = state["params"]
        h._grads = state["grads"]
        h._disk_paths = state["disk_paths"]
        h.final_params = state["final_params"]
        h._bases = dict(state.get("bases", {}))
        if state.get("spill_window", 0) > 1:
            h._win_paths = list(state.get("win_paths", []))
            h._spill_flushed = int(state.get("spill_flushed", 0))
        if state.get("stacked") is not None:
            Ws, Gs = state["stacked"]
            h.set_stacked(jax.tree.map(jnp.asarray, Ws),
                          jax.tree.map(jnp.asarray, Gs))
        if h.tier == "disk" and state["final_params"] is not None:
            # disk reads unflatten with the ENCODED treedef (set during
            # recording); rebuild it from a zero probe shaped like params
            probe = jax.tree.map(lambda x: np.zeros((), np.float32),
                                 state["final_params"])
            inner = h.codec.inner if h.is_delta else h.codec
            h._treedef = jax.tree.structure(inner.encode(probe))
        return h

    def nbytes(self) -> int:
        total = 0
        trees = list(self._params) + list(self._grads)
        if self._stacked is not None and self._stacked_is_storage:
            trees += list(self._stacked)
        for bp, bg in self._bases.values():  # keyframes are host RAM too
            trees += [bp, bg]
        for tree in trees:
            if tree is None:
                continue
            for leaf in jax.tree.leaves(tree):
                total += (leaf.nbytes if isinstance(leaf, HostShards)
                          else np.asarray(leaf).nbytes)
        return total

    def disk_nbytes(self) -> int:
        """Bytes currently occupied by the disk spill (0 for other tiers)."""
        paths = [p for p in self._disk_paths if p] + list(self._win_paths)
        return sum(os.path.getsize(p) for p in paths if os.path.exists(p))
