"""HistoryStore — where history bytes live and how they reach the scan.

DeltaGrad's replay is bottlenecked by the cached optimization path, not the
model: the stacked tier burns ``O(T * |params|)`` HBM per host, and the
paper-faithful offload tiers (host/disk) used to abandon the compiled
``lax.scan`` engine for the per-step python loop.  This module owns the
placement/transport layer between `TrainingHistory` and the engines:

  * ``ResidentStore`` — stacked/device tiers.  The whole (T, ...) cache is
    one device pytree; with a `PlacementPolicy` each leaf is placed by
    `dist.sharding.stacked_spec_for_leaf` (time axis never sharded), so the
    cache shards across the mesh exactly like the live parameters and the
    per-host HBM share drops by the mesh factor.

  * ``SegmentStreamer`` — host/disk tiers.  History entries stay encoded on
    host (or spilled .npz); the replay scan is served device-resident
    WINDOWS of ``window`` steps, assembled + uploaded by a single worker
    thread with double buffering: while the scan for window *s* computes,
    the host stacks and ships window *s+1* (prefetch), so the compiled
    path never blocks on the offload tier and device high-water stays at
    ~2 windows instead of the whole path.  When measured host stacking is
    SLOWER than the scan (small windows on the disk tier), the prefetch
    depth adapts: up to ``max_prefetch`` windows stage ahead so the scan
    never starves (`stats.extra["prefetch_depth"]` reports the depth
    used).  Online-request rewrites are committed back through the codec
    per window.

  * ``ShardedStreamer`` — host/disk tiers placed on a mesh: the
    composition of the two.  Each staged window's leaves are split into
    PER-SHARD encoded segments along the same `stacked_spec_for_leaf`
    axes as `ResidentStore` (time axis never sharded); the worker threads
    stack and upload ONLY each mesh shard's slice of each leaf
    (`jax.make_array_from_single_device_arrays` assembles the global
    window), the codec decodes shard-local on device.  Device high-water
    is ~2 windows of the SHARD; per-host RAM holds the encoded path
    (/codec ratio) plus one window of staged slices.

A mesh-placed store's replays run `PlacedReplay`'s programs: parameters,
gradients, L-BFGS pairs and windows keep their `dist.sharding.spec_for_leaf`
placements for the whole replay, the programs are plain jits over those
sharded arrays with XLA inserting the collectives, and ``shard_map`` runs
only around the Pallas update kernels, which XLA does not partition.

Both streamers additionally support DECODE-IN-KERNEL reads
(``decode="kernel"``, the default for lossy codecs): windows stay ENCODED
on device as `EncodedLeaf` leaves (int8/bf16 payload + per-step scale +
delta keyframe bases) and the replay scan dequantizes one step at a time
in registers — `entry_at` slices then decodes (XLA fuses the elementwise
dequant; `kernels.dequant_update` fuses it with the approx update on
TPU), so device high-water drops by the codec ratio and no f32 copy of a
window is ever materialized.  ``decode="fetch"`` restores the
decode-on-arrival behaviour; both paths share one decode expression (and
both run it under jit, so XLA contracts the multiply-add identically),
which keeps delta-codec replays BITWISE identical across the two modes —
plain int8 may drift by 1 ulp where the lone decode multiply fuses into
a downstream subtract.

Every store exposes one engine-facing API: ``window(a, b) -> (W, G, off)``
(leaves indexed ``W[t - off]`` inside the scan), ``entry(t)`` for host-driven
explicit steps, and ``commit(...)`` for the online engine's end-of-request
rewrite flush.  `core.engine` and `core.online` consume it; `core.session`
chooses the policy.
"""

from __future__ import annotations

import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.history import TrainingHistory, host_block
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace


def auto_window(steps: int, window: int = 0) -> int:
    """Steps per device-resident window on the offload tiers — ONE knob
    shared by the recorder (`core.engine.run_training`) and the read path
    (`SegmentStreamer`): large enough to amortize dispatch, small enough
    that two buffered windows stay far below the full path."""
    return int(window) if window else max(1, min(steps, 32))


def tree_nbytes(tree) -> int:
    """Logical bytes of a pytree, without forcing any device transfer."""
    return sum(int(np.prod(x.shape, dtype=np.int64))
               * np.dtype(x.dtype).itemsize
               for x in jax.tree.leaves(tree))


def tree_device_nbytes(tree) -> int:
    """Bytes a pytree holds on ONE device: sharded leaves count a single
    shard, so a mesh-placed window reports the per-device cost the sharding
    is supposed to buy.  Equals `tree_nbytes` for unsharded arrays."""
    total = 0
    for x in jax.tree.leaves(tree):
        sh = getattr(x, "sharding", None)
        shape = sh.shard_shape(x.shape) if sh is not None else x.shape
        total += (int(np.prod(shape, dtype=np.int64))
                  * np.dtype(x.dtype).itemsize)
    return total


# --------------------------------------------------------------------------
# Encoded windows (decode-in-kernel streaming)
# --------------------------------------------------------------------------


class EncodedLeaf(NamedTuple):
    """One stacked history leaf kept ENCODED on device.

    ``q`` is the (L, ...) quantized payload (int8 residuals with a
    per-step ``scale`` (L,), or a bf16 residual with no scale); for delta
    codecs ``base`` stacks the window's f32 keyframes (n_kw, ...) and
    ``kidx`` (L,) maps each step to its keyframe row, so any
    stream-window/key-interval combination decodes without alignment
    constraints.  A NamedTuple is a pytree, so encoded windows flow
    through jit/scan/shard_map unchanged; every decode site uses the one
    expression ``q.astype(f32) * scale (+ base)`` — see
    `kernels.dequant_update.ref.dequant_ref` — which is what keeps
    kernel-mode and fetch-mode replays bitwise identical (slicing
    commutes with elementwise decode)."""

    q: Any
    scale: Optional[Any] = None
    base: Optional[Any] = None
    kidx: Optional[Any] = None


def _is_window_leaf(x) -> bool:
    return isinstance(x, EncodedLeaf)


def is_encoded_window(tree) -> bool:
    """True when a window() result carries EncodedLeaf leaves (the scan
    must decode per step; pytree structure is static under jit)."""
    found = [False]

    def probe(x):
        if isinstance(x, EncodedLeaf):
            found[0] = True
        return x

    jax.tree.map(probe, tree, is_leaf=_is_window_leaf)
    return found[0]


def _decode_leaf_slice(leaf, i):
    """Step ``i`` of one window leaf, decoded to f32 when encoded."""
    if isinstance(leaf, EncodedLeaf):
        x = leaf.q[i].astype(jnp.float32)
        if leaf.scale is not None:
            x = x * leaf.scale[i]
        if leaf.base is not None:
            x = x + leaf.base[leaf.kidx[i]]
        return x
    return leaf[i]


def decode_window_tree(tree):
    """Whole-window decode of EncodedLeaf leaves to stacked f32 — the
    fetch-mode read path.  Agrees bitwise, per step, with
    `_decode_leaf_slice` (elementwise decode commutes with slicing)."""

    def dec(x):
        if isinstance(x, EncodedLeaf):
            q = x.q.astype(jnp.float32)
            if x.scale is not None:
                q = q * x.scale.reshape((-1,) + (1,) * (q.ndim - 1))
            if x.base is not None:
                q = q + x.base[x.kidx]
            return q
        return x

    return jax.tree.map(dec, tree, is_leaf=_is_window_leaf)


@jax.jit
def _decode_window_pair(Wh, Gh):
    return decode_window_tree(Wh), decode_window_tree(Gh)


def decoded_window_nbytes(tree) -> int:
    """Logical f32 bytes the window WOULD occupy decoded (the numerator
    of the reported compression ratio)."""
    total = 0
    for leaf in jax.tree.leaves(tree, is_leaf=_is_window_leaf):
        shape = leaf.q.shape if isinstance(leaf, EncodedLeaf) else leaf.shape
        total += int(np.prod(shape, dtype=np.int64)) * 4
    return total


# --------------------------------------------------------------------------
# Placement policy (picklable mesh descriptor — session save/restore needs
# to round-trip it, and jax Mesh objects hold live Device handles)
# --------------------------------------------------------------------------


@dataclass
class PlacementPolicy:
    """Describes the replay mesh; builds the live `jax.sharding.Mesh` lazily.

    ``mesh_shape``/``axis_names`` feed `jax.make_mesh`.  The descriptor is
    plain data so `UnlearnerSession.save()` can round-trip it through a
    checkpoint and rebuild the mesh on the restoring host."""

    mesh_shape: Tuple[int, ...]
    axis_names: Tuple[str, ...] = ("data", "model")
    model_cfg: Any = None  # optional ModelConfig for the MoE spec rules

    def __post_init__(self):
        self.mesh_shape = tuple(int(s) for s in self.mesh_shape)
        self.axis_names = tuple(self.axis_names)
        self._mesh = None

    @classmethod
    def from_mesh(cls, mesh, model_cfg=None) -> "PlacementPolicy":
        pol = cls(mesh_shape=tuple(mesh.devices.shape),
                  axis_names=tuple(mesh.axis_names), model_cfg=model_cfg)
        pol._mesh = mesh
        return pol

    @classmethod
    def local(cls, data: Optional[int] = None) -> "PlacementPolicy":
        """1-D data mesh over the local devices (the CPU-mesh test shape)."""
        n = jax.local_device_count() if data is None else int(data)
        return cls(mesh_shape=(n,), axis_names=("data",))

    @property
    def mesh(self):
        if self._mesh is None:
            from repro.dist.sharding import make_mesh
            self._mesh = make_mesh(self.mesh_shape, self.axis_names)
        return self._mesh

    @property
    def shards_state(self) -> bool:
        """Whether the mesh shards the model's state: a ``model`` axis of
        size > 1, over which `dist.sharding.spec_for_leaf` splits the
        parameters (recording then trains on the placement too)."""
        return ("model" in self.axis_names
                and self.mesh_shape[self.axis_names.index("model")] > 1)

    def param_shardings(self, params):
        """NamedSharding pytree of `params` on this mesh."""
        from repro.dist.sharding import params_shardings
        return params_shardings(self.plan(), params)

    def plan(self):
        from repro.dist.sharding import ShardingPlan
        return ShardingPlan(mesh=self.mesh, cfg=self.model_cfg)

    # -- pickling (drop the live mesh; rebuilt lazily on the other side) ----

    def __getstate__(self):
        state = dict(self.__dict__)
        state["_mesh"] = None
        return state

    def __setstate__(self, state):
        self.__dict__.update(state)

    def describe(self) -> Dict[str, Any]:
        """DISPLAY-only summary (stats.extra["mesh"]).  Not a round-trip:
        session save/restore pickles the policy object itself, which is
        what preserves ``model_cfg`` (the MoE spec rules)."""
        return {"mesh_shape": list(self.mesh_shape),
                "axis_names": list(self.axis_names)}


# --------------------------------------------------------------------------
# HistoryStore
# --------------------------------------------------------------------------


class HistoryStore:
    """Engine-facing storage/placement layer over one `TrainingHistory`."""

    kind = "abstract"

    @staticmethod
    def create(history: TrainingHistory,
               placement: Optional[PlacementPolicy] = None,
               window: int = 0, decode: str = "auto") -> "HistoryStore":
        """Pick the store for the history's tier: stacked/device →
        `ResidentStore` (optionally mesh-placed); host/disk →
        `SegmentStreamer` (``window`` steps per device-resident segment,
        0 → auto), or `ShardedStreamer` when a multi-device placement is
        given (each mesh shard streams only its slice of every window).

        ``decode`` picks the streamers' read path: "fetch" decodes every
        window to f32 on arrival (the pre-encoded-window behaviour);
        "kernel" keeps windows ENCODED on device and the scan dequantizes
        per step in registers (HBM high-water drops by the codec ratio);
        "auto" → "kernel" for every non-f32 codec.

        Without a `placement`, the one the path was recorded under
        (`TrainingHistory.placement`) places the store."""
        if placement is None:
            placement = history.placement
        if history.tier in ("host", "disk"):
            if placement is not None \
                    and int(np.prod(placement.mesh_shape)) > 1:
                return ShardedStreamer(history, placement, window=window,
                                       decode=decode)
            return SegmentStreamer(history, window=window, decode=decode)
        return ResidentStore(history, placement=placement)

    # engine-facing API ------------------------------------------------------

    @property
    def meta(self):
        return self.history.meta

    @property
    def T(self) -> int:
        return self.history.meta.steps

    def span_end(self, t: int, t2: int) -> int:
        """Largest b <= t2 such that [t, b) fits one `window()` fetch."""
        raise NotImplementedError

    def window(self, a: int, b: int):
        """(W, G, off) device pytrees covering steps [a, b); scan bodies
        index ``W[t - off]``."""
        raise NotImplementedError

    def entry_window(self, t: int):
        """(W, G, i): a device window holding entry `t` at index ``i``
        (encoded leaves stay encoded), for programs that read the entry
        themselves instead of a decoded copy."""
        raise NotImplementedError

    def entry(self, t: int):
        """(w_t, g_t) decoded, as ONE jitted program."""
        return _entry_slices(*self.entry_window(t))

    def params0(self):
        return self.entry(0)[0]

    def commit(self, regions, final_params) -> None:
        """Land an online request's deferred rewrites (see
        `core.engine.run_online_request` for the region format) and
        finalize `final_params` into the history."""
        raise NotImplementedError

    def sharded_replay(self) -> Optional["PlacedReplay"]:
        """The store's `PlacedReplay` when it is mesh-placed."""
        return None

    def hbm_high_water(self) -> int:
        """Max device-resident history bytes this store ever held per
        device."""
        raise NotImplementedError


def _chunk_lift(p, kind):
    """Stack an explicit-step run into a (len, ...) chunk; scanned segments
    are already stacked."""
    if kind == "run":
        return jax.tree.map(lambda *xs: jnp.stack(xs), *p)
    return p


@jax.jit
def _scatter_chunk(W, G, t0, w_cat, g_cat):
    upd = partial(jax.lax.dynamic_update_slice_in_dim, axis=0)
    return (jax.tree.map(lambda x, u: upd(x, u.astype(x.dtype), t0), W, w_cat),
            jax.tree.map(lambda x, u: upd(x, u.astype(x.dtype), t0), G, g_cat))


@partial(jax.jit, static_argnames=("kinds",))
def _assemble_chunk(parts_w, parts_g, *, kinds):
    """One contiguous rewrite region as a single stacked (len, ...) pair."""
    ws = [_chunk_lift(p, k) for p, k in zip(parts_w, kinds)]
    gs = [_chunk_lift(p, k) for p, k in zip(parts_g, kinds)]
    return (jax.tree.map(lambda *xs: jnp.concatenate(xs), *ws),
            jax.tree.map(lambda *xs: jnp.concatenate(xs), *gs))


def _freeze_parts(parts):
    return tuple(tuple(p) if isinstance(p, list) else p for p in parts)


@jax.jit
def _entry_slices(W, G, t):
    """(w_t, g_t) as ONE jitted program — a host-driven explicit step costs
    one dispatch here, not 2 * n_leaves eager slice ops.  Encoded windows
    (kernel decode mode) slice-then-dequant per leaf via `entry_at`."""
    return entry_at(W, t, 0), entry_at(G, t, 0)


class ResidentStore(HistoryStore):
    """Whole-path device residency (stacked/device tiers), optionally
    sharded across a mesh by `dist.sharding.stacked_spec_for_leaf`."""

    kind = "resident"

    def __init__(self, history: TrainingHistory,
                 placement: Optional[PlacementPolicy] = None):
        self.history = history
        self.placement = placement
        W, G = history.stacked_view()
        if placement is not None:
            from repro.dist.sharding import history_shardings
            plan = placement.plan()
            W = jax.tree.map(jax.device_put, W, history_shardings(plan, W))
            G = jax.tree.map(jax.device_put, G, history_shardings(plan, G))
        self.W, self.G = W, G
        self._sharded: Optional["PlacedReplay"] = None
        self._hbm = self._per_device_bytes()

    def _per_device_bytes(self) -> int:
        """History bytes resident on ONE device — the number sharding is
        supposed to shrink (nbytes / mesh factor for sharded leaves)."""
        return tree_device_nbytes((self.W, self.G))

    def span_end(self, t: int, t2: int) -> int:
        return t2  # the whole path is resident; never split a segment

    def window(self, a: int, b: int):
        return self.W, self.G, 0

    def entry_window(self, t: int):
        return self.W, self.G, t

    def commit(self, regions, final_params) -> None:
        for t0, kinds, pw, pg in regions:
            w_cat, g_cat = _assemble_chunk(_freeze_parts(pw),
                                           _freeze_parts(pg),
                                           kinds=tuple(kinds))
            self.W, self.G = _scatter_chunk(self.W, self.G, jnp.int32(t0),
                                            w_cat, g_cat)
        # O(1) pointer swap for stacked/device storage
        self.history.replace_from_stacked(self.W, self.G,
                                          final_params=final_params)

    def sharded_replay(self) -> Optional["PlacedReplay"]:
        if self.placement is None:
            return None
        if self._sharded is None:
            self._sharded = PlacedReplay(self)
        return self._sharded

    def hbm_high_water(self) -> int:
        return self._hbm


class SegmentStreamer(HistoryStore):
    """Serve a host/disk-tier history to the compiled scan in device-resident
    segment windows with double-buffered async host→device copies.

    Prefetch depth is ADAPTIVE: it starts at 1 (classic double buffering)
    and, when the measured host stacking time of a window exceeds the scan
    time the device spends consuming one, grows to
    ``ceil(stack / scan)`` windows (capped at ``max_prefetch``) so the
    compiled path never starves on the offload tier.  The depth actually
    used is reported via `stats.extra["prefetch_depth"]`; device
    high-water grows by one ENCODED window per extra depth step."""

    kind = "streamed"
    placement = None

    def __init__(self, history: TrainingHistory, window: int = 0,
                 prefetch: bool = True, max_prefetch: int = 4,
                 stage_threads: Optional[int] = None,
                 decode: str = "auto"):
        assert history.tier in ("host", "disk"), history.tier
        if decode not in ("auto", "kernel", "fetch"):
            raise ValueError(
                f"unknown decode mode {decode!r}; pick 'fetch' (decode "
                "windows to f32 on arrival), 'kernel' (keep windows "
                "encoded on device, dequantize per step in the scan), or "
                "'auto' (kernel for every non-f32 codec)")
        self.history = history
        # f32 windows have nothing to decode — kernel mode degenerates to
        # fetch (the staged window IS the decoded window)
        if history.codec.name == "f32":
            decode = "fetch"
        elif decode == "auto":
            decode = "kernel"
        self.decode_mode = decode
        self.window_len = auto_window(history.meta.steps, window)
        self.prefetch = prefetch
        # depth > 1 only pays when that many windows can STAGE concurrently
        # — a queued future behind one busy worker adds device bytes, not
        # throughput — so the depth cap IS the worker count (default: spare
        # cores; 1 on small hosts → classic double buffering, ~2-window
        # high-water)
        import os as _os
        workers = stage_threads if stage_threads is not None \
            else (_os.cpu_count() or 2) - 1
        self.max_prefetch = max(1, min(int(max_prefetch), int(workers)))
        self._pool = ThreadPoolExecutor(max_workers=self.max_prefetch) \
            if prefetch else None
        self._buf: Dict[int, Tuple[Any, Any]] = {}
        self._inflight: Dict[int, Future] = {}
        self._hbm_now = 0
        self._hbm_high = 0
        self._enc_bytes = 0  # ENCODED per-device bytes of the last staged
        # window (the in-flight prefetch copy is pre-decode, so lossy codecs
        # stage at 1/2 or 1/4 of the decoded f32 size)
        self.enc_bytes_high = 0  # high-water of encoded window bytes
        self.compression_ratio = 1.0  # decoded f32 bytes / encoded bytes
        self.windows_fetched = 0
        self.prefetch_hits = 0
        self.host_wait_s = 0.0
        # adaptive prefetch state: EMAs of host stacking time vs the scan
        # time between consecutive window() calls (both in seconds)
        self.prefetch_depth = 1  # depth chosen for the NEXT windows
        self.depth_used = 1  # high-water of chosen depths (stats.extra)
        # host RAM of staged windows: host_stage_high is the largest
        # SINGLE window's staged bytes (depth k stages up to k windows
        # concurrently); guarded by a lock because staging runs on pool
        # threads once the depth exceeds 1
        self._meter_lock = threading.Lock()
        self.host_stage_bytes = 0
        self.host_stage_high = 0
        self._stack_ema = 0.0
        self._scan_ema = 0.0
        self._last_return_ts: Optional[float] = None

    # -- window plumbing -----------------------------------------------------

    def _wid(self, t: int) -> int:
        return t // self.window_len

    def _bounds(self, wid: int) -> Tuple[int, int]:
        a = wid * self.window_len
        return a, min(self.T, a + self.window_len)

    def span_end(self, t: int, t2: int) -> int:
        return min(t2, self._bounds(self._wid(t))[1])

    def _window_bases(self, a: int, b: int):
        """(kidx, base_w, base_g) for a delta-codec window [a, b): the
        stacked f32 keyframes of every key window the steps touch, plus
        the per-step row index into that stack — computed here so ANY
        stream window works with ANY key interval, aligned or not."""
        K = self.history.key_interval
        kw0 = a // K
        kwids = list(range(kw0, (b - 1) // K + 1))
        pairs = [self.history.base_entry(k) for k in kwids]
        stack = lambda *xs: np.stack([np.asarray(x) for x in xs])
        base_w = jax.tree.map(stack, *(p for p, _ in pairs))
        base_g = jax.tree.map(stack, *(g for _, g in pairs))
        kidx = np.asarray([t // K - kw0 for t in range(a, b)], np.int32)
        return kidx, base_w, base_g

    def _wrap_encoded(self, tree, base_tree, kidx):
        """Stacked encoded tree → EncodedLeaf leaves (device-ready form)."""

        def wrap(x, b):
            if _is_enc_leaf(x):  # int8 inner: {"q": (L, ...), "scale": (L,)}
                return EncodedLeaf(q=x["q"], scale=x["scale"], base=b,
                                   kidx=None if b is None else kidx)
            return EncodedLeaf(q=x, scale=None, base=b,
                               kidx=None if b is None else kidx)

        if base_tree is None:
            return jax.tree.map(lambda x: wrap(x, None), tree,
                                is_leaf=_is_enc_leaf)
        return jax.tree.map(wrap, tree, base_tree, is_leaf=_is_enc_leaf)

    def _stage_window(self, wid: int):
        """Host side of a fetch: stack the window's ENCODED entries per leaf
        and ship them with `jax.device_put` (async dispatch).  Runs on the
        worker thread for prefetches; no tracing happens here.  Non-f32
        codecs stage EncodedLeaf leaves (decoded on fetch or consumed
        encoded by the scan, per `decode_mode`); delta codecs ride their
        key windows' keyframe bases along."""
        a, b = self._bounds(wid)
        enc_p, enc_g = [], []
        for t in range(a, b):
            p, g = self.history.encoded_entry(t)
            enc_p.append(p)
            enc_g.append(g)
        stack = lambda *xs: np.stack([np.asarray(x) for x in xs])
        Wh = jax.tree.map(stack, *enc_p) if len(enc_p) > 1 else \
            jax.tree.map(lambda x: np.asarray(x)[None], enc_p[0])
        Gh = jax.tree.map(stack, *enc_g) if len(enc_g) > 1 else \
            jax.tree.map(lambda x: np.asarray(x)[None], enc_g[0])
        if self.history.codec.name != "f32":
            if self.history.is_delta:
                kidx, base_w, base_g = self._window_bases(a, b)
            else:
                kidx = base_w = base_g = None
            Wh = self._wrap_encoded(Wh, base_w, kidx)
            Gh = self._wrap_encoded(Gh, base_g, kidx)
        nbytes = tree_nbytes((Wh, Gh))
        self._note_stage_bytes(nbytes, nbytes)
        return jax.device_put((Wh, Gh))

    def _stack_host(self, wid: int):
        """`_stage_window` + the stacking-time EMA the adaptive prefetch
        depth feeds on (updated from whichever thread runs the stage).
        The ``store.window_stage`` span records on the staging-pool thread
        for prefetches — its own track in the exported trace."""
        t0 = time.perf_counter()
        with obs_trace.span("store.window_stage", wid=wid):
            staged = self._stage_window(wid)
        dt = time.perf_counter() - t0
        self._stack_ema = dt if self._stack_ema == 0.0 \
            else 0.5 * self._stack_ema + 0.5 * dt
        return staged

    def _note_stage_bytes(self, nbytes: int, per_chip: float) -> None:
        """`nbytes` staged on the host for one window, `per_chip` of them
        put on each device (``store.staged_bytes``)."""
        with self._meter_lock:
            self.host_stage_bytes = int(nbytes)
            self.host_stage_high = max(self.host_stage_high, int(nbytes))
        obs_metrics.get_registry().counter(
            "store.staged_bytes", unit="B", owner="core.store").inc(per_chip)

    def _decode(self, staged):
        """Read path: fetch mode decodes the whole window to f32 on
        arrival; kernel mode hands the ENCODED window straight to the
        scan (per-step dequant in `entry_at` / the Pallas kernels).
        Both modes share one decode expression whose product the int8
        codec keeps exact (`core.history._int8_encode_window`), which is
        what makes fetch-mode and kernel-mode replays bitwise identical."""
        if self.decode_mode == "kernel":
            return staged
        Wh, Gh = staged
        if is_encoded_window(Wh) or is_encoded_window(Gh):
            return _decode_window_pair(Wh, Gh)
        codec = self.history.codec
        return codec.decode_stacked(Wh), codec.decode_stacked(Gh)

    def _fetch(self, wid: int):
        if wid in self._buf:
            return self._buf[wid]
        reg = obs_metrics.get_registry()
        fut = self._inflight.pop(wid, None)
        if fut is not None:
            t0 = time.perf_counter()
            with obs_trace.span("store.prefetch_wait", wid=wid):
                staged = fut.result()
            wait = time.perf_counter() - t0
            self.host_wait_s += wait
            self.prefetch_hits += 1
            reg.counter("store.prefetch_hits", owner="core.store").inc()
        else:
            t0 = time.perf_counter()
            staged = self._stack_host(wid)
            wait = time.perf_counter() - t0
            self.host_wait_s += wait
        reg.counter("store.host_wait_s", unit="s",
                    owner="core.store").inc(wait)
        self._enc_bytes = tree_device_nbytes(staged)
        self.enc_bytes_high = max(self.enc_bytes_high, self._enc_bytes)
        if self._enc_bytes:
            self.compression_ratio = (decoded_window_nbytes(staged)
                                      / self._enc_bytes)
        W, G = self._decode(staged)
        self._buf[wid] = (W, G)
        self._hbm_now += tree_device_nbytes(W) + tree_device_nbytes(G)
        self._hbm_high = max(self._hbm_high, self._hbm_now)
        self.windows_fetched += 1
        reg.counter("store.windows_fetched", owner="core.store").inc()
        return W, G

    def _evict_outside(self, wid: int) -> None:
        """Drop every buffered window but `wid`, and the prefetches behind
        it: a window left from an earlier pass over the path is not read
        before it is fetched again, and would only hold device memory."""
        for old in [w for w in self._buf if w != wid]:
            W, G = self._buf.pop(old)
            self._hbm_now -= tree_device_nbytes(W) + tree_device_nbytes(G)
        for old in [w for w in self._inflight if w < wid]:
            self._inflight.pop(old)

    def _prefetch(self, wid: int, cur: int) -> None:
        if (self._pool is None or wid in self._buf or wid in self._inflight
                or wid * self.window_len >= self.T
                or not self._stages_beside(cur, wid)):
            return
        self._inflight[wid] = self._pool.submit(self._stack_host, wid)

    def _stages_beside(self, cur: int, wid: int) -> bool:
        """Whether window `wid` may stage while window `cur` is in use."""
        return True

    def _choose_depth(self) -> int:
        """Prefetch depth for the next windows: 1 while the host keeps up,
        ceil(stack / scan) once stacking is MEASURABLY slower than the
        scan that consumes a window (ROADMAP adaptive-depth item).  The
        1 ms floor keeps microsecond-scale timing noise from buying extra
        device-resident windows that cannot possibly pay for themselves.
        Windows in flight hold at most a tenth of a device's memory
        (`_depth_cap`): at LM scale one window is gigabytes a device."""
        if (self._scan_ema <= 0.0 or self._stack_ema <= 1e-3
                or self._stack_ema <= self._scan_ema):
            return 1
        depth = min(self.max_prefetch, self._depth_cap(),
                    int(np.ceil(self._stack_ema / self._scan_ema)))
        return max(1, depth)

    def _depth_cap(self) -> int:
        stats = jax.local_devices()[0].memory_stats() or {}
        limit = stats.get("bytes_limit", 0)
        if not limit or not self._enc_bytes:
            return self.max_prefetch
        return max(1, int(0.1 * limit // self._enc_bytes))

    def window(self, a: int, b: int):
        now = time.perf_counter()
        if self._last_return_ts is not None:
            # time since the previous window was handed out ≈ the scan
            # time that consumed it (the denominator of the depth rule)
            dt = now - self._last_return_ts
            self._scan_ema = dt if self._scan_ema == 0.0 \
                else 0.5 * self._scan_ema + 0.5 * dt
        wid = self._wid(a)
        assert b <= self._bounds(wid)[1], (a, b, self.window_len)
        W, G = self._window_at(wid)
        self._last_return_ts = time.perf_counter()
        return W, G, wid * self.window_len

    def entry_window(self, t: int):
        """Explicit steps read their entry out of the OWNING window, fetched
        (and the next ones prefetched) as for a scanned segment: the
        sharded and single-device, streamed and resident explicit-step
        programs then decode alike, which keeps their parity exact."""
        wid = self._wid(t)
        W, G = self._window_at(wid)
        return W, G, t - wid * self.window_len

    def _window_at(self, wid: int):
        if wid not in self._buf:
            with obs_trace.span("store.window", wid=wid,
                                hit=wid in self._inflight):
                self._evict_outside(wid)
                self._fetch(wid)
        # double buffering (depth 1), or deeper when the host is the
        # bottleneck: ship windows s+1..s+k while the device reads s
        depth = self._choose_depth()
        self.prefetch_depth = depth
        self.depth_used = max(self.depth_used, depth)
        for ahead in range(1, depth + 1):
            self._prefetch(wid + ahead, wid)
        # in-flight staged copies are device-resident too — that is the
        # buffering cost the high-water must report (at ENCODED size:
        # decode happens on the consuming fetch)
        self._hbm_high = max(self._hbm_high,
                             self._hbm_now
                             + len(self._inflight) * self._enc_bytes)
        obs_metrics.get_registry().gauge(
            "store.hbm_high_water_bytes", unit="B",
            owner="core.store").set_max(self._hbm_high)
        return self._buf[wid]

    # -- online rewrite commit ----------------------------------------------

    def commit(self, regions, final_params) -> None:
        # drain in-flight prefetches first: a worker mid-read of the same
        # entries we are about to overwrite is a read/write race on the
        # disk tier's .npz files
        for fut in self._inflight.values():
            try:
                fut.result()
            except Exception:
                pass  # a failed prefetch of soon-stale data is harmless
        for t0, kinds, pw, pg in regions:
            w_cat, g_cat = _assemble_chunk(_freeze_parts(pw),
                                           _freeze_parts(pg),
                                           kinds=tuple(kinds))
            w_host = jax.device_get(w_cat)
            g_host = jax.device_get(g_cat)
            span = jax.tree.leaves(w_host)[0].shape[0]
            for i in range(span):
                self.history.overwrite(
                    t0 + i, jax.tree.map(lambda x: x[i], w_host),
                    jax.tree.map(lambda x: x[i], g_host))
        self.history.finalize(final_params)
        # buffered windows hold pre-request values — drop them
        self._buf.clear()
        self._inflight.clear()
        self._hbm_now = 0

    def hbm_high_water(self) -> int:
        return self._hbm_high


def _is_enc_leaf(x) -> bool:
    """Codec-dict leaves (int8's {"q", "scale"}) in an ENCODED entry."""
    return isinstance(x, dict) and "q" in x


class ShardedStreamer(SegmentStreamer):
    """Host/disk-tier history sharded across a mesh AND streamed per window
    — the composition `HistoryStore.create` used to refuse.

    Placement: every staged window takes the same
    `dist.sharding.stacked_spec_for_leaf` placements a `ResidentStore`
    would give the full (T, ...) leaves (time axis never sharded —
    `stacked_entry_shardings`).  The staging path is PER-SHARD end to end:
    for each leaf, each mesh shard's worker thread stacks only its slice
    of the window's encoded entries (host RAM stages one window of
    slices, never a full stacked leaf) and uploads it to its own device;
    `jax.make_array_from_single_device_arrays` assembles the global
    window without any device ever holding a whole leaf.  The codec
    decodes shard-local on device (`out_shardings` pins the decoded
    window to the same placement), and `sharded_replay()` hands the
    engines a `PlacedReplay` as the resident path does, so `run_replay`
    and `run_online_request` run unchanged.

    Online rewrites commit exactly like `SegmentStreamer`: the request's
    (replicated) rewrite chunks land back in the owning history entries
    through the codec — the per-shard segments are staging artifacts,
    re-sliced from the rewritten entries on the next fetch.

    Per-device high-water: ~2 windows of the SHARD (decoded window +
    in-flight encoded window), i.e. ``2 * L * 2P / (mesh * ratio-ish)``
    instead of the full path — see the tier guide in `core.history`."""

    kind = "sharded_streamed"

    def __init__(self, history: TrainingHistory,
                 placement: PlacementPolicy, window: int = 0,
                 prefetch: bool = True, max_prefetch: int = 4,
                 stage_threads: Optional[int] = None,
                 stage_workers: int = 8, decode: str = "auto"):
        assert placement is not None
        need = int(np.prod(np.asarray(placement.mesh_shape, dtype=np.int64)))
        have = jax.device_count()
        if need > have:
            raise ValueError(
                f"sharded streaming asks for a {placement.mesh_shape} mesh "
                f"({need} shards) but only {have} device(s) are visible — "
                "the shard count must match the mesh the process can "
                "build (e.g. XLA_FLAGS=--xla_force_host_platform_device_"
                "count=N for CPU tests), or drop the placement to stream "
                "single-device")
        self.placement = placement
        super().__init__(history, window=window, prefetch=prefetch,
                         max_prefetch=max_prefetch,
                         stage_threads=stage_threads, decode=decode)
        from jax.sharding import NamedSharding, PartitionSpec

        plan = placement.plan()
        from repro.dist.sharding import stacked_entry_shardings
        # per-step template (paths + shapes): the stored entry's payloads,
        # read on the host without decoding it onto a device
        payload = lambda x: x["q"] if _is_enc_leaf(x) else x
        w0, g0 = (jax.tree.map(payload, e, is_leaf=_is_enc_leaf)
                  for e in history.encoded_entry(0))
        self._shard_w = stacked_entry_shardings(plan, w0)
        self._shard_g = stacked_entry_shardings(plan, g0)
        self._rep_sharding = NamedSharding(placement.mesh, PartitionSpec())
        self._stage_pool = ThreadPoolExecutor(
            max_workers=max(1, int(stage_workers)))
        self._decode_fn = None
        self._sharded: Optional["PlacedReplay"] = None
        # staged keyframe bases, keyed by the key windows a stream window
        # touches: bases are IMMUTABLE (online rewrites re-encode against
        # the same keyframe), so windows of one key window share one
        # staging.  Only the current one is kept, and no window of other
        # key windows stages beside it (`_stages_beside`): at LM scale a
        # keyframe pair is two parameter-sized f32 trees per device
        self._base_cache: Dict[Tuple[int, ...], Tuple[Any, Any]] = {}
        self._base_lock = threading.Lock()

    # -- per-shard staging ---------------------------------------------------

    def _stage_leaf(self, sharding, column, meter: List[int]):
        """One leaf of one window: stack PER-SHARD host slices of the
        ``len(column)`` encoded entries and upload each to its owning
        device — the per-shard encoded segment.  Fanned out over the
        stage pool so shards stack/ship concurrently; each shard appends
        its slice bytes to `meter` (list.append is atomic, and the meter
        is local to ONE window's stage, so concurrent windows under
        adaptive depth never clobber each other's sums).  Returns a
        function that waits for the uploads and assembles the global
        leaf, so a window starts every leaf before it waits for one."""
        gshape = (len(column),) + tuple(np.shape(column[0]))
        idx_map = sharding.addressable_devices_indices_map(gshape)

        def one_shard(dev, index):
            per_entry = index[1:]  # the time axis is never sharded
            blocks = [host_block(e, per_entry) for e in column]
            # one entry goes as it is stored: a `HostShards` block is
            # already this device's slice, so nothing is copied
            buf = blocks[0][None] if len(blocks) == 1 else np.stack(blocks)
            meter.append(buf.nbytes)
            return jax.device_put(buf, dev)

        futs = [self._stage_pool.submit(one_shard, d, ix)
                for d, ix in idx_map.items()]
        return lambda: jax.make_array_from_single_device_arrays(
            gshape, sharding, [f.result() for f in futs])

    def _stage_tree(self, entries, shardings, meter: List[int],
                    base_flat=None, kidx_dev=None):
        """Stack one window of encoded per-step pytrees into globally
        sharded (L, ...) leaves.  Codec-dict leaves shard their payload
        ("q") like the decoded leaf; per-entry scales stack to a
        replicated (L,) vector shipped in ONE broadcast put.  Non-f32
        codecs come back as EncodedLeaf leaves; delta keyframe bases
        arrive pre-staged (immutable → cached, see `_staged_bases`)."""
        flat0, tdef = jax.tree.flatten(entries[0], is_leaf=_is_enc_leaf)
        cols = list(zip(*(jax.tree.leaves(e, is_leaf=_is_enc_leaf)
                          for e in entries)))
        if base_flat is None:
            base_flat = [None] * len(flat0)
        encoded = self.history.codec.name != "f32"
        staged = []
        for proto, sh, col, bs in zip(flat0, jax.tree.leaves(shardings),
                                      cols, base_flat):
            if not encoded:
                staged.append((self._stage_leaf(sh, col, meter), None, bs))
                continue
            if _is_enc_leaf(proto):
                q = self._stage_leaf(sh, [c["q"] for c in col], meter)
                buf = np.stack([np.asarray(c["scale"]) for c in col])
                meter.append(buf.nbytes)
                scale = jax.device_put(buf, self._rep_sharding)
            else:  # bf16 residual — no per-step scale
                q = self._stage_leaf(sh, col, meter)
                scale = None
            staged.append((q, scale, bs))
        if not encoded:
            return jax.tree.unflatten(tdef, [q() for q, _, _ in staged])
        return jax.tree.unflatten(tdef, [
            EncodedLeaf(q=q(), scale=scale, base=bs,
                        kidx=None if bs is None else kidx_dev)
            for q, scale, bs in staged])

    def _key_windows(self, wid: int) -> Tuple[int, ...]:
        a, b = self._bounds(wid)
        K = self.history.key_interval
        return tuple(range(a // K, (b - 1) // K + 1))

    def _stages_beside(self, cur: int, wid: int) -> bool:
        """A window of other key windows brings its own keyframe pair; it
        stages once the current window is dropped, so that one pair is on
        the devices at a time."""
        return (not self.history.is_delta
                or self._key_windows(wid) == self._key_windows(cur))

    def _staged_bases(self, wid: int, a: int, b: int):
        """(kidx_dev, flat base_w, flat base_g, new_bytes) for window
        `wid`: the keyframes of the key windows it touches, per-shard
        staged, or reused from the cache when a window of the same key
        windows staged them.  `new_bytes` (host bytes staged) is 0 on a
        hit, so the meters count each staging once."""
        K = self.history.key_interval
        kwids = self._key_windows(wid)
        kidx_dev = jax.device_put(
            np.asarray([t // K - kwids[0] for t in range(a, b)], np.int32),
            self._rep_sharding)
        with self._base_lock:
            hit = self._base_cache.get(kwids)
            if hit is not None:
                return (kidx_dev, *hit, 0)
            self._base_cache.clear()  # the old pair goes before the new
        pairs = [self.history.base_entry(k) for k in kwids]
        meter: List[int] = []

        def stage(trees, shardings):
            return [self._stage_leaf(sh, list(col), meter)
                    for col, sh in zip(zip(*(jax.tree.leaves(x)
                                             for x in trees)),
                                       jax.tree.leaves(shardings))]

        bw, bg = stage([p for p, _ in pairs], self._shard_w), \
            stage([g for _, g in pairs], self._shard_g)
        bw, bg = [f() for f in bw], [f() for f in bg]

        with self._base_lock:
            self._base_cache[kwids] = (bw, bg)
        return kidx_dev, bw, bg, sum(meter)

    def _stage_window(self, wid: int):
        a, b = self._bounds(wid)
        enc_p, enc_g = [], []
        for t in range(a, b):
            p, g = self.history.encoded_entry(t)
            enc_p.append(p)
            enc_g.append(g)
        # per-shard staging: this window's host footprint is the SUM of
        # its staged slices (incl. replicated leaves once per device)
        if self.history.is_delta:
            kidx_dev, bw, bg, base_bytes = self._staged_bases(wid, a, b)
        else:
            kidx_dev = bw = bg = None
            base_bytes = 0
        meter: List[int] = [base_bytes]
        staged = (self._stage_tree(enc_p, self._shard_w, meter,
                                   bw, kidx_dev),
                  self._stage_tree(enc_g, self._shard_g, meter,
                                   bg, kidx_dev))
        per_chip = tree_device_nbytes(staged)
        if self.history.is_delta and not base_bytes:  # reused keyframes
            per_chip -= tree_device_nbytes((bw, bg))
        self._note_stage_bytes(sum(meter), per_chip)
        return staged

    def _decode(self, staged):
        """Decode the staged (encoded, sharded) window ON DEVICE, with
        `out_shardings` pinning every decoded leaf to its resident-path
        placement — shard-local work, no gather.  Kernel mode skips the
        decode entirely: the scan consumes the encoded window."""
        if self.decode_mode == "kernel":
            return staged
        if self._decode_fn is None:
            codec = self.history.codec
            if is_encoded_window(staged[0]) or is_encoded_window(staged[1]):
                fn = lambda Wh, Gh: (decode_window_tree(Wh),
                                     decode_window_tree(Gh))
            else:
                fn = lambda Wh, Gh: (codec.decode_stacked(Wh),
                                     codec.decode_stacked(Gh))
            self._decode_fn = jax.jit(
                fn, out_shardings=(self._shard_w, self._shard_g))
        return self._decode_fn(*staged)

    def sharded_replay(self) -> Optional["PlacedReplay"]:
        if self._sharded is None:
            self._sharded = PlacedReplay(self)
        return self._sharded


# --------------------------------------------------------------------------
# Sharded replay: the segment programs of a mesh-placed store
# --------------------------------------------------------------------------


class PlacedReplay:
    """Segment programs for a store placed on a mesh.

    Parameters, gradients, the L-BFGS pairs and the history windows all
    keep their `dist.sharding.spec_for_leaf` placements for the whole
    replay — no device holds a whole parameter-sized tree.  The engines'
    programs are plain jits over those `NamedSharding`-placed arrays, so
    XLA partitions the model's forward and backward and inserts the
    collectives itself; inner products over sharded leaves reduce
    shard-locally and all-reduce scalars.  Only the Pallas update kernels,
    which XLA does not partition, run under ``shard_map``, one call per
    leaf on each device's block (`kernel_shards`).  Every program's
    parameter outputs are pinned to the parameter placement (`pin`)."""

    def __init__(self, store: HistoryStore):
        assert store.placement is not None
        self.store = store
        self._cache: Dict[Any, Any] = {}
        self._sh: Dict[Any, Any] = {}

    @property
    def placement(self) -> PlacementPolicy:
        return self.store.placement

    def _shardings(self, params):
        tdef = jax.tree.structure(params)
        if tdef not in self._sh:
            sh = self.placement.param_shardings(params)
            self._sh[tdef] = (sh, tuple(jax.tree.leaves(sh)))
        return self._sh[tdef]

    def place(self, params):
        """`params` on their placements (a no-op when already there)."""
        return jax.device_put(params, self._shardings(params)[0])

    def pin(self, params) -> Tuple[Any, ...]:
        """Flat NamedShardings of the parameter leaves: a static argument
        of the engines' jitted steps, which constrain their parameter-
        shaped outputs to it."""
        return self._shardings(params)[1]

    def kernel_shards(self, params):
        """(mesh, flat PartitionSpecs) of the parameter leaves: a static
        argument that routes each leaf's Pallas kernel call per shard."""
        return (self.placement.mesh,
                tuple(s.spec for s in self._shardings(params)[1]))

    def wrap(self, impl_fn, key, n_outputs: int):
        """jit for ``impl_fn(params, ...)``, its first output (the
        parameters) pinned to their placement; cached by `key`."""
        if key in self._cache:
            return self._cache[key]

        def call(params, *args):
            out = impl_fn(params, *args)
            return (constrain(out[0], self.pin(params)),) + tuple(out[1:])

        self._cache[key] = jax.jit(call)
        return self._cache[key]


def constrain(tree, pin):
    """`tree` with each leaf constrained to the matching sharding of the
    flat `pin` (`PlacedReplay.pin`); no-op when `pin` is None."""
    if pin is None:
        return tree
    leaves, tdef = jax.tree.flatten(tree)
    return jax.tree.unflatten(tdef, [
        jax.lax.with_sharding_constraint(x, s) for x, s in zip(leaves, pin)])


def per_shard(fn, shards, i: int, scalars, arrays):
    """``fn(*scalars, *arrays)`` for parameter leaf `i` (the `arrays` each
    in the leaf's shape): a plain call, or, with `shards`
    (`PlacedReplay.kernel_shards`), one call per device on its block of
    every array, the scalars replicated.  Exact for the elementwise update
    kernels."""
    if shards is None:
        return fn(*scalars, *arrays)
    from jax.sharding import PartitionSpec as P

    mesh, specs = shards
    in_specs = (P(),) * len(scalars) + (specs[i],) * len(arrays)
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=specs[i], check_vma=False)(*scalars,
                                                              *arrays)


def entry_at(W, t, off):
    """Slice one step out of stacked history leaves.  Encoded windows
    (`EncodedLeaf` leaves) dequantize the SLICE, so the window itself stays
    encoded in HBM."""
    leaves, tdef = jax.tree.flatten(W, is_leaf=_is_window_leaf)
    return jax.tree.unflatten(
        tdef, [_decode_leaf_slice(x, t - off) for x in leaves])
