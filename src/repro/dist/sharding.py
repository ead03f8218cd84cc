"""Parameter / input sharding resolver for the (data, model) mesh.

Megatron-style rules driven by leaf PATH + SHAPE only (no per-model tables):

  * column-parallel projections (wq/wk/wv, w_up/w_gate, ...): model
    parallelism on the OUTPUT dim, data-axis FSDP on the input dim;
  * row-parallel projections (wo, w_down, out_proj): the transpose — model
    on the input dim so the pair (column @ row) needs one all-reduce;
  * the stacked layer axis (scan-over-layers models stack every block
    parameter along a leading ``n_units`` axis) is NEVER sharded — it is
    scanned over, and splitting it would serialize the scan's DMA;
  * any dim not divisible by its mesh axis replicates (GSPMD would pad;
    padding a 140-dim head projection 16 ways wastes >10% of the shard);
  * norms / 1-D leaves replicate on model and FSDP-shard on data when
    divisible;
  * embeddings: vocab-sharded on data (the lm_head matmul wants d_model
    contiguous), on model where data does not divide the vocabulary;
  * MoE routed experts (leaves shaped (E, d_in, d_out) under ``mlp``):
    expert-parallel on the model axis when E divides it, else
    tensor-parallel on (d_in, d_out) with the expert axis replicated.

Pure functions over a `ShardingPlan` (mesh + optional model config), so unit
tests drive them with a fake mesh and no devices.
"""

from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Any, Optional, Tuple

from jax.sharding import PartitionSpec as P

_ROW_PARALLEL = ("wo", "w_down", "out_proj")
_NORM_PARENTS = re.compile(r"(^|/)(ln\d*|.*norm)(/|$)")
_STACKED_PREFIX = re.compile(r"^u\d+(/|$)")


@dataclass
class ShardingPlan:
    mesh: Any
    cfg: Optional[Any] = None  # ModelConfig; enables the MoE rules

    def axis_size(self, name: str) -> int:
        names = tuple(self.mesh.axis_names)
        if name not in names:
            return 1
        return int(self.mesh.devices.shape[names.index(name)])


def make_plan(mesh, cfg=None) -> ShardingPlan:
    return ShardingPlan(mesh=mesh, cfg=cfg)


def make_mesh(shape: Tuple[int, ...], axis_names: Tuple[str, ...]):
    """`jax.make_mesh` with every axis ``Auto``: the shardings this module
    resolves are placement hints for GSPMD, so no axis may be ``Explicit``
    (the default since JAX 0.8, which rejects e.g. a dot over a sharded
    contracting dimension instead of inserting the collective)."""
    import jax
    from jax.sharding import AxisType

    shape, axis_names = tuple(shape), tuple(axis_names)
    return jax.make_mesh(shape, axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names))


def _fit(plan: ShardingPlan, axis: Optional[str], dim: int) -> Optional[str]:
    """axis if dim divides its mesh size, else replicate."""
    if axis is None:
        return None
    size = plan.axis_size(axis)
    return axis if (size > 1 and dim % size == 0) else None


def _matrix_spec(plan, dims: Tuple[int, ...], row_parallel: bool):
    """Spec for the trailing (..., d_in, d_out) dims of a projection."""
    lead = (None,) * (len(dims) - 2)
    d_in, d_out = dims[-2], dims[-1]
    if row_parallel:
        return lead + (_fit(plan, "model", d_in), _fit(plan, "data", d_out))
    return lead + (_fit(plan, "data", d_in), _fit(plan, "model", d_out))


def spec_for_leaf(plan: ShardingPlan, path: str, shape: Tuple[int, ...]) -> P:
    """PartitionSpec for one parameter leaf, keyed by its path and shape."""
    parts = path.split("/")
    name = parts[-1]
    stacked = bool(_STACKED_PREFIX.match(path))
    dims = tuple(shape[1:]) if stacked else tuple(shape)
    prefix: Tuple[Optional[str], ...] = (None,) if stacked else ()

    def done(spec_dims) -> P:
        return P(*(prefix + tuple(spec_dims)))

    # embeddings: vocab rows FSDP-sharded on data, d_model contiguous; on
    # a mesh whose data axis does not divide them (a tensor-parallel host),
    # the rows split over model, a vocabulary slice per device like the
    # lm_head's columns
    if name == "embed":
        rows = _fit(plan, "data", dims[0]) or _fit(plan, "model", dims[0])
        return done((rows,) + (None,) * (len(dims) - 1))

    # norms and other vectors: data-FSDP the feature dim when divisible
    if name in ("scale", "bias") or (len(parts) > 1
                                     and _NORM_PARENTS.search("/".join(parts[:-1]))):
        spec = [None] * len(dims)
        if dims:
            spec[-1] = _fit(plan, "data", dims[-1])
        return done(spec)

    # MoE routed experts: (E, d_in, d_out) under an mlp block
    moe = plan.cfg.moe if (plan.cfg is not None
                           and getattr(plan.cfg, "moe", None)) else None
    if (moe is not None and len(dims) == 3 and "mlp" in parts
            and name in ("w_gate", "w_up", "w_down")):
        E = dims[0]
        if plan.axis_size("model") > 1 and E % plan.axis_size("model") == 0:
            # expert-parallel: experts on model, FSDP the widest matmul dim
            return done(("model", None, _fit(plan, "data", dims[-1])))
        # TP fallback: expert axis replicated, usual column/row split
        return done((None,) + _matrix_spec(
            plan, dims[1:], row_parallel=(name in _ROW_PARALLEL)))

    # projections (>= 2 trailing dims): column- or row-parallel
    if len(dims) >= 2:
        return done(_matrix_spec(plan, dims,
                                 row_parallel=(name in _ROW_PARALLEL)))

    # unknown vectors/scalars: replicate
    return done((None,) * len(dims))


def stacked_spec_for_leaf(plan: ShardingPlan, path: str,
                          shape: Tuple[int, ...]) -> P:
    """PartitionSpec for a HISTORY leaf: a per-step parameter leaf stacked
    along a leading time axis ``(T, ...)`` (core/history's stacked tier).

    The TIME axis is never sharded — the replay scan iterates it step by
    step, and splitting it would serialize every `lax.dynamic_slice` into a
    cross-host fetch.  The per-step dims inherit the live parameter's
    placement from `spec_for_leaf`, so the cached path shards exactly like
    the model it caches and per-host HBM drops by the mesh factor."""
    per_step = spec_for_leaf(plan, path, tuple(shape[1:]))
    return P(None, *tuple(per_step))


def history_shardings(plan: ShardingPlan, stacked_tree):
    """NamedSharding pytree for a stacked (T, ...) history pytree."""
    import jax
    from jax.sharding import NamedSharding

    def one(key_path, leaf):
        spec = stacked_spec_for_leaf(plan, _path_str(key_path),
                                     tuple(leaf.shape))
        return NamedSharding(plan.mesh, spec)

    return jax.tree_util.tree_map_with_path(one, stacked_tree)


def stacked_entry_shardings(plan: ShardingPlan, entry_tree):
    """NamedSharding pytree for stacked (L, ...) WINDOWS of one history
    entry (a per-step (w, g)-shaped pytree — shapes WITHOUT the time axis).

    This is `core.store.ShardedStreamer`'s placement driver: every window a
    host/disk-tier shard streams takes exactly the `stacked_spec_for_leaf`
    placement a `ResidentStore` would give the full (T, ...) leaf — the
    window length rides the (never sharded) leading time axis, so the
    per-shard encoded segments the streamer stages line up with the
    resident store's shards and the same per-step all-gather plan serves
    both."""
    import jax
    from jax.sharding import NamedSharding

    def one(key_path, leaf):
        spec = stacked_spec_for_leaf(plan, _path_str(key_path),
                                     (1,) + tuple(leaf.shape))
        return NamedSharding(plan.mesh, spec)

    return jax.tree_util.tree_map_with_path(one, entry_tree)


def batch_pspec(plan: ShardingPlan, shape: Tuple[int, ...]) -> P:
    """Inputs: batch-dim data parallelism when the global batch divides the
    data axis (batch-1 decode shapes replicate)."""
    if not shape:
        return P()
    return P(_fit(plan, "data", shape[0]), *([None] * (len(shape) - 1)))


# --------------------------------------------------------------------------
# Pytree drivers
# --------------------------------------------------------------------------


def _path_str(key_path) -> str:
    import jax

    parts = []
    for k in key_path:
        if isinstance(k, jax.tree_util.DictKey):
            parts.append(str(k.key))
        elif isinstance(k, jax.tree_util.SequenceKey):
            parts.append(str(k.idx))
        elif isinstance(k, jax.tree_util.GetAttrKey):
            parts.append(str(k.name))
        else:
            parts.append(str(k))
    return "/".join(parts)


def params_shardings(plan: ShardingPlan, params_tree):
    """NamedSharding pytree for a params pytree (arrays or ShapeDtypeStructs)."""
    import jax
    from jax.sharding import NamedSharding

    def one(key_path, leaf):
        spec = spec_for_leaf(plan, _path_str(key_path), tuple(leaf.shape))
        return NamedSharding(plan.mesh, spec)

    return jax.tree_util.tree_map_with_path(one, params_tree)


def inputs_shardings(plan: ShardingPlan, specs_tree):
    """NamedSharding pytree for model inputs (batch-leading tensors)."""
    import jax
    from jax.sharding import NamedSharding

    return jax.tree.map(
        lambda s: NamedSharding(plan.mesh, batch_pspec(plan, tuple(s.shape))),
        specs_tree)
