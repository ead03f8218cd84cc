"""DeltaGrad — Algorithm 1 (batch deletion/addition, GD and SGD).

Reference: Wu, Dobriban, Davidson, "DeltaGrad: Rapid retraining of machine
learning models", ICML 2020.  Notation follows the paper:

  w_t    — cached original iterates            (TrainingHistory)
  g_t    — cached (mini-)batch mean gradients  (TrainingHistory)
  w^I_t  — DeltaGrad ("incrementally updated") iterates
  w^U_t  — exact retraining iterates ("BaseL", eq. (1)/(S6))

This module holds the OBJECTIVE abstraction and the public entry points;
the execution itself — vectorized schedule precomputation, scanned approx
segments, stacked-history reads, the Pallas fused update — lives in
`core.engine` (see its module docstring for the phase-by-phase mapping to
the paper's Algorithms 1/3).  `DeltaGradConfig(impl="python")` selects the
pre-refactor per-step loop, kept as the parity oracle.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# Re-exported so existing imports (`from repro.core.deltagrad import ...`)
# keep working after the engine extraction.
from repro.core.engine import (  # noqa: F401
    DeltaGradConfig,
    RetrainStats,
    _approx_gradient,
    _approx_update,
    _momentum_apply,
    _next_pow2,
    _sgd_apply,
    _tree_zeros,
    run_baseline,
    run_replay,
    run_training,
)
from repro.core.history import HistoryMeta, TrainingHistory
from repro.data.dataset import Dataset


# --------------------------------------------------------------------------
# Objective
# --------------------------------------------------------------------------


@dataclass(eq=False)  # eq=False -> hashable by id, so jit caches persist
class Objective:
    """Per-example loss; the engine derives every gradient flavor from it.

    per_example_loss(params, batch_columns) -> (k,) losses, one per row.
    l2: coefficient of the (lambda/2)||w||^2 term included in every F_i
        (the paper's regularized objectives).
    """

    per_example_loss: Callable[[Any, Dict[str, jax.Array]], jax.Array]
    l2: float = 0.0

    def weighted_mean_loss(self, params, batch, weights):
        losses = self.per_example_loss(params, batch)
        denom = jnp.maximum(jnp.sum(weights), 1.0)
        data_term = jnp.sum(losses * weights) / denom
        if self.l2:
            sq = sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(params))
            return data_term + 0.5 * self.l2 * sq
        return data_term

    def make_grad_fn(self):
        """(params, batch, weights) -> mean gradient over weighted rows.

        Cached per Objective instance: repeated retraining calls (jackknife,
        conformal folds, online streams) must reuse compiled code."""
        if not hasattr(self, "_grad_fn"):
            self._grad_fn = jax.jit(jax.grad(self.weighted_mean_loss))
        return self._grad_fn

    def make_value_grad_fn(self):
        if not hasattr(self, "_vg_fn"):
            self._vg_fn = jax.jit(jax.value_and_grad(self.weighted_mean_loss))
        return self._vg_fn

    @classmethod
    def from_model(cls, model, *, remat: bool = False,
                   loss_chunk: Optional[int] = None, l2: float = 0.0,
                   attn_impl: Optional[str] = None, dtype=None,
                   precision: Optional[str] = None) -> "Objective":
        """Build an Objective from a `models.registry.Model`.

        The model's ``loss_fn(params, batch) -> ()`` is a mean loss over a
        batch (masked token cross-entropy for LMs); the engine needs a
        per-EXAMPLE loss, so this vmaps the model loss over singleton
        slices of each batch column — row i's loss is exactly the model's
        mean loss on the batch ``{k: col[i:i+1]}``.  This replaces the
        hand-rolled inline vmap every LM caller used to write.

        remat / loss_chunk are forwarded to ``loss_fn`` (activation
        rematerialization and chunked cross-entropy — the memory knobs at
        real model scale).  ``attn_impl`` pins the attention
        implementation (`models.attention_config`) for every trace of
        this objective: ``"flash"`` routes the Pallas flash kernel onto
        the replay forward where shapes allow.

        ``dtype`` is the loss's compute dtype (the model's default, bf16
        for the LMs, when None) and ``precision`` the matmul precision its
        trace asks for (``"highest"``: f32 products on a TPU, whose default
        f32 dot rounds its inputs to bf16).  An f32 path, history and
        L-BFGS pairs need both.
        """
        kw: Dict[str, Any] = {"remat": remat}
        if loss_chunk is not None:
            kw["loss_chunk"] = loss_chunk
        if dtype is not None:
            kw["dtype"] = dtype

        def per_example_loss(params, batch):
            from repro.models.attention_config import use_attention_impl

            def one(row):
                return model.loss_fn(
                    params, jax.tree.map(lambda c: c[None], row), **kw)

            with use_attention_impl(attn_impl), \
                    jax.default_matmul_precision(precision):
                return jax.vmap(one)(batch)

        return cls(per_example_loss=per_example_loss, l2=l2)


# --------------------------------------------------------------------------
# Entry points (thin frontends over core.engine)
# --------------------------------------------------------------------------


def sgd_train_with_cache(
    objective: Objective,
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
    """Train w_t by plain SGD (the paper's optimizer), caching (w_t, g_t);
    `placement` as in `core.engine.run_training`."""
    return run_training(objective, params0, ds, meta, tier=tier, codec=codec,
                        spill_dir=spill_dir, impl=impl, window=window,
                        spill_window=spill_window, placement=placement)


def baseline_retrain(
    objective: Objective,
    ds: Dataset,
    meta: HistoryMeta,
    params0,
    changed_idx: np.ndarray,
    mode: str = "delete",
    impl: str = "scan",
) -> Tuple[Any, RetrainStats]:
    """BaseL: exact retraining from scratch on the modified dataset,
    replaying the original schedule (paper eq. (1) / (S6))."""
    return run_baseline(objective, ds, meta, params0, changed_idx, mode=mode,
                        impl=impl)


def deltagrad_retrain(
    objective: Objective,
    history: TrainingHistory,
    ds: Dataset,
    changed_idx: np.ndarray,
    cfg: DeltaGradConfig,
    mode: str = "delete",
    params0=None,
    placement=None,
    store=None,
) -> Tuple[Any, RetrainStats]:
    """Algorithm 1 (GD + SGD unified; GD == SGD with batch_size >= n).

    `placement` (a `core.store.PlacementPolicy`) shards the replay across a
    mesh; `store` reuses a prebuilt `core.store.HistoryStore` (and its
    compiled-program cache) across calls."""
    return run_replay(objective, history, ds, changed_idx, cfg, mode=mode,
                      params0=params0, placement=placement, store=store)
