"""The paper's own model family: L2-regularized (multinomial) logistic
regression and a 2-layer ReLU network — plus their DeltaGrad Objectives."""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.deltagrad import Objective


def _mm(x, w):
    """The models' matmul.  They are f32 by contract (histories, replays
    and the exact-retrain reference); HIGHEST because a TPU's default f32
    matmul rounds its inputs to bf16."""
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


# --------------------------------------------------------------------------
# Binary logistic regression (RCV1 / HIGGS experiments)
# --------------------------------------------------------------------------


def logreg_init(d: int, seed: int = 0):
    k = jax.random.PRNGKey(seed)
    return {
        "w": 0.01 * jax.random.normal(k, (d,), dtype=jnp.float32),
        "b": jnp.zeros((), dtype=jnp.float32),
    }


def logreg_per_example_loss(params, batch: Dict[str, jax.Array]) -> jax.Array:
    logits = _mm(batch["x"], params["w"]) + params["b"]
    y = batch["y"].astype(jnp.float32)
    # numerically stable BCE-with-logits
    return jnp.maximum(logits, 0.0) - logits * y + jnp.log1p(jnp.exp(-jnp.abs(logits)))


def logreg_objective(l2: float = 5e-3) -> Objective:
    return Objective(per_example_loss=logreg_per_example_loss, l2=l2)


def logreg_predict(params, x: np.ndarray) -> np.ndarray:
    return (np.asarray(x @ np.asarray(params["w"]) + float(params["b"])) > 0).astype(
        np.int32
    )


def logreg_accuracy(params, ds) -> float:
    pred = logreg_predict(params, ds.columns["x"])
    return float((pred == ds.columns["y"]).mean())


# --------------------------------------------------------------------------
# Multinomial logistic regression (MNIST / covtype experiments)
# --------------------------------------------------------------------------


def multiclass_init(d: int, num_classes: int, seed: int = 0):
    k = jax.random.PRNGKey(seed)
    return {
        "w": 0.01 * jax.random.normal(k, (d, num_classes), dtype=jnp.float32),
        "b": jnp.zeros((num_classes,), dtype=jnp.float32),
    }


def multiclass_per_example_loss(params, batch):
    logits = _mm(batch["x"], params["w"]) + params["b"]
    logz = jax.nn.logsumexp(logits, axis=-1)
    true = jnp.take_along_axis(logits, batch["y"][:, None].astype(jnp.int32), axis=-1)[
        :, 0
    ]
    return logz - true


def multiclass_objective(l2: float = 5e-3) -> Objective:
    return Objective(per_example_loss=multiclass_per_example_loss, l2=l2)


def multiclass_accuracy(params, ds) -> float:
    logits = ds.columns["x"] @ np.asarray(params["w"]) + np.asarray(params["b"])
    return float((logits.argmax(-1) == ds.columns["y"]).mean())


# --------------------------------------------------------------------------
# 2-layer ReLU network (the paper's MNIST^n experiment; non-convex →
# run DeltaGrad with cfg.guard=True, curvature_eps>0: Algorithm 4)
# --------------------------------------------------------------------------


def mlp_init(d: int, hidden: int, num_classes: int, seed: int = 0):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    s1 = 1.0 / np.sqrt(d)
    s2 = 1.0 / np.sqrt(hidden)
    return {
        "w1": s1 * jax.random.normal(k1, (d, hidden), dtype=jnp.float32),
        "b1": jnp.zeros((hidden,), dtype=jnp.float32),
        "w2": s2 * jax.random.normal(k2, (hidden, num_classes), dtype=jnp.float32),
        "b2": jnp.zeros((num_classes,), dtype=jnp.float32),
    }


def mlp_per_example_loss(params, batch):
    h = jax.nn.relu(_mm(batch["x"], params["w1"]) + params["b1"])
    logits = _mm(h, params["w2"]) + params["b2"]
    logz = jax.nn.logsumexp(logits, axis=-1)
    true = jnp.take_along_axis(logits, batch["y"][:, None].astype(jnp.int32), axis=-1)[
        :, 0
    ]
    return logz - true


def mlp_objective(l2: float = 1e-3) -> Objective:
    return Objective(per_example_loss=mlp_per_example_loss, l2=l2)


def mlp_accuracy(params, ds) -> float:
    h = np.maximum(ds.columns["x"] @ np.asarray(params["w1"]) + np.asarray(params["b1"]), 0)
    logits = h @ np.asarray(params["w2"]) + np.asarray(params["b2"])
    return float((logits.argmax(-1) == ds.columns["y"]).mean())
