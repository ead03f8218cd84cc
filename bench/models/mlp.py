"""The paper MLP through the program's normal path, and the inputs the
benchmark makes for it from the seed.

Data and weights are made here, on the device, in one jitted call; the
program and the reference both take them from here.
"""

from __future__ import annotations

import functools

import jax
import numpy as np

from bench.counts import mlp as counts


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _make(key, n, d, hidden, classes, noise):
    import jax.numpy as jnp

    kc, ky, kx, k1, k2 = jax.random.split(key, 5)
    centers = jax.random.normal(kc, (classes, d), jnp.float32)
    y = jax.random.randint(ky, (n,), 0, classes, jnp.int32)
    x = centers[y] + noise * jax.random.normal(kx, (n, d), jnp.float32)
    params = {
        "w1": jax.random.normal(k1, (d, hidden), jnp.float32) / np.sqrt(d),
        "b1": jnp.zeros((hidden,), jnp.float32),
        "w2": jax.random.normal(k2, (hidden, classes), jnp.float32)
        / np.sqrt(hidden),
        "b2": jnp.zeros((classes,), jnp.float32),
    }
    return x, y, params


def make_inputs(cfg, key):
    """(x, y, params0) on the device, from a PRNG key."""
    import jax.numpy as jnp

    return _make(key, int(cfg["n_rows"]), int(cfg["d_in"]),
                 int(cfg["hidden"]), int(cfg["classes"]),
                 jnp.float32(cfg["blob_noise"]))


def n_rows(cfg) -> int:
    return int(cfg["n_rows"])


def session(cfg, inputs, seed: int):
    """The program's `UnlearnerSession` for this configuration (not fit)."""
    from repro.core.deltagrad import DeltaGradConfig
    from repro.core.session import UnlearnerConfig, UnlearnerSession
    from repro.data.dataset import Dataset
    from repro.models.simple import mlp_objective

    x, y, params0 = inputs
    h = cfg["history"]
    ucfg = UnlearnerConfig(
        steps=int(cfg["steps"]), batch_size=int(cfg["batch_size"]),
        lr_schedule=tuple(tuple(p) for p in cfg["lr_schedule"]),
        seed=seed % (2**31), history_tier=h["tier"],
        history_codec=h["codec"],
        deltagrad=DeltaGradConfig(**cfg["deltagrad"]))
    ds = Dataset({"x": np.asarray(x), "y": np.asarray(y)})
    return UnlearnerSession(mlp_objective(l2=float(cfg["l2"])), params0, ds,
                            ucfg)


def shape_counts(cfg):
    """What the per-layer readers count with."""
    return {"n_params": counts.n_params(cfg),
            "grad_flops_per_row": counts.grad_flops_per_row(cfg)}


def reference_args(inputs):
    """What the reference trains on: (params0, x, y)."""
    x, y, params0 = inputs
    return params0, x, y
