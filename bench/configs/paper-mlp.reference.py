"""Plain reference for `paper-mlp`: exact retraining of the 784-300-10 ReLU
MLP by full-batch gradient descent, in straightforward jax.numpy.

It imports nothing of the program.  Weights and data come from the
benchmark's own generator (`bench/models/mlp.py`).  The configuration
states float32 with every matmul at `highest`.  `precision` selects the
control's lower precision, written out so that it computes the same on any
platform: ``"high"`` is three bfloat16 passes (hi*hi + hi*lo + lo*hi, f32
accumulation), ``"bf16"`` one pass.
"""

import jax
import jax.numpy as jnp


def _split(a):
    hi = a.astype(jnp.bfloat16)
    return hi, (a - hi.astype(jnp.float32)).astype(jnp.bfloat16)


def matmul(a, b, precision):
    if precision == "highest":
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
    dot = lambda u, v: jnp.matmul(u, v, preferred_element_type=jnp.float32)
    ah, al = _split(a)
    bh, bl = _split(b)
    if precision == "bf16":
        return dot(ah, bh)
    if precision == "high":
        return dot(ah, bh) + dot(ah, bl) + dot(al, bh)
    raise ValueError(f"unknown precision {precision!r}")


def _loss(p, x, y, wts, l2, prec):
    h = jax.nn.relu(matmul(x, p["w1"], prec) + p["b1"])
    logits = matmul(h, p["w2"], prec) + p["b2"]
    ce = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, y[:, None], axis=-1)[:, 0]
    data = jnp.sum(ce * wts) / jnp.maximum(jnp.sum(wts), 1.0)
    return data + 0.5 * l2 * sum(jnp.sum(v * v) for v in jax.tree.leaves(p))


def _lr_table(cfg):
    lr = []
    for t in range(cfg["steps"]):
        v = cfg["lr_schedule"][0][1]
        for start, value in cfg["lr_schedule"]:
            if t >= start:
                v = value
        lr.append(v)
    return jnp.asarray(lr, jnp.float32)


def make_train(cfg, precision="highest"):
    """(params0, x, y, live) -> params after cfg['steps'] full-batch GD
    steps on the rows where `live` is True."""
    prec = precision
    lrs = _lr_table(cfg)
    l2 = float(cfg["l2"])
    grad = jax.grad(_loss)

    @jax.jit
    def train(params0, x, y, live):
        wts = live.astype(jnp.float32)

        def step(p, lr):
            g = grad(p, x, y, wts, l2, prec)
            return jax.tree.map(lambda a, b: a - lr * b, p, g), None

        return jax.lax.scan(step, params0, lrs)[0]

    return train
