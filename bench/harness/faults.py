"""Faults planted in the timed path by the tests, to see `correct` come
out false.  Each wraps one program function; the wrapper acts only while
the window runs (`armed`), so set-up and warm-up stay sound.

  unchanged   a replay returns its state unchanged (the trained model)
  half_batch  half of every batch is left out, the mean taken over the rest
  altered     an answer is altered where it is produced
"""

from __future__ import annotations

import contextlib

_ARMED = [False]

FAULTS = ("unchanged", "half_batch", "altered")


@contextlib.contextmanager
def armed():
    _ARMED[0] = True
    try:
        yield
    finally:
        _ARMED[0] = False


def _alter(params):
    import jax

    leaves, tree = jax.tree.flatten(params)
    i = max(range(len(leaves)), key=lambda j: leaves[j].size)
    leaves[i] = leaves[i] * 1.001  # the largest leaf, by a tenth of a percent
    return jax.tree.unflatten(tree, leaves)


@contextlib.contextmanager
def planted(fault):
    if fault is None:
        yield
        return
    assert fault in FAULTS, fault
    import jax.numpy as jnp

    from repro.core import deltagrad, engine, online

    saved = [(deltagrad, "run_replay", deltagrad.run_replay),
             (online, "run_online_request", online.run_online_request),
             (engine, "to_device", engine.to_device)]

    def replay(objective, history, *a, **kw):
        params, st = saved[0][2](objective, history, *a, **kw)
        if not _ARMED[0]:
            return params, st
        if fault == "unchanged":
            return history.final_params, st
        return (_alter(params) if fault == "altered" else params), st

    def online_request(grad_fn, store, *a, **kw):
        if _ARMED[0] and fault == "unchanged":
            # the request returns the current model and rewrites nothing
            return store.history.final_params, engine.RetrainStats()
        params, st = saved[1][2](grad_fn, store, *a, **kw)
        if _ARMED[0] and fault == "altered":
            return _alter(params), st
        return params, st

    def to_device(sched, *a, **kw):
        sd = saved[2][2](sched, *a, **kw)
        if not _ARMED[0] or fault != "half_batch":
            return sd
        B = sd.kept_w.shape[1]
        keep = (jnp.arange(B) < B // 2).astype(sd.kept_w.dtype)
        return sd._replace(kept_w=sd.kept_w * keep[None, :])

    deltagrad.run_replay = replay
    online.run_online_request = online_request
    engine.to_device = to_device
    try:
        yield
    finally:
        for mod, name, fn in saved:
            setattr(mod, name, fn)
