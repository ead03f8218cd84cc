"""InternLM2 through the program's normal path on a (data, model) mesh,
and the inputs the benchmark makes for it from the seed.

Documents, weights and the minibatch sampler's seed are made here from the
PRNG key; documents and weights on the device, in one jitted call, each
parameter already on its place on the mesh.  The program and the reference
both take them from here.  The mesh is the configuration's, over the
devices there are when the host has fewer (the CPU tests run it on one).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.counts import lm as counts


def _mesh_shape(cfg):
    """The configuration's (data, model) mesh, its model axis cut to the
    devices there are."""
    data, model = cfg["mesh"]["shape"]
    return (data, max(1, min(model, jax.device_count() // data)))


def _placement(cfg):
    from repro.core.store import PlacementPolicy

    return PlacementPolicy(_mesh_shape(cfg), tuple(cfg["mesh"]["axes"]))


def _param_shapes(cfg):
    """The program's parameter tree of an InternLM2 stack, as shapes."""
    d, F, V = cfg["d_model"], cfg["d_ff"], cfg["vocab"]
    L, D = cfg["n_layers"], cfg["head_dim"]
    hd, kvd = cfg["n_heads"] * D, cfg["n_kv_heads"] * D
    sds = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    return {
        "embed": sds(V, d),
        "final_norm": {"scale": sds(d)},
        "lm_head": sds(d, V),
        "u0": {"ln1": {"scale": sds(L, d)}, "ln2": {"scale": sds(L, d)},
               "mixer": {"wq": sds(L, d, hd), "wk": sds(L, d, kvd),
                         "wv": sds(L, d, kvd), "wo": sds(L, hd, d)},
               "mlp": {"w_gate": sds(L, d, F), "w_up": sds(L, d, F),
                       "w_down": sds(L, F, d)}},
    }


def _init(key, shapes):
    """Random weights as the program initialises them: the embedding
    N(0, 0.02^2), projections N(0, 1/fan_in), norm scales 1."""
    leaves, tdef = jax.tree_util.tree_flatten_with_path(shapes)
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, (path, s) in zip(keys, leaves):
        name = jax.tree_util.keystr(path)
        if "scale" in name:
            out.append(jnp.ones(s.shape, s.dtype))
        elif "embed" in name:
            out.append(0.02 * jax.random.normal(k, s.shape, s.dtype))
        else:
            out.append(jax.random.normal(k, s.shape, s.dtype)
                       / np.sqrt(s.shape[-2]))
    return jax.tree.unflatten(tdef, out)


def _documents(key, n, S, V):
    """Bigram-chain documents like `repro.data.synthetic.token_stream`:
    token j+1 = token j + a per-document shift + U{0, 1, 2}, mod V."""
    k0, k1, k2 = jax.random.split(key, 3)
    start = jax.random.randint(k0, (n, 1), 0, V)
    shift = jax.random.randint(k1, (n, 1), 1, V)
    step = shift + jax.random.randint(k2, (n, S - 1), 0, 3)
    walk = jnp.concatenate([jnp.zeros((n, 1), jnp.int32),
                            jnp.cumsum(step % V, axis=1) % V], axis=1)
    return ((start + walk) % V).astype(jnp.int32)


def make_inputs(cfg, key):
    """(tokens, params0, sampler seed): documents replicated and weights
    sharded on the configuration's mesh."""
    from jax.sharding import NamedSharding, PartitionSpec

    pol = _placement(cfg)
    shapes = _param_shapes(cfg)
    kd, kw, ks = jax.random.split(key, 3)
    make = jax.jit(
        lambda kd, kw: (_documents(kd, cfg["n_rows"], cfg["seq_len"],
                                   cfg["vocab"]), _init(kw, shapes)),
        out_shardings=(NamedSharding(pol.mesh, PartitionSpec()),
                       pol.param_shardings(shapes)))
    tokens, params0 = make(kd, kw)
    seed = int(jax.random.randint(ks, (), 0, 2**31 - 1))
    return tokens, params0, seed


def n_rows(cfg) -> int:
    return int(cfg["n_rows"])


def _model_config(cfg):
    """The registry's InternLM2 at the configuration's depth, every width
    checked against the configuration's."""
    import dataclasses

    from repro.configs.registry import get_config

    mc = dataclasses.replace(get_config("internlm2-1.8b"),
                             n_layers=int(cfg["n_layers"]))
    for key, got in (("d_model", mc.d_model), ("n_heads", mc.n_heads),
                     ("n_kv_heads", mc.n_kv_heads), ("head_dim", mc.head_dim),
                     ("d_ff", mc.d_ff), ("vocab", mc.vocab),
                     ("rope_theta", mc.rope_theta), ("norm_eps", mc.norm_eps)):
        want = cfg[key]
        if got != want:  # the CPU tests' small sizes
            mc = dataclasses.replace(
                mc, **{"d_head" if key == "head_dim" else key: want})
    return mc


def session(cfg, inputs, seed: int):
    """The program's `UnlearnerSession` for this configuration (not fit),
    placed on the configuration's mesh.  The sampler's seed is the inputs'
    (the reference replays the same minibatches from it)."""
    from repro.core.deltagrad import DeltaGradConfig, Objective
    from repro.core.session import UnlearnerConfig, UnlearnerSession
    from repro.data.dataset import Dataset
    from repro.models.registry import build

    tokens, params0, sampler_seed = inputs
    h = cfg["history"]
    objective = Objective.from_model(
        build(_model_config(cfg)), remat=bool(cfg["remat"]),
        loss_chunk=int(cfg["loss_chunk"]), l2=float(cfg["l2"]),
        dtype=jnp.float32, precision="highest")
    ucfg = UnlearnerConfig(
        steps=int(cfg["steps"]), batch_size=int(cfg["batch_size"]),
        lr_schedule=tuple(tuple(p) for p in cfg["lr_schedule"]),
        seed=sampler_seed, history_tier=h["tier"],
        history_codec=h["codec"],
        deltagrad=DeltaGradConfig(**cfg["deltagrad"]),
        placement=_placement(cfg))
    ds = Dataset({"tokens": np.asarray(tokens)})
    return UnlearnerSession(objective, params0, ds, ucfg)


def shape_counts(cfg):
    """What the per-layer readers count with.  Called at the end of set-up,
    it also takes the program's ``store.staged_bytes`` counter there, so
    that a reader counts the window's staging alone."""
    from repro.obs import metrics

    staged = metrics.get_registry().counter("store.staged_bytes", unit="B",
                                            owner="core.store")
    return {"n_params": counts.n_params(cfg),
            "grad_flops_per_row": counts.grad_flops_per_row(cfg),
            "staged_bytes_setup": staged.value}


def reference_args(inputs):
    """What the reference trains on: (params0, tokens, sampler seed)."""
    tokens, params0, seed = inputs
    return params0, tokens, seed
