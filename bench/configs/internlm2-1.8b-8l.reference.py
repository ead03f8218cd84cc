"""Plain reference for `internlm2-1.8b-8l`: the InternLM2 forward pass, the
masked next-token loss and exact minibatch-SGD retraining, in
straightforward jax.numpy.

It imports nothing of the program.  Weights, documents and the sampler's
seed come from the benchmark's own generator (`bench/models/lm.py`).  The
configuration states float32 with every matmul at `highest`; the whole
computation runs under ``jax.default_matmul_precision("highest")`` and asks
for it in each contraction.  `precision` selects the control's lower
precision, written out so that it computes the same on any platform:
``"high"`` is three bfloat16 passes (hi*hi + hi*lo + lo*hi, f32
accumulation), ``"bf16"`` one pass.

The block follows the published equations (arXiv:2403.17297; the
`internlm/internlm2-1_8b` modelling code): pre-norm RMSNorm, grouped-query
attention with rotate-half RoPE, causal softmax over q.k / sqrt(d_head),
a SwiGLU feed-forward ``w_down(silu(x w_gate) * (x w_up))``, a final
RMSNorm and an untied output head, no biases.  Departures, each a layout
and not a change of the mathematics:

  * q, k and v have separate projections where the published checkpoint
    packs them into one ``wqkv`` (a permutation of the same weights);
  * parameters of the layers are stacked along a leading layer axis, the
    layout the benchmark's generator writes;
  * a document's loss is the mean cross-entropy of its ``seq_len - 1``
    next-token predictions; a step's loss is the mean over the batch's
    documents that are not removed, and a step whose documents are all
    removed makes no update (the replay's convention, DeltaGrad §3).

Gradients are accumulated over blocks of `BLOCK` documents so that a step
fits (the cell's whole batch of 4 is one block: compiled for four v5e
chips, its temporaries take 4.5 GiB a chip); the batch schedule is the
program's documented rule for replaying the same minibatches (DeltaGrad
§A.1.2): step t draws ``batch_size`` of the ``n_rows`` documents without
replacement from ``numpy.random.default_rng(SeedSequence([seed, t]))``.
"""

from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 4  # documents per gradient block

_HIGHEST = jax.lax.Precision.HIGHEST


def _split(a):
    hi = a.astype(jnp.bfloat16)
    return hi, (a - hi.astype(jnp.float32)).astype(jnp.bfloat16)


def einsum(spec, a, b, precision):
    if precision == "highest":
        return jnp.einsum(spec, a, b, precision=_HIGHEST,
                          preferred_element_type=jnp.float32)
    dot = lambda u, v: jnp.einsum(spec, u, v,
                                  preferred_element_type=jnp.float32)
    ah, al = _split(a)
    bh, bl = _split(b)
    if precision == "bf16":
        return dot(ah, bh)
    if precision == "high":
        return dot(ah, bh) + dot(ah, bl) + dot(al, bh)
    raise ValueError(f"unknown precision {precision!r}")


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def rope(x, theta):
    """x: (S, H, D); rotate-half RoPE over positions 0..S-1."""
    S, _, D = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(p, x, cfg, prec):
    S = x.shape[0]
    H, Hk, D = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    q = einsum("sd,de->se", x, p["wq"], prec).reshape(S, H, D)
    k = einsum("sd,de->se", x, p["wk"], prec).reshape(S, Hk, D)
    v = einsum("sd,de->se", x, p["wv"], prec).reshape(S, Hk, D)
    q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
    # query head h reads key/value head h // (H / Hk)
    k = jnp.repeat(k, H // Hk, axis=1)
    v = jnp.repeat(v, H // Hk, axis=1)
    s = einsum("qhd,khd->hqk", q, k, prec) / np.sqrt(D)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    s = jnp.where(causal[None], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = einsum("hqk,khd->qhd", a, v, prec).reshape(S, H * D)
    return einsum("se,ed->sd", o, p["wo"], prec)


def swiglu(p, x, prec):
    h = jax.nn.silu(einsum("sd,df->sf", x, p["w_gate"], prec)) \
        * einsum("sd,df->sf", x, p["w_up"], prec)
    return einsum("sf,fd->sd", h, p["w_down"], prec)


def doc_loss(params, tokens, cfg, prec):
    """Mean next-token cross-entropy of one document (seq_len,)."""
    eps = cfg["norm_eps"]
    x = params["embed"][tokens]

    def layer(x, p):
        x = x + attention(p["mixer"], rmsnorm(x, p["ln1"]["scale"], eps),
                          cfg, prec)
        x = x + swiglu(p["mlp"], rmsnorm(x, p["ln2"]["scale"], eps), prec)
        return x, None

    x, _ = jax.lax.scan(layer, x, params["u0"])
    x = rmsnorm(x, params["final_norm"]["scale"], eps)
    logits = einsum("sd,dv->sv", x[:-1], params["lm_head"], prec)
    nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, tokens[1:, None], axis=-1)[:, 0]
    return jnp.mean(nll)


def batch_indices(seed, t, n, batch_size):
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), t]))
    return rng.choice(n, size=batch_size, replace=False)


def make_train(cfg, precision="highest"):
    """(params0, tokens, seed, live) -> params after cfg['steps'] minibatch
    SGD steps on the documents where `live` is True, computed in f32 and
    handed back as float64 host arrays: the precision the comparison
    computes in, so that each reference is widened once and not at every
    comparison it enters (at 882 M parameters a widening takes seconds)."""
    prec = precision
    lr = float(cfg["lr_schedule"][0][1])
    assert len(cfg["lr_schedule"]) == 1 and float(cfg["l2"]) == 0.0
    B, T = int(cfg["batch_size"]), int(cfg["steps"])

    @jax.jit
    def block_grad(params, toks, wts):
        with jax.default_matmul_precision("highest"):
            def loss(p):
                per = jax.vmap(lambda d: doc_loss(p, d, cfg, prec))(toks)
                return jnp.sum(per * wts)
            return jax.grad(loss)(params)

    @jax.jit
    def sgd(params, g, scale):
        return jax.tree.map(lambda w, d: w - scale * d, params, g)

    def train(params0, tokens, seed, live):
        live = np.asarray(live)
        n = live.shape[0]
        params = params0
        for t in range(T):
            rows = batch_indices(np.asarray(seed), t, n, B)
            wts = live[rows].astype(np.float32)
            if wts.sum() == 0:
                continue
            g = None
            for a in range(0, B, BLOCK):
                gb = block_grad(params, tokens[rows[a:a + BLOCK]],
                                jnp.asarray(wts[a:a + BLOCK]))
                g = gb if g is None else jax.tree.map(jnp.add, g, gb)
            params = sgd(params, g, jnp.float32(lr / wts.sum()))
        leaves, tdef = jax.tree.flatten(params)
        for x in leaves:
            if isinstance(x, jax.Array):
                x.copy_to_host_async()
        with ThreadPoolExecutor(max_workers=8) as pool:
            wide = pool.map(lambda x: np.asarray(x, dtype=np.float64), leaves)
            return jax.tree.unflatten(tdef, list(wide))

    return train
