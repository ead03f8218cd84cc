"""Model FLOPs and kernel bytes of the InternLM2 configurations, from the
configuration's shapes alone.

A document's gradient needs the forward and backward passes of every
matmul: 6 operations a matmul parameter a token (2 forward, 4 backward).
The matmul parameters are the projections of each layer (q, k, v, o and
the three SwiGLU matrices) and the output head; the embedding is a gather.
Attention's two contractions (q.k and a.v, 2 * seq_len^2 * d each forward)
add 12 * layers * seq_len^2 * d a document with d = heads * head_dim,
counted without the causal half.  Norms, RoPE, softmax and the loss are
left out: under 1% at the published widths.
"""


def matmul_params(cfg) -> int:
    d, F, V = int(cfg["d_model"]), int(cfg["d_ff"]), int(cfg["vocab"])
    hd = int(cfg["n_heads"]) * int(cfg["head_dim"])
    kvd = int(cfg["n_kv_heads"]) * int(cfg["head_dim"])
    layer = d * hd + 2 * d * kvd + hd * d + 3 * d * F
    return int(cfg["n_layers"]) * layer + d * V


def n_params(cfg) -> int:
    """Every parameter: matmuls, the embedding and the RMSNorm scales."""
    d = int(cfg["d_model"])
    return matmul_params(cfg) + int(cfg["vocab"]) * d \
        + (2 * int(cfg["n_layers"]) + 1) * d


def grad_flops_per_row(cfg) -> int:
    """Model FLOPs of one document's gradient."""
    S = int(cfg["seq_len"])
    d = int(cfg["n_heads"]) * int(cfg["head_dim"])
    return 6 * matmul_params(cfg) * S + 12 * int(cfg["n_layers"]) * S * S * d


def dequant_update(p: int, base: bool = True):
    """``kernels/dequant_update``'s fused dequant + update on a block of
    `p` elements (those of the result's shape on the trace):
    reads the parameters, the int8 gradient payload, the L-BFGS correction,
    the changed rows' gradient and, for a delta codec, the f32 keyframe,
    and writes the parameters: ``4+1+4+4(+4)+4`` bytes an element; about
    nine operations an element."""
    per = 4 + 1 + 4 + 4 + (4 if base else 0) + 4
    return {"bytes": per * int(p), "flops": 9 * int(p)}
