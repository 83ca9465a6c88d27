"""Model FLOPs per token of Mamba-2 language-model training with the MIDX
sampled head, from the configuration's sizes.

Forward, per token and layer: the projections to z, x, B, C and dt and the
out-projection (2 FLOPs per weight); the SSD at chunk Q: within a chunk the
C·Bᵀ scores (2·N per earlier position), the weighted sum of inputs
(2·H·P per earlier position), both over the Q/2 earlier positions a token
has on average; across chunks the state read (2·H·N·P) and the state
update (2·H·N·P). The head: the positive logit and the M shared negatives'
logits (2·(M+1)·D). Training is three times the forward (forward,
gradients of activations and of weights). Recomputation is not counted, nor
the full softmax the sampled head avoids, nor elementwise work.
"""
from __future__ import annotations


def forward_per_token(spec: dict, negatives: int) -> float:
    d = spec["d_model"]
    d_in = spec["expand"] * d
    n = spec["d_state"]
    p = spec["headdim"]
    h = d_in // p
    q = spec["chunk_size"]
    proj = 2.0 * d * (2 * d_in + 2 * n + h) + 2.0 * d_in * d
    ssd = (q / 2) * (2.0 * n + 2.0 * h * p) + 2 * (2.0 * h * n * p)
    head = 2.0 * (negatives + 1) * d
    return spec["n_layer"] * (proj + ssd) + head


def train_per_token(spec: dict, negatives: int) -> float:
    return 3.0 * forward_per_token(spec, negatives)
