"""Least work of the shared-negative sampled-softmax cross-entropy kernels
(`kernels/sampled_ce`, forward and backward), per sequence of T tokens
against M shared negatives of width D, float32 operands.

Forward: the positive logits (2·T·D) and the negative logits (2·T·M·D);
reads the hidden rows, the positive rows, the negative rows, log q and the
ids once, writes the per-token loss and log-sum-exp.
Backward, with the forward's log-sum-exp kept: the softmax weights need the
negative logits again only if they were not kept; the least work keeps
them, so the backward is the gradient of the hidden rows (2·T·M·D + 2·T·D)
and of the negative rows (2·T·M·D) and positive rows (T·D); it reads what
the forward read plus the loss gradient and writes the four gradients.

Any kernel that does this work in another way reads against the same
count, so a faster one reads a higher share.
"""
from __future__ import annotations

#: names under which the kernels' operations appear in a device trace
TRACE_PATTERNS = (r"^%\S*sampled_ce\S* = ",)


def per_sequence(t: int, m: int, d: int, elt: int = 4) -> tuple[float, float]:
    """(flops, bytes) of forward plus backward for one sequence."""
    fwd_flops = 2.0 * t * m * d + 2.0 * t * d
    bwd_flops = 4.0 * t * m * d + 3.0 * t * d
    rows = (2 * t + m) * d * elt                  # hidden, positive, negative
    small = (2 * m + t) * 4                        # log q, ids, positive ids
    fwd_bytes = rows + small + 2 * t * 4           # + loss, lse
    bwd_bytes = rows + small + 2 * t * 4 + rows + m * 4   # + dh, dpe, dne, dlq
    return fwd_flops + bwd_flops, float(fwd_bytes + bwd_bytes)


def window_work(rec: dict) -> tuple[float, float]:
    """(flops, bytes) of every call the window's steps made."""
    w = rec["work"]
    f, b = per_sequence(w["seq"], w["negatives"], w["d_model"])
    n = len(rec["steps"]) * w["batch"]
    return f * n, b * n
