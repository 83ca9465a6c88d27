"""Least work of the MIDX proposal-table kernel (`kernels/midx_probs`) per
query row: the codeword scores s1 = z·C1ᵀ and s2 = z·C2ᵀ (2·2·K·D), the
stage-2 mass psi = exp(s2)·countsᵀ (2·K² plus K exponentials), and the
normaliser; it reads the row (D floats) and writes s1, s2, log psi and the
normaliser (3K + 1 floats). The codebooks (2·K·D floats) and the counts
(K² ints) are read once per call.

In serving, each decode wave makes one call for the slots that are
decoding, and each prefill wave one for the requests whose first token it
draws; only those count as rows.
"""
from __future__ import annotations

#: names under which the kernel's operations appear in a device trace
TRACE_PATTERNS = (r"^%\S*midx_probs\S* = ",)


def per_call(rows: int, k: int, d: int) -> tuple[float, float]:
    flops = rows * (4.0 * k * d + 2.0 * k * k)
    nbytes = rows * (d + 3 * k + 1) * 4 + (2 * k * d + k * k) * 4
    return flops, float(nbytes)


def window_work(rec: dict) -> tuple[float, float]:
    """(flops, bytes) of every call of the window's decode and prefill
    waves."""
    w = rec["work"]
    t0, t1 = rec["window"]
    f = b = 0.0
    for kind, a, z, decoding, firsts in rec["ticks"]:
        rows = firsts if kind == "prefill" else decoding
        if rows and a >= t0 and z <= t1:
            df, db = per_call(rows, w["midx_k"], w["d_model"])
            f, b = f + df, b + db
    return f, b
