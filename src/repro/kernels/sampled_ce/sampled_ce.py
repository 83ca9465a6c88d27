"""Pallas TPU kernel: fused shared-negative sampled-softmax CE ("flash-CE").

Grid (nT, nM), nM innermost. Per (token-block, negative-block):
  logits = h @ negEᵀ − ln(M·q)      (MXU + VPU)
  online logsumexp accumulation      (VMEM scratch m/l, flash-style)
On the last negative block the positive logit joins the lse and the loss
block is written. The [T, M] corrected-logit matrix never exists in HBM —
that is the memory the fusion saves (M=1024, T=65k ⇒ 268 MB per step).
Collision masking (neg id == pos id) happens in-kernel, to the canonical
`core.sampled_softmax.NEG_INF` sentinel.

The backward (`sampled_ce_bwd`) is fused too: softmax weights are rebuilt
block-wise from the saved lse (flash-style recompute), so neither the
forward nor the backward ever materializes [T, M] in HBM.

Arbitrary T and M are supported: inputs are padded to the block grid here
(mirroring midx_probs/ops._pad_t) — padded negatives carry log_q = -NEG_INF
so their corrected logit falls below NEG_INF_THRESHOLD and is dropped by the
same validity guard that drops collisions; padded token rows are sliced off.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.sampled_softmax import NEG_INF, NEG_INF_THRESHOLD


def _kernel(h_ref, pe_ref, ne_ref, lq_ref, nid_ref, pid_ref, *rest,
            num_neg: int, include_pos: bool = True, quantized: bool = False):
    if quantized:
        ps_ref, ns_ref, loss_ref, lse_ref, m_ref, l_ref = rest
    else:
        loss_ref, lse_ref, m_ref, l_ref = rest
    im = pl.program_id(1)
    nm = pl.num_programs(1)

    @pl.when(im == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    h = h_ref[...].astype(jnp.float32)                   # [Tb, D]
    ne = ne_ref[...].astype(jnp.float32)                 # [Mb, D]
    if quantized:
        ne = ne * ns_ref[...]                            # per-row dequant
    logits = jax.lax.dot_general(h, ne, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # [Tb,Mb]
    corr = logits - (jnp.log(float(num_neg)) + lq_ref[...])       # [1, Mb]
    hit = nid_ref[...] == pid_ref[...]             # [1, Mb] vs [Tb, 1]
    corr = jnp.where(hit, NEG_INF, corr)
    # validity guard: masked/padded entries contribute exactly 0 even when
    # the running max itself is NEG_INF (exp(corr - m) would be 1, not 0).
    valid = corr > NEG_INF_THRESHOLD

    m_prev = m_ref[...]                                  # [Tb, 1]
    m_new = jnp.maximum(m_prev, jnp.max(corr, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    contrib = jnp.where(valid, jnp.exp(corr - m_new), 0.0)
    l_new = l_ref[...] * alpha + jnp.sum(contrib, axis=-1, keepdims=True)
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(im == nm - 1)
    def _finish():
        if include_pos:
            pe = pe_ref[...].astype(jnp.float32)         # [Tb, D]
            if quantized:
                pe = pe * ps_ref[...]
            pos_logit = jnp.sum(h * pe, axis=-1, keepdims=True)    # [Tb,1]
            m_fin = jnp.maximum(m_ref[...], pos_logit)
            l_fin = (l_ref[...] * jnp.exp(m_ref[...] - m_fin)
                     + jnp.exp(pos_logit - m_fin))
            lse = jnp.log(jnp.maximum(l_fin, 1e-30)) + m_fin
            loss_ref[...] = lse - pos_logit
            lse_ref[...] = lse
        else:
            # partial mode (vocab-parallel shard): no positive join — emit
            # the negatives-only partial lse; an all-masked block lands at
            # ~NEG_INF, which the cross-shard merge treats as zero mass.
            lse = jnp.log(jnp.maximum(l_ref[...], 1e-30)) + m_ref[...]
            loss_ref[...] = lse
            lse_ref[...] = lse


def _pad_dim(x: jax.Array, mult: int, axis: int = 0, fill=0):
    """Pad `axis` of x up to a multiple of `mult` with `fill`."""
    pad = (-x.shape[axis]) % mult
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=fill)


def _padded(hidden, pos_emb, neg_emb, log_q, neg_ids, pos_ids, block_t,
            block_m):
    """Pad every operand to the block grid: padded negatives are invalidated
    via log_q, padded token rows are sliced off by the callers. The id and
    log_q vectors become 2-D — [1, Mp] rows and a [Tp, 1] column — so every
    block is tile-aligned for the TPU (a 1-D block is not)."""
    return (_pad_dim(hidden, block_t),
            _pad_dim(pos_emb, block_t),
            _pad_dim(neg_emb, block_m),
            _pad_dim(log_q, block_m, fill=-NEG_INF)[None, :],
            _pad_dim(neg_ids, block_m, fill=-1)[None, :],
            _pad_dim(pos_ids, block_t, fill=-2)[:, None])


@functools.partial(jax.jit, static_argnames=("block_t", "block_m",
                                             "interpret", "include_pos",
                                             "num_neg"))
def sampled_ce(hidden: jax.Array, pos_emb: jax.Array, neg_emb: jax.Array,
               log_q: jax.Array, neg_ids: jax.Array, pos_ids: jax.Array, *,
               pos_scale: jax.Array | None = None,
               neg_scale: jax.Array | None = None,
               block_t: int = 256, block_m: int = 256,
               interpret: bool = False, include_pos: bool = True,
               num_neg: int | None = None) -> tuple[jax.Array, jax.Array]:
    """hidden/pos_emb [T,D]; neg_emb [M,D]; log_q/neg_ids [M]; pos_ids [T]
    -> (loss [T], lse [T]) fp32; lse is the fused backward's residual.
    T and M may be arbitrary (padded to blocks here).

    include_pos=False: partial mode for the vocab-parallel head — the
    positive never joins, both outputs are the negatives-only partial lse,
    and `num_neg` gives the GLOBAL negative count for the ln(M·q) correction
    (defaults to this shard's M).

    pos_scale/neg_scale != None: quantized mode (DESIGN §12) — pos_emb /
    neg_emb are gathered rows of the low-bit table and the [T,1]/[M,1] fp32
    scales dequantize them in-register before the dot."""
    t, d = hidden.shape
    m = neg_emb.shape[0]
    block_t, block_m = min(block_t, t), min(block_m, m)
    quantized = neg_scale is not None
    hidden, pos_emb, neg_emb, log_q, neg_ids, pos_ids = _padded(
        hidden, pos_emb, neg_emb, log_q, neg_ids, pos_ids, block_t, block_m)
    tp, mp = hidden.shape[0], neg_emb.shape[0]
    grid = (tp // block_t, mp // block_m)
    kernel = functools.partial(_kernel, num_neg=num_neg or m,
                               include_pos=include_pos, quantized=quantized)
    in_specs = [
        pl.BlockSpec((block_t, d), lambda it, im: (it, 0)),
        pl.BlockSpec((block_t, d), lambda it, im: (it, 0)),
        pl.BlockSpec((block_m, d), lambda it, im: (im, 0)),
        pl.BlockSpec((1, block_m), lambda it, im: (0, im)),
        pl.BlockSpec((1, block_m), lambda it, im: (0, im)),
        pl.BlockSpec((block_t, 1), lambda it, im: (it, 0)),
    ]
    operands = [hidden, pos_emb, neg_emb, log_q, neg_ids, pos_ids]
    if quantized:
        if pos_scale is None:
            pos_scale = jnp.ones((t, 1), jnp.float32)
        in_specs += [pl.BlockSpec((block_t, 1), lambda it, im: (it, 0)),
                     pl.BlockSpec((block_m, 1), lambda it, im: (im, 0))]
        operands += [_pad_dim(pos_scale.astype(jnp.float32), block_t),
                     _pad_dim(neg_scale.astype(jnp.float32), block_m)]
    loss, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((block_t, 1), lambda it, im: (it, 0)),
            pl.BlockSpec((block_t, 1), lambda it, im: (it, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((tp, 1), jnp.float32),
            jax.ShapeDtypeStruct((tp, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_t, 1), jnp.float32),
            pltpu.VMEM((block_t, 1), jnp.float32),
        ],
        name="sampled_ce",
        interpret=interpret,
    )(*operands)
    return loss[:t, 0], lse[:t, 0]


# ---------------------------------------------------------------------------
# fused backward: flash-style recompute from the saved lse. Two kernels with
# opposite grid orders (like flash attention's dq vs dk/dv): dh/dpe
# accumulate over negative blocks per token block (grid (nT, nM), innermost
# nM keeps the VMEM accumulator resident); dne/dlq accumulate over token
# blocks per negative block (grid (nM, nT)). The [T, M] softmax-weight
# matrix w = exp(corr - lse) only ever exists one block at a time in VMEM.
# ---------------------------------------------------------------------------

def _w_block(h, ne_ref, lq_ref, nid_ref, pid_ref, lse, *, num_neg: int,
             ns_ref=None):
    """Recompute one [Tb, Mb] block of masked softmax weights."""
    ne = ne_ref[...].astype(jnp.float32)                 # [Mb, D]
    if ns_ref is not None:
        ne = ne * ns_ref[...]                            # per-row dequant
    logits = jax.lax.dot_general(h, ne, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    corr = logits - (jnp.log(float(num_neg)) + lq_ref[...])
    hit = nid_ref[...] == pid_ref[...]
    corr = jnp.where(hit, NEG_INF, corr)
    w = jnp.where(corr > NEG_INF_THRESHOLD, jnp.exp(corr - lse), 0.0)
    return w, ne


def _bwd_dh_kernel(g_ref, h_ref, pe_ref, ne_ref, lq_ref, nid_ref, pid_ref,
                   lse_ref, *rest, num_neg: int, include_pos: bool = True,
                   quantized: bool = False):
    if quantized:
        ps_ref, ns_ref, dh_ref, dpe_ref, acc_ref = rest
    else:
        ns_ref = None
        dh_ref, dpe_ref, acc_ref = rest
    im = pl.program_id(1)
    nm = pl.num_programs(1)

    @pl.when(im == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    h = h_ref[...].astype(jnp.float32)                   # [Tb, D]
    w, ne = _w_block(h, ne_ref, lq_ref, nid_ref, pid_ref, lse_ref[...],
                     num_neg=num_neg, ns_ref=ns_ref)
    acc_ref[...] += jax.lax.dot_general(w, ne, (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    @pl.when(im == nm - 1)
    def _finish():
        g = g_ref[...]                                   # [Tb, 1]
        if include_pos:
            pe = pe_ref[...].astype(jnp.float32)
            if quantized:
                pe = pe * ps_ref[...]
            pos_logit = jnp.sum(h * pe, axis=-1, keepdims=True)
            p_pos = jnp.exp(pos_logit - lse_ref[...])    # [Tb, 1]
            dh_ref[...] = g * (acc_ref[...] + (p_pos - 1.0) * pe)
            # dpe stays scale-unaware: g·(p_pos−1)·h IS the straight-through
            # master-row cotangent (row values never enter the row-gradient).
            dpe_ref[...] = g * (p_pos - 1.0) * h
        else:
            # partial mode: d(partial lse)/dh = Σ_j w_j ne_j; no pos terms.
            dh_ref[...] = g * acc_ref[...]
            dpe_ref[...] = jnp.zeros_like(dpe_ref)


def _bwd_dne_kernel(g_ref, h_ref, ne_ref, lq_ref, nid_ref, pid_ref,
                    lse_ref, *rest, num_neg: int, quantized: bool = False):
    if quantized:
        ns_ref, dne_ref, dlq_ref, ne_acc, lq_acc = rest
    else:
        ns_ref = None
        dne_ref, dlq_ref, ne_acc, lq_acc = rest
    it = pl.program_id(1)
    nt = pl.num_programs(1)

    @pl.when(it == 0)
    def _init():
        ne_acc[...] = jnp.zeros_like(ne_acc)
        lq_acc[...] = jnp.zeros_like(lq_acc)

    h = h_ref[...].astype(jnp.float32)                   # [Tb, D]
    w, _ = _w_block(h, ne_ref, lq_ref, nid_ref, pid_ref, lse_ref[...],
                    num_neg=num_neg, ns_ref=ns_ref)
    gw = g_ref[...] * w                                  # [Tb, Mb]
    ne_acc[...] += jax.lax.dot_general(gw, h, (((0,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32)
    lq_acc[...] += -jnp.sum(gw, axis=0, keepdims=True)   # [1, Mb]

    @pl.when(it == nt - 1)
    def _finish():
        dne_ref[...] = ne_acc[...]
        dlq_ref[...] = lq_acc[...]


@functools.partial(jax.jit, static_argnames=("block_t", "block_m",
                                             "interpret", "include_pos",
                                             "num_neg"))
def sampled_ce_bwd(g: jax.Array, hidden: jax.Array, pos_emb: jax.Array,
                   neg_emb: jax.Array, log_q: jax.Array, neg_ids: jax.Array,
                   pos_ids: jax.Array, lse: jax.Array, *,
                   pos_scale: jax.Array | None = None,
                   neg_scale: jax.Array | None = None,
                   block_t: int = 256, block_m: int = 256,
                   interpret: bool = False, include_pos: bool = True,
                   num_neg: int | None = None):
    """Fused backward. g/lse [T]; others as sampled_ce.
    -> (dh [T,D], dpe [T,D], dne [M,D], dlq [M]) fp32.
    include_pos=False: lse is the PARTIAL lse and the pos terms vanish —
    dpe is zeros; num_neg again overrides the global M.
    Quantized mode (scales given): dh and the softmax-weight recompute use
    dequantized rows; dpe/dne stay scale-unaware — they are the
    straight-through master-table cotangents."""
    t, d = hidden.shape
    m = neg_emb.shape[0]
    num_neg = num_neg or m
    block_t, block_m = min(block_t, t), min(block_m, m)
    quantized = neg_scale is not None
    hidden, pos_emb, neg_emb, log_q, neg_ids, pos_ids = _padded(
        hidden, pos_emb, neg_emb, log_q, neg_ids, pos_ids, block_t, block_m)
    g2 = _pad_dim(g.astype(jnp.float32)[:, None], block_t)   # pad 0: padded
    lse2 = _pad_dim(lse[:, None], block_t)                   # rows contribute 0
    tp, mp = hidden.shape[0], neg_emb.shape[0]
    if quantized:
        if pos_scale is None:
            pos_scale = jnp.ones((t, 1), jnp.float32)
        ps2 = _pad_dim(pos_scale.astype(jnp.float32), block_t)
        ns2 = _pad_dim(neg_scale.astype(jnp.float32), block_m)
    dh_in_specs = [
        pl.BlockSpec((block_t, 1), lambda it, im: (it, 0)),
        pl.BlockSpec((block_t, d), lambda it, im: (it, 0)),
        pl.BlockSpec((block_t, d), lambda it, im: (it, 0)),
        pl.BlockSpec((block_m, d), lambda it, im: (im, 0)),
        pl.BlockSpec((1, block_m), lambda it, im: (0, im)),
        pl.BlockSpec((1, block_m), lambda it, im: (0, im)),
        pl.BlockSpec((block_t, 1), lambda it, im: (it, 0)),
        pl.BlockSpec((block_t, 1), lambda it, im: (it, 0)),
    ]
    dh_operands = [g2, hidden, pos_emb, neg_emb, log_q, neg_ids, pos_ids,
                   lse2]
    if quantized:
        dh_in_specs += [pl.BlockSpec((block_t, 1), lambda it, im: (it, 0)),
                        pl.BlockSpec((block_m, 1), lambda it, im: (im, 0))]
        dh_operands += [ps2, ns2]
    dh, dpe = pl.pallas_call(
        functools.partial(_bwd_dh_kernel, num_neg=num_neg,
                          include_pos=include_pos, quantized=quantized),
        grid=(tp // block_t, mp // block_m),
        in_specs=dh_in_specs,
        out_specs=[
            pl.BlockSpec((block_t, d), lambda it, im: (it, 0)),
            pl.BlockSpec((block_t, d), lambda it, im: (it, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((tp, d), jnp.float32),
            jax.ShapeDtypeStruct((tp, d), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_t, d), jnp.float32)],
        name="sampled_ce_bwd_dh",
        interpret=interpret,
    )(*dh_operands)
    dne_in_specs = [
        pl.BlockSpec((block_t, 1), lambda im, it: (it, 0)),
        pl.BlockSpec((block_t, d), lambda im, it: (it, 0)),
        pl.BlockSpec((block_m, d), lambda im, it: (im, 0)),
        pl.BlockSpec((1, block_m), lambda im, it: (0, im)),
        pl.BlockSpec((1, block_m), lambda im, it: (0, im)),
        pl.BlockSpec((block_t, 1), lambda im, it: (it, 0)),
        pl.BlockSpec((block_t, 1), lambda im, it: (it, 0)),
    ]
    dne_operands = [g2, hidden, neg_emb, log_q, neg_ids, pos_ids, lse2]
    if quantized:
        dne_in_specs.append(
            pl.BlockSpec((block_m, 1), lambda im, it: (im, 0)))
        dne_operands.append(ns2)
    dne, dlq = pl.pallas_call(
        functools.partial(_bwd_dne_kernel, num_neg=num_neg,
                          quantized=quantized),
        grid=(mp // block_m, tp // block_t),
        in_specs=dne_in_specs,
        out_specs=[
            pl.BlockSpec((block_m, d), lambda im, it: (im, 0)),
            pl.BlockSpec((1, block_m), lambda im, it: (0, im)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((mp, d), jnp.float32),
            jax.ShapeDtypeStruct((1, mp), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_m, d), jnp.float32),
            pltpu.VMEM((1, block_m), jnp.float32),
        ],
        name="sampled_ce_bwd_dne",
        interpret=interpret,
    )(*dne_operands)
    return dh[:t], dpe[:t], dne[:m], dlq[0, :m]
