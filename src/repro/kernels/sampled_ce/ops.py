"""Jit'd wrappers with autodiff for the fused sampled-softmax CE kernels.

sampled_ce_op (shared negatives):
  Forward: Pallas flash-CE (no [T, M] logits in HBM).
  Backward: fused Pallas backward (sampled_ce.sampled_ce_bwd) — softmax
  weights rebuilt block-wise from the saved lse; dh/dpe and dne/dlq each
  accumulate in VMEM, [T, M] never reaches HBM in either direction.

sampled_ce_pt_op (per-token negatives):
  Forward: Pallas per-token flash-CE — the class table stays in its native
  dtype, the [T, M, D] gather and [T, M] logits never exist in HBM.
  Backward: the fused Pallas backward (per_token.sampled_ce_pt_bwd) — dh,
  dlq, and the d-table scatter all happen in-kernel from the saved lse.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.sampled_ce.per_token import (sampled_ce_pt,
                                                sampled_ce_pt_bwd)
from repro.kernels.sampled_ce.sampled_ce import sampled_ce, sampled_ce_bwd


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def sampled_ce_op(hidden, pos_emb, neg_emb, log_q, neg_ids, pos_ids,
                  interpret: bool = False):
    loss, _ = sampled_ce(hidden, pos_emb, neg_emb, log_q, neg_ids, pos_ids,
                         interpret=interpret)
    return loss


def _fwd(hidden, pos_emb, neg_emb, log_q, neg_ids, pos_ids, interpret):
    loss, lse = sampled_ce(hidden, pos_emb, neg_emb, log_q, neg_ids, pos_ids,
                           interpret=interpret)
    return loss, (hidden, pos_emb, neg_emb, log_q, neg_ids, pos_ids, lse)


def _bwd(interpret, res, g):
    hidden, pos_emb, neg_emb, log_q, neg_ids, pos_ids, lse = res
    dh, dpe, dne, dlq = sampled_ce_bwd(g, hidden, pos_emb, neg_emb, log_q,
                                       neg_ids, pos_ids, lse,
                                       interpret=interpret)
    return (dh.astype(hidden.dtype), dpe.astype(pos_emb.dtype),
            dne.astype(neg_emb.dtype), dlq.astype(log_q.dtype), None, None)


sampled_ce_op.defvjp(_fwd, _bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def sampled_ce_pt_op(hidden, table, log_q, neg_ids, pos_ids,
                     interpret: bool = False, block_t: int = 128,
                     chunk: int = 128):
    """Per-token fused CE. hidden [T,D]; table [V,D] native dtype;
    log_q/neg_ids [T,M]; pos_ids [T] -> loss [T] fp32."""
    loss, _ = sampled_ce_pt(hidden, table, log_q, neg_ids, pos_ids,
                            block_t=block_t, chunk=chunk, interpret=interpret)
    return loss


def _pt_fwd(hidden, table, log_q, neg_ids, pos_ids, interpret, block_t,
            chunk):
    loss, lse = sampled_ce_pt(hidden, table, log_q, neg_ids, pos_ids,
                              block_t=block_t, chunk=chunk,
                              interpret=interpret)
    return loss, (hidden, table, log_q, neg_ids, pos_ids, lse)


def _pt_bwd(interpret, block_t, chunk, res, g):
    hidden, table, log_q, neg_ids, pos_ids, lse = res
    dh, dtab, dlq = sampled_ce_pt_bwd(g, hidden, table, log_q, neg_ids,
                                      pos_ids, lse, block_t=block_t,
                                      chunk=chunk, interpret=interpret)
    return (dh.astype(hidden.dtype), dtab.astype(table.dtype), dlq,
            None, None)


sampled_ce_pt_op.defvjp(_pt_fwd, _pt_bwd)


# ---------------------------------------------------------------------------
# partial (include_pos=False) variants for the vocab-parallel head: each op
# returns this shard's negatives-only partial lse [T]. The saved residual is
# the PARTIAL lse, so the in-kernel softmax weights are exp(corr − partial);
# the upstream LSE merge (core.sampled_softmax.merge_sampled_softmax_loss)
# supplies a cotangent carrying exp(partial − lse_global), and the chain rule
# composes the two into the exact global weights. num_neg is the GLOBAL M.
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def sampled_ce_partial_op(hidden, pos_emb, neg_emb, log_q, neg_ids, pos_ids,
                          num_neg: int, interpret: bool = False):
    """Shared-negative partial lse. Shapes as sampled_ce_op -> lse [T] fp32.
    pos_emb/pos_ids only collision-mask (pass zeros / local-or--1 ids)."""
    _, lse = sampled_ce(hidden, pos_emb, neg_emb, log_q, neg_ids, pos_ids,
                        interpret=interpret, include_pos=False,
                        num_neg=num_neg)
    return lse


def _partial_fwd(hidden, pos_emb, neg_emb, log_q, neg_ids, pos_ids, num_neg,
                 interpret):
    lse = sampled_ce_partial_op(hidden, pos_emb, neg_emb, log_q, neg_ids,
                                pos_ids, num_neg, interpret)
    return lse, (hidden, pos_emb, neg_emb, log_q, neg_ids, pos_ids, lse)


def _partial_bwd(num_neg, interpret, res, g):
    hidden, pos_emb, neg_emb, log_q, neg_ids, pos_ids, lse = res
    dh, dpe, dne, dlq = sampled_ce_bwd(g, hidden, pos_emb, neg_emb, log_q,
                                       neg_ids, pos_ids, lse,
                                       interpret=interpret, include_pos=False,
                                       num_neg=num_neg)
    return (dh.astype(hidden.dtype), dpe.astype(pos_emb.dtype),
            dne.astype(neg_emb.dtype), dlq.astype(log_q.dtype), None, None)


sampled_ce_partial_op.defvjp(_partial_fwd, _partial_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def sampled_ce_pt_partial_op(hidden, table, log_q, neg_ids, pos_ids,
                             num_neg: int, interpret: bool = False,
                             block_t: int = 128, chunk: int = 128):
    """Per-token partial lse. table is this shard's row slice; neg_ids are
    LOCAL rows (non-owned clipped + log_q=-NEG_INF); pos_ids local-or--1.
    -> partial lse [T] fp32."""
    _, lse = sampled_ce_pt(hidden, table, log_q, neg_ids, pos_ids,
                           block_t=block_t, chunk=chunk, interpret=interpret,
                           include_pos=False, num_neg=num_neg)
    return lse


def _pt_partial_fwd(hidden, table, log_q, neg_ids, pos_ids, num_neg,
                    interpret, block_t, chunk):
    lse = sampled_ce_pt_partial_op(hidden, table, log_q, neg_ids, pos_ids,
                                   num_neg, interpret, block_t, chunk)
    return lse, (hidden, table, log_q, neg_ids, pos_ids, lse)


def _pt_partial_bwd(num_neg, interpret, block_t, chunk, res, g):
    hidden, table, log_q, neg_ids, pos_ids, lse = res
    dh, dtab, dlq = sampled_ce_pt_bwd(g, hidden, table, log_q, neg_ids,
                                      pos_ids, lse, block_t=block_t,
                                      chunk=chunk, interpret=interpret,
                                      include_pos=False, num_neg=num_neg)
    return (dh.astype(hidden.dtype), dtab.astype(table.dtype), dlq,
            None, None)


sampled_ce_pt_partial_op.defvjp(_pt_partial_fwd, _pt_partial_bwd)


# ---------------------------------------------------------------------------
# quantized (low-bit table) variants, DESIGN §12. Each op takes BOTH the
# master-precision rows/table (the differentiable leaf the optimizer updates)
# and the low-bit copy + per-row scales the kernel actually reads. The master
# operand is DEAD in the forward — XLA DCEs its HBM read — and the backward
# returns the straight-through cotangent onto it: the kernels' scale-unaware
# row-scatters are exactly d(loss)/d(master row) evaluated at the dequantized
# point, so training keeps full-precision updates while the hot path streams
# 1-byte rows.
# ---------------------------------------------------------------------------

def _dead(x):
    """Residual standing in for a dead primal: a one-row slice that keeps
    only the dtype (the bwd rules read `.dtype`, never the values). A real,
    non-empty array rather than an aval so the residual stays a valid JAX
    type when custom_vjp runs under shard_map / pjit (a zero-size residual
    loses its sharding there)."""
    return jax.lax.slice_in_dim(x, 0, 1, axis=0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def sampled_ce_pt_q_op(hidden, table, qdata, qscale, log_q, neg_ids, pos_ids,
                       interpret: bool = False, block_t: int = 128,
                       chunk: int = 128):
    """Per-token fused CE over the quantized table. table [V,D] master
    (dead primal); qdata [V,D] int8/fp8; qscale [V,1] fp32 -> loss [T]."""
    del table  # dead in the forward: the kernel reads qdata + qscale
    loss, _ = sampled_ce_pt(hidden, qdata, log_q, neg_ids, pos_ids,
                            scale=qscale, block_t=block_t, chunk=chunk,
                            interpret=interpret)
    return loss


def _pt_q_fwd(hidden, table, qdata, qscale, log_q, neg_ids, pos_ids,
              interpret, block_t, chunk):
    loss, lse = sampled_ce_pt(hidden, qdata, log_q, neg_ids, pos_ids,
                              scale=qscale, block_t=block_t, chunk=chunk,
                              interpret=interpret)
    return loss, (hidden, _dead(table), qdata, qscale, log_q, neg_ids,
                  pos_ids, lse)


def _pt_q_bwd(interpret, block_t, chunk, res, g):
    hidden, tab_aval, qdata, qscale, log_q, neg_ids, pos_ids, lse = res
    dh, dtab, dlq = sampled_ce_pt_bwd(g, hidden, qdata, log_q, neg_ids,
                                      pos_ids, lse, scale=qscale,
                                      block_t=block_t, chunk=chunk,
                                      interpret=interpret)
    return (dh.astype(hidden.dtype), dtab.astype(tab_aval.dtype), None, None,
            dlq, None, None)


sampled_ce_pt_q_op.defvjp(_pt_q_fwd, _pt_q_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10))
def sampled_ce_pt_q_partial_op(hidden, table, qdata, qscale, log_q, neg_ids,
                               pos_ids, num_neg: int, interpret: bool = False,
                               block_t: int = 128, chunk: int = 128):
    """Quantized per-token partial lse (vocab-parallel shard): qdata/qscale
    are this shard's row slices; semantics as sampled_ce_pt_partial_op."""
    del table
    _, lse = sampled_ce_pt(hidden, qdata, log_q, neg_ids, pos_ids,
                           scale=qscale, block_t=block_t, chunk=chunk,
                           interpret=interpret, include_pos=False,
                           num_neg=num_neg)
    return lse


def _pt_q_partial_fwd(hidden, table, qdata, qscale, log_q, neg_ids, pos_ids,
                      num_neg, interpret, block_t, chunk):
    lse = sampled_ce_pt_q_partial_op(hidden, table, qdata, qscale, log_q,
                                     neg_ids, pos_ids, num_neg, interpret,
                                     block_t, chunk)
    return lse, (hidden, _dead(table), qdata, qscale, log_q, neg_ids,
                 pos_ids, lse)


def _pt_q_partial_bwd(num_neg, interpret, block_t, chunk, res, g):
    hidden, tab_aval, qdata, qscale, log_q, neg_ids, pos_ids, lse = res
    dh, dtab, dlq = sampled_ce_pt_bwd(g, hidden, qdata, log_q, neg_ids,
                                      pos_ids, lse, scale=qscale,
                                      block_t=block_t, chunk=chunk,
                                      interpret=interpret, include_pos=False,
                                      num_neg=num_neg)
    return (dh.astype(hidden.dtype), dtab.astype(tab_aval.dtype), None, None,
            dlq, None, None)


sampled_ce_pt_q_partial_op.defvjp(_pt_q_partial_fwd, _pt_q_partial_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(10,))
def sampled_ce_q_op(hidden, pos_emb, neg_emb, pos_q, pos_scale, neg_q,
                    neg_scale, log_q, neg_ids, pos_ids,
                    interpret: bool = False):
    """Shared-negative fused CE over gathered quantized rows. pos_emb/neg_emb
    are the master-precision gathers (dead primals); pos_q/neg_q the low-bit
    gathers with [T,1]/[M,1] fp32 scales."""
    del pos_emb, neg_emb
    loss, _ = sampled_ce(hidden, pos_q, neg_q, log_q, neg_ids, pos_ids,
                         pos_scale=pos_scale, neg_scale=neg_scale,
                         interpret=interpret)
    return loss


def _q_fwd(hidden, pos_emb, neg_emb, pos_q, pos_scale, neg_q, neg_scale,
           log_q, neg_ids, pos_ids, interpret):
    loss, lse = sampled_ce(hidden, pos_q, neg_q, log_q, neg_ids, pos_ids,
                           pos_scale=pos_scale, neg_scale=neg_scale,
                           interpret=interpret)
    return loss, (hidden, _dead(pos_emb), _dead(neg_emb), pos_q, pos_scale,
                  neg_q, neg_scale, log_q, neg_ids, pos_ids, lse)


def _q_bwd(interpret, res, g):
    (hidden, pe_aval, ne_aval, pos_q, pos_scale, neg_q, neg_scale, log_q,
     neg_ids, pos_ids, lse) = res
    dh, dpe, dne, dlq = sampled_ce_bwd(g, hidden, pos_q, neg_q, log_q,
                                       neg_ids, pos_ids, lse,
                                       pos_scale=pos_scale,
                                       neg_scale=neg_scale,
                                       interpret=interpret)
    return (dh.astype(hidden.dtype), dpe.astype(pe_aval.dtype),
            dne.astype(ne_aval.dtype), None, None, None, None,
            dlq.astype(log_q.dtype), None, None)


sampled_ce_q_op.defvjp(_q_fwd, _q_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(10, 11))
def sampled_ce_q_partial_op(hidden, pos_emb, neg_emb, pos_q, pos_scale,
                            neg_q, neg_scale, log_q, neg_ids, pos_ids,
                            num_neg: int, interpret: bool = False):
    """Quantized shared-negative partial lse (vocab-parallel shard)."""
    del pos_emb, neg_emb
    _, lse = sampled_ce(hidden, pos_q, neg_q, log_q, neg_ids, pos_ids,
                        pos_scale=pos_scale, neg_scale=neg_scale,
                        interpret=interpret, include_pos=False,
                        num_neg=num_neg)
    return lse


def _q_partial_fwd(hidden, pos_emb, neg_emb, pos_q, pos_scale, neg_q,
                   neg_scale, log_q, neg_ids, pos_ids, num_neg, interpret):
    lse = sampled_ce_q_partial_op(hidden, pos_emb, neg_emb, pos_q, pos_scale,
                                  neg_q, neg_scale, log_q, neg_ids, pos_ids,
                                  num_neg, interpret)
    return lse, (hidden, _dead(pos_emb), _dead(neg_emb), pos_q, pos_scale,
                 neg_q, neg_scale, log_q, neg_ids, pos_ids, lse)


def _q_partial_bwd(num_neg, interpret, res, g):
    (hidden, pe_aval, ne_aval, pos_q, pos_scale, neg_q, neg_scale, log_q,
     neg_ids, pos_ids, lse) = res
    dh, dpe, dne, dlq = sampled_ce_bwd(g, hidden, pos_q, neg_q, log_q,
                                       neg_ids, pos_ids, lse,
                                       pos_scale=pos_scale,
                                       neg_scale=neg_scale,
                                       interpret=interpret, include_pos=False,
                                       num_neg=num_neg)
    return (dh.astype(hidden.dtype), dpe.astype(pe_aval.dtype),
            dne.astype(ne_aval.dtype), None, None, None, None,
            dlq.astype(log_q.dtype), None, None)


sampled_ce_q_partial_op.defvjp(_q_partial_fwd, _q_partial_bwd)
