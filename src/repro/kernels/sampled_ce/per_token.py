"""Pallas TPU kernels: per-token fused sampled-softmax CE ("flash-CE pt").

The per-token MIDX proposal draws a *different* negative set per token
(`neg_ids [T, M]`), so the unfused loss materializes a [T, M, D] negative
embedding gather plus the [T, M] corrected-logit matrix in HBM, and casts
the whole [V, D] class table to fp32 first. These kernels keep all of that
on-chip:

Forward — grid (nT,), everything per token block resident in VMEM:
  for each token t:  DMA the positive row and M negative rows straight out
  of the class table (kept in its NATIVE dtype in HBM/ANY, gathered in
  chunks of `chunk` rows), compute chunk logits, apply the ln(M·q)
  correction and collision mask in-register, and fold into an online
  logsumexp. Outputs loss [T] and lse [T] (the backward residual).
  Neither the [T, M, D] gather nor the [T, M] logits ever exist in HBM.

Backward — same gather loop, recompute-style (flash): softmax weights are
  rebuilt from the saved lse, then
    dh  [T, D]  accumulated in VMEM,
    dlq [T, M]  written per chunk,
    dtab [V, D] scatter-accumulated IN-KERNEL via read-modify-write row DMAs
  into a zero-initialized fp32 buffer (input_output_aliased). TPU grids are
  sequential, and each RMW is awaited before the next, so duplicate ids —
  including positive/negative collisions across tokens — accumulate safely.

Memory placement (what the TPU compiler accepts): the row ids that address
the DMAs and the positive ids are read as scalars, so they live in SMEM —
`neg_ids` as one row of `block_t · M` ids per grid step, `pos_ids` as one
row of `block_t` ids. SMEM is small, so `block_t` shrinks as M
grows (`_block_t`). The same ids also arrive as a VMEM block for the
vectorised collision mask. A token's values are read from the refs with
`pl.ds` (never dynamic slices of loaded values), and a chunk's negatives
span the lane axis: `chunk` is M itself or a multiple of 128.

The row gathers are random-access HBM reads — the intrinsic cost of a
gather; the chunked DMA issue (start `chunk` copies on one semaphore, then
wait for each) overlaps latency within a chunk. Collision masking uses the
canonical `core.sampled_softmax.NEG_INF` and the same validity-guard
convention as the shared-negative kernel.

Quantized mode (DESIGN §12): pass `scale` ([V, 1] fp32 per-row scales) and
`table` becomes the low-bit (int8 / fp8) copy. Each row DMA is paired with
a scale-row DMA and the row is dequantized in-register (`q * s`) before the
dot — the HBM read per negative shrinks from 4·D (fp32) / 2·D (bf16) bytes
to D+4. The backward's d-table scatter is scale-UNAWARE by design: under
the straight-through estimator d(loss)/d(master_row) = coeff · h exactly as
in the fp path (the row *values* never enter the row-gradient), so the
scattered buffer is the master-table cotangent and the optimizer keeps
updating full precision.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.sampled_softmax import NEG_INF, NEG_INF_THRESHOLD
from repro.kernels.sampled_ce.sampled_ce import _pad_dim

# ids held in SMEM per grid step (one flattened [block_t, M] row); the
# pipeline double-buffers it, and a v5e core has 1 MiB of SMEM
_SMEM_IDS = 8192
_HIGHEST = jax.lax.Precision.HIGHEST
_LANES = 128


def _block_t(block_t: int, t: int, mp: int) -> int:
    """Token block: the requested size, capped so that a block of ids fits
    the SMEM budget, a multiple of 8 (sublane tile) unless it covers T."""
    cap = max(8, (_SMEM_IDS // mp) // 8 * 8)
    block_t = min(block_t, cap)
    return t if t <= block_t else block_t


def _chunk(chunk: int, m: int) -> int:
    """Negatives gathered per DMA round: all of M when it fits one chunk,
    otherwise `chunk` (a multiple of 128, so chunks start lane-aligned)."""
    return m if m <= chunk else chunk


def _pack(dtype) -> int:
    """Rows of `dtype` that share a 32-bit word of a TPU memory tile."""
    return max(1, 4 // jnp.dtype(dtype).itemsize)


def _grouped(table):
    """[V, D] -> [V/p, p, D] row groups, p = _pack(dtype): the smallest unit
    a TPU DMA may slice out of a tiled table is a group of p rows (one
    32-bit word deep), so a gather fetches the group and the kernel picks
    row `id % p` out of it (`_pick`)."""
    p = _pack(table.dtype)
    table = _pad_dim(table, p)
    return table.reshape(table.shape[0] // p, p, table.shape[1])


def _scale_groups(scale):
    """[V, 1] per-row scales -> [V/128, 1, 128]: one lane-dense group of 128
    scales per DMA (a [V, 1] column cannot be sliced a row at a time)."""
    s = _pad_dim(scale.astype(jnp.float32).reshape(-1), _LANES)
    return s.reshape(-1, 1, _LANES)


def _row_copy(src_ref, idx, dst_ref, j, sem, per: int):
    """The group holding row idx of `src_ref` (`per` rows each) -> dst[j]."""
    src = src_ref.at[idx // per if per > 1 else idx]
    return pltpu.make_async_copy(src, dst_ref.at[j], sem)


def _gather_chunk(tab_ref, ids_smem, off, rows, sem, chunk: int, per: int):
    """rows[j] <- group of table row ids_smem[0, off + j], j < chunk: start
    every copy on one semaphore, then wait once per (equal-sized) copy."""
    def start(j, carry):
        _row_copy(tab_ref, ids_smem[0, off + j], rows, j, sem, per).start()
        return carry

    def wait(j, carry):
        _row_copy(tab_ref, 0, rows, 0, sem, per).wait()
        return carry

    jax.lax.fori_loop(0, chunk, start, 0)
    jax.lax.fori_loop(0, chunk, wait, 0)


def _fetch_row(tab_ref, idx, row, sem, per: int):
    """row[0] <- group of table row idx, awaited."""
    copy = _row_copy(tab_ref, idx, row, 0, sem, per)
    copy.start()
    copy.wait()


def _pick_lane(groups, r):
    """groups [n, 128], r [n, 1] (or scalar) lane -> [n, 1]."""
    lane = jax.lax.broadcasted_iota(jnp.int32, groups.shape, 1)
    return jnp.sum(jnp.where(lane == r, groups, 0.0), axis=-1, keepdims=True)


def _pick(groups, r):
    """groups [n, p, D] fp32, r [n, 1] (or scalar) row-in-group -> [n, D]."""
    e = groups[:, 0, :]
    for k in range(1, groups.shape[1]):
        e = jnp.where(r == k, groups[:, k, :], e)
    return e


def _chunk_rows(rows, srows, nid_c):
    """The gathered chunk as fp32 [chunk, D], dequantized when scaled.
    nid_c [1, chunk] are its ids (their low bits pick the row in group)."""
    p = rows.shape[1]
    groups = rows[...].astype(jnp.float32)
    if p > 1:
        e = _pick(groups, _as_column((nid_c & (p - 1)).astype(jnp.float32)))
    else:
        e = groups[:, 0, :]
    if srows is not None:                                # per-row dequant
        lane = _as_column((nid_c & (_LANES - 1)).astype(jnp.float32))
        e = e * _pick_lane(srows[:, 0, :], lane.astype(jnp.int32))
    return e


def _pos_row(tab_ref, stab_ref, pid, prow, psrow, psem, pssem):
    """The positive row as fp32 [1, D] (dequantized when scaled)."""
    _fetch_row(tab_ref, pid, prow, psem, prow.shape[1])
    pe = _pick(prow[...].astype(jnp.float32), pid & (prow.shape[1] - 1))
    if stab_ref is not None:
        _fetch_row(stab_ref, pid, psrow, pssem, _LANES)
        pe = pe * _pick_lane(psrow[0], pid & (_LANES - 1))
    return pe


def _as_column(x):
    """[1, n] -> [n, 1] (diagonal select + lane reduce: no transpose op)."""
    n = x.shape[-1]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))
    return jnp.sum(jnp.where(eye, x, 0.0), axis=-1, keepdims=True)


def _chunk_logits(h_t, e):
    """[1, D] · [chunk, D]ᵀ -> [1, chunk] at full fp32 precision."""
    return jax.lax.dot_general(h_t, e, (((1,), (1,)), ((), ())),
                               precision=_HIGHEST,
                               preferred_element_type=jnp.float32)


def _corrected(logits, lq_c, nid_c, pid, num_neg: int):
    corr = logits - (jnp.log(float(num_neg)) + lq_c)
    return jnp.where(nid_c == pid, NEG_INF, corr)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(h_ref, lq_ref, nid_ref, nid_smem, pid_smem, tab_ref, *rest,
                num_neg: int, chunk: int, include_pos: bool = True,
                quantized: bool = False):
    if quantized:
        (stab_ref, loss_ref, lse_ref, rows, prow, srows, psrow,
         sem, psem, ssem, pssem) = rest
    else:
        loss_ref, lse_ref, rows, prow, sem, psem = rest
        stab_ref = srows = psrow = ssem = pssem = None
    block_t, mp = lq_ref.shape

    def token(t, carry):
        pid = pid_smem[0, t]
        h_t = h_ref[pl.ds(t, 1), :].astype(jnp.float32)  # [1, D]
        if include_pos:
            pe = _pos_row(tab_ref, stab_ref, pid, prow, psrow, psem, pssem)
            pos_logit = jnp.sum(h_t * pe, axis=-1, keepdims=True)   # [1, 1]

        lq_t = lq_ref[pl.ds(t, 1), :]                    # [1, Mp]
        nid_t = nid_ref[pl.ds(t, 1), :]
        m_f = jnp.full((1, 1), NEG_INF, jnp.float32)
        l_f = jnp.zeros((1, 1), jnp.float32)
        for base in range(0, mp, chunk):    # static: lane slices stay aligned
            _gather_chunk(tab_ref, nid_smem, t * mp + base, rows, sem, chunk,
                          rows.shape[1])
            if quantized:
                _gather_chunk(stab_ref, nid_smem, t * mp + base, srows, ssem,
                              chunk, _LANES)
            nid_c = nid_t[:, base:base + chunk]
            logits = _chunk_logits(h_t, _chunk_rows(rows, srows, nid_c))
            corr = _corrected(logits, lq_t[:, base:base + chunk], nid_c, pid,
                              num_neg)
            valid = corr > NEG_INF_THRESHOLD
            m_new = jnp.maximum(m_f, jnp.max(corr, axis=-1, keepdims=True))
            contrib = jnp.where(valid, jnp.exp(corr - m_new), 0.0)
            l_f = (l_f * jnp.exp(m_f - m_new)
                   + jnp.sum(contrib, axis=-1, keepdims=True))
            m_f = m_new
        if include_pos:
            m_fin = jnp.maximum(m_f, pos_logit)
            l_fin = l_f * jnp.exp(m_f - m_fin) + jnp.exp(pos_logit - m_fin)
            lse = jnp.log(jnp.maximum(l_fin, 1e-30)) + m_fin
            loss_ref[pl.ds(t, 1), :] = lse - pos_logit
        else:
            # partial mode: negatives-only lse (pid only collision-masks;
            # its row is never DMA'd, so pid == -1 off-owner is safe).
            lse = jnp.log(jnp.maximum(l_f, 1e-30)) + m_f
            loss_ref[pl.ds(t, 1), :] = lse
        lse_ref[pl.ds(t, 1), :] = lse
        return carry

    jax.lax.fori_loop(0, block_t, token, 0)


def _prepared(hidden, log_q, neg_ids, pos_ids, block_t, chunk):
    """Pad to the (block_t, chunk) grid; padded negatives are invalidated
    via log_q, padded token rows are sliced off by the callers."""
    hidden = _pad_dim(hidden.astype(jnp.float32), block_t)
    pos_ids = _pad_dim(pos_ids.astype(jnp.int32), block_t)
    log_q = _pad_dim(log_q.astype(jnp.float32), block_t)
    log_q = _pad_dim(log_q, chunk, axis=1, fill=-NEG_INF)
    neg_ids = _pad_dim(_pad_dim(neg_ids.astype(jnp.int32), block_t), chunk,
                       axis=1)
    return hidden, log_q, neg_ids, pos_ids


def _id_specs(block_t: int, mp: int):
    """VMEM [block_t, Mp] log_q and ids; SMEM rows of the block's ids and
    positive ids (operands reshaped by `_smem_ids`)."""
    smem = pltpu.MemorySpace.SMEM
    return [
        pl.BlockSpec((block_t, mp), lambda i: (i, 0)),
        pl.BlockSpec((block_t, mp), lambda i: (i, 0)),
        pl.BlockSpec((pl.Squeezed(), 1, block_t * mp), lambda i: (i, 0, 0),
                     memory_space=smem),
        pl.BlockSpec((pl.Squeezed(), 1, block_t), lambda i: (i, 0, 0),
                     memory_space=smem),
    ]


def _smem_ids(neg_ids, pos_ids, block_t: int):
    """One (1, n) row per token block — [nT, 1, block_t·Mp] ids and
    [nT, 1, block_t] positive ids — so each block spans its array's last
    two dims, as the TPU lowering requires of a block."""
    nt = neg_ids.shape[0] // block_t
    return neg_ids.reshape(nt, 1, -1), pos_ids.reshape(nt, 1, block_t)


@functools.partial(jax.jit, static_argnames=("block_t", "chunk", "interpret",
                                             "include_pos", "num_neg"))
def sampled_ce_pt(hidden: jax.Array, table: jax.Array, log_q: jax.Array,
                  neg_ids: jax.Array, pos_ids: jax.Array, *,
                  scale: jax.Array | None = None,
                  block_t: int = 128, chunk: int = 128,
                  interpret: bool = False, include_pos: bool = True,
                  num_neg: int | None = None) -> tuple[jax.Array, jax.Array]:
    """hidden [T,D] fp32; table [V,D] native dtype; log_q/neg_ids [T,M];
    pos_ids [T] -> (loss [T], lse [T]) fp32. Arbitrary T and M (padded).

    include_pos=False: partial mode for the vocab-parallel head. `table` is
    this shard's row slice, neg_ids are LOCAL row indices (non-owned entries
    clipped in-range and invalidated via log_q = -NEG_INF), pos_ids is the
    local positive row on the owner shard and -1 elsewhere, and `num_neg`
    gives the GLOBAL negative count for the ln(M·q) correction. Both outputs
    are the negatives-only partial lse.

    scale != None: quantized mode — `table` is the low-bit copy and `scale`
    [V, 1] fp32 holds per-row scales; rows dequantize in-register."""
    t, d = hidden.shape
    # lane-dense rows: pad D to the 128-lane tile (zero columns add 0)
    hidden = _pad_dim(hidden, _LANES, axis=1)
    table = _pad_dim(table, _LANES, axis=1)
    dp = hidden.shape[1]
    m = neg_ids.shape[-1]
    chunk = _chunk(chunk, m)
    block_t = _block_t(block_t, t, pl.cdiv(m, chunk) * chunk)
    quantized = scale is not None
    hidden, log_q, neg_ids, pos_ids = _prepared(hidden, log_q, neg_ids,
                                                pos_ids, block_t, chunk)
    tp, mp = hidden.shape[0], log_q.shape[1]
    kernel = functools.partial(_fwd_kernel, num_neg=num_neg or m, chunk=chunk,
                               include_pos=include_pos, quantized=quantized)
    in_specs = [pl.BlockSpec((block_t, dp), lambda i: (i, 0)),
                *_id_specs(block_t, mp),
                pl.BlockSpec(memory_space=pl.ANY)]
    operands = [hidden, log_q, neg_ids, *_smem_ids(neg_ids, pos_ids, block_t),
                _grouped(table)]
    scratch = [
        pltpu.VMEM((chunk, _pack(table.dtype), dp), table.dtype),
        pltpu.VMEM((1, _pack(table.dtype), dp), table.dtype),
    ]
    if quantized:
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        operands.append(_scale_groups(scale))
        scratch += [pltpu.VMEM((chunk, 1, _LANES), jnp.float32),
                    pltpu.VMEM((1, 1, _LANES), jnp.float32)]
    scratch += [pltpu.SemaphoreType.DMA, pltpu.SemaphoreType.DMA]
    if quantized:
        scratch += [pltpu.SemaphoreType.DMA, pltpu.SemaphoreType.DMA]
    loss, lse = pl.pallas_call(
        kernel,
        grid=(tp // block_t,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((block_t, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_t, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((tp, 1), jnp.float32),
            jax.ShapeDtypeStruct((tp, 1), jnp.float32),
        ],
        scratch_shapes=scratch,
        name="sampled_ce_pt",
        interpret=interpret,
    )(*operands)
    return loss[:t, 0], lse[:t, 0]


# ---------------------------------------------------------------------------
# fused backward: dh, dlq, and the d-table scatter, all in-kernel
# ---------------------------------------------------------------------------

def _bwd_kernel(g_ref, h_ref, lq_ref, nid_ref, nid_smem, pid_smem, lse_ref,
                tab_ref, *rest, num_neg: int, chunk: int,
                include_pos: bool = True, quantized: bool = False):
    if quantized:
        (stab_ref, dtab_in_ref, dh_ref, dlq_ref, dtab_ref,
         rows, prow, arow, drows, srows, psrow,
         sem, psem, asem, ssem, pssem) = rest
    else:
        (dtab_in_ref, dh_ref, dlq_ref, dtab_ref,
         rows, prow, arow, drows, sem, psem, asem) = rest
        stab_ref = srows = psrow = ssem = pssem = None
    del dtab_in_ref  # aliased with dtab_ref; zeros provided by the wrapper
    block_t, mp = lq_ref.shape

    def rmw_row(idx, delta):
        """dtab[idx] += delta ([1, D]), awaited read-modify-write."""
        _fetch_row(dtab_ref, idx, arow, asem, 1)
        arow[0] = arow[0] + delta
        copy = _row_copy(arow, 0, dtab_ref, idx, asem, 1)
        copy.start()
        copy.wait()

    def token(t, carry):
        g = g_ref[pl.ds(t, 1), :]                        # [1, 1]
        lse = lse_ref[pl.ds(t, 1), :]
        pid = pid_smem[0, t]
        h_t = h_ref[pl.ds(t, 1), :].astype(jnp.float32)  # [1, D]
        if include_pos:
            pe = _pos_row(tab_ref, stab_ref, pid, prow, psrow, psem, pssem)
            pos_logit = jnp.sum(h_t * pe, axis=-1, keepdims=True)
            coeff_pos = g * (jnp.exp(pos_logit - lse) - 1.0)  # dloss/dpos · g
            # scale-unaware scatter: coeff·h IS d(master row) under the STE
            rmw_row(pid, coeff_pos * h_t)
            dh_init = coeff_pos * pe
        else:
            # partial mode: no pos terms; pid (-1 off-owner) is never used
            # as a row index. lse here is the PARTIAL lse residual.
            dh_init = jnp.zeros_like(h_t)

        lq_t = lq_ref[pl.ds(t, 1), :]                    # [1, Mp]
        nid_t = nid_ref[pl.ds(t, 1), :]
        dh_t, dlq_t = dh_init, []
        for base in range(0, mp, chunk):    # static: lane slices stay aligned
            off = t * mp + base
            _gather_chunk(tab_ref, nid_smem, off, rows, sem, chunk,
                          rows.shape[1])
            if quantized:
                _gather_chunk(stab_ref, nid_smem, off, srows, ssem, chunk,
                              _LANES)
            nid_c = nid_t[:, base:base + chunk]
            e = _chunk_rows(rows, srows, nid_c)          # [chunk, D]
            corr = _corrected(_chunk_logits(h_t, e),
                              lq_t[:, base:base + chunk], nid_c, pid, num_neg)
            gw = g * jnp.where(corr > NEG_INF_THRESHOLD,
                               jnp.exp(corr - lse), 0.0)  # g · softmax wts
            dlq_t.append(-gw)
            drows[...] = _as_column(gw) * h_t            # [chunk, D] deltas

            def scatter(j, carry, off=off):
                rmw_row(nid_smem[0, off + j], drows[pl.ds(j, 1), :])
                return carry

            jax.lax.fori_loop(0, chunk, scatter, 0)
            dh_t = dh_t + jax.lax.dot_general(
                gw, e, (((1,), (0,)), ((), ())), precision=_HIGHEST,
                preferred_element_type=jnp.float32)
        dh_ref[pl.ds(t, 1), :] = dh_t
        # one full-width row store: Mosaic refuses a dynamic-row store of a
        # lane sub-range
        dlq_ref[pl.ds(t, 1), :] = jnp.concatenate(dlq_t, axis=-1)
        return carry

    jax.lax.fori_loop(0, block_t, token, 0)


@functools.partial(jax.jit, static_argnames=("block_t", "chunk", "interpret",
                                             "include_pos", "num_neg"))
def sampled_ce_pt_bwd(g: jax.Array, hidden: jax.Array, table: jax.Array,
                      log_q: jax.Array, neg_ids: jax.Array,
                      pos_ids: jax.Array, lse: jax.Array, *,
                      scale: jax.Array | None = None,
                      block_t: int = 128, chunk: int = 128,
                      interpret: bool = False, include_pos: bool = True,
                      num_neg: int | None = None):
    """Fused backward. g/lse [T]; others as sampled_ce_pt.
    -> (dh [T,D] fp32, dtab [V,D] fp32, dlq [T,M] fp32).
    include_pos=False: lse is the PARTIAL lse; no pos scatter or dh init.
    scale != None: quantized mode — rows dequantize in-register for dh and
    the softmax-weight recompute, while the dtab scatter stays scale-unaware
    (it is the straight-through master-table cotangent)."""
    t, d = hidden.shape
    # lane-dense rows: pad D to the 128-lane tile (zero columns add 0)
    hidden = _pad_dim(hidden, _LANES, axis=1)
    table = _pad_dim(table, _LANES, axis=1)
    dp = hidden.shape[1]
    v = table.shape[0]
    m = neg_ids.shape[-1]
    chunk = _chunk(chunk, m)
    block_t = _block_t(block_t, t, pl.cdiv(m, chunk) * chunk)
    quantized = scale is not None
    hidden, log_q, neg_ids, pos_ids = _prepared(hidden, log_q, neg_ids,
                                                pos_ids, block_t, chunk)
    g2 = _pad_dim(g.astype(jnp.float32)[:, None], block_t)  # pad g with 0 —
    lse2 = _pad_dim(lse[:, None], block_t)                  # rows contribute 0
    tp, mp = hidden.shape[0], log_q.shape[1]
    kernel = functools.partial(_bwd_kernel, num_neg=num_neg or m, chunk=chunk,
                               include_pos=include_pos, quantized=quantized)
    in_specs = [pl.BlockSpec((block_t, 1), lambda i: (i, 0)),
                pl.BlockSpec((block_t, dp), lambda i: (i, 0)),
                *_id_specs(block_t, mp),
                pl.BlockSpec((block_t, 1), lambda i: (i, 0)),
                pl.BlockSpec(memory_space=pl.ANY)]
    operands = [g2, hidden, log_q, neg_ids,
                *_smem_ids(neg_ids, pos_ids, block_t), lse2, _grouped(table)]
    if quantized:
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        operands.append(_scale_groups(scale))
    in_specs.append(pl.BlockSpec(memory_space=pl.ANY))      # dtab_in (alias)
    operands.append(jnp.zeros((v, 1, dp), jnp.float32))
    scratch = [
        pltpu.VMEM((chunk, _pack(table.dtype), dp), table.dtype),
        pltpu.VMEM((1, _pack(table.dtype), dp), table.dtype),
        pltpu.VMEM((1, 1, dp), jnp.float32),
        pltpu.VMEM((chunk, dp), jnp.float32),
    ]
    if quantized:
        scratch += [pltpu.VMEM((chunk, 1, _LANES), jnp.float32),
                    pltpu.VMEM((1, 1, _LANES), jnp.float32)]
    scratch += [pltpu.SemaphoreType.DMA] * 3
    if quantized:
        scratch += [pltpu.SemaphoreType.DMA] * 2
    dh, dlq, dtab = pl.pallas_call(
        kernel,
        grid=(tp // block_t,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((block_t, dp), lambda i: (i, 0)),
            pl.BlockSpec((block_t, mp), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((tp, dp), jnp.float32),
            jax.ShapeDtypeStruct((tp, mp), jnp.float32),
            jax.ShapeDtypeStruct((v, 1, dp), jnp.float32),
        ],
        scratch_shapes=scratch,
        input_output_aliases={len(operands) - 1: 2},
        name="sampled_ce_pt_bwd",
        interpret=interpret,
    )(*operands)
    return dh[:t, :d], dtab[:, 0, :d], dlq[:t, :m]
