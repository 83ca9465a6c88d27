"""Vocab-parallel MIDX head: row-shard the class table + index (DESIGN §9).

The paper's regime is millions-to-billions of classes; a replicated [V, D]
table + index makes one device's HBM the ceiling on V. This module shards
BOTH over a `vocab` mesh axis while keeping training bit-for-bit faithful to
the replicated path (the test_vocab_parallel.py contract):

Layout. Shard p of n owns the contiguous row range [p·rows, (p+1)·rows).
The tiny [K, D'] codebooks are replicated; the CSR cluster state is LOCAL —
shard p's `sorted_ids` hold local row ids of its own classes, with per-shard
`offsets`/`counts`. The global cluster sizes are one integer psum away, so
every piece of proposal math (ψ tables, the Eq.(6) normalizer, the k1/k2
categorical draws) runs on exact global counts and is bitwise identical to
the replicated sampler given the same key.

Member draws. `_csr_from_assignments` sorts with a STABLE argsort, and row
ownership is contiguous, so the global within-cluster order equals the
concatenation of the shard-local orders. A replicated draw r ~ U[0, |Ω(c)|)
therefore lands on exactly one shard, located by the exclusive prefix sum of
per-shard counts (one all_gather of the [K²] int32 counts); that shard
gathers the member locally and a psum broadcasts it — the same id the
replicated `_member_uniform` would return, bit for bit.

Loss. Each shard computes a partial CE over its owned negatives (jnp or the
include_pos=False flash-CE kernels) plus an owner-masked positive logit;
`dist/decode.py`'s LSE-merge trick (pmax shift + psum of shifted exps)
reassembles the loss, ≤1e-5 from the replicated value (pure reassociation).

Gradients. shard_map autodiff is already exact here — no scaling, no extra
collectives: psum transposes to psum, and a replicated (P()) in-spec
transposes to a cross-shard sum of the per-shard cotangents. Each shard's CE
terms yield owner-partial hidden cotangents; the in-spec transpose adds them
up, so grads w.r.t. replicated inputs (hidden, backbone params) come out
complete, while the sharded table's row gradients are intrinsically local
and complete per shard.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.core import midx as midx_mod
from repro.core.index import MultiIndex, _csr_from_assignments
from repro.core.sampled_softmax import (NEG_INF, NEG_INF_THRESHOLD,
                                        partial_sampled_lse)
from repro.index.quantized import (dequant_rows, quantize_rows,
                                   quantized_query_scores,
                                   resolve_table_dtype)
from repro.kernels import dispatch as kd
from repro.kernels.sampled_ce.ops import (sampled_ce_partial_op,
                                          sampled_ce_pt_partial_op,
                                          sampled_ce_pt_q_partial_op,
                                          sampled_ce_q_partial_op)


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=("codebook1", "codebook2", "assign1", "assign2",
                                "sorted_ids", "offsets", "counts",
                                "log_counts"),
                   meta_fields=("kind", "num_shards"))
@dataclasses.dataclass(frozen=True)
class VocabShardedIndex:
    """Stacked per-shard MIDX state. Codebooks replicated (no shard dim);
    CSR leaves carry a leading [n] shard dim — PartitionSpec P("vocab") on
    them (dist.sharding.vocab_index_specs) gives each shard its slice."""
    kind: str                 # 'pq' | 'rq'
    num_shards: int
    codebook1: jax.Array      # [K, D or D/2]        replicated
    codebook2: jax.Array      # [K, D or D/2]        replicated
    assign1: jax.Array        # [n, rows]
    assign2: jax.Array        # [n, rows]
    sorted_ids: jax.Array     # [n, rows] int32      LOCAL row ids
    offsets: jax.Array        # [n, K²+1] int32
    counts: jax.Array         # [n, K, K] int32      Σ_p == global counts
    log_counts: jax.Array     # [n, K, K] float32

    @property
    def num_codewords(self) -> int:
        return self.codebook1.shape[0]

    @property
    def rows_per_shard(self) -> int:
        return self.sorted_ids.shape[-1]

    @property
    def num_classes(self) -> int:
        return self.num_shards * self.rows_per_shard


def shard_index(index: MultiIndex, num_shards: int) -> VocabShardedIndex:
    """Partition a replicated index into the vocab-sharded layout.

    Pure re-layout: shard p keeps the assignments of its contiguous row
    range and rebuilds a local CSR over them. Σ_p counts_p == index.counts
    and concat_p (sorted_ids_p + p·rows) == index.sorted_ids restricted to
    each cluster (stable argsort + contiguous ownership)."""
    n = index.num_classes
    if n % num_shards:
        raise ValueError(f"num_classes {n} must divide num_shards "
                         f"{num_shards}; pad the class table first")
    rows = n // num_shards
    k = index.num_codewords
    a1 = index.assign1.reshape(num_shards, rows)
    a2 = index.assign2.reshape(num_shards, rows)
    sorted_ids, offsets, counts, log_counts = jax.vmap(
        lambda x, y: _csr_from_assignments(x, y, k))(a1, a2)
    return VocabShardedIndex(index.kind, num_shards, index.codebook1,
                             index.codebook2, a1, a2, sorted_ids, offsets,
                             counts, log_counts)


def unshard_index(sharded: VocabShardedIndex) -> MultiIndex:
    """Merge the vocab-sharded layout back into one replicated MultiIndex —
    the exact inverse of `shard_index` (pure re-layout, bit-identical
    assignments/codebooks, global CSR rebuilt from the concatenated
    assignments). This is the serving-export path: `serve.Engine` consumes
    the replicated layout, so a vocab-parallel training run unshards its
    final index before `save_serving_state` (DESIGN §9/§13). Residuals are
    not kept by the sharded layout, so the merged index has none (the
    serving head's proposal+rescore path never reads them)."""
    a1 = sharded.assign1.reshape(-1)
    a2 = sharded.assign2.reshape(-1)
    k = sharded.num_codewords
    sorted_ids, offsets, counts, log_counts = _csr_from_assignments(a1, a2, k)
    d = sharded.codebook1.shape[-1]
    return MultiIndex(sharded.kind, sharded.codebook1, sharded.codebook2,
                      a1, a2, jnp.zeros((0, d), jnp.float32),
                      sorted_ids, offsets, counts, log_counts)


def local_index(sharded: VocabShardedIndex) -> MultiIndex:
    """Inside shard_map: squeeze the [1, ...] shard dim into a local
    MultiIndex view (counts/log_counts are this shard's partial counts)."""
    d = sharded.codebook1.shape[-1]
    return MultiIndex(sharded.kind, sharded.codebook1, sharded.codebook2,
                      sharded.assign1[0], sharded.assign2[0],
                      jnp.zeros((0, d), jnp.float32),
                      sharded.sorted_ids[0], sharded.offsets[0],
                      sharded.counts[0], sharded.log_counts[0])


def proposal_index(local_idx: MultiIndex, axis: str) -> MultiIndex:
    """Local index with GLOBAL cluster counts (integer psum — exact).

    All proposal math (joint_logits, twostage_tables, the categorical
    draws) run on this view bitwise-identically to the replicated index."""
    counts_g = jax.lax.psum(local_idx.counts, axis)
    log_counts_g = jnp.where(
        counts_g > 0,
        jnp.log(jnp.maximum(counts_g, 1).astype(jnp.float32)), -jnp.inf)
    return dataclasses.replace(local_idx, counts=counts_g,
                               log_counts=log_counts_g)


def make_member_fn(local_idx: MultiIndex, counts_global: jax.Array, *,
                   axis: str):
    """Owner-locating member draw, bitwise equal to `_member_uniform` on the
    replicated index: draw r from the GLOBAL count, find the owner via the
    exclusive prefix of per-shard counts, gather locally, psum the id.
    (A zero-probability empty cluster psums to id 0 instead of the
    replicated path's arbitrary resident — unreachable by construction.)"""
    rows = local_idx.sorted_ids.shape[0]
    shard = jax.lax.axis_index(axis)
    counts_loc = local_idx.counts.reshape(-1)                    # [K²]
    counts_all = jax.lax.all_gather(counts_loc, axis)            # [n, K²]
    prefix_here = (jnp.cumsum(counts_all, axis=0) - counts_all)[shard]
    cnt_g = counts_global.reshape(-1)

    def member_fn(key: jax.Array, cluster: jax.Array) -> jax.Array:
        cnt = cnt_g[cluster]
        r = jax.random.randint(key, cluster.shape, 0, jnp.maximum(cnt, 1))
        local_r = r - prefix_here[cluster]
        own = (local_r >= 0) & (local_r < counts_loc[cluster])
        pos = local_idx.offsets[cluster] + jnp.where(own, local_r, 0)
        ids_local = local_idx.sorted_ids[jnp.clip(pos, 0, rows - 1)]
        ids = jnp.where(own, ids_local + shard * rows, 0)
        return jax.lax.psum(ids, axis)

    return member_fn


def embed_lookup(table_local: jax.Array, tokens: jax.Array, *,
                 axis: str) -> jax.Array:
    """Vocab-parallel embedding gather: owner-masked local gather + psum
    (Megatron's vocab-parallel embedding). Exactly equals the replicated
    `table[tokens]` — non-owners contribute zeros. Autodiff is exact: the
    psum transposes to psum, handing each shard the complete output
    cotangent, which the owner mask restricts to its rows."""
    rows = table_local.shape[0]
    shard = jax.lax.axis_index(axis)
    loc = tokens - shard * rows
    ok = (loc >= 0) & (loc < rows)
    e = table_local[jnp.clip(loc, 0, rows - 1)]
    e = jnp.where(ok[..., None], e, jnp.zeros_like(e))
    return jax.lax.psum(e, axis)


def _merge_loss(pos_logit: jax.Array, partial: jax.Array,
                axis: str) -> jax.Array:
    """Cross-shard LSE merge (dist/decode.py trick): loss [...] from the
    replicated positive logit and this shard's partial lse. The shift is
    stop_gradient'd, so partial/pos cotangents are the exact softmax
    weights of the merged distribution."""
    shift = jnp.maximum(jax.lax.pmax(jax.lax.stop_gradient(partial), axis),
                        jax.lax.stop_gradient(pos_logit))
    term = jnp.where(partial > NEG_INF_THRESHOLD,
                     jnp.exp(partial - shift), 0.0)
    total = jax.lax.psum(term, axis) + jnp.exp(pos_logit - shift)
    return jnp.log(jnp.maximum(total, 1e-30)) + shift - pos_logit


# ---------------------------------------------------------------------------
# the vocab-parallel MIDX loss (mirrors models/heads.loss_midx)
# ---------------------------------------------------------------------------

def loss_midx_vp(cfg, table_local: jax.Array, local_idx: MultiIndex,
                 hidden: jax.Array, labels: jax.Array, key: jax.Array,
                 mask=None, *, axis: str, fused=None,
                 interpret: bool = False) -> jax.Array:
    """Per-shard MIDX sampled CE + LSE merge. Call inside shard_map over
    `axis`; hidden [B,S,D] and labels [B,S] replicated over the vocab axis,
    table_local [rows, D] this shard's row slice, local_idx from
    `local_index`. Matches `heads.loss_midx` on the replicated layout to
    ≤1e-5 — loss AND grads, no scaling needed — for all three proposals,
    fused and unfused (shard_map transposes replicated in-specs to a
    cross-shard cotangent sum, so autodiff through the psums is exact).

    cfg.head.table_dtype int8/fp8 turns on the quantized shard path
    (DESIGN §12): the shard quantizes its OWN row slice in-step (per-row
    scales are row-local, so the [rows,1] scale vector shards with the
    table for free), proposal scoring quantizes the replicated codebooks
    the same way the replicated QuantHeadState does (bitwise-equal draws),
    and the partial CE runs the quantized kernels / `dequant_rows` with
    STE gradients landing on the master `table_local`."""
    m = cfg.head.num_negatives
    rows = table_local.shape[0]
    shard = jax.lax.axis_index(axis)
    h32 = hidden.astype(jnp.float32)
    b, s, d = h32.shape
    use_fused = kd.fused_head_active(cfg.head, fused=fused,
                                    interpret=interpret)
    fmt = resolve_table_dtype(getattr(cfg.head, "table_dtype", "bf16"))
    quantized = fmt != "bf16"
    if quantized:
        qd, qsc = quantize_rows(jax.lax.stop_gradient(
            table_local.astype(jnp.float32)), fmt)               # [rows,·]
        qcb1, scb1 = quantize_rows(local_idx.codebook1, fmt)
        qcb2, scb2 = quantize_rows(local_idx.codebook2, fmt)
    prop = proposal_index(local_idx, axis)
    member = make_member_fn(local_idx, prop.counts, axis=axis)

    # owner-masked positive logit, replicated by the psum
    lpos = labels - shard * rows
    okp = (lpos >= 0) & (lpos < rows)
    lpos_c = jnp.where(okp, lpos, 0)
    pid_local = jnp.where(okp, lpos_c, -1)
    if quantized:
        pos_e = dequant_rows(table_local, qd, qsc, lpos_c)       # [B,S,D]
    else:
        pos_e = table_local[lpos_c].astype(jnp.float32)          # [B,S,D]
    pos_logit = jax.lax.psum(
        jnp.where(okp, jnp.sum(h32 * pos_e, axis=-1), 0.0), axis)

    proposal = cfg.head.proposal
    if proposal == "per_token":
        if quantized:
            tables_fn = kd.midx_tables_fn_q(qcb1, scb1, qcb2, scb2,
                                            use_kernel=use_fused,
                                            interpret=interpret)
        else:
            tables_fn = (kd.midx_tables_fn(use_kernel=True,
                                           interpret=interpret)
                         if use_fused else None)
        draw = midx_mod.sample_twostage(prop, key, h32, m,
                                        tables_fn=tables_fn,
                                        member_fn=member)        # [B,S,M]
        lneg = draw.ids - shard * rows
        okn = (lneg >= 0) & (lneg < rows)
        lneg_c = jnp.where(okn, lneg, 0)
        if use_fused:
            lq_m = jnp.where(okn, draw.log_q, -NEG_INF)
            if quantized:
                partial = sampled_ce_pt_q_partial_op(
                    h32.reshape(b * s, d), table_local, qd, qsc,
                    lq_m.reshape(b * s, m), lneg_c.reshape(b * s, m),
                    pid_local.reshape(b * s), m, interpret).reshape(b, s)
            else:
                partial = sampled_ce_pt_partial_op(
                    h32.reshape(b * s, d), table_local,
                    lq_m.reshape(b * s, m), lneg_c.reshape(b * s, m),
                    pid_local.reshape(b * s), m, interpret).reshape(b, s)
        else:
            if quantized:
                neg_e = dequant_rows(table_local, qd, qsc, lneg_c)
            else:
                neg_e = table_local[lneg_c].astype(jnp.float32)  # [B,S,M,D]
            neg_logits = jnp.einsum("bsd,bsmd->bsm", h32, neg_e)
            partial = partial_sampled_lse(
                neg_logits, draw.log_q, m, draw.ids, labels,
                cfg.head.mask_collisions, valid=okn)
    else:
        sampler = (midx_mod.sample_pooled if proposal == "pooled"
                   else midx_mod.sample_mixture)
        scores_fn = None
        if quantized:
            scores_fn = (lambda idx, z: quantized_query_scores(
                idx.kind, qcb1, scb1, qcb2, scb2, z))
        draw = sampler(prop, key, h32, m, member_fn=member,
                       scores_fn=scores_fn)                      # [B,M]
        lneg = draw.ids - shard * rows
        okn = (lneg >= 0) & (lneg < rows)
        lneg_c = jnp.where(okn, lneg, 0)
        if use_fused:
            neg_emb = table_local[lneg_c]                        # [B,M,D]
            lq_m = jnp.where(okn, draw.log_q, -NEG_INF)
            if quantized:
                zero_pq = jnp.zeros((s, d), qd.dtype)
                one_ps = jnp.ones((s, 1), jnp.float32)
                partial = jax.vmap(
                    lambda hb, ne, nq, ns, lq, ni, pi:
                    sampled_ce_q_partial_op(
                        hb, jnp.zeros_like(hb), ne, zero_pq, one_ps,
                        nq, ns, lq, ni, pi, m, interpret)
                )(h32, neg_emb, qd[lneg_c], qsc[lneg_c],
                  lq_m, lneg_c, pid_local)                       # [B,S]
            else:
                partial = jax.vmap(
                    lambda hb, ne, lq, ni, pi:
                    sampled_ce_partial_op(hb, jnp.zeros_like(hb), ne, lq,
                                          ni, pi, m, interpret)
                )(h32, neg_emb, lq_m, lneg_c, pid_local)         # [B,S]
        else:
            if quantized:
                neg_e = dequant_rows(table_local, qd, qsc, lneg_c)
            else:
                neg_e = table_local[lneg_c].astype(jnp.float32)  # [B,M,D]
            neg_logits = jnp.einsum("bsd,bmd->bsm", h32, neg_e)
            partial = partial_sampled_lse(
                neg_logits, draw.log_q[:, None, :], m,
                draw.ids[:, None, :], labels, cfg.head.mask_collisions,
                valid=okn[:, None, :])

    loss = _merge_loss(pos_logit, partial, axis)
    if mask is not None:
        return jnp.sum(loss * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(loss)


def sample_twostage_vp(local_idx: MultiIndex, key: jax.Array, z: jax.Array,
                       m: int, *, axis: str, tables_fn=None) -> midx_mod.Draw:
    """Vocab-parallel two-stage sampler: identical draws (ids AND log_q) to
    `midx.sample_twostage` on the replicated index, given the same key."""
    prop = proposal_index(local_idx, axis)
    member = make_member_fn(local_idx, prop.counts, axis=axis)
    return midx_mod.sample_twostage(prop, key, z, m, tables_fn=tables_fn,
                                    member_fn=member)
