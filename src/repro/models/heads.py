"""LM heads: full softmax vs sampled softmax over any proposal (DESIGN §10).

Train-time losses:
  loss_full    : [T,V] logits + CE — the O(V·D) baseline the paper replaces.
  loss_midx    : MIDX-sampled CE — O((M+K²)·D) per token/sequence; the
                 paper's technique and the fused-kernel fast lane.
  loss_sampled : the generic seam — any repro.proposals contender. MIDX-
                 backed proposals short-circuit to loss_midx, so the
                 registry-routed MIDX path is bit-identical to the
                 pre-refactor head (tests/test_proposals.py parity guard).
Also head-state management (index/proposal refresh cadence) and the decode
heads: `midx_decode_head` (the O(K²+M·D) serving hot path) plus its generic
`proposal_decode_head` counterpart.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import index as index_mod
from repro.core import midx as midx_mod
from repro.core.index import MultiIndex
from repro.core.sampled_softmax import (full_softmax_loss,
                                        sampled_softmax_loss)
from repro.index.quantized import (QuantHeadState, code_scores, dequant_rows,
                                   quantize_head_state,
                                   quantized_query_scores,
                                   resolve_table_dtype, unwrap_index)
from repro.kernels import dispatch as kd
from repro.kernels.sampled_ce.ops import (sampled_ce_op, sampled_ce_pt_op,
                                          sampled_ce_pt_q_op, sampled_ce_q_op)
from repro.models.model import class_embeddings, logits_full


def init_head_state(cfg: ModelConfig, params: dict, key: jax.Array):
    """Build the inverted multi-index over the class-embedding table.

    table_dtype='bf16' returns the bare MultiIndex (unchanged seed path);
    'int8'/'fp8' wraps it in a QuantHeadState carrying the low-bit table,
    quantized codebooks and residual PQ codes (DESIGN §12)."""
    fmt = resolve_table_dtype(cfg.head.table_dtype)
    table = class_embeddings(cfg, params).astype(jnp.float32)
    index = index_mod.build(key, table, kind=cfg.head.quantizer,
                            k=cfg.head.midx_k, iters=cfg.head.kmeans_iters,
                            keep_residuals=False)
    if fmt == "bf16":
        return index
    return quantize_head_state(index, table, fmt,
                               key=jax.random.fold_in(key, 1))


def _requantized(cfg: ModelConfig, state: QuantHeadState,
                 new_index: MultiIndex, table: jax.Array,
                 key: jax.Array) -> QuantHeadState:
    """Rebuild the low-bit twins around a refreshed index. With
    quantize_on_refresh=False only the index swaps — the low-bit copies stay
    frozen at their previous values (an approximation knob; the CSR/member
    draw still uses the fresh index)."""
    if not cfg.head.quantize_on_refresh:
        return dataclasses.replace(state, index=new_index)
    rc = state.residual_codes
    return quantize_head_state(new_index, table, state.fmt,
                               key=jax.random.fold_in(key, 1),
                               n_sub=rc.n_sub, ksub=rc.ksub)


def refresh_head_state(cfg: ModelConfig, params: dict, state,
                       key: jax.Array):
    """Full refit against the current class table (warm-started, DESIGN §8).

    Back-compat entry point returning only the head state; the lifecycle
    call sites use `refresh_head_state_with_policy` for drift metrics and
    the reassign-only escalation path."""
    table = class_embeddings(cfg, params).astype(jnp.float32)
    new_index = index_mod.refresh(unwrap_index(state), key, table,
                                  iters=cfg.head.kmeans_iters)
    if isinstance(state, QuantHeadState):
        return _requantized(cfg, state, new_index, table, key)
    return new_index


def refresh_head_state_with_policy(cfg: ModelConfig, params: dict,
                                   state, key: jax.Array,
                                   policy: Optional[str] = None):
    """One refresh event under cfg.head.refresh_policy (or an override).

    Returns (new_state, metrics) where metrics carries reassigned_frac /
    codeword_drift / did_full / distortion — the step-log payload
    (DESIGN §8). Quantized head states re-derive their low-bit twins here,
    riding the same IndexLifecycle double buffer as the index itself."""
    from repro.index import lifecycle as lifecycle_mod
    table = class_embeddings(cfg, params).astype(jnp.float32)
    new_index, metrics = lifecycle_mod.refresh_with_policy(
        unwrap_index(state), key, table, iters=cfg.head.kmeans_iters,
        policy=policy or cfg.head.refresh_policy,
        threshold=cfg.head.refresh_drift_threshold)
    if isinstance(state, QuantHeadState):
        return _requantized(cfg, state, new_index, table, key), metrics
    return new_index, metrics


def loss_full(cfg: ModelConfig, params: dict, hidden: jax.Array,
              labels: jax.Array, mask: Optional[jax.Array] = None) -> jax.Array:
    logits = logits_full(cfg, params, hidden)
    # padded vocab rows never win: they are random-init but labels < V.
    return _masked_mean(full_softmax_loss(logits, labels), mask)


def loss_midx(cfg: ModelConfig, params: dict, index: MultiIndex,
              hidden: jax.Array, labels: jax.Array, key: jax.Array,
              mask: Optional[jax.Array] = None, *,
              fused: Optional[bool] = None,
              interpret: bool = False) -> jax.Array:
    """MIDX sampled softmax CE. hidden [B,S,D], labels [B,S].

    Two implementations behind `cfg.head.use_fused_head` (DESIGN §3):

    fused (the TPU path): proposal scoring runs the one-pass midx_probs
      kernel via the `tables_fn` hook; the CE runs flash-CE — per-token
      proposals through `sampled_ce_pt_op` (in-kernel gather from the
      native-dtype table, fused Pallas backward), shared-negative proposals
      through `sampled_ce_op` vmapped over the batch. No [B,S,M,D] gather,
      no [B,S,M] corrected-logit tensor, and no fp32 copy of the [V,D]
      table in the traced graph.

    unfused (jnp oracle): the reference formulation parity tests compare
      against; also casts per gathered row, never the whole table.

    `fused=None` defers to kernels.dispatch (backend-gated); `interpret`
    runs the kernels under the Pallas interpreter (CPU parity tests).

    When `index` is a QuantHeadState (cfg.head.table_dtype int8/fp8), the
    whole hot path goes low-bit (DESIGN §12): proposal scoring reads the
    quantized codebooks (both fused and jnp, so draws match across
    backends), the fused CE gathers int8/fp8 rows + per-row scales and
    dequantizes in-register, and the unfused CE dequantizes through
    `dequant_rows` so gradients land on the master table (STE).
    """
    qs = index if isinstance(index, QuantHeadState) else None
    index = unwrap_index(index)
    table = class_embeddings(cfg, params)
    m = cfg.head.num_negatives
    h32 = hidden.astype(jnp.float32)
    b, s, d = h32.shape
    use_fused = kd.fused_head_active(cfg.head, fused=fused,
                                    interpret=interpret)

    proposal = cfg.head.proposal
    if proposal == "per_token":
        # two-stage form: O(K) Gumbels per draw instead of a K² table/token
        if qs is not None:
            tables_fn = kd.midx_tables_fn_q(
                qs.qcb1, qs.qcb1_scale, qs.qcb2, qs.qcb2_scale,
                use_kernel=use_fused, interpret=interpret)
        else:
            tables_fn = (kd.midx_tables_fn(use_kernel=True,
                                           interpret=interpret)
                         if use_fused else None)
        draw = midx_mod.sample_twostage(index, key, h32, m,
                                        tables_fn=tables_fn)  # ids [B,S,M]
        if use_fused:
            if qs is not None:
                loss = sampled_ce_pt_q_op(
                    h32.reshape(b * s, d), table, qs.qdata, qs.qscale,
                    draw.log_q.reshape(b * s, m), draw.ids.reshape(b * s, m),
                    labels.reshape(b * s), interpret).reshape(b, s)
            else:
                loss = sampled_ce_pt_op(
                    h32.reshape(b * s, d), table,
                    draw.log_q.reshape(b * s, m), draw.ids.reshape(b * s, m),
                    labels.reshape(b * s), interpret).reshape(b, s)
            return _masked_mean(loss, mask)
        pos_e, neg_e = _gathered_rows(table, qs, labels, draw.ids)
        pos_logit = jnp.sum(h32 * pos_e, axis=-1)
        neg_logits = jnp.einsum("bsd,bsmd->bsm", h32, neg_e)  # [B,S,M]
        log_q, neg_ids = draw.log_q, draw.ids
    else:
        sampler = (midx_mod.sample_pooled if proposal == "pooled"
                   else midx_mod.sample_mixture)
        scores_fn = None
        if qs is not None:
            scores_fn = (lambda idx, z: quantized_query_scores(
                idx.kind, qs.qcb1, qs.qcb1_scale, qs.qcb2, qs.qcb2_scale, z))
        draw = sampler(index, key, h32, m, scores_fn=scores_fn)  # ids [B,M]
        if use_fused:
            if qs is not None:
                loss = jax.vmap(
                    lambda hb, pe, ne, pq, ps, nq, ns, lq, ni, pi:
                    sampled_ce_q_op(hb, pe, ne, pq, ps, nq, ns, lq, ni, pi,
                                    interpret)
                )(h32, table[labels], table[draw.ids],
                  qs.qdata[labels], qs.qscale[labels],
                  qs.qdata[draw.ids], qs.qscale[draw.ids],
                  draw.log_q, draw.ids, labels)
            else:
                pos_emb = table[labels]                       # [B,S,D] native
                neg_emb = table[draw.ids]                     # [B,M,D] native
                loss = jax.vmap(
                    lambda hb, pe, ne, lq, ni, pi:
                    sampled_ce_op(hb, pe, ne, lq, ni, pi, interpret)
                )(h32, pos_emb, neg_emb, draw.log_q, draw.ids, labels)
            return _masked_mean(loss, mask)
        pos_e, neg_e = _gathered_rows(table, qs, labels, draw.ids)
        pos_logit = jnp.sum(h32 * pos_e, axis=-1)
        neg_logits = jnp.einsum("bsd,bmd->bsm", h32, neg_e)   # [B,S,M]
        log_q = draw.log_q[:, None, :]                        # broadcast over S
        neg_ids = draw.ids[:, None, :]

    loss = sampled_softmax_loss(pos_logit, neg_logits, log_q, neg_ids, labels,
                                cfg.head.mask_collisions)
    return _masked_mean(loss, mask)


def _gathered_rows(table: jax.Array, qs: Optional[QuantHeadState],
                   labels: jax.Array, neg_ids: jax.Array):
    """fp32 (pos_rows, neg_rows) for the unfused CE — quantized states
    dequantize per gathered row with master-table STE gradients; bf16
    states cast per gathered row (never the whole [V,D] table)."""
    if qs is not None:
        pos_e = dequant_rows(table, qs.qdata, qs.qscale, labels)
        neg_e = dequant_rows(table, qs.qdata, qs.qscale, neg_ids)
        return pos_e, neg_e
    return (table[labels].astype(jnp.float32),
            table[neg_ids].astype(jnp.float32))


def _masked_mean(loss: jax.Array, mask: Optional[jax.Array]) -> jax.Array:
    if mask is not None:
        return jnp.sum(loss * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(loss)


# --------------------------------------------------------- generic proposals
def _midx_index_of(proposal, state):
    """The MultiIndex behind a midx-backed proposal state, or None.

    midx-pq/rq keep the index AS the state; midx-learnable derives one from
    the trained codebooks. midx-exact-* is NOT a fast-lane candidate — its
    sampling distribution is the exact softmax, not the index proposal."""
    if proposal is None:
        return state
    if proposal.name in ("midx-pq", "midx-rq"):
        return state
    if proposal.name.startswith("midx-learnable"):
        return state["index"]
    return None


def init_proposal_state(cfg: ModelConfig, params: dict, key: jax.Array,
                        proposal, class_freq: Optional[jax.Array] = None):
    """Proposal-state counterpart of init_head_state (any contender)."""
    table = class_embeddings(cfg, params).astype(jnp.float32)
    return proposal.init(key, table, class_freq)


def refresh_proposal_state(cfg: ModelConfig, params: dict, proposal, state,
                           key: jax.Array):
    """Refresh any proposal's state against the current class table."""
    table = class_embeddings(cfg, params).astype(jnp.float32)
    return proposal.refresh(state, key, table)


def loss_sampled(cfg: ModelConfig, params: dict, proposal, state,
                 hidden: jax.Array, labels: jax.Array, key: jax.Array,
                 mask: Optional[jax.Array] = None, *,
                 fused: Optional[bool] = None,
                 interpret: bool = False) -> jax.Array:
    """Sampled softmax CE through ANY registered proposal (DESIGN §10).

    MIDX-backed contenders (midx-pq/rq, midx-learnable-*) short-circuit to
    `loss_midx` — the fused Pallas fast lane — with their MultiIndex as the
    head state, so the registry route stays bit-identical to the dedicated
    MIDX head. Everything else runs the reference jnp formulation:

      per_token        draws [B,S,M] negatives from q(·|h_t) per position
      pooled / mixture draws [B,M] shared negatives from q(·|z̄) with
                       z̄ = mean_t h_t (generic proposals have no per-token
                       mixture form, so 'mixture' uses the pooled query too)
    """
    idx = _midx_index_of(proposal, state)
    if idx is not None:
        return loss_midx(cfg, params, idx, hidden, labels, key, mask,
                         fused=fused, interpret=interpret)
    table = class_embeddings(cfg, params)
    m = cfg.head.num_negatives
    h32 = hidden.astype(jnp.float32)
    if cfg.head.proposal == "per_token":
        draw = proposal.sample(state, key, h32, m)            # ids [B,S,M]
        pos_logit = jnp.sum(h32 * table[labels].astype(jnp.float32), axis=-1)
        neg_e = table[draw.ids].astype(jnp.float32)           # [B,S,M,D]
        neg_logits = jnp.einsum("bsd,bsmd->bsm", h32, neg_e)
        log_q, neg_ids = draw.log_q, draw.ids
    else:
        z_bar = jnp.mean(h32, axis=-2)                        # [B,D]
        draw = proposal.sample(state, key, z_bar, m)          # ids [B,M]
        pos_logit = jnp.sum(h32 * table[labels].astype(jnp.float32), axis=-1)
        neg_e = table[draw.ids].astype(jnp.float32)           # [B,M,D]
        neg_logits = jnp.einsum("bsd,bmd->bsm", h32, neg_e)
        log_q = draw.log_q[:, None, :]                        # broadcast S
        neg_ids = draw.ids[:, None, :]
    loss = sampled_softmax_loss(pos_logit, neg_logits, log_q, neg_ids, labels,
                                cfg.head.mask_collisions)
    return _masked_mean(loss, mask)


class MidxDecodeOut(NamedTuple):
    token: jax.Array      # [B] sampled next token
    log_q: jax.Array      # [B] proposal log-prob


def midx_decode_head(cfg: ModelConfig, params: dict, index: MultiIndex,
                     hidden: jax.Array, key: jax.Array,
                     num_candidates: Optional[int] = None,
                     temperature: Optional[float] = None, *,
                     fused: Optional[bool] = None,
                     interpret: bool = False) -> MidxDecodeOut:
    """Approximate next-token sampling without the [B,V] logits matrix.

    Draw `num_candidates` via the two-stage MIDX form (k1 then k2 — same
    proposal distribution as the K²-table form but O(K) Gumbels per draw,
    which is what makes it the serving hot path, DESIGN §5), rescore exactly
    (o_i), softmax over the candidate set with IS correction — O(K·M + M·D)
    per token (beyond-paper). On the fused path the candidate scoring runs
    the midx_probs kernel through the same `tables_fn` hook as training.

    `num_candidates` / `temperature` default to
    `cfg.head.decode_candidates` / `cfg.head.decode_temperature` — the knobs
    the serve CLI plumbs through (DESIGN §5).

    With a QuantHeadState the rescore never touches [V,D] rows at all: the
    candidate score is reassembled from the stage tables already computed
    for the draw plus the PQ residual codes (Theorem-1 identity
    o_i = s1[k1(i)] + s2[k2(i)] + z·r_i), reading 2 assignment ints and
    n_sub code bytes per candidate instead of D floats (DESIGN §12).
    """
    if num_candidates is None:
        num_candidates = cfg.head.decode_candidates
    if temperature is None:
        temperature = cfg.head.decode_temperature
    qs = index if isinstance(index, QuantHeadState) else None
    index = unwrap_index(index)
    table = class_embeddings(cfg, params)
    h = hidden.astype(jnp.float32)
    k_draw, k_pick = jax.random.split(key)
    use_fused = kd.fused_head_active(cfg.head, fused=fused,
                                    interpret=interpret)
    if qs is not None:
        tables_fn = kd.midx_tables_fn_q(
            qs.qcb1, qs.qcb1_scale, qs.qcb2, qs.qcb2_scale,
            use_kernel=use_fused, interpret=interpret)
        draw, (s1, s2, _, _) = midx_mod.sample_twostage(
            index, k_draw, h, num_candidates, tables_fn=tables_fn,
            return_tables=True)                                # [B,M]
        scores = code_scores(index, qs.residual_codes, h, draw.ids, s1, s2)
        logits = scores / temperature
    else:
        tables_fn = (kd.midx_tables_fn(use_kernel=True, interpret=interpret)
                     if use_fused else None)
        draw = midx_mod.sample_twostage(index, k_draw, h, num_candidates,
                                        tables_fn=tables_fn)   # [B,M]
        # cast per gathered row — never the whole [V, D] table (DESIGN §3)
        cand_e = table[draw.ids].astype(jnp.float32)          # [B,M,D]
        logits = jnp.einsum("bd,bmd->bm", h, cand_e) / temperature
    corrected = logits - draw.log_q                           # IS-corrected
    pick = jax.random.categorical(k_pick, corrected, axis=-1) # [B]
    token = jnp.take_along_axis(draw.ids, pick[:, None], axis=-1)[:, 0]
    lq = jnp.take_along_axis(draw.log_q, pick[:, None], axis=-1)[:, 0]
    return MidxDecodeOut(token, lq)


def _spec_tables_fn(cfg: ModelConfig, qs: Optional[QuantHeadState],
                    fused: Optional[bool], interpret: bool):
    """The same stage-table hook selection as midx_decode_head, so the
    speculative draft draws from exactly the distribution the serving head
    samples (quantized codebooks included, DESIGN §12)."""
    use_fused = kd.fused_head_active(cfg.head, fused=fused,
                                    interpret=interpret)
    if qs is not None:
        return kd.midx_tables_fn_q(
            qs.qcb1, qs.qcb1_scale, qs.qcb2, qs.qcb2_scale,
            use_kernel=use_fused, interpret=interpret)
    return (kd.midx_tables_fn(use_kernel=True, interpret=interpret)
            if use_fused else None)


class SpecDraftOut(NamedTuple):
    tokens: jax.Array     # [B, k] draft tokens, i.i.d. ~ q(·|h)
    log_q: jax.Array      # [B, k] their proposal log-probs
    s1: jax.Array         # [B, K] stage-1 scores (shared by the k drafts)
    s2: jax.Array         # [B, K] stage-2 scores
    lse: jax.Array        # [B]    Eq.(6) normalizer


def midx_spec_draft(cfg: ModelConfig, params: dict, index,
                    hidden: jax.Array, keys: jax.Array, k: int = 1, *,
                    fused: Optional[bool] = None,
                    interpret: bool = False) -> SpecDraftOut:
    """k MIDX draft tokens per row for speculative decoding (DESIGN §13).

    The whole wave drafts from ONE hidden per slot — the backbone state that
    predicted the slot's last committed token — so drafting costs a single
    two-stage table build + k O(K) categorical draws and runs NO backbone
    steps at all; the backbone touches the drafts exactly once, in the
    batched verify pass. The draws are i.i.d. given `hidden`: q is one
    position stale past the first draft, which costs acceptance, not
    correctness — rejection sampling only needs the verifier to score the
    drafts under the same q they were drawn from, and per-class
    q(i|h) = exp(s1[a1(i)] + s2[a2(i)] − lse) is exactly how
    `sample_twostage` normalizes its `log_q`. The stage tables come back so
    the verify pass can reconstruct log q over the *whole* vocab from two
    assignment gathers.

    hidden [B, D]; keys [B, 2] per-slot PRNG keys (vmapped: a slot's draws
    never depend on batch composition).
    """
    qs = index if isinstance(index, QuantHeadState) else None
    index = unwrap_index(index)
    tables_fn = _spec_tables_fn(cfg, qs, fused, interpret)

    def one(h, key):
        draw, (s1, s2, _, lse) = midx_mod.sample_twostage(
            index, key, h[None], k, tables_fn=tables_fn, return_tables=True)
        return draw.ids[0], draw.log_q[0], s1[0], s2[0], lse[0]

    ids, lq, s1, s2, lse = jax.vmap(one)(hidden.astype(jnp.float32), keys)
    return SpecDraftOut(ids, lq, s1, s2, lse)


class SpecVerifyOut(NamedTuple):
    tokens: jax.Array     # [k, B] committed-token matrix (rows < n_commit)
    n_commit: jax.Array   # [B] tokens to commit this wave (1..k)
    n_accept: jax.Array   # [B] accepted drafts (acceptance-rate numerator)


def spec_verify(cfg: ModelConfig, params: dict, index, hiddens: jax.Array,
                drafts: jax.Array, log_q: jax.Array, s1: jax.Array,
                s2: jax.Array, lse: jax.Array, keys: jax.Array,
                temperature: Optional[float] = None) -> SpecVerifyOut:
    """Batched full-head verification of k MIDX drafts per slot (DESIGN §13).

    hiddens [k,B,D] are the backbone states at the drafted positions — the
    one chunked backbone pass of the wave; drafts/log_q [k,B] from
    `midx_spec_draft`; s1/s2 [B,K] + lse [B] its per-slot stage tables
    (shared by all k positions: the wave drafts from one hidden per slot);
    keys [B,2] per-slot wave keys (roles are salted inside, so every random
    number a slot consumes derives from its own stream — batch composition
    never changes a request's output).

    One `logits_full` matmul over all k·B rows gives the exact target
    p(·|h_j) = softmax(logits[:V]/T). Leviathan-style rejection sampling:
    accept draft d_j with prob min(1, p(d_j)/q(d_j)); on first rejection
    emit a residual token ~ max(p−q, 0)/Z and stop. The committed prefix is
    distributed exactly as sequential sampling from p — the proposal q may
    condition on anything already decided (here: the previous wave's
    hidden), it only has to be the distribution the drafts were actually
    drawn from. The q-mass the index proposal leaks onto padded vocab rows
    is handled too: p=0 there ⇒ a padded draft always rejects, and the leak
    only feeds the residual's Z.
    temperature <= 0 is greedy verify: accept iff the draft equals argmax,
    else commit the argmax — token-identical to greedy full-head decoding.
    """
    if temperature is None:
        temperature = cfg.head.decode_temperature
    index = unwrap_index(index)
    v = cfg.vocab_size
    k, b = drafts.shape
    logits = logits_full(cfg, params, hiddens)[..., :v].astype(jnp.float32)

    if temperature > 0:
        # accept tests need only scalars: the drafted token's target logit
        # and the row normalizer — never a materialized [k,B,V] softmax
        scaled = logits / temperature
        lse_p = jax.nn.logsumexp(scaled, axis=-1)                # [k,B]
        dc = jnp.minimum(drafts, v - 1)[..., None]
        logp_d = jnp.take_along_axis(scaled, dc, axis=-1)[..., 0] - lse_p
        logp_d = jnp.where(drafts < v, logp_d, -jnp.inf)
        u = jax.vmap(lambda wk: jax.random.uniform(
            jax.random.fold_in(wk, 2), (k,)))(keys).T            # [k,B]
        accept = jnp.log(u) < logp_d - log_q
        ok = jnp.cumprod(accept.astype(jnp.int32), axis=0).astype(bool)
        n_acc = jnp.sum(ok, axis=0).astype(jnp.int32)            # [B]
        # the correction token is consumed only at the FIRST rejected
        # position j* = n_acc, so all vocab-wide work — the residual and
        # the gumbel draw — happens on one [B, V] slice instead of
        # [k, B, V] (the threefry bits for a vocab-wide categorical
        # dominate verify cost on CPU)
        jstar = jnp.minimum(n_acc, k - 1)                        # [B]
        sel = lambda x: jnp.take_along_axis(
            x, jstar[None, :].reshape((1, b) + (1,) * (x.ndim - 2)),
            axis=0)[0]
        logp_row = sel(scaled) - sel(lse_p[..., None])           # [B,V]
        # draft log-prob over the whole vocab from the stage tables: two
        # assignment gathers instead of a second scoring pass — and the
        # tables are per-slot (not per-position), so no j* selection
        logq_row = (jnp.take(s1, index.assign1[:v], axis=-1)
                    + jnp.take(s2, index.assign2[:v], axis=-1)
                    - lse[..., None])                            # [B,V]
        resid = jnp.maximum(jnp.exp(logp_row) - jnp.exp(logq_row), 0.0)
        rlog = jnp.log(resid)                                    # -inf at 0
        has = jnp.sum(resid, axis=-1) > 0                        # [B]

        def corr_slot(wk, j, rl, lp, hs):
            kj = jax.random.fold_in(jax.random.fold_in(wk, 3), j)
            # one gumbel vector serves both draws: only one branch is
            # consumed, and conditioned on `hs` the noise is independent
            # of which — argmax(logits + gumbel) IS categorical(logits)
            g = -jnp.log(-jnp.log(
                jax.random.uniform(kj, (v,), minval=jnp.finfo(jnp.float32).tiny)))
            c_r = jnp.argmax(rl + g)
            # float-degenerate residual (p <= q everywhere): fall back
            # to the exact target — this branch has probability ~0
            c_f = jnp.argmax(lp + g)
            return jnp.where(hs, c_r, c_f).astype(jnp.int32)

        corr = jax.vmap(corr_slot)(keys, jstar, rlog, logp_row, has)  # [B]
    else:
        best = jnp.argmax(logits, axis=-1).astype(jnp.int32)     # [k,B]
        accept = drafts == best
        ok = jnp.cumprod(accept.astype(jnp.int32), axis=0).astype(bool)
        n_acc = jnp.sum(ok, axis=0).astype(jnp.int32)            # [B]
        corr = jnp.take_along_axis(
            best, jnp.minimum(n_acc, k - 1)[None, :], axis=0)[0]  # [B]

    jrow = jnp.arange(k)[:, None]
    toks = jnp.where(jrow < n_acc[None, :], drafts,
                     jnp.where(jrow == n_acc[None, :], corr[None, :], 0))
    n_commit = jnp.minimum(n_acc + 1, k)
    return SpecVerifyOut(toks.astype(jnp.int32), n_commit, n_acc)


def proposal_decode_head(cfg: ModelConfig, params: dict, proposal, state,
                         hidden: jax.Array, key: jax.Array,
                         num_candidates: Optional[int] = None,
                         temperature: Optional[float] = None, *,
                         fused: Optional[bool] = None,
                         interpret: bool = False) -> MidxDecodeOut:
    """midx_decode_head generalized to any proposal: draw candidates from
    q(·|h), rescore exactly, IS-correct, sample. MIDX-backed states keep the
    dedicated (fused-kernel-capable) path."""
    idx = _midx_index_of(proposal, state)
    if idx is not None:
        return midx_decode_head(cfg, params, idx, hidden, key,
                                num_candidates, temperature,
                                fused=fused, interpret=interpret)
    if num_candidates is None:
        num_candidates = cfg.head.decode_candidates
    if temperature is None:
        temperature = cfg.head.decode_temperature
    table = class_embeddings(cfg, params)
    h = hidden.astype(jnp.float32)
    k_draw, k_pick = jax.random.split(key)
    draw = proposal.sample(state, k_draw, h, num_candidates)  # [B,M]
    cand_e = table[draw.ids].astype(jnp.float32)              # [B,M,D]
    logits = jnp.einsum("bd,bmd->bm", h, cand_e) / temperature
    corrected = logits - draw.log_q                           # IS-corrected
    pick = jax.random.categorical(k_pick, corrected, axis=-1) # [B]
    token = jnp.take_along_axis(draw.ids, pick[:, None], axis=-1)[:, 0]
    lq = jnp.take_along_axis(draw.log_q, pick[:, None], axis=-1)[:, 0]
    return MidxDecodeOut(token, lq)
