"""Step functions (train / prefill / decode) + abstract input specs.

These are what both the real drivers (train.py / serve.py) and the multi-pod
dry-run (dryrun.py) lower. Everything is a pure function of
(params, opt/index/cache state, batch, rng) — no host callbacks in the hot
path; the MIDX index refresh is a separate jitted function on its own cadence.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.index.quantized import resolve_table_dtype, unwrap_index
from repro.models import (decode_step, forward, heads, init_decode_state,
                          init_params, logits_full)
from repro.optim import Optimizer, clip_by_global_norm
from repro.proposals import registry as proposals_registry


def _model_extras(cfg: ModelConfig, batch: dict) -> dict:
    kw = {}
    if cfg.family == "vlm":
        kw["image_emb"] = batch["image_emb"]
    if cfg.family == "audio":
        kw["frames"] = batch["frames"]
    return kw


def _apply_fault(loss, batch: dict):
    """Resilience seam (DESIGN §11): when the batch carries a '_fault_scale'
    leaf ([B] float32, normally all-ones), the loss is scaled by its mean —
    multiplying by 1.0 is IEEE-exact, so an armed-but-quiet injector leaves
    the trajectory bit-identical, while NaN/Inf/spike values poison the loss
    AND (through the chain rule) every gradient leaf inside the jitted step,
    exactly where the non-finite guard must catch them. Shaped [B] so the
    leaf shards like any other batch leaf under shard_map."""
    if "_fault_scale" in batch:
        return loss * jnp.mean(batch["_fault_scale"].astype(jnp.float32))
    return loss


def _guard_select(ok, new_tree, old_tree):
    """Leafwise select: keep the freshly computed tree when `ok` (a scalar
    bool), otherwise the pre-step tree — the non-finite skip guard. When ok
    is True the select returns the new leaves bitwise, so guarded and
    unguarded healthy steps are identical."""
    return jax.tree_util.tree_map(
        lambda n, o: jnp.where(ok, n, o), new_tree, old_tree)


def resolve_proposal(cfg: ModelConfig, head_mode: Optional[str] = None):
    """(mode, Proposal-or-None) for a head config, validated early.

    Unknown modes raise the informative registry error here — at step-build
    time — instead of silently training the MIDX head (the pre-refactor
    fallthrough). 'midx' and 'full' return None: they keep their dedicated
    lanes and the Proposal object is not needed on the hot path.
    """
    mode = head_mode or cfg.head.mode
    proposals_registry.validate_mode(mode)
    if mode in ("midx", "full"):
        return mode, None
    return mode, proposals_registry.from_config(cfg.head, mode)


def make_loss_fn(cfg: ModelConfig, *, head_mode: Optional[str] = None,
                 window: Optional[int] = None,
                 fused_head: Optional[bool] = None,
                 interpret: bool = False,
                 with_aux: bool = False) -> Callable:
    """loss(params, state, batch, key) -> (loss, metrics).

    `state` is the head state for the resolved mode: the MultiIndex for
    'midx', ignored for 'full', and the proposal's state pytree for every
    registry contender (heads.loss_sampled routes it; midx-backed proposals
    keep the fused fast lane). The resolved Proposal (or None) is exposed as
    `loss_fn.proposal`.

    `fused_head` / `interpret` select the fused Pallas MIDX head
    (DESIGN §3): None defers to cfg.head.use_fused_head + the backend via
    kernels.dispatch; interpret=True runs the kernels under the Pallas
    interpreter so the fused graph lowers on any backend (dry-run, tests).

    `with_aux=True` adds a trainable proposal's L_recon+L_KL auxiliary
    objective (paper §6.2.3) to the loss — only meaningful when the caller
    also differentiates w.r.t. the state's trainable leaves
    (make_train_step's returns_state path).
    """
    mode, proposal = resolve_proposal(cfg, head_mode)
    # unknown table dtypes raise here — at step-build time — same
    # convention as resolve_proposal for unknown head modes
    resolve_table_dtype(cfg.head.table_dtype)
    include_aux = bool(with_aux and proposal is not None
                       and proposal.trainable)

    def loss_fn(params, state, batch, key):
        out = forward(cfg, params, batch["tokens"], window=window,
                      **_model_extras(cfg, batch))
        if mode == "full":
            ce = heads.loss_full(cfg, params, out["hidden"], batch["labels"])
        elif mode == "midx":
            ce = heads.loss_midx(cfg, params, state, out["hidden"],
                                 batch["labels"], key, fused=fused_head,
                                 interpret=interpret)
        else:
            ce = heads.loss_sampled(cfg, params, proposal, state,
                                    out["hidden"], batch["labels"], key,
                                    fused=fused_head, interpret=interpret)
        loss = ce + cfg.router_aux_weight * out["aux_loss"]
        metrics = {"ce": ce, "aux": out["aux_loss"]}
        if include_aux:
            from repro.models.model import class_embeddings
            h = out["hidden"].astype(jnp.float32)
            aux_p, am = proposal.aux_loss(
                state, jax.random.fold_in(key, 7),
                h.reshape(-1, h.shape[-1]), class_embeddings(cfg, params))
            loss = loss + aux_p
            metrics.update(am)
        return _apply_fault(loss, batch), metrics

    loss_fn.proposal = proposal
    return loss_fn


def make_train_step(cfg: ModelConfig, optimizer: Optimizer, *,
                    head_mode: Optional[str] = None,
                    window: Optional[int] = None,
                    clip_norm: float = 1.0,
                    fused_head: Optional[bool] = None,
                    interpret: bool = False) -> Callable:
    """Single-device train step, dispatched on the resolved head mode.

    Non-trainable modes (everything but midx-learnable-*) keep the
    historical signature
        step(params, opt_state, state, batch, key)
            -> (params, opt_state, metrics)
    with `step.returns_state = False`. Trainable proposals return the
    updated head state too —
        step(...) -> (params, opt_state, state, metrics)
    with `step.returns_state = True`: the codebook leaves take an SGD step
    at cfg.head.learnable_lr on the aux-loss gradient each call. Read the
    attribute BEFORE jit (jit-wrapped callables drop it).

    Both variants carry the non-finite guard (DESIGN §11): when the loss or
    the gradient global norm is NaN/Inf, params, opt state (and trainable
    head state) are returned unchanged and metrics['skipped'] is 1 — a
    poisoned step never reaches the optimizer, and the host-side guardrails
    read 'skipped' to drive the rollback policy.
    """
    loss_fn = make_loss_fn(cfg, head_mode=head_mode, window=window,
                           fused_head=fused_head, interpret=interpret,
                           with_aux=True)
    proposal = loss_fn.proposal

    if proposal is not None and proposal.trainable:
        lr = cfg.head.learnable_lr

        def train_step(params, opt_state, state, batch, key):
            trainable, rest = proposal.split_trainable(state)

            def lf(p, tr):
                return loss_fn(p, proposal.merge_trainable(tr, rest),
                               batch, key)

            (loss, metrics), (gp, gt) = jax.value_and_grad(
                lf, argnums=(0, 1), has_aux=True)(params, trainable)
            gp, gnorm = clip_by_global_norm(gp, clip_norm)
            ok = jnp.isfinite(loss) & jnp.isfinite(gnorm)
            new_params, new_opt = optimizer.update(gp, opt_state, params)
            params = _guard_select(ok, new_params, params)
            opt_state = _guard_select(ok, new_opt, opt_state)
            new_trainable = jax.tree_util.tree_map(lambda t, g: t - lr * g,
                                                   trainable, gt)
            trainable = _guard_select(ok, new_trainable, trainable)
            state = proposal.merge_trainable(trainable, rest)
            metrics = {**metrics, "loss": loss, "grad_norm": gnorm,
                       "skipped": 1.0 - ok.astype(jnp.float32)}
            return params, opt_state, state, metrics

        train_step.returns_state = True
        train_step.proposal = proposal
        return train_step

    def train_step(params, opt_state, state, batch, key):
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, state, batch, key)
        grads, gnorm = clip_by_global_norm(grads, clip_norm)
        ok = jnp.isfinite(loss) & jnp.isfinite(gnorm)
        new_params, new_opt = optimizer.update(grads, opt_state, params)
        params = _guard_select(ok, new_params, params)
        opt_state = _guard_select(ok, new_opt, opt_state)
        metrics = {**metrics, "loss": loss, "grad_norm": gnorm,
                   "skipped": 1.0 - ok.astype(jnp.float32)}
        return params, opt_state, metrics

    train_step.returns_state = False
    train_step.proposal = proposal
    return train_step


def init_grad_transport_state(params, grad_transport: str, dp: int = 1):
    """Error-feedback carry for 'int8_ef'; None otherwise.

    Each leaf is [dp, *param_shape]: the residual is per data shard (every
    shard quantizes a different local gradient), so the carry is stacked over
    a leading shard dimension and stays sharded over the data axes end to
    end — it must never be treated as replicated."""
    if grad_transport != "int8_ef":
        return None
    return jax.tree_util.tree_map(
        lambda p: jnp.zeros((dp,) + p.shape, jnp.float32), params)


def make_sharded_train_step(cfg: ModelConfig, optimizer: Optimizer, mesh, *,
                            data_axes=("data",),
                            grad_transport: str = "fp32",
                            head_mode: Optional[str] = None,
                            window: Optional[int] = None,
                            clip_norm: float = 1.0,
                            fused_head: Optional[bool] = None,
                            interpret: bool = False) -> Callable:
    """Data-parallel train step under shard_map with an *explicit* gradient
    all-reduce, so the transport precision is a config choice (DESIGN §4):

      'fp32'     lax.pmean — the GSPMD-equivalent baseline
      'bf16'     dist.collectives.psum_bf16 — half the wire bytes
      'int8_ef'  dist.collectives.psum_int8_ef — quarter the wire bytes,
                 error feedback carried across steps

    Params / optimizer state / index are replicated over `data_axes`; the
    batch is sharded on its leading dim, which must divide the data degree.
    Each shard draws its own negatives (the step key is folded with the
    linear shard index over *all* data axes) — at dp shards the effective
    negative pool grows dp× for free, the shard_map analogue of per-token
    proposals.

    step(params, opt_state, index, batch, key, ef)
        -> (params, opt_state, metrics, ef)
    where `ef` is init_grad_transport_state(params, grad_transport, dp) —
    a [dp, ...]-stacked tree sharded over the data axes (each shard carries
    its own quantization residual; it is never replicated).
    """
    from jax.sharding import PartitionSpec as P
    from repro.dist import collectives

    assert grad_transport in ("fp32", "bf16", "int8_ef"), grad_transport
    loss_fn = make_loss_fn(cfg, head_mode=head_mode, window=window,
                           fused_head=fused_head, interpret=interpret)
    axes = tuple(data_axes)
    ax = axes if len(axes) > 1 else axes[0]
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    dp = 1
    for a in axes:
        dp *= sizes[a]

    def body(params, opt_state, index, batch, key, ef):
        shard = jnp.int32(0)
        for a in axes:
            shard = shard * sizes[a] + jax.lax.axis_index(a)
        key = jax.random.fold_in(key, shard)
        ef_in = ef
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, index, batch, key)
        if grad_transport == "fp32":
            grads = jax.tree_util.tree_map(
                lambda g: jax.lax.pmean(g, ax), grads)
        elif grad_transport == "bf16":
            grads = jax.tree_util.tree_map(
                lambda g: g / dp, collectives.psum_bf16(grads, ax))
        else:
            ef_local = jax.tree_util.tree_map(lambda e: e[0], ef)
            summed, ef_local = collectives.psum_int8_ef(grads, ef_local, ax)
            grads = jax.tree_util.tree_map(lambda g: g / dp, summed)
            ef = jax.tree_util.tree_map(lambda e: e[None], ef_local)
        metrics = {**metrics, "loss": loss}
        metrics = jax.tree_util.tree_map(
            lambda m: jax.lax.pmean(m, ax), metrics)
        grads, gnorm = clip_by_global_norm(grads, clip_norm)
        # non-finite guard (DESIGN §11): grads were all-reduced, so gnorm —
        # and the pmean'd loss — are identical on every shard, and all
        # shards take the same branch. The int8 error-feedback carry must
        # also roll back, or a NaN step would poison every later step
        # through the quantization residual.
        ok = jnp.isfinite(metrics["loss"]) & jnp.isfinite(gnorm)
        new_params, new_opt = optimizer.update(grads, opt_state, params)
        params = _guard_select(ok, new_params, params)
        opt_state = _guard_select(ok, new_opt, opt_state)
        ef = _guard_select(ok, ef, ef_in)
        return params, opt_state, {
            **metrics, "grad_norm": gnorm,
            "skipped": 1.0 - ok.astype(jnp.float32)}, ef

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(), P(), P(ax), P(), P(ax)),
        out_specs=(P(), P(), P(), P(ax)),
        check_vma=False)


def make_vocab_parallel_train_step(cfg: ModelConfig, optimizer: Optimizer,
                                   mesh, *, data_axes=("data",),
                                   vocab_axis: str = "vocab",
                                   window: Optional[int] = None,
                                   clip_norm: float = 1.0,
                                   fused_head: Optional[bool] = None,
                                   interpret: bool = False) -> Callable:
    """Vocab-parallel train step (DESIGN §9): the class tables (embed/head)
    and the MIDX index row-shard over `vocab_axis`; the backbone replicates
    over it and data-parallelism runs over `data_axes` as usual.

    step(params, opt_state, sharded_index, batch, key)
        -> (params, opt_state, metrics)
    with params/opt-state moments sharded by dist.sharding.vocab_param_specs
    and sharded_index a dist.vocab_parallel.VocabShardedIndex.

    Parity contract (test_vocab_parallel.py): loss and every updated param
    match the replicated make_train_step at vp=1-equivalent keys to ≤1e-5.
    Gradient bookkeeping: taking jax.grad inside shard_map sums the
    cotangents of every shard's (identical) objective, so replicated-leaf
    grads need a vocab-axis pmean and vocab-sharded leaf grads a 1/vp —
    after which they are exactly the replicated path's. The global-norm
    clip psums the sharded leaves' norm contribution so every shard scales
    by the same factor. The step key folds over the DATA shard index only:
    vocab shards must draw identical negatives.
    """
    from jax.sharding import PartitionSpec as P
    from repro.dist import vocab_parallel as vp_mod
    from repro.dist.sharding import vocab_index_specs, vocab_param_specs
    from repro.models.model import class_embeddings
    from repro.optim.optimizers import OptState

    if (cfg.head.mode or "midx") != "midx":
        raise ValueError("vocab-parallel training requires the MIDX head")
    axes = tuple(data_axes)
    dax = axes if len(axes) > 1 else axes[0]
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_vp = sizes[vocab_axis]
    params_abs = abstract_params(cfg)
    pspecs = vocab_param_specs(cfg, params_abs, vp=n_vp,
                               vocab_axis=vocab_axis)
    opt_abs = jax.eval_shape(optimizer.init, params_abs)
    ospecs = OptState(P(), pspecs,
                      None if opt_abs.nu is None else pspecs)
    idx_specs = vocab_index_specs(abstract_vocab_index(cfg, params_abs, n_vp),
                                  vocab_axis)

    def loss_fn(params, sharded_idx, batch, key):
        emb = vp_mod.embed_lookup(params["embed"], batch["tokens"],
                                  axis=vocab_axis)
        out = forward(cfg, params, batch["tokens"], window=window,
                      inputs_embeds=emb, **_model_extras(cfg, batch))
        local_idx = vp_mod.local_index(sharded_idx)
        table_local = class_embeddings(cfg, params)
        ce = vp_mod.loss_midx_vp(cfg, table_local, local_idx, out["hidden"],
                                 batch["labels"], key, axis=vocab_axis,
                                 fused=fused_head, interpret=interpret)
        loss = ce + cfg.router_aux_weight * out["aux_loss"]
        return _apply_fault(loss, batch), {"ce": ce, "aux": out["aux_loss"]}

    def is_vp(spec) -> bool:
        return any(e == vocab_axis for e in spec)

    def body(params, opt_state, sharded_idx, batch, key):
        shard = jnp.int32(0)
        for a in axes:
            shard = shard * sizes[a] + jax.lax.axis_index(a)
        key = jax.random.fold_in(key, shard)
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, sharded_idx, batch, key)
        grads = jax.tree_util.tree_map(
            lambda g, sp: g / n_vp if is_vp(sp)
            else jax.lax.pmean(g, vocab_axis), grads, pspecs)
        grads = jax.tree_util.tree_map(
            lambda g: jax.lax.pmean(g, dax), grads)
        # global-norm clip with the sharded leaves psum'd over the vocab
        # axis, so the scale — and hence the replicated leaves — stay
        # identical on every shard and equal to the replicated path's
        sq = jax.tree_util.tree_map(
            lambda g: jnp.sum(jnp.square(g.astype(jnp.float32))), grads)
        local_sq = sum(s for s, sp in zip(jax.tree_util.tree_leaves(sq),
                                          jax.tree_util.tree_leaves(pspecs))
                       if is_vp(sp))
        rep_sq = sum(s for s, sp in zip(jax.tree_util.tree_leaves(sq),
                                        jax.tree_util.tree_leaves(pspecs))
                     if not is_vp(sp))
        gnorm = jnp.sqrt(rep_sq + jax.lax.psum(local_sq, vocab_axis))
        scale = jnp.minimum(1.0, clip_norm / jnp.maximum(gnorm, 1e-12))
        grads = jax.tree_util.tree_map(
            lambda g: (g * scale).astype(g.dtype), grads)
        metrics = {**metrics, "loss": loss}
        metrics = jax.tree_util.tree_map(
            lambda m: jax.lax.pmean(m, dax), metrics)
        # non-finite guard (DESIGN §11): grads were pmean'd over the data
        # axis and gnorm psum'd over the vocab axis, so loss/gnorm — and
        # the skip decision — are identical on every shard.
        ok = jnp.isfinite(metrics["loss"]) & jnp.isfinite(gnorm)
        new_params, new_opt = optimizer.update(grads, opt_state, params)
        params = _guard_select(ok, new_params, params)
        opt_state = _guard_select(ok, new_opt, opt_state)
        return params, opt_state, {
            **metrics, "grad_norm": gnorm,
            "skipped": 1.0 - ok.astype(jnp.float32)}

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(pspecs, ospecs, idx_specs, P(dax), P()),
        out_specs=(pspecs, ospecs, P()),
        check_vma=False)


def make_vocab_index_init(cfg: ModelConfig, mesh, *,
                          vocab_axis: str = "vocab") -> Callable:
    """init(params, key) -> VocabShardedIndex, built natively per shard
    (index.sharded.build_vocab_sharded): codebook statistics psum, the CSR
    state never leaves its shard. `params` arrive sharded by
    vocab_param_specs, so each shard quantizes only its own table rows."""
    from jax.sharding import PartitionSpec as P
    from repro.dist import vocab_parallel as vp_mod
    from repro.dist.sharding import vocab_index_specs, vocab_param_specs
    from repro.index.sharded import build_vocab_sharded
    from repro.models.model import class_embeddings

    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_vp = sizes[vocab_axis]
    params_abs = abstract_params(cfg)
    pspecs = vocab_param_specs(cfg, params_abs, vp=n_vp,
                               vocab_axis=vocab_axis)
    idx_specs = vocab_index_specs(abstract_vocab_index(cfg, params_abs, n_vp),
                                  vocab_axis)

    def body(params, key):
        table = class_embeddings(cfg, params).astype(jnp.float32)
        cb1, cb2, a1, a2, si, off, cnt, lcnt = build_vocab_sharded(
            key, table, kind=cfg.head.quantizer, k=cfg.head.midx_k,
            iters=cfg.head.kmeans_iters, axis=vocab_axis)
        return vp_mod.VocabShardedIndex(
            cfg.head.quantizer, n_vp, cb1, cb2, a1[None], a2[None],
            si[None], off[None], cnt[None], lcnt[None])

    return jax.shard_map(body, mesh=mesh, in_specs=(pspecs, P()),
                         out_specs=idx_specs, check_vma=False)


def make_vocab_refresh_step(cfg: ModelConfig, mesh, *,
                            vocab_axis: str = "vocab",
                            policy: Optional[str] = None) -> Callable:
    """refresh(params, sharded_index, key) -> (sharded_index, metrics) for
    the vocab-parallel layout: psum'd drift probe + warm-started sharded
    refit, each shard rebuilding only its local CSR (no all-gather)."""
    from jax.sharding import PartitionSpec as P
    from repro.dist import vocab_parallel as vp_mod
    from repro.dist.sharding import vocab_index_specs, vocab_param_specs
    from repro.index.sharded import refresh_vocab_sharded
    from repro.models.model import class_embeddings

    pol = policy or cfg.head.refresh_policy
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_vp = sizes[vocab_axis]
    params_abs = abstract_params(cfg)
    pspecs = vocab_param_specs(cfg, params_abs, vp=n_vp,
                               vocab_axis=vocab_axis)
    idx_specs = vocab_index_specs(abstract_vocab_index(cfg, params_abs, n_vp),
                                  vocab_axis)

    def body(params, sharded_idx, key):
        table = class_embeddings(cfg, params).astype(jnp.float32)
        local = vp_mod.local_index(sharded_idx)
        leaves, metrics = refresh_vocab_sharded(
            local, key, table, axis=vocab_axis, iters=cfg.head.kmeans_iters,
            policy=pol, threshold=cfg.head.refresh_drift_threshold)
        cb1, cb2, a1, a2, si, off, cnt, lcnt = leaves
        new = vp_mod.VocabShardedIndex(
            sharded_idx.kind, sharded_idx.num_shards, cb1, cb2, a1[None],
            a2[None], si[None], off[None], cnt[None], lcnt[None])
        return new, metrics

    return jax.shard_map(body, mesh=mesh,
                         in_specs=(pspecs, idx_specs, P()),
                         out_specs=(idx_specs, P()), check_vma=False)


def make_prefill_step(cfg: ModelConfig, *, window: Optional[int] = None):
    """Full-sequence forward -> last-position logits (serving prefill)."""

    def prefill_step(params, batch):
        out = forward(cfg, params, batch["tokens"], window=window,
                      **_model_extras(cfg, batch))
        last = out["hidden"][:, -1, :]
        return logits_full(cfg, params, last)

    return prefill_step


def make_decode_step(cfg: ModelConfig, *, window: Optional[int] = None,
                     sample: bool = True):
    """One new token against a seq_len KV cache (serving decode)."""

    def serve_step(params, cache, token, pos, key):
        hidden, cache = decode_step(cfg, params, token, pos, cache,
                                    window=window)
        logits = logits_full(cfg, params, hidden)
        if sample:
            nxt = jax.random.categorical(key, logits, axis=-1)
        else:
            nxt = jnp.argmax(logits, axis=-1)
        return nxt.astype(jnp.int32), cache

    return serve_step


def make_refresh_step(cfg: ModelConfig, mesh=None, *,
                      data_axes=("data",), policy: Optional[str] = None,
                      head_mode: Optional[str] = None):
    """Head-state refresh step: refresh(params, state, key) -> (state, metrics).

    Registry proposal modes (anything but 'midx'/'full') refresh through
    Proposal.refresh against the current class table — the TAPAS pass-1
    pool redraw, the RFF feature re-map, the learnable hard re-assign — and
    report zeroed drift metrics (drift probes are a MultiIndex concept).

    For the MIDX index: without a mesh the rebuild runs single-device under
    cfg.head.refresh_policy (DESIGN §8): 'fixed' = warm-started full refit
    every event, 'drift' = reassign-only with lax.cond escalation to the
    refit when drift exceeds cfg.head.refresh_drift_threshold.

    With a mesh, the class table is row-sliced over `data_axes`
    (dist.sharding.refresh_table_spec) so each shard quantizes only its
    rows; K-means statistics travel by psum and the assignments all-gather
    back for the replicated CSR rebuild (repro.index.sharded). A padded
    vocab that does not divide the data degree no longer silently falls
    back to the replicated step: the table is zero-padded up to
    ceil(Vpad/dp)*dp rows and the pad rows are masked out of every
    statistic (refresh_sharded's n_valid path).
    """
    mode, proposal = resolve_proposal(cfg, head_mode)
    if proposal is not None:
        def refresh_proposal(params, state, key):
            new = heads.refresh_proposal_state(cfg, params, proposal, state,
                                               key)
            zeros = {"reassigned_frac": jnp.float32(0.0),
                     "codeword_drift": jnp.float32(0.0),
                     "did_full": jnp.float32(0.0),
                     "distortion": jnp.float32(0.0)}
            return new, zeros

        refresh_proposal.proposal = proposal
        return refresh_proposal

    pol = policy or cfg.head.refresh_policy

    def refresh_replicated(params, index, key):
        return heads.refresh_head_state_with_policy(cfg, params, index, key,
                                                    policy=pol)

    if mesh is None:
        return refresh_replicated

    from jax.sharding import PartitionSpec as P
    from repro.dist.sharding import refresh_rows_per_shard
    from repro.index.sharded import refresh_sharded
    from repro.models.model import class_embeddings

    axes = tuple(data_axes)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    dp = 1
    for a in axes:
        dp *= sizes[a]
    if dp <= 1:
        return refresh_replicated
    ax = axes if len(axes) > 1 else axes[0]
    vpad = cfg.padded_vocab
    rows = refresh_rows_per_shard(vpad, dp)
    n_valid = vpad if rows * dp != vpad else None

    def body(params, index, key):
        table = class_embeddings(cfg, params).astype(jnp.float32)
        if n_valid is not None:
            table = jnp.pad(table, ((0, rows * dp - vpad), (0, 0)))
        shard = jnp.int32(0)
        for a in axes:
            shard = shard * sizes[a] + jax.lax.axis_index(a)
        local = jax.lax.dynamic_slice_in_dim(table, shard * rows, rows)
        return refresh_sharded(index, key, local, axis=ax,
                               iters=cfg.head.kmeans_iters, policy=pol,
                               threshold=cfg.head.refresh_drift_threshold,
                               n_valid=n_valid)

    sharded_step = jax.shard_map(body, mesh=mesh,
                                 in_specs=(P(), P(), P()),
                                 out_specs=(P(), P()), check_vma=False)
    if resolve_table_dtype(cfg.head.table_dtype) == "bf16":
        return sharded_step

    def refresh_quantized(params, state, key):
        # the sharded rebuild works on the bare index; the low-bit twins
        # re-derive outside shard_map (elementwise per-row — cheap next to
        # the refit, and the scales come out identical on every device)
        new_index, metrics = sharded_step(params, unwrap_index(state), key)
        table = class_embeddings(cfg, params).astype(jnp.float32)
        return heads._requantized(cfg, state, new_index, table,
                                  key), metrics

    return refresh_quantized


# ---------------------------------------------------------------------------
# abstract specs for the dry-run
# ---------------------------------------------------------------------------

def batch_struct(cfg: ModelConfig, shape: ShapeConfig,
                 batch_sharding=None, replicated=None) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of a train/prefill
    step (weak-type-correct, shardable, no allocation)."""
    b, s = shape.global_batch, shape.seq_len
    mk = functools.partial(jax.ShapeDtypeStruct)
    batch = {
        "tokens": mk((b, s), jnp.int32, sharding=batch_sharding),
        "labels": mk((b, s), jnp.int32, sharding=batch_sharding),
    }
    if cfg.family == "vlm":
        batch["image_emb"] = mk((b, cfg.num_image_tokens, cfg.d_model),
                                jnp.dtype(cfg.dtype), sharding=batch_sharding)
    if cfg.family == "audio":
        batch["frames"] = mk((b, cfg.encoder_seq, cfg.d_model),
                             jnp.dtype(cfg.dtype), sharding=batch_sharding)
    if shape.kind == "prefill":
        batch.pop("labels")
    return batch


def key_struct(sharding=None):
    return jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=sharding)


def abstract_params(cfg: ModelConfig, cast_dtype: Optional[str] = None):
    out = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    if cast_dtype is not None:
        dt = jnp.dtype(cast_dtype)
        out = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, dt), out)
    return out


def abstract_decode_state(cfg: ModelConfig, params_abs, bsz: int,
                          max_seq: int, window: Optional[int] = None):
    def build(params):
        kw = {}
        if cfg.family == "vlm":
            kw["image_emb"] = jnp.zeros((bsz, cfg.num_image_tokens, cfg.d_model),
                                        jnp.dtype(cfg.dtype))
        if cfg.family == "audio":
            kw["frames"] = jnp.zeros((bsz, cfg.encoder_seq, cfg.d_model),
                                     jnp.dtype(cfg.dtype))
        return init_decode_state(cfg, params, bsz, max_seq, window=window, **kw)

    return jax.eval_shape(build, params_abs)


def abstract_index(cfg: ModelConfig, params_abs):
    def build(params):
        return heads.init_head_state(cfg, params, jax.random.PRNGKey(0))
    return jax.eval_shape(build, params_abs)


def abstract_vocab_index(cfg: ModelConfig, params_abs, vp: int):
    """ShapeDtypeStructs of the VocabShardedIndex at `vp` shards."""
    from repro.dist import vocab_parallel as vp_mod

    def build(params):
        # quantized head states shard their bare MultiIndex — the vp loss
        # quantizes each shard's row slice in-step (dist/vocab_parallel.py)
        state = heads.init_head_state(cfg, params, jax.random.PRNGKey(0))
        return vp_mod.shard_index(unwrap_index(state), vp)

    return jax.eval_shape(build, params_abs)
