"""Training driver: fault-tolerant loop with the MIDX head as first-class.

Runs on any mesh: the CPU examples use a 1x1 debug mesh, the production
launch uses make_production_mesh(). Features (DESIGN §4):
  - checkpoint/restart: atomic step dirs; exact data-pipeline skip-ahead
  - index refresh cadence (the paper's per-epoch rebuild, jitted)
  - straggler watchdog: step-time EWMA; slow-step log + microbatch
    re-balancing hook
  - optional bf16-compressed DP all-reduce (config)

Usage (CPU demo):
  PYTHONPATH=src python -m repro.launch.train --arch paper-lm --steps 200
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import (CheckpointError, CheckpointManager,
                              save_serving_state)
from repro.configs import get_config
from repro.data import ZipfLM, make_lm_stream
from repro.index import IndexLifecycle
from repro.launch import steps as steps_mod
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import (make_debug_mesh, make_vocab_mesh, mesh_dp_tp,
                               mesh_vp)
from repro.models import heads, init_params
from repro.optim import adamw, cosine_schedule
from repro.resilience import FaultSpec, InjectedFault, TrainGuardrails
from repro.utils import metrics as metrics_mod


@dataclasses.dataclass
class StragglerWatchdog:
    """EWMA step-time monitor. At scale each host reports its step time; a
    host whose EWMA exceeds `threshold` x the fleet median gets its grad-accum
    microbatches re-balanced (the data pipeline's (step, shard) determinism
    makes the handoff stateless). Here we expose detection + the re-balance
    decision; the single-process demo logs it."""
    alpha: float = 0.2
    threshold: float = 1.8
    ewma: Optional[float] = None
    trips: int = 0

    def observe(self, dt: float, fleet_median: Optional[float] = None) -> bool:
        self.ewma = dt if self.ewma is None else \
            (1 - self.alpha) * self.ewma + self.alpha * dt
        ref = fleet_median if fleet_median is not None else self.ewma
        slow = dt > self.threshold * max(ref, 1e-9)
        if slow:
            self.trips += 1
        return slow

    def rebalance_plan(self, num_microbatches: int) -> dict:
        """Shed one microbatch to the fastest peer (returned as a plan; the
        multi-host launcher applies it via the deterministic pipeline)."""
        return {"shed_microbatches": 1 if self.trips > 0 else 0,
                "of": num_microbatches}


def train_loop(cfg, *, steps: int, batch_size: int, seq_len: int,
               ckpt_dir: Optional[str] = None, ckpt_every: int = 100,
               corpus: Optional[np.ndarray] = None, lr: float = 3e-4,
               head_mode: Optional[str] = None, log_every: int = 20,
               seed: int = 0, mesh=None, total_steps: Optional[int] = None,
               grad_transport: str = "fp32",
               fused_head: Optional[bool] = None,
               fused_interpret: bool = False,
               refresh_every: Optional[int] = None,
               refresh_policy: Optional[str] = None,
               refresh_lag: Optional[int] = None,
               on_metrics: Optional[Callable[[int, dict], None]] = None,
               on_refresh: Optional[Callable[[Any], None]] = None,
               injector=None, guardrails=None):
    """Single-process training loop (the multi-host launcher shards this).

    total_steps: the JOB's schedule horizon — must stay fixed across
    preemption/resume legs so the LR schedule (and therefore the resumed
    trajectory) is bit-identical to an uninterrupted run.

    injector: an optional repro.resilience.FaultInjector. The loop feeds it
    the step clock and routes its faults through three seams: a [batch]
    `_fault_scale` leaf multiplied into the loss (always present, 1.0 when
    quiet — multiplying by 1.0 is IEEE-exact, so a fault-free injector
    leaves the trajectory bit-identical), the IndexLifecycle refresh_fn
    wrapper, and the CheckpointManager save-phase hook.

    guardrails: an optional repro.resilience.GuardrailConfig. The host-side
    TrainGuardrails monitor always runs; a 'rollback' action restores the
    newest checkpoint that passes verification, rewinds the step counter and
    replays (DESIGN §11). Replay is bit-exact because batches, step keys and
    the LR schedule are all pure functions of (seed, step, total_steps).

    mesh / grad_transport: with a mesh (or a non-fp32 transport, which forces
    a data-only debug mesh over all local devices) the loop runs
    steps.make_sharded_train_step — explicit shard_map data parallelism with
    the chosen gradient all-reduce transport (DESIGN §4).  The int8 error-
    feedback carry is step-local state: it deliberately re-zeros on restart
    rather than being checkpointed (it is a sub-quantum correction).
    """
    refresh_kw = {k: v for k, v in (("refresh_every", refresh_every),
                                    ("refresh_policy", refresh_policy),
                                    ("refresh_lag", refresh_lag))
                  if v is not None}
    if refresh_kw:
        cfg = cfg.with_head(**refresh_kw)
    # resolve + validate the head mode up front: an unknown mode raises the
    # registry's informative error here instead of silently training MIDX
    mode, proposal = steps_mod.resolve_proposal(cfg, head_mode)
    key = jax.random.PRNGKey(seed)
    k_init, k_index, k_loop = jax.random.split(key, 3)
    horizon = total_steps or steps

    params = init_params(cfg, k_init)
    optimizer = adamw(cosine_schedule(lr,
                                      warmup_steps=min(100, horizon // 10 + 1),
                                      total_steps=horizon))
    opt_state = optimizer.init(params)

    if corpus is None:
        gen = ZipfLM(vocab_size=cfg.vocab_size, num_clusters=64,
                     seq_len=seq_len + 1, seed=seed)
        corpus = gen.sample(max(512, batch_size * 4))
    stream = make_lm_stream(corpus, batch_size, seed=seed)

    if mesh is None and grad_transport != "fp32":
        mesh = make_debug_mesh(jax.device_count(), 1)
    dp = 1
    vp = mesh_vp(mesh) if mesh is not None else 1
    if vp > 1:
        # vocab-parallel layout (DESIGN §9): class tables + MIDX index
        # row-shard over the vocab axis; its own step/init/refresh family
        if mode != "midx":
            raise ValueError("vocab-parallel training requires the midx head")
        if grad_transport != "fp32":
            raise ValueError("compressed grad transports are not wired into "
                             "the vocab-parallel step; use fp32")
    returns_state = False   # True only for trainable proposals (single-dev)
    if mesh is not None:
        dp, _ = mesh_dp_tp(mesh)
        data_axes = tuple(a for a in mesh.axis_names
                          if a not in ("model", "vocab"))
        if batch_size % dp:
            raise ValueError(f"--batch {batch_size} must be divisible by "
                             f"the data-parallel degree {dp}")
        if vp > 1:
            train_step = jax.jit(steps_mod.make_vocab_parallel_train_step(
                cfg, optimizer, mesh, data_axes=data_axes,
                fused_head=fused_head, interpret=fused_interpret))
        else:
            train_step = jax.jit(steps_mod.make_sharded_train_step(
                cfg, optimizer, mesh, data_axes=data_axes,
                grad_transport=grad_transport, head_mode=head_mode,
                fused_head=fused_head, interpret=fused_interpret))
    else:
        step_fn = steps_mod.make_train_step(
            cfg, optimizer, head_mode=head_mode, fused_head=fused_head,
            interpret=fused_interpret)
        # read BEFORE jit: the jit wrapper drops closure attributes
        returns_state = getattr(step_fn, "returns_state", False)
        train_step = jax.jit(step_fn)
    if vp > 1:
        index = jax.jit(steps_mod.make_vocab_index_init(cfg, mesh))(
            params, k_index)
    elif proposal is not None:
        # generic contender: unigram-family proposals want the corpus
        # frequency; everyone else ignores it
        freq = np.bincount(np.asarray(corpus).reshape(-1),
                           minlength=cfg.padded_vocab).astype(np.float64)
        index = heads.init_proposal_state(cfg, params, k_index, proposal,
                                          freq)
    else:
        index = heads.init_head_state(cfg, params, k_index)
    ef = steps_mod.init_grad_transport_state(params, grad_transport, dp)
    # head-state lifecycle (DESIGN §8): the refresh for step s runs on
    # dispatch while up to `refresh_lag` subsequent steps train against the
    # old state; on a mesh the MIDX rebuild is sharded over the data axes
    # (vp > 1: each vocab shard refits its own subindex natively — no
    # all-gather). Generic adaptive proposals refresh replicated.
    if vp > 1:
        refresh = jax.jit(steps_mod.make_vocab_refresh_step(cfg, mesh))
    elif mesh is not None and proposal is None:
        refresh = jax.jit(steps_mod.make_refresh_step(
            cfg, mesh, data_axes=tuple(a for a in mesh.axis_names
                                       if a != "model")))
    else:
        refresh = jax.jit(steps_mod.make_refresh_step(cfg, head_mode=mode))
    if injector is not None:
        refresh = injector.wrap_refresh(refresh)
    lifecycle = IndexLifecycle(
        refresh, every=cfg.head.refresh_every, lag=cfg.head.refresh_lag,
        base_key=k_index,
        enabled=(mode == "midx") or (proposal is not None
                                     and proposal.adaptive))

    ckpt = CheckpointManager(ckpt_dir) if ckpt_dir else None
    if ckpt is not None and injector is not None:
        injector.attach_checkpoint(ckpt)
    start_step = 0
    if ckpt is not None:
        # restore-fallback walk: resume from the newest checkpoint that
        # passes verification, skipping corrupt/mismatched step dirs
        s = ckpt.latest_verified_step((params, opt_state, index))
        if s is not None:
            params, opt_state, index = ckpt.restore(
                s, (params, opt_state, index))
            start_step = ckpt.metadata(s).get("next_step", s)
            print(f"[train] resumed from step {start_step}")

    guard = TrainGuardrails(guardrails)
    watchdog = StragglerWatchdog()
    num_micro = max(1, batch_size // max(dp, 1))
    history = []
    leg_start = start_step
    step = start_step
    while step < steps:
        if injector is not None:
            injector.note_step(step)
        batch = stream.batch_at(step)                 # skip-ahead-safe
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        # fault seam: always traced so the jitted program — and therefore
        # the fault-free trajectory — is identical with or without chaos
        scale = injector.loss_scale(step) if injector is not None else 1.0
        batch["_fault_scale"] = jnp.full((batch_size,), scale, jnp.float32)
        k_step = jax.random.fold_in(k_loop, step)
        t0 = time.time()
        if injector is not None:
            injector.maybe_sleep(step)
        if vp > 1:
            params, opt_state, metrics = train_step(params, opt_state, index,
                                                    batch, k_step)
        elif mesh is not None:
            params, opt_state, metrics, ef = train_step(
                params, opt_state, index, batch, k_step, ef)
        elif returns_state:
            params, opt_state, index, metrics = train_step(
                params, opt_state, index, batch, k_step)
        else:
            params, opt_state, metrics = train_step(params, opt_state, index,
                                                    batch, k_step)
        loss = float(metrics["loss"])                  # sync point
        skipped = float(metrics.get("skipped", 0.0)) > 0.5
        dt = time.time() - t0
        slow = watchdog.observe(dt)
        if slow:
            print(f"[train] straggler warning at step {step}: {dt:.3f}s "
                  f"(ewma {watchdog.ewma:.3f}s) -> "
                  f"{watchdog.rebalance_plan(num_micro)}")
        action = guard.observe(step, loss, skipped=skipped)
        if skipped:
            print(f"[train] step {step}: non-finite update skipped "
                  f"(loss {loss}, params/opt state unchanged)")
        if action == "rollback":
            if ckpt is None:
                print(f"[train] guardrails requested rollback at step {step} "
                      "but no ckpt_dir is set — continuing degraded")
            else:
                try:
                    # the pending refresh was built from params that are
                    # about to be discarded — never swap it in
                    lifecycle.abort()
                    s2, (params, opt_state, index) = \
                        ckpt.restore_latest_verified((params, opt_state,
                                                      index))
                    resume = ckpt.metadata(s2).get("next_step", s2)
                    print(f"[train] rollback at step {step}: restored "
                          f"checkpoint {s2}, replaying from step {resume}")
                    del history[max(0, resume - leg_start):]
                    step = resume
                    continue
                except CheckpointError as e:
                    print(f"[train] rollback impossible ({e}) — continuing")
        index, ev = lifecycle.step(step, params, index)
        if ev is not None:
            print(f"[train] refresh @{ev.step} (swap @{ev.swap_step}) "
                  f"mode={ev.mode} {ev.seconds:.3f}s "
                  f"reassigned={float(ev.metrics.get('reassigned_frac', 0.0)):.3f} "
                  f"drift={float(ev.metrics.get('codeword_drift', 0.0)):.3f}")
            if ev.rejected:
                print(f"[train] refresh @{ev.step} REJECTED: "
                      f"{'; '.join(ev.reasons)} — keeping live state")
            if on_refresh:
                on_refresh(ev)
        if step % log_every == 0 or step == steps - 1:
            print(f"[train] step {step:5d} loss {loss:.4f} "
                  f"ce {float(metrics['ce']):.4f} "
                  f"gnorm {float(metrics['grad_norm']):.3f} ({dt:.3f}s)")
        history.append(loss)
        if on_metrics:
            on_metrics(step, {**metrics, "guard_action": action,
                              "straggler": 1.0 if slow else 0.0})
        if ckpt is not None and (step + 1) % ckpt_every == 0:
            # the saved index must never be mid-flight: force-complete any
            # pending refresh so restore resumes from a self-contained state
            index, ev = lifecycle.flush(step, index)
            if ev is not None and on_refresh:
                on_refresh(ev)
            try:
                ckpt.save(step + 1, (params, opt_state, index),
                          metadata={"next_step": step + 1})
            except InjectedFault as e:
                print(f"[train] checkpoint save at step {step + 1} "
                      f"killed: {e} — previous checkpoint still intact")
        step += 1
    index, ev = lifecycle.flush(steps - 1, index)
    if ev is not None and on_refresh:
        on_refresh(ev)
    if lifecycle.events:
        s = metrics_mod.refresh_summary(lifecycle.events)
        print(f"[train] refresh summary: {s['refreshes']} events "
              f"({s['full_refits']} full / {s['reassign_only']} reassign / "
              f"{s.get('rejected', 0)} rejected) "
              f"{s['refresh_s']:.2f}s total")
    if guard.events:
        gs = guard.summary()
        print(f"[train] guardrail summary: {gs['skips']} skips, "
              f"{gs['spikes']} spikes, {gs['rollbacks']} rollbacks")
    if ckpt is not None:
        try:
            ckpt.save(steps, (params, opt_state, index),
                      metadata={"next_step": steps})
        except InjectedFault as e:
            print(f"[train] final checkpoint save killed: {e}")
        # serving export: {"params","index"} only (no opt state) — what
        # `serve.Engine.from_checkpoint` restores (DESIGN §5). The serving
        # stack consumes the replicated index layout, so a vocab-parallel
        # run first merges its sharded index (pure re-layout, bit-identical
        # assignments) and gathers params to host before the export
        if vp > 1:
            from repro.dist.vocab_parallel import unshard_index
            export_index = jax.device_get(unshard_index(index))
            export_params = jax.tree_util.tree_map(jax.device_get, params)
        else:
            export_index, export_params = index, params
        save_serving_state(os.path.join(ckpt_dir, "serve"), steps,
                           export_params, export_index,
                           metadata={"arch": cfg.name})
    return params, opt_state, index, history


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="paper-lm")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--reduced", action="store_true",
                    help="use the reduced (CPU smoke) config")
    from repro.proposals import proposal_modes
    ap.add_argument("--head", default=None,
                    choices=(None, *proposal_modes()),
                    help="head mode: midx/full use the dedicated heads; any "
                         "other repro.proposals contender routes through "
                         "the generic sampled-softmax seam")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--dp", type=int, default=0,
                    help="data-parallel degree; >0 runs the shard_map step "
                         "on a (dp, 1) debug mesh")
    ap.add_argument("--vocab-parallel", type=int, default=1,
                    help="vocab-parallel degree; >1 row-shards the class "
                         "table + MIDX index over a (dp, vocab) mesh "
                         "(DESIGN §9; needs dp*vocab local devices)")
    ap.add_argument("--grad-transport", default="fp32",
                    choices=("fp32", "bf16", "int8_ef"),
                    help="gradient all-reduce transport (DESIGN §4)")
    ap.add_argument("--fused-head", default="auto",
                    choices=("auto", "on", "interpret", "off"),
                    help="fused Pallas MIDX head (DESIGN §3): auto = "
                         "cfg.head.use_fused_head gated on backend; on = "
                         "compiled kernels (TPU only); interpret = fused "
                         "graph via the Pallas interpreter and off = the "
                         "jnp head (CPU test tools, refused on a TPU)")
    ap.add_argument("--table-dtype", default=None,
                    help="class-table storage on the head hot path "
                         "(DESIGN §12): bf16 = master precision (default), "
                         "int8/fp8 = per-row-scaled low-bit table + "
                         "quantized proposal codebooks + PQ-code residual "
                         "rescore; unknown values raise at step-build time")
    ap.add_argument("--refresh-every", type=int, default=None,
                    help="steps between index refresh events "
                         "(default: cfg.head.refresh_every)")
    ap.add_argument("--refresh-policy", default=None,
                    choices=(None, "fixed", "drift"),
                    help="index refresh policy (DESIGN §8): fixed = full "
                         "warm-started refit every event; drift = reassign-"
                         "only, escalating to the refit when drift exceeds "
                         "cfg.head.refresh_drift_threshold")
    ap.add_argument("--refresh-lag", type=int, default=None,
                    help="staleness window: swap the rebuilt index in this "
                         "many steps after dispatch (0 = synchronous)")
    ap.add_argument("--chaos", default=None,
                    help="fault plan, comma-separated 'kind@step[:mode_or_"
                         "arg]' specs (DESIGN §11), e.g. 'nan_loss@10,"
                         "degenerate_refresh@24:empty,slow_step@5:0.2,"
                         "kill_mid_save@100:committed'")
    ap.add_argument("--chaos-seed", type=int, default=0,
                    help="seed of the injector's (seed, step) fault streams")
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.table_dtype is not None:
        cfg = cfg.with_head(table_dtype=args.table_dtype)
    if args.vocab_parallel > 1:
        mesh = make_vocab_mesh(data=max(args.dp, 1),
                               vocab=args.vocab_parallel)
    else:
        mesh = make_debug_mesh(args.dp, 1) if args.dp > 0 else None
    fused = {"auto": None, "on": True, "interpret": True,
             "off": False}[args.fused_head]
    on_tpu = jax.default_backend() == "tpu"
    if args.fused_head == "on" and not on_tpu:
        raise SystemExit("--fused-head on compiles Pallas kernels and needs "
                         "a TPU backend; use --fused-head interpret here")
    if args.fused_head in ("interpret", "off") and on_tpu:
        raise SystemExit(f"--fused-head {args.fused_head} is a CPU test "
                         "tool; on a TPU the head runs the compiled kernels")
    injector = None
    if args.chaos:
        injector = _parse_chaos(args.chaos, args.chaos_seed)
    train_loop(cfg, steps=args.steps, batch_size=args.batch, seq_len=args.seq,
               ckpt_dir=args.ckpt, head_mode=args.head, lr=args.lr,
               mesh=mesh, grad_transport=args.grad_transport,
               fused_head=fused,
               fused_interpret=args.fused_head == "interpret",
               refresh_every=args.refresh_every,
               refresh_policy=args.refresh_policy,
               refresh_lag=args.refresh_lag,
               injector=injector)
    if injector is not None:
        print(f"[train] chaos report: {injector.summary()}")


def _parse_chaos(plan: str, seed: int):
    """'kind@step[:mode_or_arg]' specs -> a FaultInjector. A numeric suffix
    becomes FaultSpec.arg (spike factor, sleep seconds); anything else
    becomes FaultSpec.mode (refresh degeneracy, save phase)."""
    from repro.resilience import FaultInjector
    specs = []
    for item in plan.split(","):
        item = item.strip()
        if not item:
            continue
        kind, _, rest = item.partition("@")
        step_s, _, extra = rest.partition(":")
        spec = FaultSpec(kind=kind, step=int(step_s) if step_s else -1)
        if extra:
            try:
                spec = dataclasses.replace(spec, arg=float(extra))
            except ValueError:
                spec = dataclasses.replace(spec, mode=extra)
        specs.append(spec)
    return FaultInjector(seed, specs)


if __name__ == "__main__":
    main()
