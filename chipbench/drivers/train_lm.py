"""Training driver: the program's compiled train step and its index
lifecycle, fed seeded language-model batches, for a fixed number of seconds.

Set-up builds one object, the jitted `launch.steps.make_train_step` with its
parameters, AdamW state and MIDX index, and drives it through its first
`check_steps` steps on batches whose rows all differ. Those steps go through
the same call and feed as the window's, and the window continues from the
state they leave. The loop is `launch.train.train_loop`'s inner loop: step,
sync on the loss, then `IndexLifecycle.step`, which refreshes the index
every `refresh_every` steps (the first refresh runs once in set-up, so its
program is compiled before the window opens).

Once the window has closed and the peak memory has been read, the program's
state is freed and the reference follows the first steps from the same
weights, index, batches and keys. Compared, each by its worst case:
  loss_rel         |loss - ref| / |ref| over the checked steps
  grad_leaf_rel    the first gradient as AdamW received it (its first
                   moment after one step, / (1 - b1)), per leaf: the gap of
                   the two norms over the larger of the reference's norm of
                   that leaf and of the median leaf
  update_leaf_rel  the same for the change of each leaf after the checked
                   steps
Leaves whose reference gradient is under a thousandth of the median leaf's
are left out of both leaf comparisons (they move under Adam by round-off).

Traffic parameters (traffic/<name>.json): batch, seq, zipf_s,
distinct_batches, check_steps, and `optimizer` (lr, warmup_steps,
total_steps, final_frac, b1, b2, eps, weight_decay, clip_norm).
"""
from __future__ import annotations

import gc
import time

import numpy as np


def _leaf_names(tree) -> list[str]:
    import jax
    return [jax.tree_util.keystr(p)
            for p, _ in jax.tree_util.tree_leaves_with_path(tree)]


def compare_leaves(prog: np.ndarray, ref: np.ndarray, keep: np.ndarray
                   ) -> tuple[float, int]:
    """Worst leaf of |prog - ref| / max(ref, median ref) over `keep`."""
    base = np.maximum(ref, np.median(ref[keep]))
    rel = np.where(keep, np.abs(prog - ref) / base, 0.0)
    i = int(np.argmax(rel))
    return float(rel[i]), i


def readings(prog: dict, ref: dict) -> tuple[dict, dict]:
    """The numbers compared, and the detail behind them."""
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    loss_rel = float(np.max(np.abs(lp - lr) / np.abs(lr)))
    g_ref = ref["grad_norms"]
    keep = g_ref >= 1e-3 * np.median(g_ref)
    g_rel, gi = compare_leaves(prog["grad_norms"], g_ref, keep)
    u_rel, ui = compare_leaves(prog["change_norms"], ref["change_norms"],
                               keep)
    detail = {"losses": lp.tolist(), "ref_losses": lr.tolist(),
              "worst_grad_leaf": gi, "worst_update_leaf": ui,
              "left_out_leaves": np.flatnonzero(~keep).tolist()}
    return ({"loss_rel": loss_rel, "grad_leaf_rel": g_rel,
             "update_leaf_rel": u_rel}, detail)


def inputs(spec: dict, tr: dict, ref, seed: int, n_batches: int):
    """What a run starts from, made by the benchmark from the seed: the
    program's configuration, the keys, the weights (checked against the
    program's layout), the MIDX index arrays and n_batches batches."""
    import jax

    from chipbench import program
    cfg = program.program_config(spec)
    keys = jax.random.split(program.seed_key(seed), 6)
    params = ref.init_weights(spec, keys[0], cfg.padded_vocab)
    program.check_layout(cfg, params)
    idx = program.midx_index(params["embed"], keys[1], k=cfg.head.midx_k,
                             iters=cfg.head.kmeans_iters)
    data = program.lm_batches(keys[2], n=n_batches, batch=tr["batch"],
                              seq=tr["seq"], vocab=cfg.vocab_size,
                              zipf_s=tr["zipf_s"])
    return cfg, keys, params, idx, data


def run(ctx, *, fault=None) -> dict:
    """`fault`, for the tests only, wraps the compiled step to break it."""
    import jax
    import jax.numpy as jnp

    from chipbench import bench, program
    from repro.index import IndexLifecycle
    from repro.launch import steps as steps_mod
    from repro.optim import adamw, cosine_schedule

    spec, tr, ref = ctx.cell.spec, ctx.cell.traffic, ctx.cell.reference
    opt = tr["optimizer"]
    b, s = tr["batch"], tr["seq"]
    n_check = tr["check_steps"]
    with ctx.span("inputs"):
        cfg, keys, params, idx_arrays, (toks, labs) = inputs(
            spec, tr, ref, ctx.seed, tr["distinct_batches"])
    k_w, k_idx, k_data, k_loop, k_life, k_warm = keys
    m = cfg.head.num_negatives
    index = program.to_program_index(idx_arrays)
    ones = jnp.ones((b,), jnp.float32)
    batches = [{"tokens": toks[i], "labels": labs[i], "_fault_scale": ones}
               for i in range(toks.shape[0])]
    optimizer = adamw(cosine_schedule(opt["lr"], opt["warmup_steps"],
                                      opt["total_steps"], opt["final_frac"]),
                      b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
                      weight_decay=opt["weight_decay"])
    opt_state = optimizer.init(params)
    step_fn = jax.jit(steps_mod.make_train_step(cfg, optimizer,
                                                clip_norm=opt["clip_norm"]))
    if fault is not None:
        step_fn = fault(step_fn)
    refresh = jax.jit(steps_mod.make_refresh_step(cfg, head_mode="midx"))
    lifecycle = IndexLifecycle(refresh, every=cfg.head.refresh_every,
                               lag=cfg.head.refresh_lag, base_key=k_life)
    step_keys = [jax.random.fold_in(k_loop, i) for i in range(n_check)]

    state = {"params": params, "opt": opt_state, "index": index}
    del params, opt_state, index

    def one_step(i: int):
        with ctx.span("step"):
            p, o, met = step_fn(state["params"], state["opt"], state["index"],
                                batches[i % len(batches)],
                                jax.random.fold_in(k_loop, i))
            loss = float(met["loss"])                  # sync, as train_loop
            skipped = float(met["skipped"]) > 0.5
        state["params"], state["opt"] = p, o
        with ctx.span("refresh"):
            state["index"], ev = lifecycle.step(i, p, state["index"])
        return loss, skipped, ev

    # --- the checked steps, through the window's own call and feed
    prog = {"losses": []}
    grads_fn = jax.jit(lambda mu: [jnp.sqrt(jnp.sum(x * x)) / (1 - opt["b1"])
                                   for x in jax.tree_util.tree_leaves(mu)])
    change_fn = jax.jit(lambda p, p0: [
        jnp.sqrt(jnp.sum(jnp.square(a - b0)))
        for a, b0 in zip(jax.tree_util.tree_leaves(p),
                         jax.tree_util.tree_leaves(p0))])
    for i in range(n_check):
        loss, _, _ = one_step(i)
        prog["losses"].append(loss)
        if i == 0:
            prog["grad_norms"] = np.asarray(jax.device_get(
                grads_fn(state["opt"].mu)))
    p0 = ref.init_weights(spec, k_w, cfg.padded_vocab)
    prog["change_norms"] = np.asarray(jax.device_get(
        change_fn(state["params"], p0)))
    del p0
    # the refresh program runs once here, on a throwaway key, so the
    # window's first refresh neither compiles nor loads
    with ctx.span("warm_refresh"):
        jax.block_until_ready(refresh(state["params"], state["index"], k_warm))

    # --- the window
    steps, refreshes, failed = [], [], 0
    i = n_check
    t_open = ctx.open_window()
    while True:
        t0 = time.perf_counter()
        loss, skipped, ev = one_step(i)
        t1 = time.perf_counter()
        steps.append((t0, t1, b * s))
        failed += int(skipped or not np.isfinite(loss))
        if ev is not None:
            refreshes.append(ev.seconds)
        i += 1
        if t1 - t_open >= ctx.seconds:
            break
    t_close = ctx.close_window(steps[-1][1])
    trace = ctx.reduce_trace(ctx.cell.chips)
    peak = bench.memory_peak_bytes(ctx.cell.chips)

    # --- the reference, once the program's state is freed
    state.clear()
    batches_ref = [(batches[i]["tokens"], batches[i]["labels"])
                   for i in range(n_check)]
    del batches
    gc.collect()
    t_ref = time.perf_counter()
    params0 = ref.init_weights(spec, k_w, cfg.padded_vocab)
    ref_out = ref.train_readings(spec, params0, idx_arrays, batches_ref,
                                 step_keys, opt, m)
    ref_s = time.perf_counter() - t_ref
    nums, detail = readings(prog, ref_out)
    names = _leaf_names(params0)
    detail["worst_grad_leaf"] = names[detail["worst_grad_leaf"]]
    detail["worst_update_leaf"] = names[detail["worst_update_leaf"]]
    detail["left_out_leaves"] = [names[j] for j in detail["left_out_leaves"]]
    bench.log(f"check: {detail}; reference {ref_s:.1f}s")

    return {
        "setup_s": t_open - ctx.t_process,
        "window": (t_open, t_close),
        "attempted": len(steps),
        "failed": failed,
        "steps": steps,
        "refresh_s": refreshes,
        "spans": ctx.spans,
        "trace": trace,
        "memory_peak_bytes": peak,
        "readings": nums,
        "reference_s": ref_s,
        "work": {"batch": b, "seq": s, "negatives": m,
                 "d_model": cfg.d_model, "vocab_rows": cfg.padded_vocab},
        "spec": spec,
        "prog_readings": prog,
        "ref_readings": ref_out,
    }
