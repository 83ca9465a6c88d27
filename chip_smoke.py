"""Smoke run of MIDX training and serving on a TPU, through the entry points
users call (`repro.launch.train.train_loop`, `repro.serve.Engine`), at the
published widths and depths of the models, with random weights from a seed.

    python chip_smoke.py               # one chip: the three phases below
    python chip_smoke.py --four-chips  # the vocab-parallel head on 4 chips

One chip:
  train-pooled     mamba2-370m (48 layers, d=1024, V=50280), MIDX head with
                   its configured shared-negative `pooled` proposal, batch
                   8 x seq 1024, 4 steps, one index refresh;
  train-per-token  paper-lm (the paper's §6.2 LM) with the `per_token`
                   proposal: midx_probs and the per-token flash-CE, forward
                   and backward;
  serve            granite-moe-1b-a400m through serve.Engine, head="midx":
                   8 greedy requests (128-token prompts, 32 new tokens,
                   8 slots). The engine decodes greedily with the MIDX head
                   only through speculative decoding (MIDX drafts, exact
                   verify), so the engine runs with spec_decode=4.
Each training phase checks that every loss is finite and no step was
skipped, that the jitted step holds compiled Pallas kernels
(`tpu_custom_call`), and that its step-0 loss matches the unfused jnp head on
the same batch and key. The serving phase checks that every request returns
its 32 tokens and that one request replayed alone returns the same tokens.

Four chips: mamba2-370m's vocab-parallel train step on a (data=1, vocab=4)
mesh against the replicated one-device step on the same batches and keys,
and the class-table bytes each device holds.

Nothing is caught and nothing falls back: without a TPU, or with an
environment override of the kernels set, the script exits non-zero before
any phase. The last line of standard output is the JSON result.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.configs.base import pad_to  # noqa: E402
from repro.data import ZipfLM, make_lm_stream  # noqa: E402
from repro.launch import steps as steps_mod  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.mesh import make_vocab_mesh  # noqa: E402
from repro.launch.train import train_loop  # noqa: E402
from repro.models import heads, init_params  # noqa: E402
from repro.optim import adamw  # noqa: E402
from repro.serve import Engine, Request  # noqa: E402

# overrides older code read to divert the kernels; a run with either set
# would not say what it claims to
FORBIDDEN_ENV = ("REPRO_PALLAS_INTERPRET", "REPRO_FUSED_HEAD")

# Fused vs unfused step-0 loss, relative. XLA may run the jnp head's
# matmuls at the TPU's default precision (one bf16 pass, ~2^-8 relative per
# dot) while the kernels accumulate in fp32, and the per-token jnp path
# builds its proposal tables without the midx_probs kernel, so a draw whose
# two best candidates tie to that precision may differ. Either moves the
# mean loss far less than 1% (on a v5e the gap was below 1e-6); a faulty
# kernel (wrong row, lost ln(M·q) correction, no collision mask) moves it by
# O(1) (ln M is 7 for M=1024, 3 for M=20).
FUSED_RTOL = 1e-2
# Vocab-parallel vs replicated loss, relative, per step. Identical draws
# and identical per-row logits; the only difference is the order in which
# the partial log-sum-exps and the gradients combine (fp32, ~1e-7). From
# the second step on, AdamW's normalised update turns a rounding-sized
# difference in a near-zero gradient coordinate into a step of size lr.
VP_RTOL = 1e-3

TRAIN_BATCH, TRAIN_SEQ = 8, 1024
SERVE_REQUESTS, SERVE_PROMPT, SERVE_NEW, SERVE_SPEC = 8, 128, 32, 4


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def peak_bytes() -> int | None:
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def device_check(chips: int) -> dict:
    for name in FORBIDDEN_ENV:
        if os.environ.get(name):
            raise SystemExit(f"{name} is set; unset it: on a TPU the head "
                             "runs the compiled kernels and nothing else")
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    log(f"devices: {dev}")
    if dev["platform"] != "tpu":
        raise SystemExit(f"no TPU: JAX found {dev['platform']}")
    if dev["count"] < chips:
        raise SystemExit(f"needs {chips} chips, JAX found {dev['count']}")
    return dev


def lm_batches(cfg, batch: int, seq: int, steps: int, seed: int) -> list:
    """The batches train_loop feeds at steps 0..steps-1 (same corpus, same
    stream)."""
    corpus = ZipfLM(vocab_size=cfg.vocab_size, num_clusters=64,
                    seq_len=seq + 1, seed=seed).sample(max(512, batch * 4))
    stream = make_lm_stream(corpus, batch, seed=seed)
    return [{"tokens": jnp.asarray(b["tokens"]),
             "labels": jnp.asarray(b["labels"]),
             "_fault_scale": jnp.ones((batch,), jnp.float32)}
            for b in map(stream.batch_at, range(steps))]


def assert_kernels(cfg, params, opt_state, index, batch) -> None:
    """The train step train_loop jits (same builder, same head decision)
    lowers to compiled Pallas kernels: neither the interpreter nor the jnp
    oracle emits `tpu_custom_call`."""
    step = steps_mod.make_train_step(cfg, adamw(3e-4))
    text = jax.jit(step).lower(params, opt_state, index, batch,
                               jax.random.PRNGKey(0)).as_text()
    n = text.count("tpu_custom_call")
    log(f"{cfg.name}: step HLO holds {n} tpu_custom_call sites")
    if n == 0:
        raise AssertionError(f"{cfg.name}: the train step runs no kernel")


def train_phase(name: str, cfg, *, steps: int, refresh_every: int,
                batch: int = TRAIN_BATCH, seq: int = TRAIN_SEQ,
                seed: int = 0, check=assert_kernels) -> dict:
    stamps, losses, skipped, refreshes = [], [], [], []

    def on_metrics(step, m):
        stamps.append(time.perf_counter())
        losses.append(float(m["loss"]))
        skipped.append(float(m["skipped"]))

    t0 = time.perf_counter()
    params, opt_state, index, _ = train_loop(
        cfg, steps=steps, batch_size=batch, seq_len=seq, seed=seed,
        refresh_every=refresh_every, log_every=1, on_metrics=on_metrics,
        on_refresh=refreshes.append)
    first_s = stamps[0] - t0
    step_s = np.diff(stamps).tolist()
    if not np.all(np.isfinite(losses)):
        raise AssertionError(f"{name}: non-finite loss {losses}")
    if any(skipped):
        raise AssertionError(f"{name}: skipped steps {skipped}")
    if not refreshes:
        raise AssertionError(f"{name}: no index refresh ran")
    check(cfg, params, opt_state, index,
          lm_batches(cfg, batch, seq, 1, seed)[0])
    del params, opt_state, index

    # the unfused jnp head on the same params, batch and key (same seed)
    t1 = time.perf_counter()
    *_, ref = train_loop(cfg, steps=1, batch_size=batch, seq_len=seq,
                         seed=seed, refresh_every=refresh_every,
                         fused_head=False, log_every=1)
    unfused_s = time.perf_counter() - t1
    rel = abs(losses[0] - ref[0]) / abs(ref[0])
    out = {"phase": name, "arch": cfg.name, "proposal": cfg.head.proposal,
           "batch": batch, "seq": seq, "losses": losses,
           "unfused_step0_loss": ref[0], "fused_vs_unfused_rel": rel,
           "rtol": FUSED_RTOL, "first_step_s_incl_setup_compile": first_s,
           "step_s": step_s,
           "refresh_s": [ev.seconds for ev in refreshes],
           "unfused_run_s_incl_compile": unfused_s,
           "peak_bytes": peak_bytes()}
    log(json.dumps(out))
    if not rel <= FUSED_RTOL:
        raise AssertionError(f"{name}: fused step-0 loss {losses[0]} vs "
                             f"unfused {ref[0]} (rel {rel:.3g})")
    return out


def serve_phase(cfg, *, seed: int = 0) -> dict:
    cfg = cfg.with_head(decode_temperature=0.0).with_serve(
        max_slots=SERVE_REQUESTS, page_size=16,
        max_seq=pad_to(SERVE_PROMPT + SERVE_NEW + SERVE_SPEC, 16),
        spec_decode=SERVE_SPEC)
    t0 = time.perf_counter()
    engine = Engine(cfg, init_key=jax.random.PRNGKey(seed), head="midx")
    engine.warmup([SERVE_PROMPT])
    warm_s = time.perf_counter() - t0
    rng = np.random.default_rng(seed)
    reqs = [Request(rid=i, tokens=rng.integers(0, cfg.vocab_size,
                                               SERVE_PROMPT).astype(np.int32),
                    max_new=SERVE_NEW, seed=seed)
            for i in range(SERVE_REQUESTS)]
    t1 = time.perf_counter()
    results = engine.run(reqs)
    run_s = time.perf_counter() - t1
    for r in reqs:
        res = results[r.rid]
        if res.status != "ok" or len(res.tokens) != SERVE_NEW:
            raise AssertionError(f"request {r.rid}: {res.status} "
                                 f"{res.reason} with {len(res.tokens)} tokens")
    t2 = time.perf_counter()
    solo = engine.replay_single(reqs[0])
    solo_s = time.perf_counter() - t2
    if not np.array_equal(solo, results[reqs[0].rid].tokens):
        raise AssertionError("request 0 replayed alone gave other tokens: "
                             f"{solo.tolist()} vs "
                             f"{results[reqs[0].rid].tokens.tolist()}")
    out = {"phase": "serve", "arch": cfg.name, "requests": len(reqs),
           "new_tokens": SERVE_NEW, "spec_decode": SERVE_SPEC,
           "engine_init_and_warmup_s": warm_s, "run_s": run_s,
           "solo_replay_s_incl_compile": solo_s,
           "accept_rate": engine.stats.accept_rate(),
           "summary": engine.stats.summary(),
           "sample_tokens": results[reqs[0].rid].tokens[:8].tolist(),
           "peak_bytes": peak_bytes()}
    log(json.dumps(out))
    return out


def four_chip_phase(cfg, *, steps: int = 3, vp: int = 4,
                    batch: int = TRAIN_BATCH, seq: int = TRAIN_SEQ,
                    seed: int = 0) -> dict:
    """make_vocab_parallel_train_step on a (1, vp) mesh vs make_train_step
    on one device, from the same params, index, batches and keys."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.dist import vocab_parallel as vp_mod
    from repro.dist.sharding import vocab_param_specs
    from repro.optim.optimizers import OptState

    opt = adamw(3e-4)
    key = jax.random.PRNGKey(seed)
    k_init, k_index, k_step = jax.random.split(key, 3)
    batches = lm_batches(cfg, batch, seq, steps, seed)
    keys = [jax.random.fold_in(k_step, s) for s in range(steps)]

    def run(step_fn, index, key_of, place=lambda *state: state):
        params = init_params(cfg, k_init)
        params, opt_state = place(params, opt.init(params))
        losses, stamps = [], [time.perf_counter()]
        for s in range(steps):
            params, opt_state, m = step_fn(params, opt_state, index,
                                           batches[s], key_of(keys[s]))
            losses.append(float(m["loss"]))
            stamps.append(time.perf_counter())
        return params, losses, np.diff(stamps).tolist()

    index = heads.init_head_state(cfg, init_params(cfg, k_init), k_index)
    mesh = make_vocab_mesh(data=1, vocab=vp)
    step_vp = jax.jit(steps_mod.make_vocab_parallel_train_step(cfg, opt,
                                                               mesh))

    def place(params, opt_state):
        # start from the layout the step returns, so it compiles once
        specs = vocab_param_specs(cfg, params, vp=vp)

        def put(tree, tree_specs):
            return jax.tree.map(
                lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
                tree, tree_specs)

        return put(params, specs), OptState(put(opt_state.step, P()),
                                            put(opt_state.mu, specs),
                                            put(opt_state.nu, specs))

    params, vp_losses, vp_s = run(step_vp, vp_mod.shard_index(index, vp),
                                  lambda k: k, place)
    table = params["embed"]
    shards = {s.device.id: s.data.nbytes for s in table.addressable_shards}
    del params
    # the vp step folds its key with the data-shard index (0 here)
    step_ref = jax.jit(steps_mod.make_train_step(cfg, opt))
    _, ref_losses, ref_s = run(step_ref, index,
                               lambda k: jax.random.fold_in(k, 0))
    rel = [abs(a - b) / abs(b) for a, b in zip(vp_losses, ref_losses)]
    out = {"phase": "vocab-parallel", "arch": cfg.name, "vp": vp,
           "vp_losses": vp_losses, "replicated_losses": ref_losses,
           "rel": rel, "rtol": VP_RTOL, "vp_step_s": vp_s,
           "replicated_step_s": ref_s, "table_bytes": table.nbytes,
           "table_bytes_per_device": shards, "peak_bytes": peak_bytes()}
    log(json.dumps(out))
    if not all(np.isfinite(vp_losses + ref_losses)):
        raise AssertionError("non-finite loss")
    if not max(rel) <= VP_RTOL:
        raise AssertionError(f"vp losses {vp_losses} vs replicated "
                             f"{ref_losses}")
    if (len(shards) != vp
            or any(b * vp != table.nbytes for b in shards.values())):
        raise AssertionError(f"class table not split {vp} ways: {shards}")
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the vocab-parallel head on 4 chips and "
                         "its comparison with the replicated step")
    args = ap.parse_args()
    dev = device_check(4 if args.four_chips else 1)
    use_compile_cache()
    t0 = time.perf_counter()
    if args.four_chips:
        four_chip_phase(get_config("mamba2-370m"))
    else:
        train_phase("train-pooled", get_config("mamba2-370m"), steps=4,
                    refresh_every=3)
        train_phase("train-per-token", get_config("paper-lm"), steps=4,
                    refresh_every=3)
        serve_phase(get_config("granite-moe-1b-a400m"))
    log(f"all phases passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": dev}))


if __name__ == "__main__":
    main()
