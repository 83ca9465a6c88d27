"""Serving CLI: a thin driver over `repro.serve.Engine` (DESIGN §5).

Continuous batching over a paged KV pool, batched single-pass prefill, and
two decode heads:
  --head midx : MIDX-approximate sampling head (default) — no [B, V] matrix;
                candidates drawn through one replicated index, rescored
                exactly (beyond-paper application of the paper's sampler).
  --head full : exact [B, V] logits each step — the O(V·D) fallback.

The synthetic traffic driver is open-loop: arrival times are drawn ahead of
time (Poisson at --rate req/s; 0 = all arrive at t0) and honored against
wall-clock, independent of completions. Reports tokens/s and p50/p95/p99
per-token latency, and verifies --verify requests against a solo replay
(batched output must be identical to running the request alone).

Serving-tier extras (DESIGN §13):
  --spec-decode K   MIDX-draft speculative decoding (K drafts per wave, one
                    batched full-head verify; reports the acceptance rate)
  --prefix-cache    refcounted prompt-prefix page sharing (+ chunked prefill)
  --prefill-chunk N page-aligned prefill chunks interleaved with decode
  --replicas N      N engine replicas behind the load-weighted router
  --greedy          temperature-0 decoding (with --spec-decode: greedy
                    verify, token-identical to full-head greedy decode)

CPU demo:
  PYTHONPATH=src python -m repro.launch.serve --traffic synthetic \
      --requests 16 --max-slots 4 --head midx --spec-decode 4 --replicas 2
"""
from __future__ import annotations

import argparse
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.configs.base import pad_to
from repro.launch.compile_cache import use_compile_cache
from repro.serve import Engine, Request, Router


def proposal_kl(cfg, params, index, key, probes: int = 16) -> float:
    """Mean KL(full softmax ‖ MIDX proposal) over random probe queries —
    the serving-quality number an index swap moves (DESIGN §8)."""
    from repro.core import midx
    from repro.models.model import class_embeddings
    table = class_embeddings(cfg, params).astype(jnp.float32)
    return float(midx.proposal_kl(index, table, key, probes))


def make_stale_index(cfg, engine: Engine, sigma: float, seed: int):
    """An index fit to where the class embeddings were `sigma` of drift ago
    (table + sigma·noise) — simulates serving against a stale index so the
    --swap-step hot swap has a measurable KL gap to close."""
    from repro.index import build
    from repro.models.model import class_embeddings
    table = class_embeddings(cfg, engine.params).astype(jnp.float32)
    noise = jax.random.normal(jax.random.PRNGKey(seed ^ 0x5747A7E),
                              table.shape)
    return build(engine._index_key, table + sigma * noise,
                 kind=cfg.head.quantizer, k=cfg.head.midx_k,
                 iters=cfg.head.kmeans_iters, keep_residuals=False)


def prompt_buckets(prompt: int) -> list[int]:
    """Prompt-length bucket set (all <= prompt, the documented max) — shared
    by traffic generation and warmup so a warmed engine never compiles
    during the measured run."""
    return sorted({max(1, prompt // 2), max(1, (3 * prompt) // 4), prompt})


def _make_request(cfg, rng, *, rid: int, plen: int, max_new: int, seed: int,
                  arrival: float = 0.0) -> Request:
    kw = {}
    if cfg.family == "vlm":
        kw["image_emb"] = 0.1 * rng.standard_normal(
            (cfg.num_image_tokens, cfg.d_model)).astype(np.float32)
    if cfg.family == "audio":
        kw["frames"] = 0.1 * rng.standard_normal(
            (cfg.encoder_seq, cfg.d_model)).astype(np.float32)
    toks = rng.integers(0, cfg.vocab_size, size=plen).astype(np.int32)
    return Request(rid=rid, tokens=toks, max_new=max_new, seed=seed,
                   arrival=arrival, **kw)


def synthetic_requests(cfg, *, num: int, prompt: int, max_new: int,
                       rate: float, seed: int,
                       shared_prefix: float = 0.0) -> list[Request]:
    """Open-loop synthetic traffic: mixed prompt lengths from a small bucket
    set (bounded prefill compile count), Poisson arrivals at `rate` req/s.

    `shared_prefix` in (0, 1]: that fraction of requests spells the same
    page-aligned common prefix over the first ~half of the prompt (a shared
    system prompt) — the multi-tenant mix the prefix cache deduplicates."""
    rng = np.random.default_rng(seed)
    buckets = prompt_buckets(prompt)
    arrivals = (np.cumsum(rng.exponential(1.0 / rate, size=num))
                if rate > 0 else np.zeros(num))
    reqs = []
    pfx_len = max(cfg.serve.page_size, (prompt // 2)
                  // cfg.serve.page_size * cfg.serve.page_size)
    prefix = rng.integers(0, cfg.vocab_size, size=pfx_len).astype(np.int32)
    for i in range(num):
        plen = int(rng.choice(buckets))
        r = _make_request(cfg, rng, rid=i, plen=plen, max_new=max_new,
                          seed=seed, arrival=float(arrivals[i]))
        if shared_prefix > 0 and rng.random() < shared_prefix \
                and len(r.tokens) > pfx_len:
            r.tokens[:pfx_len] = prefix
        reqs.append(r)
    return reqs


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="paper-lm")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--traffic", default="synthetic", choices=("synthetic",))
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--rate", type=float, default=0.0,
                    help="open-loop arrival rate in req/s (0 = all at t0)")
    ap.add_argument("--prompt", type=int, default=8,
                    help="max prompt length (lengths mix below it)")
    ap.add_argument("--tokens", type=int, default=16, help="tokens per request")
    ap.add_argument("--max-slots", type=int, default=4)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--max-seq", type=int, default=0,
                    help="per-slot capacity (0 = fit prompt+tokens)")
    ap.add_argument("--num-pages", type=int, default=0,
                    help="physical pool size (0 = full residency)")
    from repro.proposals import proposal_modes
    ap.add_argument("--head", default="midx", choices=proposal_modes(),
                    help="decode head: midx/full use the dedicated paths; "
                         "any other repro.proposals contender serves via "
                         "the generic candidate-rescore head")
    ap.add_argument("--num-candidates", type=int, default=0,
                    help="MIDX decode candidates (0 = cfg.head default)")
    ap.add_argument("--table-dtype", default=None,
                    help="hot-path class-table format (bf16|int8|fp8, "
                         "DESIGN §12): the two-stage draw reads quantized "
                         "codebooks and the rescore reads PQ residual "
                         "codes instead of [V,D] rows")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = cfg.head default)")
    ap.add_argument("--greedy", action="store_true",
                    help="temperature-0 decoding; with --spec-decode the "
                         "greedy verify is token-identical to full-head "
                         "greedy decode (needs --head full or --spec-decode)")
    ap.add_argument("--spec-decode", type=int, default=0,
                    help="speculative decoding: K MIDX drafts per wave, one "
                         "batched full-head verify (0 = off; DESIGN §13)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="prefill-token budget per wave: prompts prefill in "
                         "page-aligned chunks interleaved with decode waves "
                         "(0 = whole-prompt batched prefill; DESIGN §13)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="share prompt-prefix KV pages across requests via "
                         "the refcounted prefix trie (DESIGN §13)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="engine replicas behind the load-weighted router "
                         "(DESIGN §13); replicas share params + index")
    ap.add_argument("--shared-prefix", type=float, default=0.0,
                    help="fraction of requests whose prompt starts with a "
                         "common prefix (exercises --prefix-cache)")
    ap.add_argument("--window", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt", default=None,
                    help="restore params+index from a serving checkpoint dir")
    ap.add_argument("--verify", type=int, default=2,
                    help="replay N requests solo and require identical output")
    ap.add_argument("--swap-step", type=int, default=-1,
                    help="hot-swap a freshly rebuilt index before this "
                         "decode step (DESIGN §8); serving params are "
                         "frozen, so the rebuild is bit-identical and "
                         "--verify must still pass across the swap")
    ap.add_argument("--stale-sigma", type=float, default=0.0,
                    help="serve against an index fit to a sigma-perturbed "
                         "class table (simulated staleness) and report the "
                         "proposal KL gap the --swap-step swap closes; "
                         "disables --verify (tokens legitimately change "
                         "at the swap)")
    ap.add_argument("--warmup", type=int, default=1,
                    help="run a compile-absorbing warmup first so reported "
                         "latency percentiles are steady-state (0 disables)")
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    head_kw = {}
    if args.table_dtype is not None:
        head_kw["table_dtype"] = args.table_dtype
    if args.num_candidates:
        head_kw["decode_candidates"] = args.num_candidates
    if args.temperature:
        head_kw["decode_temperature"] = args.temperature
    if args.greedy:
        head_kw["decode_temperature"] = 0.0
    if head_kw:
        cfg = cfg.with_head(**head_kw)
    # speculative waves write up to spec_decode-1 scratch positions past the
    # committed token, so the auto-fit per-slot budget covers them too
    max_seq = args.max_seq or pad_to(
        args.prompt + args.tokens + max(args.spec_decode, 1), args.page_size)
    cfg = cfg.with_serve(max_slots=args.max_slots, page_size=args.page_size,
                         max_seq=max_seq, num_pages=args.num_pages,
                         spec_decode=args.spec_decode,
                         prefill_chunk=args.prefill_chunk,
                         prefix_cache=args.prefix_cache)
    window = args.window or None

    if args.ckpt:
        engine = Engine.from_checkpoint(cfg, args.ckpt, head=args.head,
                                        window=window)
    else:
        engine = Engine(cfg, init_key=jax.random.PRNGKey(args.seed),
                        head=args.head, window=window)
    replicas = [engine]
    for _ in range(1, max(args.replicas, 1)):
        replicas.append(Engine(cfg, engine.params, index=engine.index,
                               head=args.head, window=window))
    router = Router(replicas) if len(replicas) > 1 else None

    reqs = synthetic_requests(cfg, num=args.requests, prompt=args.prompt,
                              max_new=args.tokens, rate=args.rate,
                              seed=args.seed,
                              shared_prefix=args.shared_prefix)
    if not reqs:
        print("[serve] no requests to run")
        return
    if args.warmup:
        # reported percentiles then describe steady-state serving
        for eng in replicas:
            eng.warmup(prompt_buckets(args.prompt))
    if args.head == "full" and (args.swap_step >= 0 or args.stale_sigma > 0):
        raise SystemExit("--swap-step/--stale-sigma exercise the MIDX index "
                         "lifecycle; --head full has no index to swap")
    if args.head == "midx" and (args.swap_step >= 0 or args.stale_sigma > 0):
        # a restored index was built under the trainer's refresh key, so a
        # local rebuild would not be bit-identical — hot-swap a copy instead
        # (same machinery, token-identity preserved for --verify)
        fresh = (jax.tree_util.tree_map(jnp.copy, engine.index) if args.ckpt
                 else engine.rebuild_index())
        if args.stale_sigma > 0:
            stale = make_stale_index(cfg, engine, args.stale_sigma, args.seed)
            k_probe = jax.random.PRNGKey(args.seed + 1)
            kl_stale = proposal_kl(cfg, engine.params, stale, k_probe)
            kl_fresh = proposal_kl(cfg, engine.params, fresh, k_probe)
            print(f"[serve] proposal KL(softmax‖Q): stale={kl_stale:.4f} "
                  f"refreshed={kl_fresh:.4f} (gap the swap closes: "
                  f"{kl_stale - kl_fresh:.4f})")
            for eng in replicas:
                eng.swap_index(stale)
        if args.swap_step >= 0:
            for eng in replicas:
                eng.schedule_swap(fresh, at_step=args.swap_step)
            print(f"[serve] index hot-swap scheduled before decode step "
                  f"{args.swap_step}")
    if router is not None:
        results = router.run(reqs)
        s = router.summary()
    else:
        results = engine.run(reqs)
        s = engine.stats.summary()
    print(f"[serve] head={args.head} arch={cfg.name} requests={args.requests} "
          f"slots={args.max_slots} replicas={len(replicas)} "
          f"waves={s['waves']} generated={s['generated']} "
          f"tok/s={s['tok_s']} p50={s['p50_ms']}ms p95={s['p95_ms']}ms "
          f"p99={s['p99_ms']}ms")
    if args.spec_decode:
        stats = router.stats() if router is not None else engine.stats
        print(f"[serve] speculative: k={args.spec_decode} "
              f"waves={stats.spec_waves} drafted={stats.spec_drafted} "
              f"accepted={stats.spec_accepted} "
              f"acceptance={stats.accept_rate():.3f}")
    if args.prefix_cache:
        counters = {}
        for eng in replicas:
            for k, v in eng.cache.counters().items():
                counters[k] = counters.get(k, 0) + v
        hits, misses = counters["cache_hits"], counters["cache_misses"]
        rate = hits / max(hits + misses, 1)
        print(f"[serve] prefix cache: hits={hits} misses={misses} "
              f"hit_rate={rate:.3f} evictions={counters['cache_evictions']} "
              f"cached_pages={counters['cached_pages']}")
    if router is not None:
        print(f"[serve] router: routed_per_replica="
              f"{router.rstats.per_replica} shed={router.rstats.shed}")
    if s["waves"] < 2 and args.requests > args.max_slots:
        print("[serve] WARNING: expected >=2 admission waves", file=sys.stderr)

    n_verify = min(args.verify, len(reqs))
    if args.stale_sigma > 0 and n_verify:
        print("[serve] --stale-sigma active: skipping verify (tokens "
              "legitimately change when the refreshed index swaps in)")
        n_verify = 0
    if n_verify:
        bad = 0
        for r in reqs[:n_verify]:
            if results[r.rid].status != "ok":
                continue
            solo = engine.replay_single(r)
            if not np.array_equal(results[r.rid].tokens, solo):
                bad += 1
                print(f"[serve] VERIFY FAILED rid={r.rid}: batched != solo",
                      file=sys.stderr)
        print(f"[serve] verify {n_verify - bad}/{n_verify} requests: "
              f"batched == solo")
        if bad:
            raise SystemExit(1)
    rid0 = reqs[0].rid
    print("[serve] sample output ids:", results[rid0].tokens[:8].tolist())


if __name__ == "__main__":
    main()
