"""Serving driver: a closed loop of clients against the program's serving
engine (`serve.Engine`, driven through `start_run` / `tick` as the router
drives it), for a fixed number of seconds.

Each of `clients` clients sends its next request as soon as its last one
has completed. Requests are taken in turn from a pool: prompt and output
lengths come from weighted buckets in exact proportion within every block of
ten requests, each block in an order drawn from the run's seed, so every
seed serves the same sizes in its own order; prompt ids are drawn from the
seed, uniform over the vocabulary. Set-up compiles every prompt-length
bucket (`Engine.warmup`) and runs the loop for `warm_seconds`; the window
then opens on the loop as it stands.

Measured from outside the engine, on the host clock after each `tick`:
when a request was sent, when each of its tokens appeared, and what each
tick did (prefill wave or decode wave, how many slots were decoding before
it, and how many requests got their first token in it).

Once the window has closed no request is sent; the engine runs until every
request sent finished, the peak memory is read and the engine is freed.
Then `check_requests` finished requests, drawn from the seed with the
longest among them, are run through the reference, prompt and served tokens
together: at every served position the reference draws the next token
through the MIDX decode head from its own float32 hidden state, under the
request's key. Compared: `draw_mismatch`, the share of served tokens the
reference does not draw. The engine samples (temperature 1), so a served
token is judged by the draw it came from and not by the logits' argmax.

Traffic parameters (traffic/<name>.json): clients, prompt_buckets,
prompt_weights, output_buckets, output_weights, pool, warm_seconds,
check_requests, drain_seconds.
"""
from __future__ import annotations

import dataclasses
import gc
import time

import numpy as np


def stratified(values, weights, n: int, rng) -> np.ndarray:
    """n draws in blocks that each hold every value in exact proportion to
    its weight (a block of 0.4/0.3/0.2/0.1 is 4, 3, 2 and 1 of the four
    values), each block in its own seeded order: any run of whole blocks,
    such as the requests a window takes, has the same sizes on every
    seed."""
    w = np.asarray(weights, np.float64)
    block = np.repeat(values, np.round(w / w.min()).astype(int))
    blocks = -(-n // len(block))
    return np.concatenate([rng.permutation(block)
                           for _ in range(blocks)])[:n]


@dataclasses.dataclass
class Sent:
    rid: int
    plen: int
    max_new: int
    t_send: float
    times: list = dataclasses.field(default_factory=list)  # token arrivals
    status: str = ""
    tokens: object = None


def run(ctx, *, fault=None, control: bool = False) -> dict:
    """`fault`, for the tests only, alters the engine to break it;
    `control` also reads the float8 control's draws on the same requests
    (tools/control.py; the benchmark's own runs do not)."""
    import jax

    from chipbench import bench, program
    from repro.serve import Engine, Request

    spec, tr, ref = ctx.cell.spec, ctx.cell.traffic, ctx.cell.reference
    cfg = program.program_config(spec)
    key = program.seed_key(ctx.seed)
    k_w, k_idx = jax.random.split(key)
    rng = np.random.default_rng([ctx.seed & 0xFFFFFFFF, ctx.seed >> 32])

    with ctx.span("init_weights"):
        params = ref.init_weights(spec, k_w, cfg.padded_vocab)
        program.check_layout(cfg, params)
    with ctx.span("build_index"):
        # over the vocabulary's rows only: the table's padding rows are no
        # classes, and a token drawn from them would be no token
        index_arrays = program.midx_index(
            params["embed"][:cfg.vocab_size], k_idx, k=cfg.head.midx_k,
            iters=cfg.head.kmeans_iters)
    with ctx.span("engine"):
        engine = Engine(cfg, params,
                        index=program.to_program_index(index_arrays),
                        head="midx")
        if fault is not None:
            fault(engine)
        engine.warmup(tr["prompt_buckets"])
    del params

    n_pool = tr["pool"]
    plens = stratified(tr["prompt_buckets"], tr["prompt_weights"], n_pool,
                       rng)
    outs = stratified(tr["output_buckets"], tr["output_weights"], n_pool,
                      rng)
    vocab = cfg.vocab_size
    sent: dict[int, Sent] = {}
    prompts: dict[int, np.ndarray] = {}
    ticks = []              # (kind, t0, t1, decoding slots, first tokens)
    nxt = [0]

    live: set[int] = set()
    engine.start_run([])
    t_base = engine._t_start

    def send(now: float) -> None:
        """Send the pool's next request; a refused one is marked shed."""
        i = nxt[0] % n_pool
        rid = nxt[0]
        nxt[0] += 1
        toks = rng.integers(0, vocab, int(plens[i])).astype(np.int32)
        prompts[rid] = toks
        req = Request(rid=rid, tokens=toks, max_new=int(outs[i]),
                      seed=ctx.seed & 0x7FFFFFFF, arrival=now - t_base)
        sent[rid] = Sent(rid, int(plens[i]), int(outs[i]), now)
        if engine.sched.submit(req) is not None:
            sent[rid].status = "shed"
        else:
            live.add(rid)

    results = engine._results

    def tick(sending: bool) -> None:
        t0 = time.perf_counter()
        decoding = sum(1 for ss in engine.sched.active.values()
                       if not ss.prefilling)
        with ctx.span("tick"):
            kind = engine.tick(t0 - t_base)
        t1 = time.perf_counter()
        firsts = 0

        def seen(s: Sent, n: int) -> None:
            nonlocal firsts
            if n > 0 and not s.times:
                firsts += 1
            s.times.extend([t1] * (n - len(s.times)))

        for ss in engine.sched.active.values():
            seen(sent[ss.request.rid], len(ss.out))
        for rid in list(live):
            if rid in results:
                r = results[rid]
                s = sent[rid]
                seen(s, len(r.tokens))
                s.status, s.tokens = r.status, r.tokens
                live.discard(rid)
                if sending:
                    send(t1)
        ticks.append((kind, t0, t1, decoding, firsts))

    t_start = time.perf_counter()
    for _ in range(tr["clients"]):
        send(t_start)
    while time.perf_counter() - t_start < tr["warm_seconds"]:
        tick(True)
    t_open = ctx.open_window()
    first_rid = nxt[0]
    while time.perf_counter() - t_open < ctx.seconds:
        tick(True)
    jax.block_until_ready(engine.state)
    t_close = ctx.close_window()
    trace = ctx.reduce_trace(ctx.cell.chips)
    last_rid = nxt[0]
    # no more sends: every request sent completes, or the drain times out
    t_drain = time.perf_counter()
    while live and time.perf_counter() - t_drain < tr["drain_seconds"]:
        tick(False)
    drain_end = time.perf_counter()
    peak = bench.memory_peak_bytes(ctx.cell.chips)
    del engine
    gc.collect()

    window_reqs = [sent[r] for r in range(first_rid, last_rid)]
    failed = sum(1 for s in window_reqs
                 if s.status != "ok" or s.tokens is None
                 or len(s.tokens) != s.max_new)

    # --- the reference, over a seeded sample of finished requests
    t_ref = time.perf_counter()
    done = [s for s in sent.values() if s.status == "ok"
            and s.tokens is not None and len(s.tokens) == s.max_new]
    readings, detail, control_readings = {"draw_mismatch": None}, {}, None
    if done:
        longest = max(done, key=lambda s: (s.plen + s.max_new, -s.rid))
        others = [s for s in done if s.rid != longest.rid]
        pick = rng.choice(len(others), min(len(others),
                                           tr["check_requests"] - 1),
                          replace=False)
        check = [longest] + [others[i] for i in sorted(pick)]
        params = ref.init_weights(spec, k_w, cfg.padded_vocab)

        def draws(low: bool) -> np.ndarray:
            return np.concatenate([ref.served_draws(
                spec, params, index_arrays,
                np.concatenate([prompts[s.rid], s.tokens]), s.plen,
                len(s.tokens), cfg.serve.max_seq, max(tr["output_buckets"]),
                ctx.seed & 0x7FFFFFFF, s.rid, cfg.head.decode_candidates,
                cfg.head.decode_temperature, low) for s in check])

        served = np.concatenate([s.tokens for s in check])
        drawn = draws(False)
        readings = {"draw_mismatch": float(np.mean(served != drawn))}
        if control:
            control_readings = {
                "draw_mismatch": float(np.mean(draws(True) != drawn))}
        del params
        detail = {"requests": [s.rid for s in check],
                  "tokens": int(served.size),
                  "mismatched": int(np.sum(served != drawn)),
                  "outside_vocab": int(np.sum(served >= vocab))}
    ref_s = time.perf_counter() - t_ref
    bench.log(f"check: {detail}; reference {ref_s:.1f}s")

    return {
        "setup_s": t_open - ctx.t_process,
        "window": (t_open, t_close),
        "attempted": len(window_reqs),
        "failed": failed,
        "requests": window_reqs,
        "all_requests": list(sent.values()),
        "ticks": ticks,
        "drain_end": drain_end,
        "max_slots": cfg.serve.max_slots,
        "spans": ctx.spans,
        "trace": trace,
        "memory_peak_bytes": peak,
        "readings": readings,
        "reference_s": ref_s,
        "check_detail": detail,
        "control_readings": control_readings,
        "work": {"d_model": cfg.d_model, "midx_k": cfg.head.midx_k,
                 "vocab": cfg.vocab_size},
        "spec": spec,
    }
