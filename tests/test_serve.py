"""repro.serve: paged KV pool, continuous batching, checkpoint round-trip.

Covers the DESIGN §5 invariants:
  - PagePool allocator: trash page reserved, all-or-nothing alloc, reuse;
  - paged decode == forward() across every cache family, with mixed per-slot
    positions in one packed step and slot recycling in between;
  - engine interleaving requests of different lengths produces outputs
    identical to running each request alone at the same seed;
  - serving checkpoint save -> restore -> bit-identical MIDX draws.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import restore_serving_state, save_serving_state
from repro.configs import get_config
from repro.core import midx as midx_mod
from repro.models import (forward, heads, init_paged_state, init_params,
                          logits_full, paged_decode_step, prefill, reset_slot,
                          write_prefill)
from repro.serve import Engine, PagePool, Request, Scheduler, TRASH_PAGE


# ---------------------------------------------------------------------------
# allocator
# ---------------------------------------------------------------------------

def test_page_pool_invariants():
    pool = PagePool(num_pages=6, page_size=4, pages_per_slot=3, num_slots=3)
    a = pool.alloc(0, 9)               # 3 pages
    b = pool.alloc(1, 5)               # 2 pages
    assert TRASH_PAGE not in set(a.tolist()) | set(b.tolist())
    assert len(set(a.tolist()) | set(b.tolist())) == 5
    assert not pool.can_alloc(5)       # 0 pages left for 2-page request
    with pytest.raises(ValueError):
        pool.alloc(2, 5)
    with pytest.raises(ValueError):    # slot 0 already holds pages
        pool.alloc(0, 1)
    assert not pool.fits(13)           # exceeds per-slot capacity
    pool.free(0)
    assert np.all(pool.table[0] == TRASH_PAGE)
    c = pool.alloc(2, 12)              # freed pages are reusable
    assert sorted(c.tolist()) == sorted(a.tolist())


def test_scheduler_rejects_request_larger_than_pool():
    """A request that fits a slot's page table but not the whole pool must be
    rejected at submit — otherwise the engine loop would wait for pages that
    can never exist (livelock). Rejection is structured (DESIGN §11), not an
    exception: bad traffic degrades the service, it doesn't crash it."""
    pool = PagePool(num_pages=3, page_size=4, pages_per_slot=7, num_slots=1)
    sched = Scheduler(1, pool)
    rej = sched.submit(Request(rid=0, tokens=np.zeros(8, np.int32),
                               max_new=16))
    assert rej is not None and rej.reason == "oversized_pool"
    assert rej.rid == 0 and not sched.queue


def test_scheduler_next_arrival_is_fifo_head():
    """next_arrival must report the queue *head* (the admission gate), not
    the queue-wide minimum — otherwise out-of-order arrivals busy-spin the
    engine loop instead of sleeping."""
    pool = PagePool(num_pages=5, page_size=4, pages_per_slot=2, num_slots=2)
    sched = Scheduler(2, pool)
    sched.submit(Request(rid=0, tokens=np.zeros(4, np.int32), max_new=4,
                         arrival=10.0))
    sched.submit(Request(rid=1, tokens=np.zeros(4, np.int32), max_new=4,
                         arrival=0.0))
    assert sched.next_arrival() == 10.0
    assert sched.admit(now=5.0) == []       # head not arrived yet


def test_scheduler_fifo_and_recycling():
    pool = PagePool(num_pages=5, page_size=4, pages_per_slot=2, num_slots=2)
    sched = Scheduler(2, pool)
    for i in range(4):
        sched.submit(Request(rid=i, tokens=np.zeros(4, np.int32), max_new=4))
    first = sched.admit()
    assert [ss.request.rid for ss in first] == [0, 1]     # FIFO
    assert sched.admit() == []                            # no slots left
    sched.finish(first[0].slot)
    second = sched.admit()
    assert [ss.request.rid for ss in second] == [2]       # recycled mid-flight
    assert sched.waves == 2 and not sched.done


# ---------------------------------------------------------------------------
# paged decode vs forward, all cache families
# ---------------------------------------------------------------------------

FAMILY_ARCHS = ["smollm-135m", "qwen2-moe-a2.7b", "mamba2-370m", "zamba2-7b",
                "llama-3.2-vision-11b", "whisper-tiny"]


def _media(cfg, b, key):
    kw = {}
    if cfg.family == "vlm":
        kw["image_emb"] = 0.1 * jax.random.normal(
            jax.random.fold_in(key, 1), (b, cfg.num_image_tokens, cfg.d_model))
    if cfg.family == "audio":
        kw["frames"] = 0.1 * jax.random.normal(
            jax.random.fold_in(key, 2), (b, cfg.encoder_seq, cfg.d_model))
    return kw


@pytest.mark.parametrize("name", FAMILY_ARCHS)
def test_paged_decode_matches_forward(name, key):
    """Prefill + slot-packed paged decode at *different* per-slot positions
    reproduces forward() — then a recycled slot serves a second request."""
    import dataclasses
    cfg = get_config(name).reduced()
    if cfg.family == "moe":
        # capacity-based token dropping makes MoE forward() non-causal (late
        # tokens compete with early ones for expert capacity), so exact
        # prefix-prefill parity needs a no-drop capacity factor
        cfg = dataclasses.replace(cfg, capacity_factor=4.0)
    params = init_params(cfg, key)
    s = 8
    page, pps, nslots = 4, 3, 3
    state = init_paged_state(cfg, nslots, nslots * pps + 1, page, pps)
    pool = PagePool(nslots * pps + 1, page, pps, nslots)

    def admit(slot, toks, kw, plen):
        if "page_table" in state:
            pool.alloc(slot, s)
            st = dict(state)
            st["page_table"] = jnp.asarray(pool.table)
        else:
            st = state
        hid, cache = prefill(cfg, params, toks[:, :plen], **kw)
        return write_prefill(cfg, st, cache, np.array([slot]), plen=plen)

    # two requests at different prompt lengths in slots 0 and 2
    toks_a, kw_a = _tokens(cfg, key, s)
    toks_b, kw_b = _tokens(cfg, jax.random.fold_in(key, 9), s)
    plen_a, plen_b = 5, 3
    ref_a = forward(cfg, params, toks_a, **kw_a)["hidden"]
    ref_b = forward(cfg, params, toks_b, **kw_b)["hidden"]
    state = admit(0, toks_a, kw_a, plen_a)
    state = admit(2, toks_b, kw_b, plen_b)
    outs_a, outs_b = [], []
    for t in range(s - plen_a):
        pos = jnp.asarray([plen_a + t, 0, plen_b + t], jnp.int32)
        tok = jnp.asarray([int(toks_a[0, plen_a + t]), 0,
                           int(toks_b[0, plen_b + t])], jnp.int32)
        h, state = paged_decode_step(cfg, params, tok, pos, state)
        outs_a.append(h[0])
        if plen_b + t < s:
            outs_b.append(h[2])
    dec_a = jnp.stack(outs_a)
    np.testing.assert_allclose(
        np.asarray(dec_a, np.float32),
        np.asarray(ref_a[0, plen_a:], np.float32), atol=5e-2, rtol=5e-2)
    np.testing.assert_allclose(
        np.asarray(jnp.stack(outs_b), np.float32),
        np.asarray(ref_b[0, plen_b:plen_b + len(outs_b)], np.float32),
        atol=5e-2, rtol=5e-2)
    # logits parity through the head (padded-vocab rows never consulted)
    np.testing.assert_allclose(
        np.asarray(logits_full(cfg, params, dec_a[-1])[: cfg.vocab_size]),
        np.asarray(logits_full(cfg, params, ref_a[0, -1])[: cfg.vocab_size]),
        atol=5e-2, rtol=5e-2)

    # recycle slot 0 for a fresh request; no state may leak
    state = reset_slot(state, 0)
    if "page_table" in state:
        pool.free(0)
    toks_c, kw_c = _tokens(cfg, jax.random.fold_in(key, 17), s)
    ref_c = forward(cfg, params, toks_c, **kw_c)["hidden"]
    plen_c = 4
    state = admit(0, toks_c, kw_c, plen_c)
    outs_c = []
    for t in range(plen_c, s):
        pos = jnp.asarray([t, 0, 0], jnp.int32)
        tok = jnp.asarray([int(toks_c[0, t]), 0, 0], jnp.int32)
        h, state = paged_decode_step(cfg, params, tok, pos, state)
        outs_c.append(h[0])
    np.testing.assert_allclose(
        np.asarray(jnp.stack(outs_c), np.float32),
        np.asarray(ref_c[0, plen_c:], np.float32), atol=5e-2, rtol=5e-2)


def _tokens(cfg, key, s):
    toks = jax.random.randint(key, (1, s), 0, cfg.vocab_size)
    return toks, _media(cfg, 1, key)


# ---------------------------------------------------------------------------
# engine: interleaved == solo
# ---------------------------------------------------------------------------

_INTERLEAVED = [("paper-lm", "midx"), ("paper-lm", "full"),
                ("mamba2-370m", "midx"), ("qwen2-moe-a2.7b", "midx")]


@pytest.mark.parametrize("arch,head,group", [
    *(pytest.param(a, h, False, id=f"{a}-{h}") for a, h in _INTERLEAVED),
    *(pytest.param(a, h, True, id=f"{a}-{h}-group3")
      for a, h in _INTERLEAVED)])
def test_engine_interleaved_matches_single(arch, head, group):
    """Requests of different lengths interleaved through shared slots give
    outputs identical to running each request alone at the same seed.

    Includes MoE with a drop-inducing capacity factor: expert dispatch is
    vmapped per batch row, so capacity competition stays within a request
    and batch composition still cannot change its tokens.

    `group`: three requests of one prompt length arrive together on four
    slots, so they form one prefill group, and each must equal its replay
    alone: a prompt prefills at one row whatever group it came in."""
    import dataclasses
    cfg = get_config(arch).reduced()
    if cfg.family == "moe":
        cfg = dataclasses.replace(cfg, capacity_factor=0.5)
    cfg = cfg.with_serve(max_slots=4 if group else 2, page_size=4,
                         max_seq=32)
    rng = np.random.default_rng(0)
    sizes = ([(7, 5), (7, 4), (7, 6)] if group
             else [(6, 5), (9, 7), (6, 3), (11, 6)])
    reqs = [Request(rid=i,
                    tokens=rng.integers(0, cfg.vocab_size,
                                        size=int(l)).astype(np.int32),
                    max_new=int(n), seed=3)
            for i, (l, n) in enumerate(sizes)]
    eng = Engine(cfg, init_key=jax.random.PRNGKey(1), head=head)
    res = eng.run(reqs)
    if group:
        assert eng.stats.prefill_padded == 3 * 7     # one row per request
    else:
        assert eng.stats.waves >= 2          # continuous batching engaged
    for r in reqs:
        assert res[r.rid].tokens.shape == (r.max_new,)
        np.testing.assert_array_equal(res[r.rid].tokens,
                                      eng.replay_single(r))


@pytest.mark.parametrize("arch,spec", [("paper-lm", 0), ("paper-lm", 2),
                                       ("mamba2-370m", 0)])
def test_warmup_leaves_nothing_to_compile(arch, spec):
    """After `warmup(prompt_lens)`, runs at every group size from 1 to
    max_slots, at every warmed prompt length, add no entry to the cache of
    any of the engine's jitted programs: the prefill runs one row, so it has
    one program per prompt length whatever the group (a solo replay runs the
    very program its group ran), and the first-token program takes max_slots
    rows."""
    cfg = get_config(arch).reduced().with_serve(
        max_slots=4, page_size=4, max_seq=32, spec_decode=spec)
    eng = Engine(cfg, init_key=jax.random.PRNGKey(0), head="midx")
    plens = [5, 9]
    eng.warmup(plens)

    def sizes():
        return {n: f._cache_size() for n, f in vars(eng).items()
                if hasattr(f, "_cache_size")}

    warm = sizes()
    assert warm["_prefill"] == len(plens)   # one row, whatever the group
    assert warm["_first_group"] == 1     # max_slots rows, whatever the group
    rng = np.random.default_rng(0)
    rid = 0
    for plen in plens:
        for g in range(1, cfg.serve.max_slots + 1):
            reqs = [Request(rid=rid + i, tokens=rng.integers(
                        0, cfg.vocab_size, plen).astype(np.int32),
                            max_new=3, seed=1) for i in range(g)]
            rid += g
            res = eng.run(reqs)
            assert all(r.status == "ok" for r in res.values())
    assert eng.stats.prefill_padded == eng.stats.prefill_tokens == sum(
        plen * g for plen in plens for g in range(1, 5))
    assert sizes() == warm


def test_engine_page_pressure_queues_requests():
    """A pool smaller than slots×capacity forces extra admission waves but
    still completes every request."""
    cfg = get_config("paper-lm").reduced().with_serve(
        max_slots=4, page_size=4, max_seq=16, num_pages=9)  # 2 slots' worth
    rng = np.random.default_rng(1)
    reqs = [Request(rid=i, tokens=rng.integers(0, cfg.vocab_size, size=6)
                    .astype(np.int32), max_new=4) for i in range(6)]
    eng = Engine(cfg, init_key=jax.random.PRNGKey(0), head="midx")
    res = eng.run(reqs)
    assert sorted(res) == list(range(6))
    assert eng.stats.waves >= 3              # pages, not slots, are the limit
    assert eng.pool.free_pages == eng.pool.num_pages - 1


# ---------------------------------------------------------------------------
# checkpoint round-trip
# ---------------------------------------------------------------------------

def test_serving_checkpoint_roundtrip_identical_samples(tmp_path, key):
    cfg = get_config("paper-lm").reduced()
    params = init_params(cfg, key)
    index = heads.init_head_state(cfg, params, jax.random.fold_in(key, 1))
    save_serving_state(str(tmp_path), 7, params, index,
                       metadata={"arch": cfg.name})
    like_p = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    like_i = jax.eval_shape(lambda: heads.init_head_state(
        cfg, init_params(cfg, jax.random.PRNGKey(0)), jax.random.PRNGKey(1)))
    p2, i2, meta = restore_serving_state(str(tmp_path), like_p, like_i)
    assert meta["arch"] == cfg.name
    # bit-identical index state -> bit-identical proposal draws
    z = 0.3 * jax.random.normal(jax.random.fold_in(key, 2), (4, cfg.d_model))
    d1 = midx_mod.sample_twostage(index, jax.random.PRNGKey(5), z, 16)
    d2 = midx_mod.sample_twostage(i2, jax.random.PRNGKey(5), z, 16)
    np.testing.assert_array_equal(np.asarray(d1.ids), np.asarray(d2.ids))
    np.testing.assert_allclose(np.asarray(d1.log_q), np.asarray(d2.log_q))
    # and the restored engine decodes the same tokens as the original
    sv = dict(max_slots=2, page_size=4, max_seq=32)
    req = Request(rid=0, tokens=np.arange(6, dtype=np.int32), max_new=5)
    out1 = Engine(cfg.with_serve(**sv), params, index=index,
                  head="midx").run([req])[0].tokens
    eng2 = Engine.from_checkpoint(cfg.with_serve(**sv), str(tmp_path),
                                  head="midx")
    np.testing.assert_array_equal(out1, eng2.run([req])[0].tokens)
