"""Serving engine: continuous batching around the MIDX decode head (DESIGN §5).

The engine owns:
  - a paged decode state (`models.decode.init_paged_state`) + its host-side
    page allocator (`kv_pool.PagePool`), optionally fronted by the
    refcounted prompt-prefix cache (`kv_pool.PrefixCache`, DESIGN §13);
  - a FIFO continuous-batching scheduler (`scheduler.Scheduler`);
  - one jitted slot-packed decode step over all `cfg.serve.max_slots` slots
    (inactive slots ride along masked, writing only the trash page);
  - prefill, two ways: the legacy whole-prompt path (one fused
    `models.decode.prefill` + `write_prefill` program call per request, at
    one row, then one first-token call per prompt-length group), or — with
    `cfg.serve.prefill_chunk` — page-aligned chunks interleaved with decode
    waves so a long prompt never stalls in-flight decodes (DESIGN §13);
  - speculative decoding (`cfg.serve.spec_decode = k`, DESIGN §13): one
    two-stage MIDX draw per slot drafts k tokens i.i.d. from the proposal
    conditioned on the hidden that predicted the slot's last committed token
    (zero backbone steps in the draft path), then ONE chunked backbone pass
    plus one batched full-head pass verifies them with
    distribution-preserving rejection sampling. Greedy verify is
    token-identical to non-speculative full-head decoding; seeded sampling
    preserves the exact target distribution;
  - per-request PRNG streams: the token drawn after consuming position p of
    request r uses fold_in(fold_in(PRNGKey(seed), r.rid), p) (speculative
    waves salt draft/accept/residual roles off the same per-slot stream), and
    every slot samples under its own key (vmapped head), so outputs are
    identical to running the request alone at the same seed regardless of
    batch composition. This holds for MoE too: expert dispatch is vmapped
    per batch row (`models.model._apply_ffn_part`), so capacity competition
    stays within a request. (Within a request, MoE capacity makes a
    length-S prefill differ from full-sequence forward — an approximation
    of the family, not of the batching.)

Decode heads: `heads.midx_decode_head` (the paper's sampler applied at serve
time — candidates drawn through one replicated index shared by all slots,
rescored exactly) is the default approximate head; `logits_full` is the
exact [B, V] fallback. For long contexts an `attn_fn` such as
`dist.decode.flash_decode_seq_sharded` (partially applied over a mesh) plugs
into the cache attention of every self-attn layer.

The main loop is factored into resumable pieces — `start_run` / `tick` /
`finish_run` — so `serve.router.Router` can multiplex N engine replicas on
one host thread; `run` composes them for the single-engine case. Each tick
writes `repro/engine.*` spans, with counts, into the profiler's trace
(`utils.trace.span`, DESIGN §5).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import restore_serving_state, save_serving_state
from repro.configs.base import ModelConfig, pad_to
from repro.models import (heads, init_paged_state, init_params, logits_full,
                          paged_decode_step, prefill, reset_slot,
                          write_prefill)
from repro.models.decode import chunk_prefill_step
from repro.serve.kv_pool import PagePool, PrefixCache
from repro.serve.scheduler import Rejection, Request, Scheduler, SlotState
from repro.utils import metrics as metrics_mod
from repro.utils.trace import span

#: families whose paged attention cache makes speculative rollback free
#: (stale draft K/V past the committed position is overwritten before it is
#: ever attended) AND that support the chunked backbone pass the verify
#: wave runs through; ssm/hybrid carry sequential state that cannot rewind,
#: vlm/audio prefill through the batched path only.
_SPEC_FAMILIES = ("dense", "moe")
_CHUNK_FAMILIES = ("dense", "moe")


@dataclasses.dataclass
class RequestResult:
    rid: int
    tokens: np.ndarray              # generated ids (may be partial)
    latencies_s: list               # per-token wall latency
    status: str = "ok"              # 'ok' | 'shed' | 'timeout'
    reason: str = ""                # rejection reason when status != 'ok'


@dataclasses.dataclass
class EngineStats:
    generated: int = 0
    wall_s: float = 0.0
    waves: int = 0
    steps: int = 0
    shed: int = 0                   # structured admission rejections
    timeouts: int = 0               # deadline retirements (partial results)
    swap_rejected: int = 0          # degenerate indexes refused by the gate
    swaps: int = 0                  # successful index installs
    spec_waves: int = 0             # speculative waves run
    spec_drafted: int = 0           # draft tokens proposed
    spec_accepted: int = 0          # draft tokens accepted by the verifier
    prefill_chunks: int = 0         # chunked-prefill waves run
    prefill_tokens: int = 0         # prompt tokens prefilled
    prefill_padded: int = 0         # token rows the prefill programs ran
    latencies_s: list = dataclasses.field(default_factory=list)

    def counters(self) -> dict:
        return {"shed": self.shed, "timeouts": self.timeouts,
                "swap_rejected": self.swap_rejected, "swaps": self.swaps}

    def health(self) -> dict:
        """Degradation report (DESIGN §11): ok=True means no request was
        shed or timed out and no swap was refused since the last reset."""
        c = self.counters()
        return {"ok": not (self.shed or self.timeouts or self.swap_rejected),
                **c}

    def accept_rate(self) -> float:
        return self.spec_accepted / max(self.spec_drafted, 1)

    def summary(self) -> dict:
        out = {"generated": self.generated, "wall_s": round(self.wall_s, 3),
               "waves": self.waves, "steps": self.steps,
               "tok_s": round(self.generated / max(self.wall_s, 1e-9), 1)}
        if self.spec_drafted:
            out["accept_rate"] = round(self.accept_rate(), 4)
            out["spec_waves"] = self.spec_waves
        if self.prefill_chunks:
            out["prefill_chunks"] = self.prefill_chunks
        if self.prefill_padded:
            # the share of prefill rows that carry a prompt token: a group
            # runs one row per request, a chunk max_slots rows
            out["prefill_token_use"] = round(
                self.prefill_tokens / self.prefill_padded, 4)
        out.update({k: round(v, 3) for k, v in metrics_mod.latency_summary(
            self.latencies_s, counters=self.counters()).items()})
        return out


def _sample_tokens(cfg, params, index, hidden, keys, head: str,
                   proposal=None):
    """Per-slot next-token draws. hidden [B,D], keys [B] — each slot samples
    under its own key so draws never depend on batch composition. `proposal`
    set -> the generic candidate-rescore head (heads.proposal_decode_head);
    head == 'midx' -> the dedicated MIDX path; else exact [B,V] logits
    (decode_temperature <= 0 -> greedy argmax)."""
    if proposal is not None:
        def one(h, k):
            return heads.proposal_decode_head(
                cfg, params, proposal, index, h[None], k).token[0]
        return jax.vmap(one)(hidden, keys)
    if head == "midx":
        def one(h, k):
            return heads.midx_decode_head(cfg, params, index, h[None], k).token[0]
        return jax.vmap(one)(hidden, keys)
    logits = logits_full(cfg, params, hidden)[:, : cfg.vocab_size]
    t = cfg.head.decode_temperature
    if t <= 0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / t
    return jax.vmap(
        lambda k, lg: jax.random.categorical(k, lg).astype(jnp.int32)
    )(keys, logits)


class Engine:
    """Continuous-batching serving engine over the paged KV pool."""

    def __init__(self, cfg: ModelConfig, params: Optional[dict] = None, *,
                 index=None, head: str = "midx", window: Optional[int] = None,
                 attn_fn=None, init_key: Optional[jax.Array] = None):
        from repro.proposals import registry as proposals_registry
        proposals_registry.validate_mode(head)
        self.cfg = cfg
        self.head = head
        # 'midx'/'full' keep their dedicated decode paths; any other
        # registered contender serves through the generic proposal head
        self.proposal = (None if head in ("midx", "full")
                         else proposals_registry.from_config(cfg.head, head))
        self.window = window
        self.attn_fn = attn_fn
        sv = cfg.serve
        self.spec_k = int(getattr(sv, "spec_decode", 0) or 0)
        chunk = int(getattr(sv, "prefill_chunk", 0) or 0)
        use_cache = bool(getattr(sv, "prefix_cache", False))
        if use_cache and chunk == 0:
            chunk = sv.page_size  # cache hits resume mid-prompt -> chunked
        self.chunk = pad_to(chunk, sv.page_size) if chunk else 0
        if self.spec_k:
            if head != "midx":
                raise ValueError("spec_decode drafts through the MIDX index; "
                                 f"head={head!r} has no two-stage draw")
            if cfg.family not in _SPEC_FAMILIES:
                raise ValueError(
                    f"spec_decode needs a rollback-free paged attention "
                    f"cache ({'/'.join(_SPEC_FAMILIES)}), not {cfg.family}")
        if self.chunk and cfg.family not in _CHUNK_FAMILIES:
            raise ValueError(f"chunked prefill / prefix cache support "
                             f"{'/'.join(_CHUNK_FAMILIES)} families, "
                             f"not {cfg.family}")
        if (cfg.head.decode_temperature <= 0 and self.spec_k == 0
                and head != "full"):
            raise ValueError("greedy decoding (decode_temperature <= 0) "
                             "needs head='full' or spec_decode > 0")
        key = init_key if init_key is not None else jax.random.PRNGKey(0)
        k_init, k_idx = jax.random.split(key)
        self.params = init_params(cfg, k_init) if params is None else params
        self.index = index
        self._index_key = k_idx       # rebuild_index() default: same key ->
                                      # frozen params reproduce the index
        if head == "midx" and self.index is None:
            self.index = heads.init_head_state(cfg, self.params, k_idx)
        elif self.proposal is not None and self.index is None:
            self.index = heads.init_proposal_state(cfg, self.params, k_idx,
                                                   self.proposal)
        self._pending_swap = None     # (at_decode_step, index) | None
        self.pool = PagePool(sv.resolved_num_pages, sv.page_size,
                             sv.pages_per_slot, sv.max_slots)
        self.cache = PrefixCache(self.pool) if use_cache else None
        self.sched = Scheduler(sv.max_slots, self.pool,
                               max_queue=getattr(sv, "max_queue", 0) or None,
                               cache=self.cache,
                               token_slack=max(0, self.spec_k - 1))
        self.state = init_paged_state(cfg, sv.max_slots, sv.resolved_num_pages,
                                      sv.page_size, sv.pages_per_slot,
                                      window=window)
        self.stats = EngineStats()
        self._results: dict[int, RequestResult] = {}
        self._t_start = 0.0
        self._waves0 = 0
        self._prefill_fifo: list[int] = []   # chunked-prefill slot order
        # per-slot base PRNG keys, refreshed at admission; the per-step
        # fold_in(base, pos) happens inside the jitted step so the hot loop
        # issues no per-slot host dispatches
        self._base_keys = jnp.zeros((sv.max_slots, 2), jnp.uint32)
        # per-slot draft-conditioning hidden for speculative waves: the
        # backbone state that predicted the slot's last emitted token
        # (seeded at prefill, rolled forward by each wave)
        self._hdraft = jnp.zeros((sv.max_slots, cfg.d_model),
                                 jnp.dtype(cfg.dtype))

        proposal = self.proposal

        def step_fn(params, index, state, tokens, pos, base_keys, active):
            hidden, state = paged_decode_step(cfg, params, tokens, pos, state,
                                              window=window, attn_fn=attn_fn)
            keys = jax.vmap(jax.random.fold_in)(base_keys, pos)
            nxt = _sample_tokens(cfg, params, index, hidden, keys, head,
                                 proposal)
            return jnp.where(active, nxt, 0), state

        # donate the state: the pool scatter aliases in place instead of
        # copying the whole KV pool every token
        self._step = jax.jit(step_fn, donate_argnums=(2,))
        # speculative engines sample the *first* token from the exact target
        # distribution too (the verify head), not the MIDX approximation
        first_head = "full" if self.spec_k else head

        def first_token_fn(params, index, hidden, keys):
            return _sample_tokens(cfg, params, index, hidden, keys,
                                  first_head,
                                  None if self.spec_k else proposal)

        self._first_token = jax.jit(first_token_fn)

        def prefill_fn(params, state, toks, slots, **kw):
            hidden, cache = prefill(cfg, params, toks, window=window, **kw)
            state = write_prefill(cfg, state, cache, slots,
                                  plen=toks.shape[1])
            return hidden[:, -1], state

        # one request's prefill and its cache write in one program, the
        # state donated so the write lands in place; it always runs one row,
        # so it compiles once per prompt length and returns only the last
        # hidden state
        self._prefill = jax.jit(prefill_fn, donate_argnums=(1,))
        # admission hot path, batched: one fused call builds every admitted
        # request's base key (fold_in(PRNGKey(seed), rid), bit-identical to
        # the scalar construction) and one vmapped fold_in derives a group's
        # first-token keys — per-request host dispatches are what dominates
        # admission cost on a CPU host, not the arithmetic
        def bind_keys_fn(seeds, rids, slots, base_keys):
            base = jax.vmap(lambda s, r: jax.random.fold_in(
                jax.random.PRNGKey(s), r))(seeds, rids)
            return base_keys.at[slots].set(base), base

        self._bind_keys_jit = jax.jit(bind_keys_fn, donate_argnums=(3,))
        spec_on = bool(self.spec_k)

        # compiles once: `h_rows` is max_slots one-row hidden states, every
        # other argument max_slots rows, plen1 traced
        def first_group_fn(params, index, base, gidx, h_rows, hdraft,
                           slots, plen1):
            keys = jax.vmap(jax.random.fold_in, in_axes=(0, None))(
                base[gidx], plen1)
            h_last = jnp.concatenate(h_rows)
            if spec_on:   # the last prompt hidden seeds the first draft wave
                hdraft = hdraft.at[slots].set(h_last.astype(hdraft.dtype))
            first = _sample_tokens(cfg, params, index, h_last, keys,
                                   first_head, None if spec_on else proposal)
            return first, hdraft

        self._first_group = jax.jit(first_group_fn, donate_argnums=(5,))
        if self.chunk:
            def chunk_fn(params, toks, start, length, state):
                return chunk_prefill_step(cfg, params, toks, start, length,
                                          state, window=window)

            self._chunk_step = jax.jit(chunk_fn, donate_argnums=(4,))
        if self.spec_k:
            spec_k = self.spec_k

            def spec_fn(params, index, state, tokens, pos, hdraft,
                        base_keys, active):
                wave_keys = jax.vmap(jax.random.fold_in)(base_keys, pos)
                dkeys = jax.vmap(lambda wk: jax.random.fold_in(wk, 1))(
                    wave_keys)
                # draft the whole wave from the hidden that predicted each
                # slot's last committed token: one two-stage table build +
                # k O(K) draws per slot, zero backbone steps
                d = heads.midx_spec_draft(cfg, params, index, hdraft,
                                          dkeys, spec_k)
                # one chunked backbone pass over the wave: input j is the
                # token at position pos+j (the last committed token, then
                # the drafts), so the output at chunk position j is the
                # exact target state that verifies draft j
                chunk_toks = jnp.concatenate(
                    [tokens[:, None], d.tokens[:, :-1]], axis=1)
                length = jnp.where(active, spec_k, 0)
                hiddens, state = chunk_prefill_step(
                    cfg, params, chunk_toks, pos, length, state,
                    window=window)                      # [B, k, D]
                ver = heads.spec_verify(
                    cfg, params, index, jnp.swapaxes(hiddens, 0, 1),
                    d.tokens.T, d.log_q.T, d.s1, d.s2, d.lse, wave_keys)
                toks = jnp.where(active[None, :], ver.tokens, 0)
                # the state that predicted this wave's last committed token
                # seeds the next wave's draft
                nh = jnp.take_along_axis(
                    hiddens, (ver.n_commit - 1)[:, None, None], axis=1)[:, 0]
                hdraft = jnp.where(active[:, None], nh, hdraft)
                return toks, ver.n_commit, ver.n_accept, hdraft, state

            self._spec_step = jax.jit(spec_fn, donate_argnums=(2,))

    # ------------------------------------------------------------ checkpoints
    @classmethod
    def from_checkpoint(cls, cfg: ModelConfig, root: str, *,
                        step: Optional[int] = None, **kw) -> "Engine":
        """Restore params + head state saved by `save_checkpoint` (or by
        `launch.train`'s serving export) and build an engine around them."""
        from repro.proposals import registry as proposals_registry
        head = kw.get("head", "midx")
        proposals_registry.validate_mode(head)
        like_p = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
        if head in ("midx", "full"):
            like_i = jax.eval_shape(
                lambda: heads.init_head_state(
                    cfg, init_params(cfg, jax.random.PRNGKey(0)),
                    jax.random.PRNGKey(1)))
        else:
            # concrete, not eval_shape: proposal init may run host-side code
            # (the unigram Vose alias build) that cannot trace abstractly
            prop = proposals_registry.from_config(cfg.head, head)
            like_i = heads.init_proposal_state(
                cfg, init_params(cfg, jax.random.PRNGKey(0)),
                jax.random.PRNGKey(1), prop)
        params, index, _ = restore_serving_state(root, like_p, like_i, step)
        return cls(cfg, params, index=index, **kw)

    def save_checkpoint(self, root: str, step: int = 0) -> str:
        return save_serving_state(root, step, self.params, self.index,
                                  metadata={"arch": self.cfg.name,
                                            "head": self.head})

    # ------------------------------------------------------------ index swap
    def swap_index(self, index, validate: bool = True) -> bool:
        """Atomically install a freshly built index (DESIGN §8).

        The index is only read between decode waves (the jitted step takes
        it as an argument), so installing a new one never disturbs in-flight
        slots: their KV pages, positions and PRNG streams are untouched, and
        the very next step samples through the new proposal. Swapping an
        index rebuilt from unchanged params is token-identity-preserving —
        what the serve CLI's --verify machinery checks across --swap-step
        (including speculative engines: the draft distribution and verify
        target both read the swapped-in index/params pair).

        Validation gate (DESIGN §11): a degenerate candidate (NaN codebooks,
        empty CSR, wrong tree structure) is refused — the live index stays,
        stats.swap_rejected increments, and False comes back. Decode then
        proceeds token-identical to never having attempted the swap."""
        if validate:
            from repro.resilience.validate import validate_state
            reasons = validate_state(index, like=self.index)
            if reasons:
                self.stats.swap_rejected += 1
                print(f"[engine] swap_index rejected: {'; '.join(reasons)}")
                return False
        self.index = index
        self.stats.swaps += 1
        if getattr(self, "_solo", None) is not None:
            self._solo.index = index
        return True

    def schedule_swap(self, index, at_step: int) -> None:
        """Install `index` just before decode step `at_step` (counted by
        self.stats.steps) of a subsequent `run` — the mid-stream hot swap."""
        self._pending_swap = (at_step, index)

    def rebuild_index(self, key: Optional[jax.Array] = None):
        """Rebuild the head state (MIDX index or proposal state) from the
        engine's current params.

        With the default key this reproduces the construction the engine
        booted with, so unchanged params yield a bit-identical state — the
        'unchanged index' swap. A training loop pushing updated params would
        pass its own refresh key here."""
        k = key if key is not None else self._index_key
        if self.proposal is not None:
            return heads.init_proposal_state(self.cfg, self.params, k,
                                             self.proposal)
        return heads.init_head_state(self.cfg, self.params, k)

    def _maybe_swap(self) -> None:
        if self._pending_swap is not None and \
                self.stats.steps >= self._pending_swap[0]:
            self.swap_index(self._pending_swap[1])
            self._pending_swap = None

    # ------------------------------------------------------------ key streams
    def _req_key(self, req: Request) -> jax.Array:
        return jax.random.fold_in(jax.random.PRNGKey(req.seed), req.rid)

    def _bind_keys(self, admitted: list[SlotState], *,
                   set_slot_keys: bool = False) -> jax.Array:
        """Bind per-request base PRNG keys for newly admitted slots in ONE
        fused device call — bit-identical to chaining `_req_key` per request,
        but a single dispatch instead of ~4 per admission. Rows pad to
        max_slots (duplicating row 0) so the kernel compiles once and the
        padded scatter rows are no-ops. `set_slot_keys` materializes per-slot
        `ss.key` rows (chunked prefill folds from them later); the batched
        prefill path derives everything from the returned stack instead."""
        n, b = len(admitted), self.cfg.serve.max_slots
        pad = [admitted[0]] * (b - n)
        seeds = np.asarray([ss.request.seed for ss in admitted + pad],
                           np.uint32)
        rids = np.asarray([ss.request.rid for ss in admitted + pad],
                          np.uint32)
        slots = np.asarray([ss.slot for ss in admitted + pad], np.int32)
        self._base_keys, base = self._bind_keys_jit(
            jnp.asarray(seeds), jnp.asarray(rids), jnp.asarray(slots),
            self._base_keys)
        if set_slot_keys:
            for i, ss in enumerate(admitted):
                ss.key = base[i]
        return base

    # ------------------------------------------------------------ admission
    def _prefill_wave(self, admitted: list[SlotState]) -> None:
        """Prefill newly admitted slots: one fused prefill-and-write program
        call per request, at one row, cache written straight into the paged
        state; then one call per prompt-length group draws the group's first
        tokens. First-token latency is charged per group, not per wave."""
        # pool.alloc already updated the host table; push it to device first
        # so the cache write sees the new page rows
        if "page_table" in self.state:
            self.state["page_table"] = jnp.asarray(self.pool.table)
        base = self._bind_keys(admitted)
        b = self.cfg.serve.max_slots
        groups: dict[int, list[int]] = {}
        for i, ss in enumerate(admitted):
            groups.setdefault(len(ss.request.tokens), []).append(i)
        for plen, idxs in groups.items():
            sss = [admitted[i] for i in idxs]
            g = len(sss)
            self.stats.prefill_tokens += g * plen
            self.stats.prefill_padded += g * plen
            with span("engine.prefill_group", plen=plen, rows=g,
                      tokens=g * plen, padded=g * plen):
                t0 = time.perf_counter()
                # each request prefills alone, at one row: its arithmetic
                # cannot depend on what was admitted with it (a TPU may
                # round a one-row matmul differently from a four-row one),
                # and the program compiles once per prompt length
                h_rows = [self._prefill_one(ss) for ss in sss]
                # pad to max_slots rows duplicating row 0, so the first-token
                # program compiles once and its padded hdraft writes repeat
                # row 0's
                pad = b - g
                first, self._hdraft = self._first_group(
                    self.params, self.index, base,
                    jnp.asarray(idxs + idxs[:1] * pad, jnp.int32),
                    tuple(h_rows + h_rows[:1] * pad), self._hdraft,
                    jnp.asarray([ss.slot for ss in sss] + [sss[0].slot] * pad,
                                jnp.int32), plen - 1)
                with span("engine.device_wait"):
                    first = np.asarray(first)
                for ss, tok in zip(sss, first[:g]):
                    ss.out.append(int(tok))
                    ss.prefill_pos = plen
                dt = time.perf_counter() - t0
                for ss in sss:        # first-token latency: this group only
                    ss.latencies.append(dt)
                self.stats.latencies_s.extend(dt for _ in sss)
        self.stats.generated += len(admitted)

    def _prefill_one(self, ss: SlotState) -> jax.Array:
        """Prefill one request's prompt into its slot (one dispatch of the
        fused prefill-and-write program); returns its last hidden state,
        [1, d_model]."""
        req, kw = ss.request, {}
        if self.cfg.family == "vlm":
            kw["image_emb"] = jnp.asarray(req.image_emb[None])
        if self.cfg.family == "audio":
            kw["frames"] = jnp.asarray(req.frames[None])
        h_last, self.state = self._prefill(
            self.params, self.state, jnp.asarray(req.tokens[None]),
            jnp.asarray([ss.slot], jnp.int32), **kw)
        return h_last

    def _admit_chunked(self, admitted: list[SlotState]) -> None:
        """Chunked-mode admission: bind keys and queue the slot for prefill
        chunks; no forward work happens here. A cache hit starts the slot's
        `prefill_pos` at the end of the reused page-aligned prefix."""
        if "page_table" in self.state:
            self.state["page_table"] = jnp.asarray(self.pool.table)
        self._bind_keys(admitted, set_slot_keys=True)
        for ss in admitted:
            self._prefill_fifo.append(ss.slot)

    def _chunk_wave(self) -> None:
        """Run one page-aligned prefill chunk (≤ `cfg.serve.prefill_chunk`
        tokens) for the oldest prefilling slot. Chunk boundaries live on the
        absolute token grid, so a cache-hit resume replays exactly the chunk
        shapes a cold run uses for the same suffix — the bitwise-identity
        property tests/test_serve_prefix.py checks."""
        slot = self._prefill_fifo[0]
        ss = self.sched.active[slot]
        req = ss.request
        plen = len(req.tokens)
        start = ss.prefill_pos
        end = min(plen, ((start // self.chunk) + 1) * self.chunk)
        seg = np.asarray(req.tokens[start:end], np.int32)
        b = self.cfg.serve.max_slots
        toks = np.zeros((b, self.chunk), np.int32)
        toks[slot, :len(seg)] = seg
        starts = np.zeros((b,), np.int32)
        starts[slot] = start
        lens = np.zeros((b,), np.int32)
        lens[slot] = len(seg)
        self.stats.prefill_tokens += len(seg)
        self.stats.prefill_padded += b * self.chunk
        t0 = time.perf_counter()
        with span("engine.prefill_chunk", tokens=len(seg),
                  padded=b * self.chunk):
            hidden, self.state = self._chunk_step(
                self.params, jnp.asarray(toks), jnp.asarray(starts),
                jnp.asarray(lens), self.state)
            ss.prefill_pos = end
            self.stats.prefill_chunks += 1
            if end == plen:
                self._prefill_fifo.pop(0)
                if self.cache is not None:
                    self.cache.insert(req.tokens, self.pool.table[slot])
                key = jax.random.fold_in(ss.key, plen - 1)
                if self.spec_k:
                    self._hdraft = self._hdraft.at[slot].set(
                        hidden[slot, len(seg) - 1].astype(self._hdraft.dtype))
                first = self._first_token(
                    self.params, self.index, hidden[slot, len(seg) - 1][None],
                    key[None])
                with span("engine.device_wait"):
                    first = np.asarray(first)
                ss.out.append(int(first[0]))
                self.stats.generated += 1
        dt = time.perf_counter() - t0
        ss.prefill_s += dt
        if not ss.prefilling:
            # first-token latency spans every chunk wave the prompt took
            ss.latencies.append(ss.prefill_s)
            self.stats.latencies_s.append(ss.prefill_s)

    def warmup(self, prompt_lens) -> None:
        """Absorb jit compiles — one prefill per prompt-length bucket, the
        first-token program and the slot-packed decode step — then reset
        stats, so subsequent runs report steady-state throughput/latency.
        A prefill runs one row whatever its group, so one request per bucket
        compiles every prefill program. Callers pass the same bucket set
        their traffic draws prompt lengths from."""
        rng = np.random.default_rng(0)
        reqs = []
        for i, plen in enumerate(sorted(set(prompt_lens))):
            kw = {}
            if self.cfg.family == "vlm":
                kw["image_emb"] = 0.1 * rng.standard_normal(
                    (self.cfg.num_image_tokens, self.cfg.d_model)
                ).astype(np.float32)
            if self.cfg.family == "audio":
                kw["frames"] = 0.1 * rng.standard_normal(
                    (self.cfg.encoder_seq, self.cfg.d_model)).astype(np.float32)
            # rids high in the int32 range to stay clear of user rids (and
            # positive: fold_in takes uint32 data)
            reqs.append(Request(rid=0x7FFF0000 + i,
                                tokens=np.zeros(plen, np.int32),
                                max_new=max(2, self.spec_k + 1), **kw))
        self.run(reqs)
        self.stats = EngineStats()

    # ------------------------------------------------------------ main loop
    def start_run(self, requests: list[Request]) -> dict[int, RequestResult]:
        """Submit `requests` (shedding bad traffic as structured results)
        and arm the run clock. Drive with `tick`; close with `finish_run`."""
        self._results = {}
        for r in requests:
            rej = self.sched.submit(r)
            if rej is not None:
                self.stats.shed += 1
                self._results[r.rid] = RequestResult(
                    r.rid, np.zeros(0, np.int32), [],
                    status="shed", reason=f"{rej.reason}: {rej.detail}")
        self._t_start = time.perf_counter()
        self._waves0 = self.sched.waves
        return self._results

    def tick(self, now: float) -> str:
        """One engine iteration at wall-time `now` (seconds since
        `start_run`). Returns what happened: 'prefill' (batched prefill
        wave), 'work' (chunk and/or decode wave), 'idle' (waiting on an
        arrival), 'done' (nothing queued or active)."""
        with span("engine.tick"):
            with span("engine.admit") as admit:
                for req in self.sched.drop_expired(now):
                    self.stats.timeouts += 1
                    self._results[req.rid] = RequestResult(
                        req.rid, np.zeros(0, np.int32), [],
                        status="timeout", reason="expired before admission")
                self._expire(now)
                admitted = self.sched.admit(now)
                admit.set_metadata(admitted=len(admitted))
            if admitted:
                if self.chunk:
                    self._admit_chunked(admitted)
                else:
                    self._prefill_wave(admitted)
                    self._retire()    # max_new == 1 finishes at prefill
                    return "prefill"
            worked = False
            if self._prefill_fifo:
                # one prefill chunk per wave, interleaved with the decode wave
                # below — a long prompt never stalls in-flight decodes
                self._chunk_wave()
                self._retire()        # max_new == 1 finishes at the last chunk
                worked = True
            decoding = {slot: ss for slot, ss in self.sched.active.items()
                        if not ss.prefilling}
            if decoding:
                # hot-swap window: between decode waves, never mid-wave
                self._maybe_swap()
                if self.spec_k:
                    self._spec_wave(decoding)
                else:
                    self._decode_wave(decoding)
                self._retire()
                worked = True
            if worked:
                return "work"
            return "done" if self.sched.done else "idle"

    def finish_run(self) -> dict[int, RequestResult]:
        self.stats.wall_s += time.perf_counter() - self._t_start
        self.stats.waves += self.sched.waves - self._waves0
        return self._results

    def run(self, requests: list[Request]) -> dict[int, RequestResult]:
        """Drive all requests to completion; open-loop arrivals honored
        against wall-clock time since `run` started. Shed and timed-out
        requests come back in the same result dict with status 'shed' /
        'timeout' (partial tokens) instead of raising (DESIGN §11)."""
        self.start_run(requests)
        while not self.sched.done:
            now = time.perf_counter() - self._t_start
            if self.tick(now) == "idle":
                nxt = self.sched.next_arrival()
                if nxt is not None and nxt > now:
                    time.sleep(min(nxt - now, 0.05))
        return self.finish_run()

    # ------------------------------------------------------------ decode waves
    def _pack(self, decoding: dict[int, SlotState]):
        sv = self.cfg.serve
        tokens = np.zeros((sv.max_slots,), np.int32)
        pos = np.zeros((sv.max_slots,), np.int32)
        active = np.zeros((sv.max_slots,), bool)
        for slot, ss in decoding.items():
            tokens[slot] = ss.out[-1]
            pos[slot] = ss.pos
            active[slot] = True
        return tokens, pos, active

    def _decode_wave(self, decoding: dict[int, SlotState]) -> None:
        """One slot-packed single-token decode step over `decoding` slots."""
        with span("engine.decode_wave", active=len(decoding),
                  slots=self.cfg.serve.max_slots):
            tokens, pos, active = self._pack(decoding)
            t0 = time.perf_counter()
            nxt, self.state = self._step(
                self.params, self.index, self.state, jnp.asarray(tokens),
                jnp.asarray(pos), self._base_keys, jnp.asarray(active))
            with span("engine.device_wait"):
                nxt = np.asarray(nxt)
            dt = time.perf_counter() - t0
            self.stats.steps += 1
            for slot, ss in decoding.items():
                ss.out.append(int(nxt[slot]))
                ss.pos += 1
                ss.latencies.append(dt)
                self.stats.latencies_s.append(dt)
                self.stats.generated += 1

    def _spec_wave(self, decoding: dict[int, SlotState]) -> None:
        """One speculative wave: k drafted backbone steps inside a jitted
        scan + one batched verify, committing 1..k tokens per slot. Wave
        latency is charged per committed token (amortized: the wave's dt
        divided by its committed count — the steady streaming rate)."""
        with span("engine.spec_wave", active=len(decoding),
                  slots=self.cfg.serve.max_slots, k=self.spec_k):
            tokens, pos, active = self._pack(decoding)
            t0 = time.perf_counter()
            toks, n_commit, n_acc, self._hdraft, self.state = self._spec_step(
                self.params, self.index, self.state, jnp.asarray(tokens),
                jnp.asarray(pos), self._hdraft, self._base_keys,
                jnp.asarray(active))
            with span("engine.device_wait"):
                toks = np.asarray(toks)
                n_commit = np.asarray(n_commit)
                n_acc = np.asarray(n_acc)
            dt = time.perf_counter() - t0
            self.stats.steps += self.spec_k
            self.stats.spec_waves += 1
            for slot, ss in decoding.items():
                c = min(int(n_commit[slot]),
                        ss.request.max_new - len(ss.out))
                ss.out.extend(int(t) for t in toks[:c, slot])
                ss.pos += c
                ss.drafted += self.spec_k
                ss.accepted += int(n_acc[slot])
                self.stats.spec_drafted += self.spec_k
                self.stats.spec_accepted += int(n_acc[slot])
                per_tok = dt / max(c, 1)
                ss.latencies.extend(per_tok for _ in range(c))
                self.stats.latencies_s.extend(per_tok for _ in range(c))
                self.stats.generated += c

    # ------------------------------------------------------------ retirement
    def _drop_prefilling(self, slot: int) -> None:
        if slot in self._prefill_fifo:
            self._prefill_fifo.remove(slot)

    def _retire(self) -> None:
        done = [s for s, ss in self.sched.active.items() if ss.done]
        with span("engine.retire", retired=len(done)):
            for slot in done:
                ss = self.sched.finish(slot)
                self._drop_prefilling(slot)
                self.state = reset_slot(self.state, slot)
                self._results[ss.request.rid] = RequestResult(
                    ss.request.rid, np.asarray(ss.out, np.int32),
                    ss.latencies)
            # one table push for the whole batch of retirements: pool.free
            # reset every freed row to TRASH_PAGE host-side, so the single
            # upload matches reset_slot's per-slot zeroing
            if done and "page_table" in self.state:
                self.state["page_table"] = jnp.asarray(self.pool.table)

    def _expire(self, now: float) -> None:
        """Retire active slots whose deadline passed: the tokens generated so
        far come back as a partial 'timeout' result, the slot and its KV
        pages are recycled for the queue (DESIGN §11)."""
        expired = [s for s, ss in self.sched.active.items()
                   if ss.request.deadline is not None
                   and now > ss.request.deadline]
        for slot in expired:
            ss = self.sched.finish(slot)
            self._drop_prefilling(slot)
            self.state = reset_slot(self.state, slot)
            self.stats.timeouts += 1
            self._results[ss.request.rid] = RequestResult(
                ss.request.rid, np.asarray(ss.out, np.int32), ss.latencies,
                status="timeout",
                reason=f"deadline {ss.request.deadline:.3f}s exceeded at "
                       f"{now:.3f}s with {len(ss.out)}/{ss.request.max_new} "
                       "tokens")
        if expired and "page_table" in self.state:
            self.state["page_table"] = jnp.asarray(self.pool.table)

    # ------------------------------------------------------------ verification
    def replay_single(self, req: Request) -> np.ndarray:
        """Run one request alone with the same weights, index and key
        stream — the reference the batched output must match exactly
        (DESIGN §5; speculative and chunked engines replay through the same
        wave structure, so per-slot streams line up). The solo engine keeps
        this engine's slot count, the other slots idle: a TPU may round a
        one-row matmul differently from an eight-row one, so only the same
        shapes make "alone" comparable bit for bit. A prompt prefills at one
        row in both engines, whatever group it was admitted with. The solo
        engine is cached across calls so repeated verification doesn't
        recompile its prefill/decode programs; reusing its state is safe
        because a recycled slot's reads are masked to the new request's own
        writes."""
        if getattr(self, "_solo", None) is None:
            self._solo = Engine(self.cfg, self.params, index=self.index,
                                head=self.head, window=self.window,
                                attn_fn=self.attn_fn)
        res = self._solo.run([dataclasses.replace(req, arrival=0.0)])
        return res[req.rid].tokens
