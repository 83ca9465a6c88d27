"""Index lifecycle: drift-triggered incremental refresh + overlapped swap.

The adaptive half of the paper lives here (DESIGN §8). The MIDX proposal
only stays close to the true softmax while the index tracks the moving class
embeddings; proposal staleness translates directly into estimator bias. But
a full cold K-means refit on every cadence point is a periodic training
stall. This module provides:

  drift_metrics     cheap on-device drift probe: fraction of classes whose
                    frozen-codebook assignment changed + one-Lloyd-step
                    codeword movement.
  refresh_adaptive  jitted refresh that runs the cheap reassign-only rebuild
                    and, via lax.cond on the drift score, escalates to a
                    warm-started full refit only when the table has actually
                    moved — one dispatch, no host round-trip.
  refresh_with_policy
                    'fixed' (always full, warm-started) vs 'drift'
                    (adaptive) — the cfg.head.refresh_policy switch.
  IndexLifecycle    host-side double buffer: dispatch the rebuild for step s
                    asynchronously and keep training against the old index
                    for `lag` steps (the config-bounded staleness window),
                    swapping when the result is ready.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.index.build import (MultiIndex, _build_impl, _reassign_impl,
                               reassign)
from repro.index.quantization import assign_against
from repro.utils.trace import span

REFRESH_POLICIES = ("fixed", "drift")


def drift_metrics(index: MultiIndex,
                  class_embeddings: jax.Array) -> dict[str, jax.Array]:
    """Drift of the class table relative to the index, without a refit.

      reassigned_frac  fraction of classes whose (k1, k2) changes under the
                       frozen codebooks — proposal-support drift.
      codeword_drift   relative movement of the stage-1 codebook after ONE
                       Lloyd update against the new table — codebook drift
                       that reassignment alone cannot absorb. A codeword
                       left empty by the reassignment keeps its old value
                       (no random re-seed: the probe must be deterministic
                       and identical to the sharded probe so the drift
                       policy takes the same branch on either path).
    """
    a1, a2 = assign_against(index.kind, index.codebook1, index.codebook2,
                            class_embeddings)
    reassigned = (a1 != index.assign1) | (a2 != index.assign2)
    frac = jnp.mean(reassigned.astype(jnp.float32))
    x1 = (class_embeddings[:, : class_embeddings.shape[-1] // 2]
          if index.kind == "pq" else class_embeddings)
    one_hot = jax.nn.one_hot(a1, index.num_codewords, dtype=x1.dtype)
    counts = jnp.sum(one_hot, axis=0)
    cb1_next = jnp.where((counts > 0)[:, None],
                         (one_hot.T @ x1)
                         / jnp.maximum(counts, 1.0)[:, None],
                         index.codebook1)
    num = jnp.sqrt(jnp.sum((cb1_next - index.codebook1) ** 2))
    den = jnp.sqrt(jnp.sum(index.codebook1 ** 2)) + 1e-12
    return {"reassigned_frac": frac, "codeword_drift": num / den}


def _distortion(index: MultiIndex, class_embeddings: jax.Array) -> jax.Array:
    from repro.index.quantization import reconstruct
    recon = reconstruct(index.kind, index.codebook1, index.codebook2,
                        index.assign1, index.assign2)
    return jnp.mean(jnp.sum((class_embeddings - recon) ** 2, axis=-1))


@functools.partial(jax.jit, static_argnames=("iters",))
def refresh_adaptive(index: MultiIndex, key: jax.Array,
                     class_embeddings: jax.Array, *, iters: int = 10,
                     threshold: float = 0.1):
    """Drift-triggered refresh: reassign-only below `threshold`, warm-started
    full refit above it. Returns (new_index, metrics).

    The branch predicate is a pure function of (index, table, key), identical
    on every shard of a replicated computation, so the whole thing stays one
    jitted dispatch — the overlapped lifecycle can run it without a host
    sync (DESIGN §8).
    """
    d = drift_metrics(index, class_embeddings)
    do_full = ((d["reassigned_frac"] > threshold) |
               (d["codeword_drift"] > threshold))

    def full(_):
        idx = _build_impl(key, class_embeddings, kind=index.kind,
                          k=index.num_codewords, iters=iters,
                          keep_residuals=index.has_residuals,
                          init=(index.codebook1, index.codebook2))
        return idx, _distortion(idx, class_embeddings)

    def cheap(_):
        idx = _reassign_impl(index, class_embeddings)
        return idx, _distortion(idx, class_embeddings)

    new_index, distortion = jax.lax.cond(do_full, full, cheap, None)
    metrics = {**d, "did_full": do_full.astype(jnp.float32),
               "distortion": distortion}
    return new_index, metrics


def refresh_with_policy(index: MultiIndex, key: jax.Array,
                        class_embeddings: jax.Array, *, iters: int = 10,
                        policy: str = "fixed", threshold: float = 0.1):
    """One refresh event under `policy`. Returns (new_index, metrics).

    'fixed'  the cadence-only baseline: every event is a full (warm-started)
             refit; drift metrics are still reported for the step log.
    'drift'  refresh_adaptive — full refit only when drift > threshold.
    """
    if policy not in REFRESH_POLICIES:
        raise ValueError(f"refresh_policy must be one of {REFRESH_POLICIES}, "
                         f"got {policy!r}")
    if policy == "drift":
        return refresh_adaptive(index, key, class_embeddings, iters=iters,
                                threshold=threshold)
    return _refresh_fixed(index, key, class_embeddings, iters=iters)


@functools.partial(jax.jit, static_argnames=("iters",))
def _refresh_fixed(index, key, class_embeddings, *, iters):
    d = drift_metrics(index, class_embeddings)
    idx = _build_impl(key, class_embeddings, kind=index.kind,
                      k=index.num_codewords, iters=iters,
                      keep_residuals=index.has_residuals,
                      init=(index.codebook1, index.codebook2))
    metrics = {**d, "did_full": jnp.float32(1.0),
               "distortion": _distortion(idx, class_embeddings)}
    return idx, metrics


# ---------------------------------------------------------------------------
# host-side overlapped double buffer
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class RefreshEvent:
    """One completed refresh, as reported to the step log / metrics sink."""
    step: int                 # step whose params the rebuild used
    swap_step: int            # step at which the new index went live
    seconds: float            # host wall time attributable to the refresh:
                              # dispatch, wait and the validation gate
    metrics: dict             # drift / did_full / distortion (python floats)
    rejected: bool = False    # validation gate kept the old state
    reasons: tuple = ()       # why (repro.resilience.validate strings)

    @property
    def mode(self) -> str:
        if self.rejected:
            return "rejected"
        return "full" if self.metrics.get("did_full", 1.0) >= 0.5 else "reassign"


class IndexLifecycle:
    """Double-buffered head-state refresh driver for the train loop
    (DESIGN §8, generalized to any proposal in §10).

    `refresh_fn(params, state, key) -> (state, metrics)` is dispatched at
    every cadence point; the state is whatever pytree the resolved proposal
    maintains — the MultiIndex for 'midx', the TAPAS pool, the RFF feature
    map, the learnable {"cb", "index"} pair — the driver never looks inside
    it. With `lag > 0` the result is left in flight (JAX dispatch is
    asynchronous) while the next `lag` steps train against the old state,
    then swapped in — the rebuild cost overlaps training instead of
    stalling it. `lag = 0` degenerates to the synchronous swap-at-dispatch
    behaviour. The staleness of the live state is bounded by `every + lag`
    steps.

    Determinism: the refresh key is folded from the dispatch step, so two
    runs that dispatch at the same steps build identical indexes. On
    restart, `flush()`-then-checkpoint guarantees the saved index is never
    mid-flight (the train loop calls it before `ckpt.save`).
    """

    def __init__(self, refresh_fn: Callable, *, every: int, base_key: jax.Array,
                 lag: int = 0, enabled: bool = True, validate: bool = True):
        if lag < 0:
            raise ValueError(f"lag must be >= 0, got {lag}")
        self.refresh_fn = refresh_fn
        self.every = every
        self.lag = lag
        self.base_key = base_key
        self.enabled = enabled and bool(every)
        self.validate = validate
        self.events: list[RefreshEvent] = []
        self._pending: Optional[tuple] = None   # (dispatch_step, ready_at,
                                                #  index, metrics, t_dispatch)

    @property
    def in_flight(self) -> bool:
        return self._pending is not None

    def abort(self) -> None:
        """Discard any in-flight refresh without swapping it in (rollback:
        the pending state was built from params that no longer exist)."""
        self._pending = None

    def _complete(self, swap_step: int,
                  current: Any = None) -> tuple[MultiIndex, RefreshEvent]:
        step, _ready, index, metrics, t_disp = self._pending
        self._pending = None
        with span("index.refresh_complete", step=step) as sp:
            t0 = time.perf_counter()
            # the state is any proposal pytree (MultiIndex, TAPAS pool, RFF
            # features, ...) — block on the whole tree, not a MIDX-only leaf
            jax.block_until_ready(index)
            metrics = {k: float(v) for k, v in metrics.items()}
            # validation gate (DESIGN §11): a degenerate rebuild (empty CSR,
            # zeroed codebooks, NaN leaves) must never become the live
            # proposal — keep the old state, record the rejection, keep
            # training
            reasons = ()
            if self.validate and current is not None:
                from repro.resilience.validate import validate_state
                with span("index.validate"):
                    reasons = tuple(validate_state(index, like=current))
            # blocked time + gate + dispatch time = host cost attributable
            # to the refresh; device time hidden under the lag window is
            # free by construction
            seconds = (time.perf_counter() - t0) + t_disp
            sp.set_metadata(rejected=int(bool(reasons)))
        ev = RefreshEvent(step, swap_step, seconds, metrics,
                          rejected=bool(reasons), reasons=reasons)
        self.events.append(ev)
        return (current if reasons else index), ev

    def step(self, step: int, params: Any,
             index: MultiIndex) -> tuple[MultiIndex, Optional[RefreshEvent]]:
        """Advance the lifecycle after train step `step`. Returns the index
        the NEXT train step should use, plus a RefreshEvent if a swap
        happened this step."""
        if not self.enabled:
            return index, None
        event = None
        if self._pending is not None and step >= self._pending[1]:
            index, event = self._complete(step, index)
        if (step + 1) % self.every == 0 and self._pending is None:
            key = jax.random.fold_in(self.base_key, step)
            with span("index.refresh_dispatch", step=step):
                t0 = time.perf_counter()
                new_index, metrics = self.refresh_fn(params, index, key)
                t_disp = time.perf_counter() - t0
            self._pending = (step, step + self.lag, new_index, metrics, t_disp)
            if self.lag == 0:
                index, event = self._complete(step, index)
        return index, event

    def flush(self, step: int,
              index: MultiIndex) -> tuple[MultiIndex, Optional[RefreshEvent]]:
        """Force-complete any in-flight refresh (checkpoint boundaries: the
        saved index must be a function of saved params, not of a rebuild
        that would be lost on restore)."""
        if self._pending is None:
            return index, None
        return self._complete(step, index)

    def summary(self) -> dict:
        from repro.utils.metrics import refresh_summary
        return refresh_summary(self.events)
