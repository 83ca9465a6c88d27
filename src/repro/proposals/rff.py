"""Random Fourier Features proposals (Rawat et al. 2019).

q(i|z) ∝ max(φ(z)·φ(c_i), 1e-8) with φ(x) = [cos(Wx̂); sin(Wx̂)]/√R over the
normalized query/table — a positive-definite softmax-kernel surrogate whose
class features φ(C) are precomputed and re-mapped on refresh.

Two contenders share the state {emb, w, tau, phi_c}:

  rff        jnp path: materialize the [.., N] score row, categorical draw.
  rff-fused  the scores + Gumbel-top-m + logsumexp run as ONE Pallas kernel
             (kernels/rff_sample) — the [T, N] score matrix never leaves
             VMEM. Identical draw distribution; the draws themselves come
             from a counter-based hash shared with the kernel's jnp oracle,
             so kernel / interpreter / oracle backends produce identical
             negatives (kernels.dispatch.rff_sample_fn picks the path).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.proposals.base import Draw, categorical_draw


def rff_map(x: jax.Array, w: jax.Array, tau: jax.Array) -> jax.Array:
    """φ(x) = [cos(Wx̂); sin(Wx̂)] / √R over the normalized input."""
    xn = x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-6)
    proj = jnp.sqrt(tau) * (xn @ w.T)
    r = w.shape[0]
    return jnp.concatenate([jnp.cos(proj), jnp.sin(proj)],
                           axis=-1) / jnp.sqrt(float(r))


def rff_init(key, class_emb, class_freq=None, r: int = 32, tau: float = 4.0):
    d = class_emb.shape[-1]
    w = jax.random.normal(key, (r, d), jnp.float32)
    phi_c = rff_map(class_emb.astype(jnp.float32), w, tau)       # [N, 2R]
    return {"emb": class_emb, "w": w, "tau": jnp.float32(tau), "phi_c": phi_c}


def rff_log_p(state, z):
    phi_z = rff_map(z.astype(jnp.float32), state["w"], state["tau"])
    scores = jnp.maximum(phi_z @ state["phi_c"].T, 1e-8)         # [..., N]
    return jnp.log(scores) - jnp.log(jnp.sum(scores, axis=-1, keepdims=True))


def rff_sample(state, key, z, m):
    return categorical_draw(key, rff_log_p(state, z), m)


def rff_log_prob(state, z, ids):
    return jnp.take_along_axis(rff_log_p(state, z), ids, axis=-1)


def rff_refresh(state, key, class_emb):
    phi_c = rff_map(class_emb.astype(jnp.float32), state["w"], state["tau"])
    return {**state, "emb": class_emb, "phi_c": phi_c}


# ---------------------------------------------------------------------- fused
def rff_fused_sample_factory(*, use_kernel=None, interpret: bool = False):
    """sample(state, key, z, m) routed through kernels/rff_sample.

    `use_kernel=None` defers to kernels.dispatch (TPU -> compiled kernel,
    else the bit-identical jnp oracle; `interpret=True` forces the
    interpreter). The draw distribution equals the unfused `rff` proposal;
    only the noise source differs (hash counters vs jax.random), so log_prob
    and refresh are shared with it.
    """
    def sample(state, key, z, m):
        from repro.kernels import dispatch as kd
        fn = kd.rff_sample_fn(use_kernel=use_kernel, interpret=interpret)
        phi_z = rff_map(z.astype(jnp.float32), state["w"], state["tau"])
        lead = z.shape[:-1]
        phi_2d = phi_z.reshape(-1, phi_z.shape[-1])
        # fold the two key words into one int32 hash seed
        seed = (key[0] ^ key[1]).astype(jnp.int32)
        ids, log_q = fn(phi_2d, state["phi_c"], seed, m)
        return Draw(ids.reshape(*lead, m), log_q.reshape(*lead, m))

    return sample
