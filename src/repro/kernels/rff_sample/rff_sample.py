"""Pallas TPU kernel: fused RFF proposal sampling (DESIGN §10).

One pass per (query-block, draw-block, class-block) grid cell:
  s      = φ(z) @ φ(C)ᵀ                      (MXU; the RFF score matrix)
  logits = log max(s, 1e-8), cols ≥ n_valid masked to NEG_INF
  g      = hash-Gumbel(seed, t, draw, col)   (VPU; counter-based, stateless)
  running argmax of logits + g per draw      (Gumbel-max ⇒ m iid categorical
                                              draws from softmax(logits))
  running logsumexp of logits per query      (the log_q normalizer, j == 0)
vs. the unfused path: an HBM-materialized [T, N] score matrix plus a [T, m, N]
(or m-looped) perturbation pass. Kernel writes m ids + m scores + 2 floats per
query; the [T, N] scores never leave VMEM.

Grid iteration order is (t, draw, class) with the class dim innermost; the
running-max / logsumexp outputs revisit their block across the class dim
(same accumulation pattern as flash attention). The noise is a pure function
of (seed, global t, global draw, global col), so the blocked draw is
bit-identical to the oracle in ref.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.rff_sample.ref import NEG_INF, gumbel_noise


def _kernel(meta_ref, z_ref, c_ref, ids_ref, score_ref, pert_ref, mrun_ref,
            lrun_ref, *, block_t: int, block_m: int, block_n: int):
    i = pl.program_id(0)
    j = pl.program_id(1)
    n = pl.program_id(2)
    seed = meta_ref[0, 0]
    n_valid = meta_ref[0, 1]
    phi_z = z_ref[...].astype(jnp.float32)             # [Tb, R2]
    phi_c = c_ref[...].astype(jnp.float32)             # [Nb, R2]
    s = jax.lax.dot_general(phi_z, phi_c, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    col = (jax.lax.broadcasted_iota(jnp.int32, (block_t, block_n), 1)
           + n * block_n)
    valid = col < n_valid
    logits = jnp.where(valid, jnp.log(jnp.maximum(s, 1e-8)), NEG_INF)

    @pl.when(n == 0)
    def _init_argmax():
        pert_ref[...] = jnp.full((block_t, block_m), NEG_INF, jnp.float32)
        ids_ref[...] = jnp.zeros((block_t, block_m), jnp.int32)
        score_ref[...] = jnp.full((block_t, block_m), NEG_INF, jnp.float32)

    @pl.when((n == 0) & (j == 0))
    def _init_lse():
        mrun_ref[...] = jnp.full((block_t, 1), NEG_INF, jnp.float32)
        lrun_ref[...] = jnp.zeros((block_t, 1), jnp.float32)

    @pl.when(j == 0)
    def _lse():
        m_old = mrun_ref[...]
        m_new = jnp.maximum(m_old, jnp.max(logits, axis=-1, keepdims=True))
        # masked cols contribute 0 even when the whole block is masked
        # (logits − m_new would be 0−0 there, not −inf)
        e = jnp.where(valid, jnp.exp(logits - m_new), 0.0)
        lrun_ref[...] = (lrun_ref[...] * jnp.exp(m_old - m_new)
                         + jnp.sum(e, axis=-1, keepdims=True))
        mrun_ref[...] = m_new

    shape3 = (block_t, block_m, block_n)
    t_ids = jax.lax.broadcasted_iota(jnp.int32, shape3, 0) + i * block_t
    d_ids = jax.lax.broadcasted_iota(jnp.int32, shape3, 1) + j * block_m
    n_ids = jax.lax.broadcasted_iota(jnp.int32, shape3, 2) + n * block_n
    g = gumbel_noise(seed, t_ids, d_ids, n_ids)
    # NEG_INF absorbs the O(10) Gumbel in f32, so masked cols never win
    pert = logits[:, None, :] + g                      # [Tb, Mb, Nb]
    cand = jnp.max(pert, axis=-1)                      # [Tb, Mb]
    is_max = pert >= cand[..., None]
    big = jnp.int32(2 ** 30)
    sel = jnp.min(jnp.where(is_max, n_ids, big), axis=-1)
    sel_score = jnp.min(jnp.where(n_ids == sel[..., None],
                                  logits[:, None, :], jnp.float32(3.4e38)),
                        axis=-1)
    # strict > keeps the earlier block on cross-block ties == global min col
    better = cand > pert_ref[...]
    ids_ref[...] = jnp.where(better, sel, ids_ref[...])
    score_ref[...] = jnp.where(better, sel_score, score_ref[...])
    pert_ref[...] = jnp.where(better, cand, pert_ref[...])


@functools.partial(jax.jit,
                   static_argnames=("m", "block_t", "block_m", "block_n",
                                    "interpret"))
def rff_sample(phi_z: jax.Array, phi_c: jax.Array, meta: jax.Array,
               m: int, *, block_t: int = 8, block_m: int = 16,
               block_n: int = 128, interpret: bool = False):
    """phi_z [T, R2], phi_c [N, R2], meta [1, 2] int32 = [[seed, n_valid]].
    T, N, and m must be multiples of the block sizes (ops.py pads).
    Returns (ids [T, m] i32, score [T, m], m_run [T, 1], l_run [T, 1]);
    the Eq.-style normalizer is lse = m_run + log(l_run) and
    log_q = score − lse."""
    t, _ = phi_z.shape
    n = phi_c.shape[0]
    assert t % block_t == 0 and n % block_n == 0 and m % block_m == 0, \
        (t, n, m, block_t, block_n, block_m)
    grid = (t // block_t, m // block_m, n // block_n)
    out_shape = (
        jax.ShapeDtypeStruct((t, m), jnp.int32),       # ids
        jax.ShapeDtypeStruct((t, m), jnp.float32),     # score
        jax.ShapeDtypeStruct((t, m), jnp.float32),     # running perturbed max
        jax.ShapeDtypeStruct((t, 1), jnp.float32),     # lse running max
        jax.ShapeDtypeStruct((t, 1), jnp.float32),     # lse running sum
    )
    kernel = functools.partial(_kernel, block_t=block_t, block_m=block_m,
                               block_n=block_n)
    ids, score, _pert, m_run, l_run = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 2), lambda i, j, n: (0, 0)),
            pl.BlockSpec((block_t, phi_z.shape[1]), lambda i, j, n: (i, 0)),
            pl.BlockSpec((block_n, phi_c.shape[1]), lambda i, j, n: (n, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_t, block_m), lambda i, j, n: (i, j)),
            pl.BlockSpec((block_t, block_m), lambda i, j, n: (i, j)),
            pl.BlockSpec((block_t, block_m), lambda i, j, n: (i, j)),
            pl.BlockSpec((block_t, 1), lambda i, j, n: (i, 0)),
            pl.BlockSpec((block_t, 1), lambda i, j, n: (i, 0)),
        ],
        out_shape=out_shape,
        name="rff_sample",
        interpret=interpret,
    )(meta, phi_z, phi_c)
    return ids, score, m_run, l_run
