"""Pallas TPU kernel: causal GQA flash attention (online softmax).

Canonical TPU pattern: grid (B, H, nQ, nK) with nK innermost; VMEM scratch
carries (acc [Bq,hd], m [Bq,1], l [Bq,1]) across the kv dimension; the output
block is written on the last kv step. Causal skipping: kv blocks entirely
above the diagonal contribute nothing and are masked at block granularity
(Mosaic still iterates them — the XLA-visible win is VMEM locality; full
block-skip needs a scalar-prefetch grid, noted in §Perf).

GQA is expressed through the k/v BlockSpec index maps (kv head = h // group),
so no repeated KV materialization.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            causal: bool, sq: int, sk: int, block_q: int, block_k: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)                 # [Bq, hd]
    k = k_ref[0, 0].astype(jnp.float32)                 # [Bk, hd]
    scale = q.shape[-1] ** -0.5
    s = jax.lax.dot_general(q * scale, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [Bq, Bk]
    if causal:
        qi = iq * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                     (block_q, block_k), 0)
        kj = ik * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                     (block_q, block_k), 1)
        s = jnp.where(kj <= qi + (sk - sq), s, NEG_INF)

    m_prev = m_ref[...]                                 # [Bq, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)                              # [Bq, Bk]
    alpha = jnp.exp(m_prev - m_new)                     # [Bq, 1]
    l_new = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    v = v_ref[0, 0].astype(jnp.float32)                 # [Bk, hd]
    pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * alpha + pv
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(ik == nk - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False) -> jax.Array:
    """q [B,Sq,H,hd]; k/v [B,Sk,KV,hd] -> [B,Sq,H,hd]."""
    b, sq, h, hd = q.shape
    sk, kv = k.shape[1], k.shape[2]
    g = h // kv
    assert sq % block_q == 0 and sk % block_k == 0, (sq, sk)
    # layout: [B, H, S, hd] for clean 2D blocks
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    grid = (b, h, sq // block_q, sk // block_k)

    kernel = functools.partial(_kernel, causal=causal, sq=sq, sk=sk,
                               block_q=block_q, block_k=block_k)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda bi, hi, qi, ki, g=g: (bi, hi // g, ki, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda bi, hi, qi, ki, g=g: (bi, hi // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd),
                               lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        name="flash_attention",
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)
