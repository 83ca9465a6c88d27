"""Pallas TPU kernel: fused MIDX proposal tables (DESIGN §3).

One pass per query block, everything resident in VMEM:
  s1 = z1 @ C1ᵀ              (MXU)
  s2 = z2 @ C2ᵀ              (MXU)
  ψ  = exp(s2 − max) @ Wᵀ    (MXU; W = |Ω| counts, K×K)
  lse = logsumexp(s1 + logψ) (VPU)
vs. the unfused path: 3 reads of z from HBM + an HBM-materialized [T, K²]
joint table. Kernel writes 3K+1 floats per query.

Codebooks and the counts matrix are grid-invariant (index_map -> block 0),
so Mosaic keeps them in VMEM across the whole grid.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(z_ref, cb1_ref, cb2_ref, cnt_ref, *rest, split: bool,
            quantized: bool = False):
    if quantized:
        # low-bit codebooks: the [1, K] fp32 per-codeword scales dequantize
        # AFTER the dot — z @ (q·s)ᵀ = (z @ qᵀ)·sᵀ — so the MXU consumes the
        # 1-byte codebooks directly (DESIGN §12).
        sc1_ref, sc2_ref, s1_ref, s2_ref, lpsi_ref, lse_ref = rest
    else:
        s1_ref, s2_ref, lpsi_ref, lse_ref = rest
    z = z_ref[...].astype(jnp.float32)                 # [Tb, D]
    if split:
        d = z.shape[-1]
        z1, z2 = z[:, : d // 2], z[:, d // 2:]
    else:
        z1 = z2 = z
    cb1 = cb1_ref[...].astype(jnp.float32)             # [K, Dc]
    cb2 = cb2_ref[...].astype(jnp.float32)
    s1 = jax.lax.dot_general(z1, cb1, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    s2 = jax.lax.dot_general(z2, cb2, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    if quantized:
        s1 = s1 * sc1_ref[...]                         # [Tb, K] · [1, K]
        s2 = s2 * sc2_ref[...]
    c2 = jnp.max(s2, axis=-1, keepdims=True)
    e2 = jnp.exp(s2 - c2)                              # [Tb, K]
    cnt = cnt_ref[...].astype(jnp.float32)             # [K, K]
    psi = jax.lax.dot_general(e2, cnt, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
    log_psi = jnp.log(jnp.maximum(psi, 1e-30)) + c2
    l1 = s1 + log_psi
    m = jnp.max(l1, axis=-1, keepdims=True)
    lse = jnp.log(jnp.sum(jnp.exp(l1 - m), axis=-1, keepdims=True)) + m
    s1_ref[...] = s1
    s2_ref[...] = s2
    lpsi_ref[...] = log_psi
    lse_ref[...] = lse


@functools.partial(jax.jit,
                   static_argnames=("split", "block_t", "interpret"))
def midx_probs(z: jax.Array, cb1: jax.Array, cb2: jax.Array,
               counts: jax.Array, *, scale1: jax.Array | None = None,
               scale2: jax.Array | None = None, split: bool,
               block_t: int = 256, interpret: bool = False):
    """z [T, D] -> (s1 [T,K], s2 [T,K], log_psi [T,K], lse [T,1]).
    scale1/scale2 != None: quantized mode — cb1/cb2 are the low-bit
    codebooks, the [K, 1] fp32 scales dequantize the scores after the dot."""
    t, d = z.shape
    k = cb1.shape[0]
    assert t % block_t == 0, (t, block_t)
    grid = (t // block_t,)
    quantized = scale1 is not None
    out_shape = (
        jax.ShapeDtypeStruct((t, k), jnp.float32),
        jax.ShapeDtypeStruct((t, k), jnp.float32),
        jax.ShapeDtypeStruct((t, k), jnp.float32),
        jax.ShapeDtypeStruct((t, 1), jnp.float32),
    )
    kernel = functools.partial(_kernel, split=split, quantized=quantized)
    in_specs = [
        pl.BlockSpec((block_t, d), lambda i: (i, 0)),
        pl.BlockSpec((k, cb1.shape[1]), lambda i: (0, 0)),
        pl.BlockSpec((k, cb2.shape[1]), lambda i: (0, 0)),
        pl.BlockSpec((k, k), lambda i: (0, 0)),
    ]
    operands = [z, cb1, cb2, counts]
    if quantized:
        in_specs += [pl.BlockSpec((1, k), lambda i: (0, 0)),
                     pl.BlockSpec((1, k), lambda i: (0, 0))]
        operands += [scale1.astype(jnp.float32).reshape(1, k),
                     scale2.astype(jnp.float32).reshape(1, k)]
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((block_t, k), lambda i: (i, 0)),
            pl.BlockSpec((block_t, k), lambda i: (i, 0)),
            pl.BlockSpec((block_t, k), lambda i: (i, 0)),
            pl.BlockSpec((block_t, 1), lambda i: (i, 0)),
        ],
        out_shape=out_shape,
        name="midx_probs",
        interpret=interpret,
    )(*operands)
