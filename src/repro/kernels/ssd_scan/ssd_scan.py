"""Pallas TPU kernel: chunked SSD scan (mamba2 core), per (batch, head).

Grid (B, H, NC) with NC innermost; VMEM scratch carries the inter-chunk state
h [N, P] across chunk steps (flash-attention-style carry). Per chunk, all
work is MXU matmuls:
    cum  = T_lower @ adt                      (cumsum as a tril-ones matmul)
    CB   = C @ Bᵀ ;  L = tril(exp(cum_i − cum_j))
    y    = (CB ⊙ L) @ (dt·x) + e^{cum} ⊙ (C @ h)
    h'   = e^{cum_Q}·h + Bᵀ @ (e^{cum_Q − cum} ⊙ dt·x)
B/C are head-shared (ngroups=1): their BlockSpec index maps ignore the head
coordinate, so Mosaic re-reads the same [Q, N] tile for every head without
materializing per-head copies.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, b_ref, c_ref, adt_ref, dt_ref, y_ref, h_ref, *,
            chunk: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0, 0].astype(jnp.float32)                  # [Q, P]
    b = b_ref[0].astype(jnp.float32)                     # [Q, N]
    c = c_ref[0].astype(jnp.float32)                     # [Q, N]
    adt = adt_ref[0, 0].astype(jnp.float32)              # [Q, 1]
    dt = dt_ref[0, 0].astype(jnp.float32)                # [Q, 1]

    tril = jnp.tril(jnp.ones((chunk, chunk), jnp.float32))
    cum = jax.lax.dot_general(tril, adt, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)  # [Q,1]
    cb = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)   # [Q,Q]
    decay = jnp.exp(cum - cum.T)                          # [Q,Q]
    l_mat = jnp.where(tril > 0, decay, 0.0)
    dtx = x * dt                                          # [Q,P]
    y1 = jax.lax.dot_general(cb * l_mat, dtx, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    h = h_ref[...]
    y2 = jnp.exp(cum) * jax.lax.dot_general(
        c, h, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    y_ref[0, 0] = (y1 + y2).astype(y_ref.dtype)

    cum_last = cum[chunk - 1:chunk, :]                    # [1,1]
    seg = jnp.exp(cum_last - cum)                         # [Q,1]
    s_c = jax.lax.dot_general(b, dtx * seg, (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)  # [N,P]
    h_ref[...] = jnp.exp(cum_last)[0, 0] * h + s_c


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x: jax.Array, bmat: jax.Array, cmat: jax.Array, adt: jax.Array,
             dt: jax.Array, *, chunk: int = 128,
             interpret: bool = False) -> jax.Array:
    """x [Bt,S,H,P]; bmat/cmat [Bt,S,N]; adt/dt [Bt,S,H] -> y [Bt,S,H,P]."""
    bt, s, h, p = x.shape
    n = bmat.shape[-1]
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    xt = x.transpose(0, 2, 1, 3)                          # [Bt,H,S,P]
    adt_t = adt.transpose(0, 2, 1)[..., None]             # [Bt,H,S,1]
    dt_t = dt.transpose(0, 2, 1)[..., None]
    grid = (bt, h, nc)
    kernel = functools.partial(_kernel, chunk=chunk)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, chunk, n), lambda bi, hi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, chunk, n), lambda bi, hi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda bi, hi, ci: (bi, hi, ci, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, p),
                               lambda bi, hi, ci: (bi, hi, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((bt, h, s, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        name="ssd_scan",
        interpret=interpret,
    )(xt, bmat, cmat, adt_t, dt_t)
    return out.transpose(0, 2, 1, 3)
