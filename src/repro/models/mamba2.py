"""Mamba2 / SSD block (arXiv:2405.21060), chunked matmul formulation.

TPU-native adaptation: the recurrence is evaluated with the state-space
duality — intra-chunk quadratic (attention-like) matmuls + an inter-chunk
state scan — so almost all FLOPs land on the MXU. Single-step `decode`
maintains (conv_state, ssm_state) carries.

TP note: projections are SPLIT per segment (z | x | B | C | dt) rather than
one fused in_proj, so the z/x/dt outputs shard cleanly over the model axis on
d_inner (B/C are tiny and replicated) without slicing across shard boundaries
(DESIGN §4). Heads shard with d_inner since B/C are head-shared (ngroups=1).

Shapes: d_inner = expand·d_model, H = d_inner/head_dim heads, N = ssm_state.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init


def mamba2_init(key, d_model: int, *, d_state: int, head_dim: int,
                expand: int, conv_width: int):
    d_inner = expand * d_model
    nheads = d_inner // head_dim
    ks = jax.random.split(key, 6)
    return {
        "z_proj": dense_init(ks[0], d_model, d_inner),
        "x_proj": dense_init(ks[1], d_model, d_inner),
        "b_proj": dense_init(ks[2], d_model, d_state),
        "c_proj": dense_init(ks[3], d_model, d_state),
        "dt_proj": dense_init(ks[4], d_model, nheads),
        "conv_x": 0.1 * jax.random.normal(ks[5], (conv_width, d_inner), jnp.float32),
        "conv_x_b": jnp.zeros((d_inner,), jnp.float32),
        "conv_b": 0.1 * jax.random.normal(jax.random.fold_in(ks[5], 1),
                                          (conv_width, d_state), jnp.float32),
        "conv_b_b": jnp.zeros((d_state,), jnp.float32),
        "conv_c": 0.1 * jax.random.normal(jax.random.fold_in(ks[5], 2),
                                          (conv_width, d_state), jnp.float32),
        "conv_c_b": jnp.zeros((d_state,), jnp.float32),
        "a_log": jnp.log(jnp.arange(1, nheads + 1, dtype=jnp.float32)),
        "dt_bias": jnp.zeros((nheads,), jnp.float32),
        "d_skip": jnp.ones((nheads,), jnp.float32),
        "norm_scale": jnp.ones((d_inner,), jnp.float32),
        "out_proj": dense_init(jax.random.fold_in(ks[5], 3), d_inner, d_model),
    }


def _causal_conv(x, w, b):
    """Depthwise causal conv along seq. x [B,S,C]; w [W,C]; silu activation."""
    width = w.shape[0]
    pad = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    out = sum(pad[:, i: i + x.shape[1], :] * w[i] for i in range(width))
    return jax.nn.silu(out + b)


def _gated_norm(y, z, scale, eps=1e-5):
    """y ⊙ silu(z), then RMSNorm over d_inner (mamba2's gated norm)."""
    g = (y * jax.nn.silu(z)).astype(jnp.float32)
    var = jnp.mean(g * g, axis=-1, keepdims=True)
    return (g * jax.lax.rsqrt(var + eps) * scale).astype(y.dtype)


def apply_mamba2(p, x, *, d_state: int, head_dim: int, expand: int,
                 chunk: int = 256, return_state: bool = False):
    """x [B,S,D] -> [B,S,D].

    return_state=True additionally returns the single-step decode carry
    after consuming the whole sequence — the same pytree
    `mamba2_decode_state` allocates — so a batched prefill can seed
    `decode_mamba2` without a per-token Python loop (DESIGN §5).
    """
    bsz, s, d_model = x.shape
    d_inner = expand * d_model
    nheads = d_inner // head_dim
    dt_ = x.dtype

    z = x @ p["z_proj"].astype(dt_)
    u_x = x @ p["x_proj"].astype(dt_)
    u_b = x @ p["b_proj"].astype(dt_)
    u_c = x @ p["c_proj"].astype(dt_)
    xs = _causal_conv(u_x, p["conv_x"].astype(dt_), p["conv_x_b"].astype(dt_))
    bmat = _causal_conv(u_b, p["conv_b"].astype(dt_), p["conv_b_b"].astype(dt_)
                        ).astype(jnp.float32)                      # [B,S,N]
    cmat = _causal_conv(u_c, p["conv_c"].astype(dt_), p["conv_c_b"].astype(dt_)
                        ).astype(jnp.float32)                      # [B,S,N]
    dt = jax.nn.softplus((x @ p["dt_proj"].astype(dt_)).astype(jnp.float32)
                         + p["dt_bias"])                           # [B,S,H]
    a = -jnp.exp(p["a_log"])                                       # [H]
    xh = xs.reshape(bsz, s, nheads, head_dim).astype(jnp.float32)  # [B,S,H,P]

    nc = s // chunk
    assert nc * chunk == s, (s, chunk)
    # chunked views, chunk axis leading for the scan
    xc = jnp.moveaxis(xh.reshape(bsz, nc, chunk, nheads, head_dim), 1, 0)
    bc = jnp.moveaxis(bmat.reshape(bsz, nc, chunk, d_state), 1, 0)
    cc = jnp.moveaxis(cmat.reshape(bsz, nc, chunk, d_state), 1, 0)
    dtc = jnp.moveaxis(dt.reshape(bsz, nc, chunk, nheads), 1, 0)
    mask = jnp.tril(jnp.ones((chunk, chunk), bool))

    def chunk_body(h_prev, inp):
        x_c, b_c, c_c, dt_c = inp                # [B,Q,H,P],[B,Q,N],[B,Q,N],[B,Q,H]
        cum = jnp.cumsum(a[None, None, :] * dt_c, axis=1)             # [B,Q,H]
        # intra-chunk: Y1[i] = Σ_{j<=i} (C_i·B_j) exp(cum_i−cum_j) dt_j x_j
        cb = jnp.einsum("bin,bjn->bij", c_c, b_c)                     # [B,Q,Q]
        decay = cum[:, :, None, :] - cum[:, None, :, :]               # [B,i,j,H]
        # mask before the exp: above the diagonal decay > 0 overflows to inf
        # at full chunk length, and inf · 0 in the where's gradient is NaN
        l_mat = jnp.exp(jnp.where(mask[None, :, :, None], decay, -jnp.inf))
        # the [B,i,j,H] weights first, then one matmul over j: as a single
        # four-operand einsum, its transpose materialises [B,i,H,P,j]
        # (16 GiB per layer for mamba2-370m at batch 8 x 1024)
        w = cb[..., None] * l_mat * dt_c[:, None, :, :]               # [B,i,j,H]
        y1 = jnp.einsum("bijh,bjhp->bihp", w, x_c)
        # inter-chunk: Y2[i] = exp(cum_i) C_i · H_prev
        y2 = jnp.einsum("bin,bih,bhnp->bihp", c_c, jnp.exp(cum), h_prev)
        # state: H = exp(Σa) H_prev + Σ_j exp(cum_last−cum_j) B_j (dt_j x_j)ᵀ
        seg = jnp.exp(cum[:, -1:, :] - cum)                           # [B,Q,H]
        s_c = jnp.einsum("bjn,bjh,bjhp->bhnp", b_c, seg * dt_c, x_c)
        h_new = jnp.exp(cum[:, -1, :])[:, :, None, None] * h_prev + s_c
        return h_new, y1 + y2

    h0 = jnp.zeros((bsz, nheads, d_state, head_dim), jnp.float32)
    h_last, y_chunks = jax.lax.scan(chunk_body, h0, (xc, bc, cc, dtc))
    y = jnp.moveaxis(y_chunks, 0, 1).reshape(bsz, s, nheads, head_dim)
    y = y + p["d_skip"][None, None, :, None] * xh
    y = y.reshape(bsz, s, d_inner).astype(dt_)
    y = _gated_norm(y, z, p["norm_scale"])
    out = y @ p["out_proj"].astype(dt_)
    if not return_state:
        return out

    def hist(u):
        # decode's `_conv_step` keeps the last W-1 *pre-conv* projected
        # inputs; left-pad with zeros when the sequence is shorter.
        w1 = p["conv_x"].shape[0] - 1
        u = jnp.pad(u, ((0, 0), (max(0, w1 - s), 0), (0, 0)))
        return u[:, u.shape[1] - w1:]

    state = {"conv_x": hist(u_x), "conv_b": hist(u_b), "conv_c": hist(u_c),
             "ssm": h_last}
    return out, state


def mamba2_decode_state(bsz: int, d_model: int, *, d_state: int,
                        head_dim: int, expand: int, conv_width: int,
                        dtype=jnp.float32):
    d_inner = expand * d_model
    nheads = d_inner // head_dim
    return {
        "conv_x": jnp.zeros((bsz, conv_width - 1, d_inner), dtype),
        "conv_b": jnp.zeros((bsz, conv_width - 1, d_state), dtype),
        "conv_c": jnp.zeros((bsz, conv_width - 1, d_state), dtype),
        "ssm": jnp.zeros((bsz, nheads, d_state, head_dim), jnp.float32),
    }


def _conv_step(hist, cur, w, b):
    """hist [B,W-1,C], cur [B,C] -> (out [B,C], new hist)."""
    full = jnp.concatenate([hist, cur[:, None, :].astype(hist.dtype)], axis=1)
    out = jnp.sum(full * w[None], axis=1) + b
    return jax.nn.silu(out), full[:, 1:]


def decode_mamba2(p, x, state, *, d_state: int, head_dim: int, expand: int):
    """Single-token step. x [B,1,D] -> (y [B,1,D], new state)."""
    bsz, _, d_model = x.shape
    d_inner = expand * d_model
    nheads = d_inner // head_dim
    dt_ = x.dtype
    x0 = x[:, 0]

    z = x0 @ p["z_proj"].astype(dt_)
    xs, conv_x = _conv_step(state["conv_x"], x0 @ p["x_proj"].astype(dt_),
                            p["conv_x"].astype(state["conv_x"].dtype),
                            p["conv_x_b"].astype(state["conv_x"].dtype))
    bvec, conv_b = _conv_step(state["conv_b"], x0 @ p["b_proj"].astype(dt_),
                              p["conv_b"].astype(state["conv_b"].dtype),
                              p["conv_b_b"].astype(state["conv_b"].dtype))
    cvec, conv_c = _conv_step(state["conv_c"], x0 @ p["c_proj"].astype(dt_),
                              p["conv_c"].astype(state["conv_c"].dtype),
                              p["conv_c_b"].astype(state["conv_c"].dtype))
    bvec = bvec.astype(jnp.float32)
    cvec = cvec.astype(jnp.float32)
    dt = jax.nn.softplus((x0 @ p["dt_proj"].astype(dt_)).astype(jnp.float32)
                         + p["dt_bias"])                           # [B,H]
    a = -jnp.exp(p["a_log"])
    xh = xs.reshape(bsz, nheads, head_dim).astype(jnp.float32)

    decay = jnp.exp(a[None] * dt)                                  # [B,H]
    h_new = (decay[:, :, None, None] * state["ssm"]
             + jnp.einsum("bn,bh,bhp->bhnp", bvec, dt, xh))
    y = jnp.einsum("bn,bhnp->bhp", cvec, h_new)
    y = y + p["d_skip"][None, :, None] * xh
    y = y.reshape(bsz, d_inner).astype(dt_)
    y = _gated_norm(y, z, p["norm_scale"])
    out = (y @ p["out_proj"].astype(dt_))[:, None, :]
    return out, {"conv_x": conv_x, "conv_b": conv_b, "conv_c": conv_c,
                 "ssm": h_new}
