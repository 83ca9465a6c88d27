"""Sequence-sharded flash decode attention with LSE merge (DESIGN §5).

At 32k–500k context the decode-step KV cache dwarfs the weights, so it shards
over the *sequence* dimension of the model axis (dist.sharding.
decode_cache_specs picks this layout whenever the KV-head count does not
divide tp).  Each shard then owns a contiguous Smax/n slice of the cache and
scores it locally; the shards merge with the standard log-sum-exp trick:

    m   = pmax_i(max(s_i))                  one scalar per (b, head)
    l   = psum_i(Σ exp(s_i − m))
    out = psum_i(exp(s_i − m) @ v_i) / l

Numerically identical to `models.attention.decode_attention` on the gathered
cache (same fp32 softmax, merely reassociated), with per-device work Smax/n
and three tiny collectives instead of an Smax-sized all-gather.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

NEG_INF = -1e30


def flash_decode_seq_sharded(q, k_cache, v_cache, pos, mesh, *,
                             axis: str = "model", window: int | None = None):
    """q [B,1,H,hd]; k/v caches [B,Smax,KV,hd] sequence-sharded over `axis`;
    pos scalar int32 or a per-slot [B] vector (slot-packed serving,
    DESIGN §5).  Returns [B,1,H,hd] replicated.

    Matches `models.attention.decode_attention(q, k, v, pos, window=...)`:
    cache entries beyond `pos` (and outside the sliding window) are masked.
    """
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n = sizes[axis]
    smax = k_cache.shape[1]
    if smax % n:
        raise ValueError(f"seq len {smax} must divide axis {axis!r} size {n}")
    local = smax // n

    def body(q, k, v, pos):
        b, _, h, hd = q.shape
        kv = k.shape[2]
        g = h // kv
        offset = jax.lax.axis_index(axis) * local
        qg = q.reshape(b, 1, kv, g, hd).astype(jnp.float32) * hd ** -0.5
        scores = jnp.einsum("bqkgh,bmkh->bkgqm", qg,
                            k.astype(jnp.float32))       # [b,kv,g,1,local]
        j = offset + jnp.arange(local)
        pos_col = jnp.reshape(jnp.asarray(pos), (-1, 1))   # [B,1] or [1,1]
        ok = j[None, :] <= pos_col
        if window is not None:
            ok &= j[None, :] > pos_col - window
        scores = jnp.where(ok[:, None, None, None, :], scores, NEG_INF)
        # LSE merge across sequence shards. pos >= 0 guarantees at least one
        # unmasked column globally, so m is finite and masked terms vanish.
        m = jax.lax.pmax(jnp.max(scores, axis=-1), axis)  # [b,kv,g,1]
        p = jnp.exp(scores - m[..., None])
        l = jax.lax.psum(jnp.sum(p, axis=-1), axis)       # [b,kv,g,1]
        acc = jax.lax.psum(
            jnp.einsum("bkgqm,bmkh->bqkgh", p, v.astype(jnp.float32)), axis)
        out = acc / jnp.moveaxis(l, 3, 1)[..., None]      # [b,1,kv,g,hd]
        return out.reshape(b, 1, h, hd).astype(q.dtype)

    return jax.shard_map(body, mesh=mesh,
                         in_specs=(P(), P(None, axis), P(None, axis), P()),
                         out_specs=P(), check_vma=False)(q, k_cache, v_cache,
                                                         pos)
