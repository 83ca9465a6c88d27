"""Plain float32 reference of the Granite-3.0 MoE decoder
(ibm-granite/granite-3.0-1b-a400m-base) as the configuration file runs it,
and the seeded weights both the program and this reference start from.

Written from the published description, independent of the program: token
embedding; per layer a pre-RMSNorm grouped-query causal attention with
rotary position embedding (rotate-half, theta 10000) and a pre-RMSNorm
mixture of SwiGLU experts, each token sent to the 8 experts with the
largest router probabilities, weighted by those probabilities renormalised
over the 8, and no token dropped; final RMSNorm; logits against the tied
embedding. Every expert is computed densely here and the 8 chosen are
combined, which is the published result without any dispatch. The
serving head is the paper's MIDX sampler: `midx_draw` draws the next token
from the final hidden state under a given key, as the program's decode
head is specified to, so a served token can be held against the token this
reference draws under the same key.

The four Granite multipliers (embedding, attention, residual, logits) are
applied as the configuration file states them: with the neutral values it
runs at, since the program has none of them (noted there and in PERF.md).

Matrix products run at `highest` precision. With `low=True` every matrix
product's operands are first rounded to float8 (e4m3): the control, one
precision step below the bfloat16 the configuration computes in.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn


# ------------------------------------------------------------------ weights
def init_weights(spec: dict, key, vocab_rows: int) -> dict:
    """The model's weights from a key, in the program's parameter layout and
    the dtype the configuration serves them in, made in one jitted call."""
    return _init(vocab_rows, spec["hidden_size"], spec["num_hidden_layers"],
                 spec["num_attention_heads"], spec["num_key_value_heads"],
                 spec["head_dim"], spec["num_local_experts"],
                 spec["intermediate_size"], spec["param_dtype"], key)


@functools.partial(jax.jit, static_argnums=tuple(range(9)))
def _init(vocab_rows, d, n_layer, nh, nkv, hd, ne, f, dtype, key):
    dt = jnp.dtype(dtype)
    k_emb, k_layers = jax.random.split(key)
    out_scale = 1.0 / math.sqrt(2 * n_layer)

    def normal(k, shape, std):
        return (std * jax.random.normal(k, shape, jnp.float32)).astype(dt)

    def layer(k):
        ks = jax.random.split(k, 8)
        return {
            "ln1": {"scale": jnp.ones((d,), dt)},
            "attn": {"wq": normal(ks[0], (d, nh * hd), d ** -0.5),
                     "wk": normal(ks[1], (d, nkv * hd), d ** -0.5),
                     "wv": normal(ks[2], (d, nkv * hd), d ** -0.5),
                     "wo": normal(ks[3], (nh * hd, d),
                                  (nh * hd) ** -0.5 * out_scale)},
            "ln2": {"scale": jnp.ones((d,), dt)},
            "ffn": {"router": normal(ks[4], (d, ne), d ** -0.5),
                    "w_gate": normal(ks[5], (ne, d, f), d ** -0.5),
                    "w_up": normal(ks[6], (ne, d, f), d ** -0.5),
                    "w_down": normal(ks[7], (ne, f, d), f ** -0.5 * out_scale)},
        }

    return {
        "embed": normal(k_emb, (vocab_rows, d), 0.02),
        "final_norm": {"scale": jnp.ones((d,), dt)},
        "blocks": jax.vmap(layer)(jax.random.split(k_layers, n_layer)),
    }


# ------------------------------------------------------------------ forward
def _round(x, low: bool):
    x = x.astype(jnp.float32)
    return x.astype(F8).astype(jnp.float32) if low else x


def _rmsnorm(x, scale, eps):
    return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * scale.astype(jnp.float32))


def _rope(x, pos, theta):
    """x [L, heads, hd]; rotate-half rotary embedding at positions pos [L]."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freq               # [L, half]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _layer(spec, low, x, bp):
    ein = functools.partial(jnp.einsum, precision=HIGHEST)
    r = functools.partial(_round, low=low)
    eps = spec["rms_norm_eps"]
    nh, nkv, hd = (spec["num_attention_heads"], spec["num_key_value_heads"],
                   spec["head_dim"])
    k_top = spec["num_experts_per_tok"]
    l = x.shape[0]
    pos = jnp.arange(l)
    a = bp["attn"]
    h = r(_rmsnorm(x, bp["ln1"]["scale"], eps))
    q = _rope((h @ r(a["wq"])).reshape(l, nh, hd), pos, spec["rope_theta"])
    k = _rope((h @ r(a["wk"])).reshape(l, nkv, hd), pos, spec["rope_theta"])
    v = (h @ r(a["wv"])).reshape(l, nkv, hd)
    q = q.reshape(l, nkv, nh // nkv, hd)
    s = ein("qkgh,mkh->kgqm", r(q), r(k)) * spec["attention_multiplier"]
    s = jnp.where(jnp.tril(jnp.ones((l, l), bool)), s, -jnp.inf)
    o = ein("kgqm,mkh->qkgh", r(jax.nn.softmax(s, -1)), r(v)).reshape(l, -1)
    x = x + spec["residual_multiplier"] * (r(o) @ r(a["wo"]))
    f = bp["ffn"]
    h = r(_rmsnorm(x, bp["ln2"]["scale"], eps))
    probs = jax.nn.softmax(h @ r(f["router"]), -1)              # [L, E]
    top_w, top_e = jax.lax.top_k(probs, k_top)
    top_w = top_w / jnp.sum(top_w, -1, keepdims=True)
    gate = jnp.zeros_like(probs).at[pos[:, None], top_e].set(top_w)
    inner = (jax.nn.silu(ein("ld,edf->elf", h, r(f["w_gate"])))
             * ein("ld,edf->elf", h, r(f["w_up"])))
    y = ein("elf,efd->eld", r(inner), r(f["w_down"]))
    return x + spec["residual_multiplier"] * ein("le,eld->ld", gate, y)


def hidden(spec, params, tokens, low: bool = False):
    """tokens [L] -> the final normalised hidden state [L, D] at every
    position."""
    with jax.default_matmul_precision("highest"):
        x = _round(params["embed"], low)[tokens] * spec["embedding_multiplier"]
        x, _ = jax.lax.scan(lambda x, bp: (_layer(spec, low, x, bp), None),
                            x, params["blocks"])
        return _rmsnorm(x, params["final_norm"]["scale"], spec["rms_norm_eps"])


# ------------------------------------------------------- the MIDX decode head
def midx_draw(index: dict, table, z, key, m: int, temperature: float,
              low: bool = False):
    """One next token from the final hidden state z [D], drawn under `key`
    as the MIDX decode head defines it (PAPER.md, Eq. 6, sampled in two
    stages): the key splits into a draw key and a pick key, the draw key
    into three; m codeword pairs are drawn, k1 from Cat(s1 + log psi) and
    then k2 from Cat(s2 + log |Omega(k1, .)|), with s1 = z C1^T,
    s2 = z C2^T and psi[k1] = sum_k2 |Omega(k1, k2)| exp(s2[k2]); each pair
    gives a uniform member of its cluster (the CSR layout of `index`); the
    token is picked from Cat(logit / temperature - log q) over the m
    candidates, with log q = s1[k1] + s2[k2] - log sum exp(s1 + log psi)."""
    with jax.default_matmul_precision("highest"):
        z = _round(z, low)[None]                                  # [1, D]
        k_draw, k_pick = jax.random.split(key)
        k1_key, k2_key, k_member = jax.random.split(k_draw, 3)
        log_counts = index["log_counts"]                          # [K, K]
        kk = log_counts.shape[0]
        s1 = z @ _round(index["codebook1"], low).T                # [1, K]
        s2 = z @ _round(index["codebook2"], low).T
        log_psi = jax.nn.logsumexp(s2[:, None, :] + log_counts[None], -1)
        lse = jax.nn.logsumexp(s1 + log_psi, -1)                  # [1]
        k1 = jax.random.categorical(k1_key, (s1 + log_psi)[:, None, :],
                                    axis=-1, shape=(1, m))        # [1, m]
        k2 = jax.random.categorical(k2_key, s2[:, None, :] + log_counts[k1],
                                    axis=-1)                      # [1, m]
        cluster = k1 * kk + k2
        count = index["counts"].reshape(-1)[cluster]
        member = jax.random.randint(k_member, cluster.shape, 0,
                                    jnp.maximum(count, 1))
        ids = index["sorted_ids"][index["offsets"][cluster] + member]
        log_q = (jnp.take_along_axis(s1, k1, -1) +
                 jnp.take_along_axis(s2, k2, -1) - lse[:, None])
        cand = _round(table[ids[0]], low)                         # [m, D]
        score = (cand @ z[0]) / temperature - log_q[0]
        pick = jax.random.categorical(k_pick, score[None], axis=-1)
        return ids[0, pick[0]]


@functools.partial(jax.jit, static_argnums=(0, 7, 8, 9, 10))
def _draws(spec_items, params, index, tokens, seed, rid, start, m, count,
           temperature, low):
    spec = dict(spec_items)
    h = hidden(spec, params, tokens, low)
    # served token i is predicted at position start - 1 + i, drawn under the
    # request's key folded with that position
    pos = jnp.minimum(start - 1 + jnp.arange(count), tokens.shape[0] - 1)
    base = jax.random.fold_in(jax.random.PRNGKey(seed), rid)
    keys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(base, pos)
    scale = temperature * spec["logits_scaling"]
    return jax.vmap(lambda p, k: midx_draw(index, params["embed"], h[p], k, m,
                                           scale, low))(pos, keys)


def served_draws(spec: dict, params: dict, index: dict, tokens, start: int,
                 count: int, pad_to: int, count_max: int, seed: int, rid: int,
                 m: int, temperature: float, low: bool = False):
    """For one request (its prompt then its served tokens, `tokens` [n]), the
    token the MIDX decode head draws at each served position from this
    reference's hidden state, under the request's own keys: the key of
    request `rid` is PRNGKey(seed) folded with rid, and the draw that
    predicts position p + 1 folds it with p. With `low`, the float8 control
    draws instead. Served token i sits at position start + i. The sequence
    is padded to `pad_to`, and `count_max` positions are drawn, so every
    request runs one compiled program (causal: padding changes nothing
    before it)."""
    import numpy as np
    toks = np.zeros((pad_to,), np.int32)
    toks[:len(tokens)] = tokens
    hashable = tuple(sorted((k, v) for k, v in spec.items()
                            if isinstance(v, (int, float, str))))
    out = _draws(hashable, params, index, jnp.asarray(toks),
                 np.uint32(seed), np.uint32(rid), np.int32(start), m,
                 count_max, float(temperature), low)
    return np.asarray(jax.device_get(out))[:count]
