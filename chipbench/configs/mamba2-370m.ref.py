"""Plain float32 reference of Mamba-2 (arXiv:2405.21060) language-model
training with the MIDX pooled sampled softmax, and the seeded weights both
the program and this reference start from.

Written from the published description, independent of the program:
  - each block: pre-RMSNorm; in-projections to z, x, B, C, dt; one causal
    depthwise convolution over [x; B; C] with bias and SiLU; dt = softplus(
    dt + dt_bias); A = -exp(A_log); the SSD recurrence h_t = exp(A dt_t)
    h_{t-1} + dt_t B_t x_t^T, y_t = C_t h_t + D x_t, evaluated in the
    paper's minimal chunked form (chunks of 64, segment sums, a quadratic
    pass over chunk states); y = RMSNorm(y * SiLU(z)); out-projection;
    residual add. Final RMSNorm. Tied embeddings.
  - the head: per sequence, the mean final hidden state z scores the two
    codebooks, q(c1, c2) is proportional to |cluster| exp(z.c1 + z.c2); M
    negatives are drawn by inverse CDF from one uniform per draw and a
    uniform member of the cluster, with the same random bits the program's
    draw consumes; the per-token loss is
    logsumexp(o_y, o_j - log(M q_j) [j != y]) - o_y, averaged over tokens.
  - AdamW on the gradient clipped to global norm 1, cosine schedule.
Matrix products run at `highest` precision. With `low=True` every matrix
product's operands are first rounded to float8 (e4m3): the control, one
precision step below the bfloat16 the configuration computes in.

Departure, shared with the program and noted in PERF.md: the gradient also
flows through log q into the pooled query, as the program differentiates
its loss; the published estimator treats q as a constant. The residual
stream is float32 here (the program keeps it in bfloat16; the configuration
file lists that cut).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
REF_CHUNK = 64


# ------------------------------------------------------------------ weights
def init_weights(spec: dict, key, vocab_rows: int) -> dict:
    """The model's weights from a key, in the program's parameter layout,
    float32, made in one jitted call on the device."""
    return _init(vocab_rows, spec["d_model"], spec["n_layer"], spec["d_state"],
                 spec["headdim"], spec["expand"], spec["d_conv"], key)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 5, 6))
def _init(vocab_rows, d, n_layer, n_state, headdim, expand, width, key):
    d_in = expand * d
    nh = d_in // headdim
    k_emb, k_layers = jax.random.split(key)

    def normal(k, shape, std):
        return std * jax.random.normal(k, shape, jnp.float32)

    def layer(k):
        ks = jax.random.split(k, 12)
        lo, hi = math.log(1e-3), math.log(1e-1)
        dt = jnp.exp(jax.random.uniform(ks[10], (nh,), minval=lo, maxval=hi))
        cw = 1.0 / math.sqrt(width)
        return {
            "ln1": {"scale": jnp.ones((d,), jnp.float32)},
            "mamba": {
                "z_proj": normal(ks[0], (d, d_in), d ** -0.5),
                "x_proj": normal(ks[1], (d, d_in), d ** -0.5),
                "b_proj": normal(ks[2], (d, n_state), d ** -0.5),
                "c_proj": normal(ks[3], (d, n_state), d ** -0.5),
                "dt_proj": normal(ks[4], (d, nh), d ** -0.5),
                "conv_x": jax.random.uniform(ks[5], (width, d_in),
                                             minval=-cw, maxval=cw),
                "conv_x_b": jax.random.uniform(ks[6], (d_in,),
                                               minval=-cw, maxval=cw),
                "conv_b": jax.random.uniform(ks[7], (width, n_state),
                                             minval=-cw, maxval=cw),
                "conv_b_b": jnp.zeros((n_state,), jnp.float32),
                "conv_c": jax.random.uniform(ks[8], (width, n_state),
                                             minval=-cw, maxval=cw),
                "conv_c_b": jnp.zeros((n_state,), jnp.float32),
                "a_log": jnp.log(jax.random.uniform(ks[9], (nh,), minval=1.0,
                                                    maxval=16.0)),
                # inverse softplus, so softplus(dt_bias) = dt at init
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "d_skip": jnp.ones((nh,), jnp.float32),
                "norm_scale": jnp.ones((d_in,), jnp.float32),
                "out_proj": normal(ks[11], (d_in, d),
                                   d_in ** -0.5 / math.sqrt(2 * n_layer)),
            },
        }

    return {
        "embed": normal(k_emb, (vocab_rows, d), 0.02),
        "final_norm": {"scale": jnp.ones((d,), jnp.float32)},
        "blocks": jax.vmap(layer)(jax.random.split(k_layers, n_layer)),
    }


# ------------------------------------------------------------------ forward
def _round(x, low: bool):
    return x.astype(F8).astype(jnp.float32) if low else x


def _mm(x, w, low: bool):
    return jnp.matmul(_round(x, low), _round(w, low), precision=HIGHEST)


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _segsum(a):
    """a [..., T] -> [..., T, T]: sum of a over (j, i] below the diagonal,
    -inf above it."""
    t = a.shape[-1]
    x = jnp.repeat(a[..., None], t, axis=-1)               # x[..., i, j] = a_i
    x = jnp.where(jnp.tril(jnp.ones((t, t), bool), -1), x, 0.0)
    s = jnp.cumsum(x, axis=-2)
    return jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)


def ssd(x, dt, a, bm, cm, chunk: int = REF_CHUNK):
    """The SSD recurrence over a whole sequence, minimal chunked form.
    x [b,l,h,p], dt [b,l,h], a [h], bm/cm [b,l,n] -> y [b,l,h,p]."""
    b, l, h, p = x.shape
    n = bm.shape[-1]
    c = l // chunk
    ein = functools.partial(jnp.einsum, precision=HIGHEST)
    xc = (x * dt[..., None]).reshape(b, c, chunk, h, p)
    ac = (dt * a).reshape(b, c, chunk, h).transpose(0, 3, 1, 2)   # [b,h,c,Q]
    bc = bm.reshape(b, c, chunk, n)
    cc = cm.reshape(b, c, chunk, n)
    acs = jnp.cumsum(ac, axis=-1)
    lmat = jnp.exp(_segsum(ac))                                    # [b,h,c,Q,Q]
    g = ein("bcln,bcsn->bcls", cc, bc)
    y_diag = ein("bhcls,bcshp->bclhp", g[:, None] * lmat, xc)
    decay = jnp.exp(acs[..., -1:] - acs)                           # [b,h,c,Q]
    states = ein("bcln,bhcl,bclhp->bchpn", bc, decay, xc)
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], 1)
    dchunk = jnp.exp(_segsum(jnp.pad(acs[..., -1], ((0, 0), (0, 0), (1, 0)))))
    states = ein("bhzc,bchpn->bzhpn", dchunk, states)[:, :-1]
    y_off = ein("bcln,bchpn,bhcl->bclhp", cc, states, jnp.exp(acs))
    return (y_diag + y_off).reshape(b, l, h, p)


def _block(spec, low, x, bp):
    eps = spec["rms_norm_eps"]
    m = bp["mamba"]
    d_in = spec["expand"] * spec["d_model"]
    n = spec["d_state"]
    hp = spec["headdim"]
    h = _rmsnorm(x, bp["ln1"]["scale"], eps)
    z = _mm(h, m["z_proj"], low)
    xbc = jnp.concatenate([_mm(h, m["x_proj"], low), _mm(h, m["b_proj"], low),
                           _mm(h, m["c_proj"], low)], -1)
    w = jnp.concatenate([m["conv_x"], m["conv_b"], m["conv_c"]], -1)
    bias = jnp.concatenate([m["conv_x_b"], m["conv_b_b"], m["conv_c_b"]], -1)
    width = w.shape[0]
    # out[t] = sum_k w[k] x[t - (width - 1) + k]: a causal depthwise conv
    conv = sum(w[k] * jnp.pad(xbc, ((0, 0), (width - 1 - k, 0), (0, 0))
                              )[:, :xbc.shape[1]]
               for k in range(width))
    xbc = jax.nn.silu(conv + bias)
    xs, bm, cm = xbc[..., :d_in], xbc[..., d_in:d_in + n], xbc[..., d_in + n:]
    dt = jax.nn.softplus(_mm(h, m["dt_proj"], low) + m["dt_bias"])
    bsz, l, _ = x.shape
    xh = xs.reshape(bsz, l, -1, hp)
    y = ssd(xh, dt, -jnp.exp(m["a_log"]), bm, cm)
    y = (y + m["d_skip"][:, None] * xh).reshape(bsz, l, d_in)
    y = _rmsnorm(y * jax.nn.silu(z), m["norm_scale"], eps)
    return x + _mm(y, m["out_proj"], low)


def hidden_states(spec, params, tokens, low: bool = False):
    x = _round(params["embed"], low)[tokens]
    body = jax.checkpoint(lambda x, bp: (_block(spec, low, x, bp), None))
    x, _ = jax.lax.scan(body, x, params["blocks"])
    return _rmsnorm(x, params["final_norm"]["scale"], spec["rms_norm_eps"])


# ------------------------------------------------------------------ the head
def pooled_draw(index: dict, key, hidden, m: int):
    """M shared negatives per sequence from the pooled MIDX proposal.
    Returns (ids [B, M], log_q [B, M])."""
    z = jnp.mean(hidden, axis=1)                                   # [B, D]
    s1 = jnp.matmul(z, index["codebook1"].T, precision=HIGHEST)
    s2 = jnp.matmul(z, index["codebook2"].T, precision=HIGHEST)
    k = s1.shape[-1]
    logit = (s1[:, :, None] + s2[:, None, :] +
             index["log_counts"]).reshape(z.shape[0], k * k)
    k_pair, k_member = jax.random.split(key)
    lse = jax.nn.logsumexp(logit, axis=-1, keepdims=True)
    cdf = jnp.cumsum(jnp.exp(logit - lse), axis=-1)
    cdf = cdf / cdf[:, -1:]
    u = jax.random.uniform(k_pair, (z.shape[0], m))
    cluster = jax.vmap(lambda c, uu: jnp.searchsorted(c, uu, side="left"))(
        cdf, u)
    cluster = jnp.clip(cluster, 0, k * k - 1).astype(jnp.int32)
    cnt = index["counts"].reshape(-1)[cluster]
    r = jax.random.randint(k_member, cluster.shape, 0, jnp.maximum(cnt, 1))
    ids = index["sorted_ids"][index["offsets"][cluster] + r]
    log_q = (jnp.take_along_axis(logit, cluster, axis=-1)
             - index["log_counts"].reshape(-1)[cluster] - lse)
    return ids, log_q


def loss(spec, params, index, tokens, labels, key, m: int, low=False):
    h = hidden_states(spec, params, tokens, low)
    ids, log_q = pooled_draw(index, key, h, m)
    table = _round(params["embed"], low)
    hr = _round(h, low)
    pos = jnp.sum(hr * table[labels], -1)                          # [B, S]
    neg = jnp.einsum("bsd,bmd->bsm", hr, table[ids], precision=HIGHEST)
    corr = neg - (math.log(m) + log_q[:, None, :])
    corr = jnp.where(ids[:, None, :] == labels[..., None], -jnp.inf, corr)
    tok = jax.nn.logsumexp(jnp.concatenate([pos[..., None], corr], -1),
                           -1) - pos
    return jnp.mean(tok)


# ------------------------------------------------------------------ training
def _lr(opt: dict, t):
    t = t.astype(jnp.float32)
    warm = jnp.minimum(t / max(opt["warmup_steps"], 1), 1.0)
    prog = jnp.clip((t - opt["warmup_steps"]) /
                    max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0, 1.0)
    cos = opt["final_frac"] + (1 - opt["final_frac"]) * 0.5 * (
        1 + jnp.cos(jnp.pi * prog))
    return opt["lr"] * warm * cos


def _step(spec, opt, m, low, params, mu, nu, t, tokens, labels, key, index):
    lval, g = jax.value_and_grad(
        lambda p: loss(spec, p, index, tokens, labels, key, m, low))(params)
    gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree_util.tree_leaves(g)))
    g = jax.tree_util.tree_map(
        lambda x: x * jnp.minimum(1.0, opt["clip_norm"] /
                                  jnp.maximum(gnorm, 1e-9)), g)
    t = t + 1
    lr = _lr(opt, t)
    b1, b2 = opt["b1"], opt["b2"]
    mu = jax.tree_util.tree_map(lambda a, x: b1 * a + (1 - b1) * x, mu, g)
    nu = jax.tree_util.tree_map(lambda a, x: b2 * a + (1 - b2) * x * x, nu, g)
    c1 = 1 - b1 ** t.astype(jnp.float32)
    c2 = 1 - b2 ** t.astype(jnp.float32)
    params = jax.tree_util.tree_map(
        lambda p, a, v: p - lr * ((a / c1) / (jnp.sqrt(v / c2) + opt["eps"])
                                  + opt["weight_decay"] * p),
        params, mu, nu)
    return params, mu, nu, t, lval, g


def leaf_norms(tree) -> np.ndarray:
    return np.asarray(jax.device_get(jnp.stack(
        [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
         for x in jax.tree_util.tree_leaves(tree)])))


def train_readings(spec: dict, params0: dict, index: dict, batches: list,
                   keys: list, opt: dict, m: int, low: bool = False) -> dict:
    """Follow the program's first len(batches) steps from the same weights,
    index, batches and keys. Returns the loss of each step, the per-leaf
    norms of the first (clipped) gradient, and the per-leaf norms of the
    parameters' change after the last step."""
    step = jax.jit(functools.partial(_step, spec, opt, m, low),
                   donate_argnums=(0, 1, 2))
    params = jax.tree_util.tree_map(jnp.copy, params0)
    mu = jax.tree_util.tree_map(jnp.zeros_like, params0)
    nu = jax.tree_util.tree_map(jnp.zeros_like, params0)
    t = jnp.zeros((), jnp.int32)
    losses, grad0 = [], None
    for (tokens, labels), key in zip(batches, keys):
        params, mu, nu, t, lval, g = step(params, mu, nu, t, tokens, labels,
                                          key, index)
        losses.append(float(lval))
        if grad0 is None:
            grad0 = leaf_norms(g)
        del g
    change = leaf_norms(jax.tree_util.tree_map(lambda a, b: a - b, params,
                                               params0))
    return {"losses": losses, "grad_norms": grad0, "change_norms": change}
