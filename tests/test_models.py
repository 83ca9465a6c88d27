"""Per-arch smoke tests (reduced configs) + decode/forward consistency."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config
from repro.models import (class_embeddings, decode_step, forward, heads,
                          init_decode_state, init_params, logits_full)

ARCH_NAMES = sorted(ARCHS)


def _inputs(cfg, b, s, key):
    toks = jax.random.randint(key, (b, s), 0, cfg.vocab_size)
    kw = {}
    if cfg.family == "vlm":
        kw["image_emb"] = 0.1 * jax.random.normal(
            jax.random.fold_in(key, 1), (b, cfg.num_image_tokens, cfg.d_model))
    if cfg.family == "audio":
        kw["frames"] = 0.1 * jax.random.normal(
            jax.random.fold_in(key, 2), (b, cfg.encoder_seq, cfg.d_model))
    return toks, kw


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_smoke_forward_shapes_no_nans(name, key):
    cfg = get_config(name).reduced()
    params = init_params(cfg, key)
    toks, kw = _inputs(cfg, 2, 16, key)
    out = forward(cfg, params, toks, **kw)
    h = out["hidden"]
    assert h.shape == (2, 16, cfg.d_model)
    assert bool(jnp.all(jnp.isfinite(h.astype(jnp.float32))))
    logits = logits_full(cfg, params, h)
    assert logits.shape == (2, 16, cfg.padded_vocab)
    assert bool(jnp.all(jnp.isfinite(logits)))


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_smoke_one_train_step(name, key):
    """One optimizer step on the reduced config: finite loss + param change."""
    from repro.launch.steps import make_train_step
    from repro.optim import adamw
    cfg = get_config(name).reduced()
    params = init_params(cfg, key)
    opt = adamw(1e-3)
    opt_state = opt.init(params)
    index = heads.init_head_state(cfg, params, key)
    toks, kw = _inputs(cfg, 2, 16, key)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, axis=1), **kw}
    step = make_train_step(cfg, opt)
    new_params, _, metrics = step(params, opt_state, index, batch,
                                  jax.random.PRNGKey(1))
    assert np.isfinite(float(metrics["loss"]))
    before = jax.tree_util.tree_leaves(params)[0]
    after = jax.tree_util.tree_leaves(new_params)[0]
    assert not np.allclose(np.asarray(before), np.asarray(after))


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_smoke_decode_step(name, key):
    cfg = get_config(name).reduced()
    params = init_params(cfg, key)
    toks, kw = _inputs(cfg, 2, 8, key)
    state = init_decode_state(cfg, params, 2, 16,
                              image_emb=kw.get("image_emb"),
                              frames=kw.get("frames"))
    h, state = decode_step(cfg, params, toks[:, 0], jnp.int32(0), state)
    assert h.shape == (2, cfg.d_model)
    assert bool(jnp.all(jnp.isfinite(h.astype(jnp.float32))))


@pytest.mark.parametrize("name", ["smollm-135m", "qwen2-moe-a2.7b",
                                  "mamba2-370m", "zamba2-7b",
                                  "llama-3.2-vision-11b", "whisper-tiny"])
def test_decode_matches_forward(name, key):
    """Teacher-forced decode steps reproduce forward() hidden states."""
    cfg = get_config(name).reduced()
    params = init_params(cfg, key)
    b, s = 2, 8
    toks, kw = _inputs(cfg, b, s, key)
    ref = forward(cfg, params, toks, **kw)["hidden"]
    state = init_decode_state(cfg, params, b, s,
                              image_emb=kw.get("image_emb"),
                              frames=kw.get("frames"))
    outs = []
    for t in range(s):
        h, state = decode_step(cfg, params, toks[:, t], jnp.int32(t), state)
        outs.append(h)
    dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec, np.float32),
                               np.asarray(ref, np.float32),
                               atol=5e-2, rtol=5e-2)


def test_mamba2_chunked_equals_recurrence(key):
    from repro.models import mamba2 as mm
    d_model, d_state, head_dim, expand = 32, 16, 16, 2
    p = mm.mamba2_init(key, d_model, d_state=d_state, head_dim=head_dim,
                       expand=expand, conv_width=4)
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(1), (2, 24, d_model))
    y8 = mm.apply_mamba2(p, x, d_state=d_state, head_dim=head_dim,
                         expand=expand, chunk=8)
    y24 = mm.apply_mamba2(p, x, d_state=d_state, head_dim=head_dim,
                          expand=expand, chunk=24)
    np.testing.assert_allclose(np.asarray(y8), np.asarray(y24), atol=1e-5)
    st = mm.mamba2_decode_state(2, d_model, d_state=d_state,
                                head_dim=head_dim, expand=expand, conv_width=4)
    outs = []
    for t in range(24):
        o, st = mm.decode_mamba2(p, x[:, t:t + 1], st, d_state=d_state,
                                 head_dim=head_dim, expand=expand)
        outs.append(o)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(outs, 1)),
                               np.asarray(y8), atol=1e-5)


def test_mamba2_full_chunk_gradient_is_finite(key):
    """At the published chunk (256) the decay above the causal diagonal
    exceeds exp's fp32 range; the masked entries must not leak inf·0 = NaN
    into the gradient."""
    from repro.models import mamba2 as mm
    d_model, d_state, head_dim, expand = 64, 16, 16, 2
    p = mm.mamba2_init(key, d_model, d_state=d_state, head_dim=head_dim,
                       expand=expand, conv_width=4)
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(1), (1, 256, d_model))

    def loss(p, x):
        y = mm.apply_mamba2(p, x, d_state=d_state, head_dim=head_dim,
                            expand=expand, chunk=256)
        return jnp.mean(y.astype(jnp.float32) ** 2)

    grads = jax.grad(loss, argnums=(0, 1))(p, x)
    for g in jax.tree_util.tree_leaves(grads):
        assert bool(jnp.all(jnp.isfinite(g)))


def test_attention_chunked_equals_direct(key):
    """Flash (fwd + custom-vjp bwd) path == direct einsum path."""
    from repro.models.attention import _direct_attention, attention
    q = jax.random.normal(key, (2, 64, 4, 16))
    k = jax.random.normal(jax.random.fold_in(key, 1), (2, 64, 2, 16))
    v = jax.random.normal(jax.random.fold_in(key, 2), (2, 64, 2, 16))
    for causal in (True, False):
        for window in (None, 16):
            d = _direct_attention(q, k, v, causal, window)
            c = attention(q, k, v, causal=causal, window=window,
                          direct_threshold=8, q_chunk=16, kv_chunk=16)
            np.testing.assert_allclose(np.asarray(c), np.asarray(d),
                                       atol=2e-5, rtol=2e-3)
    # gradients through the custom-vjp path match autodiff-through-direct
    gf = jax.grad(lambda q: attention(q, k, v, causal=True, direct_threshold=8,
                                      q_chunk=16, kv_chunk=16).sum())(q)
    gd = jax.grad(lambda q: _direct_attention(q, k, v, True, None).sum())(q)
    np.testing.assert_allclose(np.asarray(gf), np.asarray(gd), atol=1e-4,
                               rtol=1e-3)


def test_loss_midx_close_to_full(key):
    """With many negatives the MIDX loss approaches the full softmax loss."""
    cfg = get_config("paper-lm").reduced().with_head(
        num_negatives=256, proposal="per_token")
    params = init_params(cfg, key)
    index = heads.init_head_state(cfg, params, key)
    toks = jax.random.randint(key, (2, 16), 0, cfg.vocab_size)
    out = forward(cfg, params, toks)
    labels = jnp.roll(toks, -1, 1)
    l_m = float(heads.loss_midx(cfg, params, index, out["hidden"], labels,
                                jax.random.PRNGKey(3)))
    l_f = float(heads.loss_full(cfg, params, out["hidden"], labels))
    assert abs(l_m - l_f) / l_f < 0.08, (l_m, l_f)


def test_midx_decode_head(key):
    cfg = get_config("paper-lm").reduced()
    params = init_params(cfg, key)
    index = heads.init_head_state(cfg, params, key)
    hidden = 0.3 * jax.random.normal(key, (4, cfg.d_model))
    out = heads.midx_decode_head(cfg, params, index, hidden,
                                 jax.random.PRNGKey(1))
    assert out.token.shape == (4,)
    assert bool(jnp.all((out.token >= 0) & (out.token < cfg.padded_vocab)))
