"""Data pipeline, optimizers, checkpoint manager, straggler watchdog."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.data import ZipfLM, make_lm_stream, zipf_tokens
from repro.launch.train import StragglerWatchdog
from repro.optim import (adamw, sgd, accumulate_gradients,
                         clip_by_global_norm, cosine_schedule)


# ------------------------------------------------------------------- data
def test_stream_determinism_and_skip_ahead():
    corpus = zipf_tokens(64, 17, 100, seed=0)
    s1 = make_lm_stream(corpus, 8, seed=3)
    s2 = make_lm_stream(corpus, 8, seed=3)
    b_a = s1.batch_at(41)
    b_b = s2.batch_at(41)          # O(1) skip-ahead, no iteration needed
    np.testing.assert_array_equal(b_a["tokens"], b_b["tokens"])
    # different shards see different data
    s3 = make_lm_stream(corpus, 8, shard=1, num_shards=2, seed=3)
    assert not np.array_equal(s1.batch_at(0)["tokens"],
                              s3.batch_at(0)["tokens"])
    # labels are next-token shifted
    np.testing.assert_array_equal(corpus[0, 1:],
                                  np.concatenate([corpus[0:1, 1:]])[0])


def test_zipf_lm_structure():
    gen = ZipfLM(vocab_size=200, num_clusters=8, seq_len=12, seed=0)
    toks = gen.sample(16)
    assert toks.shape == (16, 12)
    assert toks.min() >= 0 and toks.max() < 200
    counts = gen.unigram_counts(toks)
    assert counts.sum() == 16 * 12
    # Zipf: top decile of tokens carries a disproportionate share
    top = np.sort(counts)[::-1][:20].sum()
    assert top > counts.sum() * 0.3


@pytest.mark.parametrize("vocab,clusters", [(200, 8), (3, 16)])
def test_zipf_lm_matches_choice_loop(vocab, clusters):
    """The vectorised draws are numpy's `rng.choice(n, p=row)`, call by
    call, on the same stream."""
    gen = ZipfLM(vocab_size=vocab, num_clusters=clusters, seq_len=12, seed=3)
    token_cluster, trans, within, marginal = gen._tables()
    rng = np.random.default_rng(gen.seed + 1)
    ref = np.empty((16, 12), np.int32)
    cur = rng.integers(0, clusters, size=16)
    for t in range(12):
        noise = rng.random(16) < gen.within_cluster_noise
        coherent = [rng.choice(vocab, p=within[k]) for k in cur]
        tok = np.where(noise, rng.choice(vocab, p=marginal, size=16),
                       coherent)
        ref[:, t] = tok
        cur = np.array([rng.choice(clusters, p=trans[token_cluster[i]])
                        for i in tok])
    np.testing.assert_array_equal(gen.sample(16), ref)


# ------------------------------------------------------------------- optim
def test_adamw_converges_quadratic():
    opt = adamw(0.1, weight_decay=0.0)
    params = {"w": jnp.array([5.0, -3.0])}
    state = opt.init(params)
    for _ in range(200):
        grads = {"w": 2 * params["w"]}
        params, state = opt.update(grads, state, params)
    assert float(jnp.abs(params["w"]).max()) < 1e-2


def test_sgd_momentum_converges():
    opt = sgd(0.05, momentum=0.9)
    params = {"w": jnp.array([5.0])}
    state = opt.init(params)
    for _ in range(200):
        params, state = opt.update({"w": 2 * params["w"]}, state, params)
    assert float(jnp.abs(params["w"])[0]) < 1e-2


def test_clip_by_global_norm():
    g = {"a": jnp.array([3.0, 4.0])}
    clipped, norm = clip_by_global_norm(g, 1.0)
    assert abs(float(norm) - 5.0) < 1e-5
    assert abs(float(jnp.linalg.norm(clipped["a"])) - 1.0) < 1e-5


def test_grad_accumulation_matches_full_batch(key):
    w = jax.random.normal(key, (4, 3))
    x = jax.random.normal(jax.random.fold_in(key, 1), (8, 4))
    y = jax.random.normal(jax.random.fold_in(key, 2), (8, 3))

    def lg(params, batch):
        def loss(p):
            return jnp.mean((batch["x"] @ p - batch["y"]) ** 2)
        l, g = jax.value_and_grad(loss)(params)
        return l, g

    l_full, g_full = lg(w, {"x": x, "y": y})
    l_acc, g_acc = accumulate_gradients(lg, w, {"x": x, "y": y},
                                        num_microbatches=4)
    np.testing.assert_allclose(float(l_full), float(l_acc), atol=1e-6)
    np.testing.assert_allclose(np.asarray(g_full), np.asarray(g_acc),
                               atol=1e-6)


def test_cosine_schedule_shape():
    fn = cosine_schedule(1.0, warmup_steps=10, total_steps=100)
    lrs = [float(fn(jnp.int32(s))) for s in (0, 5, 10, 50, 100)]
    assert lrs[0] == 0.0 and lrs[1] == pytest.approx(0.5)
    assert lrs[2] == pytest.approx(1.0)
    assert lrs[3] < lrs[2] and lrs[4] == pytest.approx(0.1, abs=1e-3)


# ------------------------------------------------------------------- ckpt
def test_checkpoint_roundtrip_and_gc(tmp_path, key):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    tree = {"a": jax.random.normal(key, (4, 4)),
            "b": {"c": jnp.arange(5, dtype=jnp.int32)}}
    for step in (10, 20, 30):
        mgr.save(step, tree, metadata={"next_step": step})
    assert mgr.all_steps() == [20, 30]        # keep-2 GC
    assert mgr.latest_step() == 30
    like = jax.tree_util.tree_map(jnp.zeros_like, tree)
    restored = mgr.restore(30, like)
    np.testing.assert_array_equal(np.asarray(restored["a"]),
                                  np.asarray(tree["a"]))
    assert mgr.metadata(30)["next_step"] == 30


def test_checkpoint_atomicity(tmp_path, key):
    """A stale .tmp dir (simulated crash) is ignored by latest_step."""
    mgr = CheckpointManager(str(tmp_path), keep=3)
    tree = {"a": jnp.ones((2,))}
    mgr.save(5, tree)
    os.makedirs(os.path.join(str(tmp_path), "step_0000000009.tmp"))
    assert mgr.latest_step() == 5


def test_checkpoint_dtype_cast_on_restore(tmp_path, key):
    mgr = CheckpointManager(str(tmp_path))
    tree = {"w": jnp.ones((3,), jnp.float32)}
    mgr.save(1, tree)
    like = {"w": jnp.zeros((3,), jnp.bfloat16)}
    restored = mgr.restore(1, like)
    assert restored["w"].dtype == jnp.bfloat16


# ------------------------------------------------------------------- ft
def test_straggler_watchdog_detection():
    wd = StragglerWatchdog(alpha=0.5, threshold=1.5)
    for _ in range(10):
        assert not wd.observe(1.0)
    assert wd.observe(5.0)                    # injected delay trips it
    plan = wd.rebalance_plan(8)
    assert plan["shed_microbatches"] == 1
