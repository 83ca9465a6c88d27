"""Vocab-parallel head (DESIGN §9): row-sharded class table + MIDX index.

Proof obligations (the feature IS its parity suite):
  - spec factories: class tables get P(vocab, None), codebooks replicate,
    CSR leaves split their shard dim; non-dividing vocabs raise, and
    `refresh_table_spec` no longer silently replicates them (regression);
  - two-stage draws are BITWISE identical to the replicated sampler
    (contiguous row ownership + stable-argsort CSR keep the random bits);
  - loss and grads through shard_map match heads.loss_midx to <=1e-5 for
    all three proposals, fused and unfused;
  - the full vocab-parallel train step reproduces make_train_step's
    updated params and loss;
  - the native per-shard subindex build/refresh keeps the CSR invariants
    with counts psummed exactly;
  - the pad-and-mask sharded refresh on a non-dividing padded vocab
    matches the replicated refresh (regression for the old fallback).

Multi-device tests run in subprocesses with 8 forced host devices
(XLA_FLAGS, test_dist.py convention); this process must stay at 1 device.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.base import HeadConfig, ModelConfig
from repro.dist import (head_table_spec, refresh_rows_per_shard,
                        refresh_table_spec, shard_index, vocab_index_specs,
                        vocab_param_specs)
from repro.launch import steps as steps_mod
from repro.models import heads, init_params

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cfg(proposal="per_token", vocab=200):
    return ModelConfig(
        name="vp-test", family="dense", num_layers=1, d_model=32,
        num_heads=2, num_kv_heads=2, d_ff=64, vocab_size=vocab, head_dim=16,
        vocab_pad_multiple=8, remat=False, dtype="float32",
        head=HeadConfig(mode="midx", midx_k=8, num_negatives=12,
                        proposal=proposal, kmeans_iters=2))


# ---------------------------------------------------------------------------
# spec factories (single device)
# ---------------------------------------------------------------------------

def test_head_table_spec():
    assert head_table_spec(padded_vocab=200, vp=1) == P()
    assert head_table_spec(padded_vocab=200, vp=8) == P("vocab", None)
    with pytest.raises(ValueError):
        head_table_spec(padded_vocab=201, vp=8)


def test_refresh_rows_per_shard_is_ceil():
    assert refresh_rows_per_shard(96, 8) == 12
    assert refresh_rows_per_shard(100, 8) == 13      # tail pad-and-masked
    assert refresh_rows_per_shard(7, 1) == 7


def test_refresh_table_spec_non_dividing_regression():
    """Vpad % dp != 0 used to silently fall back to P() (replicated) —
    the refresh step now pads and masks instead, so the spec stays sharded."""
    assert refresh_table_spec(padded_vocab=100, dp=8) == P("data")
    assert refresh_table_spec(padded_vocab=96, dp=8) == P("data")
    assert refresh_table_spec(padded_vocab=100, dp=1) == P()


def test_vocab_param_specs_shard_only_class_tables():
    cfg = _cfg()
    p_abs = steps_mod.abstract_params(cfg)
    specs = vocab_param_specs(cfg, p_abs, vp=4)
    assert specs["embed"] == P("vocab", None)
    if "head" in specs:
        assert specs["head"] == P("vocab", None)
    for path, sp in jax.tree_util.tree_flatten_with_path(specs)[0]:
        top = path[0].key if hasattr(path[0], "key") else None
        if top not in ("embed", "head"):
            assert all(e is None for e in sp), (path, sp)


def test_vocab_index_specs_replicate_codebooks():
    cfg = _cfg()
    sh_abs = steps_mod.abstract_vocab_index(cfg, steps_mod.abstract_params(cfg),
                                            4)
    specs = vocab_index_specs(sh_abs)
    assert specs.codebook1 == P() and specs.codebook2 == P()
    for name in ("sorted_ids", "offsets", "counts", "log_counts",
                 "assign1", "assign2"):
        assert getattr(specs, name)[0] == "vocab", name


def test_shard_index_roundtrip_and_divisibility():
    cfg = _cfg()
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)
    index = heads.init_head_state(cfg, params, jax.random.fold_in(key, 1))
    n = 4
    sh = shard_index(index, n)
    v = index.assign1.shape[0]
    rows = v // n
    assert sh.num_classes == v and sh.rows_per_shard == rows
    # per-shard cell counts sum exactly to the global cell counts
    np.testing.assert_array_equal(np.asarray(sh.counts).sum(0),
                                  np.asarray(index.counts))
    for i in range(n):
        # each shard's CSR is over LOCAL row ids: a permutation of [0, rows)
        assert sorted(np.asarray(sh.sorted_ids[i]).tolist()) == \
            list(range(rows))
        assert int(np.asarray(sh.offsets[i])[-1]) == rows
        # local assignments are the owner's slice of the global ones
        np.testing.assert_array_equal(
            np.asarray(sh.assign1[i]),
            np.asarray(index.assign1[i * rows:(i + 1) * rows]))
        # per-shard log_counts describe the LOCAL cells (-inf when empty)
        cnt = np.asarray(sh.counts[i])
        lc = np.asarray(sh.log_counts[i])
        np.testing.assert_allclose(lc[cnt > 0], np.log(cnt[cnt > 0]),
                                   atol=1e-6)
        assert np.all(np.isneginf(lc[cnt == 0]))
    with pytest.raises(ValueError):
        shard_index(index, 3)


# ---------------------------------------------------------------------------
# multi-device parity (subprocess, 8 forced host devices)
# ---------------------------------------------------------------------------

def _run(py: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(py)],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    return out.stdout


_SETUP = """
    import functools
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.configs.base import HeadConfig, ModelConfig
    from repro.launch.mesh import make_mesh
    from repro.core import midx as midx_mod
    from repro.dist import vocab_parallel as vp
    from repro.dist import sharding as shd
    from repro.models import heads, init_params
    from repro.models.model import class_embeddings

    def make(proposal):
        cfg = ModelConfig(
            name="vp-test", family="dense", num_layers=1, d_model=32,
            num_heads=2, num_kv_heads=2, d_ff=64, vocab_size=200,
            head_dim=16, vocab_pad_multiple=8, remat=False, dtype="float32",
            head=HeadConfig(mode="midx", midx_k=8, num_negatives=12,
                            proposal=proposal, kmeans_iters=2))
        key = jax.random.PRNGKey(0)
        params = init_params(cfg, key)
        index = heads.init_head_state(cfg, params, jax.random.fold_in(key, 1))
        h = jax.random.normal(jax.random.fold_in(key, 2),
                              (2, 8, cfg.d_model)) * 0.3
        labels = jax.random.randint(jax.random.fold_in(key, 3), (2, 8), 0,
                                    cfg.vocab_size)
        skey = jax.random.fold_in(key, 4)
        return cfg, params, index, h, labels, skey
    n = 8
"""


def test_sample_twostage_vp_bitwise_parity():
    """Draw ids are BITWISE equal to the replicated two-stage sampler."""
    _run(_SETUP + """
    cfg, params, index, h, labels, skey = make("per_token")
    mesh = make_mesh((n,), ("vocab",))
    sharded = vp.shard_index(index, n)
    idx_specs = shd.vocab_index_specs(sharded)

    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(idx_specs, P(), P()), out_specs=P(),
                       check_vma=False)
    def draw(si, k, z):
        d = vp.sample_twostage_vp(vp.local_index(si), k, z,
                                  cfg.head.num_negatives, axis="vocab")
        return d.ids, d.log_q

    ids, lq = draw(sharded, skey, h)
    ref = midx_mod.sample_twostage(index, skey, h, cfg.head.num_negatives)
    assert bool(jnp.all(ids == ref.ids)), "draws not bitwise identical"
    assert float(jnp.max(jnp.abs(lq - ref.log_q))) < 1e-5
    """)


def test_embed_lookup_matches_gather():
    _run(_SETUP + """
    cfg, params, index, h, labels, skey = make("per_token")
    table = class_embeddings(cfg, params).astype(jnp.float32)
    mesh = make_mesh((n,), ("vocab",))

    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(P("vocab", None), P()), out_specs=P(),
                       check_vma=False)
    def emb(t, tok):
        return vp.embed_lookup(t, tok, axis="vocab")

    out = emb(table, labels)
    assert float(jnp.max(jnp.abs(out - table[labels]))) < 1e-6
    """)


@pytest.mark.parametrize("proposal,fused", [("per_token", False),
                                            ("per_token", True),
                                            ("pooled", False),
                                            ("mixture", False)])
def test_loss_and_grad_parity(proposal, fused):
    """shard_map'd loss_midx_vp == heads.loss_midx: loss and both grads
    (class table, hidden) to <=1e-5, differentiating THROUGH shard_map."""
    _run(_SETUP + f"""
    proposal, fused = {proposal!r}, {fused}
    """ + """
    cfg, params, index, h, labels, skey = make(proposal)
    table = class_embeddings(cfg, params).astype(jnp.float32)
    mesh = make_mesh((n,), ("vocab",))
    sharded = vp.shard_index(index, n)
    idx_specs = shd.vocab_index_specs(sharded)
    tbl_spec = shd.head_table_spec(padded_vocab=table.shape[0], vp=n)

    def vp_loss(tbl, hh):
        @functools.partial(jax.shard_map, mesh=mesh,
                           in_specs=(tbl_spec, idx_specs, P()),
                           out_specs=P(), check_vma=False)
        def body(t, si, z):
            return vp.loss_midx_vp(cfg, t, vp.local_index(si), z, labels,
                                   skey, axis="vocab", fused=fused,
                                   interpret=fused)
        return body(tbl, sharded, hh)

    def ref_loss(tbl, hh):
        p2 = dict(params)
        p2["embed" if cfg.tie_embeddings else "head"] = tbl
        return heads.loss_midx(cfg, p2, index, hh, labels, skey,
                               fused=fused, interpret=fused)

    lv, gv = jax.value_and_grad(vp_loss, argnums=(0, 1))(table, h)
    lr, gr = jax.value_and_grad(ref_loss, argnums=(0, 1))(table, h)
    assert abs(float(lv) - float(lr)) < 1e-5, (float(lv), float(lr))
    assert float(jnp.max(jnp.abs(gv[0] - gr[0]))) < 1e-5, "d(table)"
    assert float(jnp.max(jnp.abs(gv[1] - gr[1]))) < 1e-5, "d(hidden)"
    """)


def test_train_step_matches_replicated():
    """One full vocab-parallel train step == make_train_step: loss and every
    updated param to <=1e-5 (inside-shard_map grads + correction rule)."""
    _run(_SETUP + """
    from repro.launch import steps as steps_mod
    from repro.optim import adamw
    cfg, params, index, h, labels, skey = make("per_token")
    key = jax.random.PRNGKey(0)
    tokens = jax.random.randint(jax.random.fold_in(key, 5), (2, 8), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens, "labels": labels}
    mesh = make_mesh((1, n), ("data", "vocab"))
    opt = adamw(1e-3)
    opt_state = opt.init(params)
    sharded = vp.shard_index(index, n)

    step_vp = jax.jit(steps_mod.make_vocab_parallel_train_step(
        cfg, opt, mesh, fused_head=False))
    p_vp, o_vp, m_vp = step_vp(params, opt_state, sharded, batch, skey)

    # the vp step folds the key with the linear DATA shard index (0 here)
    step_ref = jax.jit(steps_mod.make_train_step(cfg, opt, fused_head=False))
    p_ref, o_ref, m_ref = step_ref(params, opt_state, index, batch,
                                   jax.random.fold_in(skey, 0))

    assert abs(float(m_vp["loss"]) - float(m_ref["loss"])) < 1e-5
    assert abs(float(m_vp["grad_norm"]) - float(m_ref["grad_norm"])) < 1e-5
    for (pa, a), (pb, b) in zip(
            jax.tree_util.tree_flatten_with_path(p_vp)[0],
            jax.tree_util.tree_flatten_with_path(p_ref)[0]):
        d = float(jnp.max(jnp.abs(a - b)))
        assert d < 1e-5, (pa, d)
    """)


def test_native_index_init_and_refresh():
    """make_vocab_index_init / make_vocab_refresh_step build coherent
    per-shard subindexes natively (no all-gather): counts psum to Vpad,
    every shard's CSR covers exactly its rows, and a refresh preserves it."""
    _run(_SETUP + """
    from repro.launch import steps as steps_mod
    cfg, params, index, h, labels, skey = make("per_token")
    mesh = make_mesh((1, n), ("data", "vocab"))
    vpad = cfg.padded_vocab
    rows = vpad // n

    def check(sh):
        counts_g = np.asarray(sh.counts).sum(0)
        assert counts_g.sum() == vpad
        for i in range(n):
            assert int(np.asarray(sh.offsets[i])[-1]) == rows
            assert sorted(np.asarray(sh.sorted_ids[i]).tolist()) == \\
                list(range(rows))
            cnt = np.asarray(sh.counts[i])
            lc = np.asarray(sh.log_counts[i])
            np.testing.assert_allclose(lc[cnt > 0], np.log(cnt[cnt > 0]),
                                       atol=1e-5)
            assert np.all(np.isneginf(lc[cnt == 0]))

    init = jax.jit(steps_mod.make_vocab_index_init(cfg, mesh))
    sh = init(params, skey)
    check(sh)

    refresh = jax.jit(steps_mod.make_vocab_refresh_step(cfg, mesh,
                                                        policy="fixed"))
    sh2, metrics = refresh(params, sh, jax.random.fold_in(skey, 1))
    check(sh2)
    assert np.isfinite(float(metrics["reassigned_frac"]))
    assert np.isfinite(float(metrics["codeword_drift"]))

    # the refreshed index still feeds the loss: finite and close to the
    # replicated loss over a replicated build of the same table
    idx_specs = shd.vocab_index_specs(sh2)

    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(shd.head_table_spec(padded_vocab=vpad, vp=n),
                                 idx_specs, P()),
                       out_specs=P(), check_vma=False)
    def loss(t, si, z):
        return vp.loss_midx_vp(cfg, t, vp.local_index(si), z, labels, skey,
                               axis="vocab", fused=False)

    table = class_embeddings(cfg, params).astype(jnp.float32)
    val = float(loss(table, sh2, h))
    assert np.isfinite(val) and 0.0 < val < 20.0
    """)


def test_vp_train_export_restores_into_engine():
    """Serving export from a vocab-parallel run (DESIGN §13): train_loop on a
    (data=2, vocab=2) mesh merges the sharded index back to the replicated
    layout (pure re-layout — bit-identical assignments, rebuilt global CSR)
    and the serving stack restores it directly via Engine.from_checkpoint."""
    _run("""
    import tempfile, os
    import jax, numpy as np
    from repro.configs.base import HeadConfig, ModelConfig
    from repro.dist.vocab_parallel import unshard_index
    from repro.launch.mesh import make_vocab_mesh
    from repro.launch.train import train_loop
    from repro.serve import Engine, Request

    cfg = ModelConfig(
        name="vp-export", family="dense", num_layers=1, d_model=32,
        num_heads=2, num_kv_heads=2, d_ff=64, vocab_size=200, head_dim=16,
        vocab_pad_multiple=8, remat=False, dtype="float32",
        head=HeadConfig(mode="midx", midx_k=8, num_negatives=12,
                        proposal="per_token", kmeans_iters=2))
    with tempfile.TemporaryDirectory() as tmp:
        params, _, sharded, _ = train_loop(
            cfg, steps=2, batch_size=4, seq_len=8, ckpt_dir=tmp,
            ckpt_every=100, lr=1e-3, log_every=1, seed=0,
            mesh=make_vocab_mesh(2, 2))
        scfg = cfg.with_serve(max_slots=1, page_size=4, max_seq=32)
        eng = Engine.from_checkpoint(scfg, os.path.join(tmp, "serve"),
                                     head="midx")
        # the restored index is the merged (replicated-layout) one
        merged = unshard_index(sharded)
        np.testing.assert_array_equal(np.asarray(eng.index.assign1),
                                      np.asarray(merged.assign1))
        np.testing.assert_array_equal(np.asarray(eng.index.counts),
                                      np.asarray(merged.counts))
        req = Request(rid=0, tokens=np.arange(7, dtype=np.int32),
                      max_new=4, seed=1)
        res = eng.run([req])[0]
        assert res.status == "ok" and len(res.tokens) == 4, res
        assert all(0 <= t < cfg.vocab_size for t in res.tokens)
    """)


def test_refresh_pad_and_mask_non_dividing_matches_replicated():
    """Regression: a padded vocab that does not divide the data degree used
    to silently fall back to a replicated refresh. The pad-and-mask sharded
    step must now produce the same index as the replicated step."""
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs.base import HeadConfig, ModelConfig
    from repro.launch import steps as steps_mod
    from repro.models import heads, init_params

    cfg = ModelConfig(
        name="vp-test", family="dense", num_layers=1, d_model=32,
        num_heads=2, num_kv_heads=2, d_ff=64, vocab_size=100, head_dim=16,
        vocab_pad_multiple=4, remat=False, dtype="float32",
        head=HeadConfig(mode="midx", midx_k=8, num_negatives=12,
                        proposal="per_token", kmeans_iters=2))
    assert cfg.padded_vocab % 8 != 0        # the non-dividing case
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)
    index = heads.init_head_state(cfg, params, jax.random.fold_in(key, 1))
    rkey = jax.random.fold_in(key, 2)
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((8,), ("data",))

    for policy in ("drift", "fixed"):
        i_ref, m_ref = jax.jit(steps_mod.make_refresh_step(
            cfg, policy=policy))(params, index, rkey)
        i_sh, m_sh = jax.jit(steps_mod.make_refresh_step(
            cfg, mesh, data_axes=("data",), policy=policy))(
                params, index, rkey)
        np.testing.assert_array_equal(np.asarray(i_sh.assign1),
                                      np.asarray(i_ref.assign1), policy)
        np.testing.assert_array_equal(np.asarray(i_sh.assign2),
                                      np.asarray(i_ref.assign2), policy)
        np.testing.assert_array_equal(np.asarray(i_sh.counts),
                                      np.asarray(i_ref.counts), policy)
        np.testing.assert_allclose(np.asarray(i_sh.codebook1),
                                   np.asarray(i_ref.codebook1),
                                   atol=1e-5, err_msg=policy)
        np.testing.assert_allclose(
            float(m_sh["codeword_drift"]), float(m_ref["codeword_drift"]),
            atol=1e-5, err_msg=policy)
    """)
