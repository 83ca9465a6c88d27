"""The training driver on the CPU at a reduced size: a sound run is judged
correct, and each fault the cell can have, planted under the timed path,
is judged not correct, as is the float8 control."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import bench, program
from chipbench.drivers import train_lm
from chipbench.tests import tiny


def test_sound_run_is_correct_and_reports_its_metrics():
    out, rec = tiny.run(tiny.train_cell())
    assert out["correct"], out["compared"]
    assert out["attempted"] == len(rec["steps"]) > 0 and out["failed"] == 0
    m = out["metrics"]
    assert set(m) == {"setup_s", "train_tokens_per_s"}
    assert m["train_tokens_per_s"]["value"] > 0
    assert list(out)[-1] == "compared"


def test_state_left_unchanged_is_not_correct():
    def frozen(step):
        def run(params, opt_state, index, batch, key):
            _, _, met = step(params, opt_state, index, batch, key)
            return params, opt_state, met
        return run

    out, _ = tiny.run(tiny.train_cell(), seconds=0.3, fault=frozen)
    assert not out["correct"]
    assert out["compared"]["grad_leaf_rel"]["value"] >= 0.99


def test_half_the_batch_is_not_correct():
    def half(step):
        def run(params, opt_state, index, batch, key):
            n = batch["tokens"].shape[0] // 2
            return step(params, opt_state, index,
                        {k: v[:n] for k, v in batch.items()}, key)
        return run

    out, _ = tiny.run(tiny.train_cell(), seconds=0.3, fault=half)
    assert not out["correct"], out["compared"]


def test_float8_control_is_not_correct():
    """The reference in float8 put in the program's place, compared with the
    float32 reference as the program is."""
    cell = tiny.train_cell()
    spec, tr, ref = cell.spec, cell.traffic, cell.reference
    cfg = program.program_config(spec)
    k_w, k_idx, k_data, k_loop, _, _ = jax.random.split(
        program.seed_key(tiny.SEED), 6)
    p0 = ref.init_weights(spec, k_w, cfg.padded_vocab)
    idx = program.midx_index(p0["embed"], k_idx, k=cfg.head.midx_k,
                             iters=cfg.head.kmeans_iters)
    toks, labs = program.lm_batches(k_data, n=3, batch=tr["batch"],
                                    seq=tr["seq"], vocab=cfg.vocab_size,
                                    zipf_s=tr["zipf_s"])
    batches = [(toks[i], labs[i]) for i in range(3)]
    keys = [jax.random.fold_in(k_loop, i) for i in range(3)]
    m = cfg.head.num_negatives
    f32 = ref.train_readings(spec, p0, idx, batches, keys, tr["optimizer"], m)
    f8 = ref.train_readings(spec, p0, idx, batches, keys, tr["optimizer"], m,
                            low=True)
    nums, _ = train_lm.readings(f8, f32)
    ok, _ = bench.compare(nums, cell.limits["limits"])
    assert not ok, nums


def test_reference_ssd_matches_the_sequential_recurrence():
    """The reference's chunked SSD against h_t = exp(A dt_t) h_{t-1} +
    dt_t B_t x_t^T, y_t = C_t h_t, step by step."""
    ref = tiny.train_cell().reference
    k = jax.random.split(jax.random.PRNGKey(0), 5)
    b, l, h, p, n = 2, 128, 3, 4, 5
    x = jax.random.normal(k[0], (b, l, h, p))
    dt = jax.nn.softplus(jax.random.normal(k[1], (b, l, h)))
    a = -jnp.exp(jax.random.normal(k[2], (h,)))
    bm = jax.random.normal(k[3], (b, l, n))
    cm = jax.random.normal(k[4], (b, l, n))

    def step(hs, inp):
        xt, dtt, bt, ct = inp
        hs = (jnp.exp(a * dtt)[:, :, None, None] * hs
              + jnp.einsum("bh,bn,bhp->bhnp", dtt, bt, xt))
        return hs, jnp.einsum("bn,bhnp->bhp", ct, hs)

    _, ys = jax.lax.scan(step, jnp.zeros((b, h, n, p)),
                         (x.swapaxes(0, 1), dt.swapaxes(0, 1),
                          bm.swapaxes(0, 1), cm.swapaxes(0, 1)))
    with jax.default_matmul_precision("highest"):
        got = ref.ssd(x, dt, a, bm, cm, chunk=32)
    np.testing.assert_allclose(got, ys.swapaxes(0, 1), rtol=1e-4, atol=1e-4)
