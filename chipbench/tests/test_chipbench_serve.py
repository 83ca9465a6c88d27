"""The serving driver on the CPU at a reduced size: a sound run is judged
correct and reports its metrics; a token altered where the engine produces
it, a wrong MIDX proposal table, and the float8 control are judged not
correct."""
from __future__ import annotations

from chipbench.tests import tiny


def test_sound_run_is_correct_and_reports_its_metrics():
    out, rec = tiny.run(tiny.serve_cell(), seconds=2.0)
    assert out["correct"], out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0
    m = out["metrics"]
    assert set(m) == {"setup_s", "serve_tokens_per_s", "serve_ttft_p95_ms"}
    assert all(v["value"] > 0 for v in m.values())
    # in float32 on both sides every served token is the reference's draw
    assert out["compared"]["draw_mismatch"]["value"] == 0.0
    assert rec["check_detail"]["outside_vocab"] == 0


def test_float8_control_is_not_correct():
    """At each position of the served requests, the token the reference in
    float8 draws under the same key, judged as a served token is."""
    cell = tiny.serve_cell()
    rec = cell.driver.run(tiny.ctx(cell, 1.0), control=True)
    limit = cell.limits["limits"]["draw_mismatch"]
    assert rec["readings"]["draw_mismatch"] <= limit
    assert rec["control_readings"]["draw_mismatch"] > limit


def test_altered_token_is_not_correct():
    def alter(engine):
        step = engine._step

        def run(*args):
            toks, state = step(*args)
            return (toks + 1) % engine.cfg.vocab_size, state
        engine._step = run

    out, _ = tiny.run(tiny.serve_cell(), seconds=1.0, fault=alter)
    assert not out["correct"]


def test_wrong_proposal_table_is_not_correct(monkeypatch):
    """The MIDX head's proposal tables left as zeros where they are
    computed, as a kernel that writes nothing leaves them: the draws come
    from another proposal."""
    import jax.numpy as jnp
    from repro.core import midx

    tables = midx.twostage_tables

    def zeros(index, z):
        return tuple(jnp.zeros_like(t) for t in tables(index, z))

    monkeypatch.setattr(midx, "twostage_tables", zeros)
    out, _ = tiny.run(tiny.serve_cell(), seconds=1.0)
    assert not out["correct"]


def test_per_layer_metrics_read_from_the_records():
    from chipbench import bench
    cell = tiny.serve_cell()
    _, rec = tiny.run(cell, seconds=1.0)
    b = bench.load_json(bench.REPO / "BENCHMARK.json")
    m = bench.read_metrics(b, cell, "per_layer", rec)
    # no trace on the CPU: the device metrics find nothing to read
    assert set(m) == {"slot_occupancy.serve", "prefill_share.serve",
                      "step_mfu.serve", "serve_itl_p95_ms"}
    assert m["serve_itl_p95_ms"]["value"] > 0
    assert 0 < m["slot_occupancy.serve"]["value"] <= 100
    assert 0 <= m["prefill_share.serve"]["value"] < 100
