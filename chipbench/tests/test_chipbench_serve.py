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
    assert set(m) == {"setup_s", "serve_tokens_per_s", "serve_ttft_p90_ms"}
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


def test_ttft_p90_reads_every_request_sent_in_the_window():
    """Known send and first-token times, and one request that never
    produced a token, counted until the run stopped waiting for it."""
    import types

    import numpy as np

    from chipbench import bench
    reader = bench.load_module(bench.BENCH_DIR / "metrics" /
                               "serve_ttft_p90_ms.py")
    sends = np.arange(19) * 0.5
    ttft = (np.arange(19) + 1) * 0.010
    reqs = [types.SimpleNamespace(t_send=s, times=[s + t, s + t + 0.1])
            for s, t in zip(sends, ttft)]
    reqs.append(types.SimpleNamespace(t_send=20.0, times=[]))
    rec = {"requests": reqs, "drain_end": 20.185}
    want = np.percentile(np.append(ttft, 0.185), 90)
    assert abs(reader.read(rec) - 1e3 * want) < 1e-9
    # the request with no token lies inside the interpolated tail
    rec["drain_end"] = 20.0
    assert reader.read(rec) < 1e3 * want
    assert reader.read({}) is None


def test_spread_tool_reads_the_tail_as_the_reader_does(tmp_path):
    """`tools/serve_spread.py` takes the TTFT of a run's records as the p90
    reader does, lists the requests at or above the p90, and gives a set's
    spread as the interquartile distance over the median."""
    import json

    from chipbench import bench
    tool = bench.load_module(bench.BENCH_DIR / "tools" / "serve_spread.py")
    reader = bench.load_module(bench.BENCH_DIR / "metrics" /
                               "serve_ttft_p90_ms.py")
    out, rec = tiny.run(tiny.serve_cell(), seconds=1.0)
    t = tool.tail_record(rec, out)
    assert t["attempted"] == out["attempted"] and t["correct"]
    assert abs(t["ttft_p90_ms"] - reader.read(rec)) < 1e-6
    assert (t["ttft_p50_ms"] <= t["ttft_p90_ms"] <= t["ttft_p95_ms"]
            <= t["ttft_max_ms"])
    assert t["tail"] and all(x["ttft_ms"] >= t["ttft_p90_ms"]
                             for x in t["tail"])
    assert all(x["plen"] in x["tick_plens"] for x in t["tail"]
               if x["tick"] is not None)
    for i, v in enumerate([1.0, 2.0, 3.0, 4.0, 5.0]):
        (tmp_path / f"a-{i}.json").write_text(json.dumps(
            {**t, "serve_tokens_per_s": v}))
    s = tool.summarize(list(tmp_path.glob("*.json")))
    assert s["a"]["serve_tokens_per_s"]["median"] == 3.0
    # quartiles 1.5 and 4.5 by statistics.quantiles(n=4)
    assert s["a"]["serve_tokens_per_s"]["spread"] == 1.0
    assert s["a"]["ttft_p90_ms"]["spread"] == 0.0
