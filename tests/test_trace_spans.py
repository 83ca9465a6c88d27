"""The program's spans in the profiler's trace (`repro.utils.trace`).

A tiny engine run and index refreshes recorded under `jax.profiler` on the
CPU: the serving engine writes `repro/engine.*` spans whose counts add up to
the work done (prompt tokens against padded rows, one decode-wave span per
decode step, host reads of device results inside a tick), and the index
lifecycle writes `repro/index.*` spans around its dispatch, completion and
validation gate (DESIGN §5, §8).
"""
from __future__ import annotations

import dataclasses
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.index import IndexLifecycle, build
from repro.serve import Engine, Request
from repro.utils import trace


@dataclasses.dataclass
class Span:
    name: str
    line: str
    start: int
    end: int
    counts: dict

    def holds(self, other: "Span") -> bool:
        return (self.line == other.line and self.start <= other.start
                and other.end <= self.end)


def record(out: pathlib.Path, fn):
    """Run fn() under the profiler; returns (its result, the repro/ spans
    of the host planes)."""
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(out))
    try:
        result = fn()
    finally:
        jax.profiler.stop_trace()
    pd = ProfileData.from_file(str(sorted(out.glob("**/*.xplane.pb"))[-1]))
    spans = [Span(ev.name[len(trace.PREFIX):], line.name, int(ev.start_ns),
                  int(ev.end_ns), dict(ev.stats))
             for plane in pd.planes if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events
             if ev.name.startswith(trace.PREFIX)]
    return result, spans


def named(spans, name):
    return [s for s in spans if s.name == name]


def _engine(mode: str) -> Engine:
    serve = dict(max_slots=4, page_size=4, max_seq=32)
    if mode == "chunked":
        serve["prefill_chunk"] = 4
    if mode == "spec":
        serve["spec_decode"] = 2
    cfg = get_config("paper-lm").reduced().with_serve(**serve)
    return Engine(cfg, init_key=jax.random.PRNGKey(0), head="midx")


@pytest.mark.parametrize("mode", ["batched", "chunked", "spec"])
def test_engine_spans_count_the_work(tmp_path, mode):
    eng = _engine(mode)
    rng = np.random.default_rng(0)
    plens = [6, 9, 6, 11, 9, 6]
    reqs = [Request(rid=i, tokens=rng.integers(0, eng.cfg.vocab_size, p)
                    .astype(np.int32), max_new=4, seed=1)
            for i, p in enumerate(plens)]
    res, spans = record(tmp_path, lambda: eng.run(reqs))
    assert sorted(res) == list(range(len(reqs)))
    ticks = named(spans, "engine.tick")
    assert ticks
    assert sum(s.counts["admitted"] for s in named(spans, "engine.admit")) \
        == len(reqs)
    assert sum(s.counts["retired"] for s in named(spans, "engine.retire")) \
        == len(reqs)
    groups = named(spans, "engine.prefill_group")
    chunks = named(spans, "engine.prefill_chunk")
    for s in groups + chunks:
        assert 0 < s.counts["tokens"] <= s.counts["padded"]
    if mode == "chunked":
        assert not groups
        assert sum(s.counts["tokens"] for s in chunks) == sum(plens)
        assert all(s.counts["padded"] == 4 * 4 for s in chunks)
        assert len(chunks) == eng.stats.prefill_chunks
    else:
        assert not chunks
        # each group's real rows, over the wave: every request admitted once
        assert sum(s.counts["tokens"] // s.counts["plen"] for s in groups) \
            == len(reqs)
        # a group runs one prefill row per request: nothing is padding
        for s in groups:
            assert s.counts["rows"] == s.counts["tokens"] // s.counts["plen"]
            assert s.counts["padded"] == s.counts["rows"] * s.counts["plen"]
    st, prefills = eng.stats, groups + chunks
    assert st.prefill_tokens == sum(s.counts["tokens"] for s in prefills)
    assert st.prefill_padded == sum(s.counts["padded"] for s in prefills)
    assert st.summary()["prefill_token_use"] == round(
        st.prefill_tokens / st.prefill_padded, 4)
    waves = named(spans, "engine.decode_wave")
    specs = named(spans, "engine.spec_wave")
    if mode == "spec":
        assert not waves and len(specs) == st.spec_waves > 0
        assert all(s.counts["k"] == 2 for s in specs)
    else:
        assert not specs and len(waves) == st.steps > 0
    for s in waves + specs:
        assert 0 < s.counts["active"] <= s.counts["slots"] == 4
    # the host blocks on the chip only inside a tick, inside its work
    waits = named(spans, "engine.device_wait")
    work = groups + chunks + waves + specs
    assert waits
    for w in waits:
        assert any(t.holds(w) for t in ticks)
        assert any(s.holds(w) for s in work)


def _rows_engine(max_slots: int) -> Engine:
    cfg = get_config("paper-lm").reduced().with_serve(
        max_slots=max_slots, page_size=4, max_seq=16)
    return Engine(cfg, init_key=jax.random.PRNGKey(0), head="midx")


def _same_length(eng, n, plen, rid0=0):
    rng = np.random.default_rng(rid0)
    return [Request(rid=rid0 + i, tokens=rng.integers(
                0, eng.cfg.vocab_size, plen).astype(np.int32),
                    max_new=2, seed=1) for i in range(n)]


@pytest.mark.parametrize("g", [1, 3, 5, 16])
def test_prefill_group_runs_one_row_per_request(tmp_path, g):
    """g requests of one prompt length, admitted together on 16 slots, form
    one prefill group of g one-row prefills: no row is padding."""
    eng = _rows_engine(16)
    reqs = _same_length(eng, g, 6)
    res, spans = record(tmp_path, lambda: eng.run(reqs))
    (group,) = named(spans, "engine.prefill_group")
    assert group.counts == {"plen": 6, "rows": g, "tokens": g * 6,
                            "padded": g * 6}
    assert eng.stats.prefill_padded == eng.stats.prefill_tokens == g * 6
    assert eng._prefill._cache_size() == 1     # one row, whatever g
    assert sorted(res) == list(range(g))
    assert all(r.status == "ok" for r in res.values())


def test_prefill_groups_of_one_wave_sum_to_the_stats(tmp_path):
    """Groups of 1, 3 and 5 requests of three prompt lengths in one
    admission wave run 1, 3 and 5 rows; `EngineStats.prefill_padded` is
    the sum of their spans' `padded`."""
    eng = _rows_engine(16)
    reqs = (_same_length(eng, 1, 4) + _same_length(eng, 3, 6, rid0=1)
            + _same_length(eng, 5, 8, rid0=4))
    res, spans = record(tmp_path, lambda: eng.run(reqs))
    assert sorted(res) == list(range(9))
    groups = sorted(named(spans, "engine.prefill_group"),
                    key=lambda s: s.counts["plen"])
    assert [(s.counts["plen"], s.counts["rows"], s.counts["padded"])
            for s in groups] == [(4, 1, 4), (6, 3, 18), (8, 5, 40)]
    assert eng.stats.prefill_padded == sum(s.counts["padded"]
                                           for s in groups) == 62
    assert eng.stats.prefill_tokens == 4 + 18 + 40


def _toy_refresh(params, index, key):
    del params, key
    return jax.tree_util.tree_map(jnp.asarray, index), {
        "did_full": jnp.float32(0.0)}


@pytest.fixture(scope="module")
def index():
    emb = jax.random.normal(jax.random.PRNGKey(0), (300, 16))
    return build(jax.random.PRNGKey(1), emb, kind="rq", k=4, iters=2,
                 keep_residuals=False)


@pytest.mark.parametrize("lag", [0, 2])
def test_lifecycle_spans_around_dispatch_completion_and_gate(tmp_path, index,
                                                             lag):
    lc = IndexLifecycle(_toy_refresh, every=3, lag=lag,
                        base_key=jax.random.PRNGKey(0))

    def go():
        cur = index
        for step in range(6):
            cur, _ = lc.step(step, None, cur)
        return lc.flush(6, cur)

    _, spans = record(tmp_path, go)
    dispatch = named(spans, "index.refresh_dispatch")
    complete = named(spans, "index.refresh_complete")
    validate = named(spans, "index.validate")
    assert [s.counts["step"] for s in dispatch] == [2, 5]
    assert [s.counts["step"] for s in complete] == [2, 5]
    assert [s.counts["rejected"] for s in complete] == [0, 0]
    assert len(validate) == 2
    assert all(c.holds(v) for c, v in zip(complete, validate))


def test_rejected_rebuild_is_marked_on_its_span(tmp_path, index):
    from repro.resilience.faults import poison_state

    def bad(params, state, key):
        new, metrics = _toy_refresh(params, state, key)
        return poison_state(new, "nan"), metrics

    lc = IndexLifecycle(bad, every=1, lag=0, base_key=jax.random.PRNGKey(0))
    (cur, ev), spans = record(tmp_path, lambda: lc.step(0, None, index))
    assert ev.rejected and cur is index
    (complete,) = named(spans, "index.refresh_complete")
    assert complete.counts == {"step": 0, "rejected": 1}

