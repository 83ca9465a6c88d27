"""The program-span readers on a small trace recorded on a TPU v5e
(tools/record_program_trace.py): inside the window span a tiny serving
engine serves two requests (prompts of 6 and 11 tokens, 3 tokens each, 4
slots) to the end, the host sleeps 20 ms outside any program span, then one
index refresh runs through the lifecycle (its key's fold_in compiles there,
which the 150 ms gap before the refresh holds). The file keeps the spans
and device 0's operations and programs (`trim`).

The program's spans, in ns from the window's start (41965607; it lasts
188376013), start and end, with their counts:
  engine.tick            29780    8195449
  engine.admit           31410      93250   admitted 2
  engine.prefill_group 1818880    5123009   plen 6, tokens 6, padded 24
  engine.device_wait   4174029    5112529
  engine.prefill_group 5130569    8138769   plen 11, tokens 11, padded 44
  engine.device_wait   7217319    8121869
  engine.retire        8192679    8194549   retired 0
  engine.tick          8200679   10383819   (admit 0, a decode wave
  engine.decode_wave   8224089   10372149    of 2148060 ns holding a
  engine.device_wait   9370189   10362199    device_wait, retire 0)
  engine.tick         10388249   18645378   (admit 0, a decode wave
  engine.decode_wave  10402019   12799199    of 2397180 ns holding a
  engine.device_wait  11825919   12782808    device_wait, retire 2 of
  engine.retire       12817168   18643798    5826630 ns)
  index.refresh_dispatch   168671185  169783185   step 0
  index.refresh_complete   169790705  188284953   step 0, rejected 0
  index.validate           184382104  188276893
So prefill rows carry 17 of 68 tokens (25%); the decode waves' median is
(2148060 + 2397180) / 2 ns; the refresh spans cover 1112000 + 18494248 ns
of the window (10.408%). Device 0 runs 1234 operations in 746 busy
stretches (227964 ns); a sweep over the raw events finds it idle 34209506
ns while the host is inside a program span and outside
`engine.device_wait` (18.160% of the window).
"""
from __future__ import annotations

import shutil

import pytest

from chipbench import bench, program_trace

DATA = bench.BENCH_DIR / "testdata" / "program.xplane.pb"
WINDOW = bench.BENCH_DIR / "testdata" / "window.xplane.pb"
READERS = ("prefill_token_use.serve", "decode_wave_p50_ms.serve",
           "host_idle_share.serve", "refresh_stall_share.train")
TRACED = {"trace": {"window_s": 1.0, "busy_s": 0.0}}
WINDOW_NS = (41965607, 41965607 + 188376013)
SPAN_NAMES = ["engine.tick", "engine.admit", "engine.prefill_group",
              "engine.device_wait", "engine.prefill_group",
              "engine.device_wait", "engine.retire"] + 2 * [
              "engine.tick", "engine.admit", "engine.decode_wave",
              "engine.device_wait", "engine.retire"] + [
              "index.refresh_dispatch", "index.refresh_complete",
              "index.validate"]
WAIT_PARENTS = 2 * ["engine.prefill_group"] + 2 * ["engine.decode_wave"]
BUSY_INTERVALS, BUSY_S = 746, 227964e-9
PROGRAMS = {"jit_prefill_fn", "jit_first_group_fn", "jit_step_fn",
            "jit_bind_keys_fn", "jit_refresh_replicated"}
DECODE_P50_MS = (2148060 + 2397180) / 2 / 1e6
HOST_IDLE_PCT = 100 * 34209506 / 188376013
REFRESH_PCT = 100 * (1112000 + 18494248) / 188376013


def reader(name):
    return bench.load_module(bench.BENCH_DIR / "metrics" / f"{name}.py")


@pytest.fixture
def traced(tmp_path, monkeypatch):
    """Put a recorded trace where a traced run leaves its own."""
    def put(src=DATA):
        shutil.copy(src, tmp_path / src.name)
        monkeypatch.setattr(bench, "TRACE_DIR", tmp_path)
        return dict(TRACED)
    return put


def test_spans_nesting_busy_and_programs(traced):
    pt = program_trace.of(traced())
    assert pt.window == WINDOW_NS
    names = [s.name for s in pt.spans]
    assert names == SPAN_NAMES
    for s in pt.spans:
        assert s.inside
        if s.parent is not None:
            p = pt.spans[s.parent]
            assert p.start <= s.start and s.end <= p.end
    assert [pt.spans[s.parent].name for s in pt.named("engine.device_wait")] \
        == WAIT_PARENTS
    assert [s.counts for s in pt.named("engine.prefill_group")] == [
        {"plen": 6, "tokens": 6, "padded": 24},
        {"plen": 11, "tokens": 11, "padded": 44}]
    assert pt.named("index.refresh_complete")[0].counts == {
        "step": 0, "rejected": 0}
    assert len(pt.busy) == BUSY_INTERVALS
    assert program_trace.union_s(pt.busy) == pytest.approx(BUSY_S, abs=1e-12)
    assert PROGRAMS <= set(pt.programs)


def test_readers_against_hand_computed_values(traced, capsys):
    rec = traced()
    assert reader("prefill_token_use.serve").read(rec) == \
        pytest.approx(100 * 17 / 68)
    assert reader("decode_wave_p50_ms.serve").read(rec) == \
        pytest.approx(DECODE_P50_MS, abs=1e-9)
    assert reader("host_idle_share.serve").read(rec) == \
        pytest.approx(HOST_IDLE_PCT, abs=1e-9)
    assert reader("refresh_stall_share.train").read(rec) == \
        pytest.approx(REFRESH_PCT, abs=1e-9)
    # the two longest idle gaps: the fold_in's compile, outside every
    # program span, and the refresh's wait for its rebuild
    log = capsys.readouterr().err
    assert "longest gaps (ms, span): [(150.151727, 'outside program spans')" \
        ", (19.50914, 'index.refresh_complete')" in log


def test_idle_labels_partition_the_idle_time(traced):
    pt = program_trace.of(traced())
    labels = program_trace.host_labels(pt)
    assert labels[0][0] == pt.window[0] and labels[-1][1] == pt.window[1]
    assert all(a[1] == b[0] for a, b in zip(labels, labels[1:]))
    gaps = program_trace.idle_by_label(pt)
    idle = sum(b - a for (a, b), _ in gaps)
    assert sum(sum(by.values()) for _, by in gaps) == idle
    busy = sum(min(b, pt.window[1]) - max(a, pt.window[0])
               for a, b in pt.busy if b > pt.window[0] and a < pt.window[1])
    assert idle + busy == pt.window[1] - pt.window[0]


@pytest.mark.parametrize("name", READERS)
def test_reader_finds_nothing_in_an_untraced_run(name):
    assert reader(name).read({}) is None


@pytest.mark.parametrize("name", READERS)
def test_reader_finds_nothing_in_a_trace_without_program_spans(traced,
                                                               name):
    """A program that writes no span reads as nothing, not as zero."""
    assert reader(name).read(traced(WINDOW)) is None

