"""The trace reduction on a small trace recorded on a TPU v5e
(tools/record_trace.py): three calls of one jitted matrix product in a
window span, with a 20 ms host sleep between the second and the third.

The device events, in ns on the trace's clock (copy-start, copy-done,
fusion for each call):
  call 0: 42528497-42528510, 42528512-42528515, 42528516-42620005
  call 1: 43471033-43471046, 43471048-43471051, 43471053-43562498
  call 2: 65113448-65113461, 65113463-65113466, 65113467-65204948
Host spans: window 43586859-67039269, call0 43593089-44541909,
call1 44553539-45389189, sleep 45397989-66073869, call2 66085919-67036539.
"""
from __future__ import annotations

import pytest

from chipbench import bench, trace

DATA = bench.BENCH_DIR / "testdata" / "window.xplane.pb"


@pytest.fixture(scope="module")
def reduced():
    from jax.profiler import ProfileData
    return trace.reduce(ProfileData.from_file(str(DATA)))


def test_window_and_busy(reduced):
    assert reduced["window_s"] == pytest.approx(23_452_410e-9, abs=1e-12)
    # per call 13 + 3 + the fusion: 91,505 + 91,461 + 91,497 ns
    assert reduced["busy_s"] == pytest.approx(274_463e-9, abs=1e-12)


def test_ops_self_time(reduced):
    fusion = [o for t, o in reduced["ops"].items()
              if t.startswith("%convolution_tanh_fusion")]
    assert len(fusion) == 1
    assert fusion[0]["count"] == 3
    assert fusion[0]["seconds"] == pytest.approx(274_415e-9, abs=1e-12)
    assert reduced["device_ops"][0] == ["%convolution_tanh_fusion fusion",
                                        pytest.approx(274_415e-9, abs=1e-12)]
    assert trace.kernel_seconds(reduced, ["convolution_tanh"]) == \
        pytest.approx(274_415e-9, abs=1e-12)
    assert trace.kernel_seconds(reduced, ["no such op"]) is None
    assert trace.kernel_seconds(None, ["x"]) is None


def test_idle_gaps_named_by_host_span(reduced):
    gaps = reduced["idle_gaps"]
    # call 1 to call 2 (65113448 - 43562498) overlaps the sleep most; after
    # call 2 to the window's end (67039269 - 65204948) overlaps call2 most;
    # call 0 to call 1 (43471033 - 42620005) precedes every host span
    assert gaps[0] == ["sleep", pytest.approx(21_550_950e-9, abs=1e-12)]
    assert gaps[1] == ["call2", pytest.approx(1_834_321e-9, abs=1e-12)]
    assert gaps[2] == ["no host span", pytest.approx(851_028e-9, abs=1e-12)]


def test_self_times_nest():
    # a loop 0-100 holding ops 10-30 and 40-50: its self time is 70
    out = trace.self_times([(0, 100, "loop"), (10, 30, "a"), (40, 50, "b")])
    assert out == {"loop": [70, 1], "a": [20, 1], "b": [10, 1]}


def test_merge_and_short_name():
    assert trace.merge([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]
    assert trace.short_name("%fusion.3 = f32[2]{0} fusion(f32[2]{0} %p), "
                            "kind=kLoop") == "%fusion.3 fusion"
    assert trace.short_name("%while.2 = (s32[]{:T(128)}, f32[4]{0}) "
                            "while((s32[]) %t)") == "%while.2 while"
