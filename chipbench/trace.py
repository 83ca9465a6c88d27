"""Reduction of a profiler trace of the measured window to numbers.

The driver starts the profiler when the window opens and stops it when the
window closes, with a host span `chipbench:window` around it, so the trace
holds the window and nothing else. From it:

  busy_s      the union of the intervals in which an operation ran on a
              device, averaged over the chips used
  window_s    the length of the host's window span
  ops         per device operation: its self time in seconds (its duration
              less that of the operations nested in it, as a loop's body is
              in the loop; averaged over the chips), its number of events,
              and its HLO text
  idle_gaps   the longest stretches between operations on device 0, each
              named by the host span that overlaps it most

On a TPU v5e the device's timestamps in the trace run about a millisecond
behind the host's (tools/record_trace.py's recording: each call's device
work starts 0.97-1.07 ms before its host span), so the device's work is
not clipped to the host's window, and a gap's name is only as good as that.

`jax.profiler.ProfileData` reads the `.xplane.pb` file with nothing but JAX.
"""
from __future__ import annotations

import pathlib
import re
from typing import Iterable, Optional

#: host spans the drivers record carry this prefix in the profiler's trace
SPAN_PREFIX = "chipbench:"
WINDOW_SPAN = SPAN_PREFIX + "window"
#: the device line that holds one event per executed operation
OPS_LINE = "XLA Ops"
TOP = 10
_OPCODE = re.compile(r"[\]})] ([a-z][a-z0-9-]*)\(")


def load(trace_dir: pathlib.Path):
    from jax.profiler import ProfileData
    files = sorted(pathlib.Path(trace_dir).glob("**/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return ProfileData.from_file(str(files[-1]))


def short_name(text: str) -> str:
    """`%fusion.12 fusion` for an op whose event name is its HLO text."""
    name = text.split(" = ", 1)[0]
    m = _OPCODE.search(text)
    return f"{name} {m.group(1)}" if m else name


def _device_planes(pd, chips: int) -> list:
    pat = re.compile(r"^/device:TPU:(\d+)$")
    return [p for p in pd.planes
            if (m := pat.match(p.name)) and int(m.group(1)) < chips]


def _host_spans(pd) -> list[tuple[str, int, int]]:
    return [(ev.name, int(ev.start_ns), int(ev.end_ns))
            for p in pd.planes if p.name.startswith("/host:")
            for line in p.lines for ev in line.events
            if ev.name.startswith(SPAN_PREFIX)]


def merge(intervals: Iterable[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def self_times(events: list[tuple[int, int, str]]) -> dict[str, list]:
    """{text: [self ns, count]} for one line's (start, end, text) events,
    where an event inside another is the other's child."""
    out: dict[str, list] = {}
    stack: list[list] = []               # [end, text, duration, child ns]

    def close(item):
        end, text, dur, child = item
        o = out.setdefault(text, [0, 0])
        o[0] += dur - child
        o[1] += 1

    for a, b, text in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][0] <= a:
            close(stack.pop())
        if stack:
            stack[-1][3] += b - a
        stack.append([b, text, b - a, 0])
    while stack:
        close(stack.pop())
    return out


def reduce(pd, chips: int = 1) -> dict:
    spans = _host_spans(pd)
    windows = [(a, b) for n, a, b in spans if n == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found "
                         f"{len(windows)}")
    w0, w1 = windows[0]
    inner = [(n[len(SPAN_PREFIX):], a, b) for n, a, b in spans
             if n != WINDOW_SPAN]
    planes = _device_planes(pd, chips)
    if not planes:
        raise ValueError("the trace holds no TPU device plane")
    ops: dict[str, dict] = {}
    busy_total = 0
    first = None
    for plane in planes:
        evs = [(int(ev.start_ns), int(ev.end_ns), ev.name)
               for line in plane.lines if line.name == OPS_LINE
               for ev in line.events]
        for text, (ns, count) in self_times(evs).items():
            o = ops.setdefault(text, {"seconds": 0.0, "count": 0})
            o["seconds"] += ns / 1e9 / len(planes)
            o["count"] += count
        merged = merge((a, b) for a, b, _ in evs)
        busy_total += sum(b - a for a, b in merged)
        if first is None:
            first = merged
    gaps = [(a[1], b[0]) for a, b in zip(first, first[1:])]
    if first:
        gaps += [(w0, first[0][0]), (first[-1][1], w1)]
    gaps = sorted((g for g in gaps if g[1] > g[0]),
                  key=lambda g: g[0] - g[1])[:TOP]
    named = []
    for a, b in gaps:
        best, who = 0, "no host span"
        for n, s0, s1 in inner:
            ov = max(0, min(b, s1) - max(a, s0))
            if ov > best:
                best, who = ov, n
        named.append([who, (b - a) / 1e9])
    window_s = (w1 - w0) / 1e9
    top = sorted(ops.items(), key=lambda kv: -kv[1]["seconds"])[:TOP]
    return {
        "window_s": window_s,
        "busy_s": min(busy_total / 1e9 / len(planes), window_s),
        "ops": ops,
        "device_ops": [[short_name(t), o["seconds"]] for t, o in top],
        "idle_gaps": named,
    }


def kernel_seconds(reduced: Optional[dict], patterns: Iterable[str]
                   ) -> Optional[float]:
    """Device seconds of the operations whose HLO text matches one of
    `patterns`; None where the trace holds none of them."""
    if reduced is None:
        return None
    rx = [re.compile(p) for p in patterns]
    hits = [o["seconds"] for text, o in reduced["ops"].items()
            if any(r.search(text) for r in rx)]
    return sum(hits) if hits else None
