"""The program's own spans in a traced run, beside the device's work.

The program writes `repro/<name>` spans into the profiler's trace
(`repro.utils.trace.span`), with counts as the events' stats. A traced run
of this process clears `out/trace`, starts the profiler when the window
opens and stops it when it closes, so the newest `.xplane.pb` there is its
window. From it, parsed once per file however many readers ask:

  window    the host span `chipbench:window`, in ns on the trace's clock
  spans     every host event named `repro/...` that overlaps the window,
            clipped to it, with its counts and the span that holds it on
            its thread
  busy      device 0's busy intervals: its `XLA Ops` merged
  programs  device seconds per program on device 0 (`XLA Modules`, the
            event name without its fingerprint), inside the window

A run that was not traced, or a trace without the program's spans (a
program that writes none), gives nothing to read: the readers return None.
"""
from __future__ import annotations

import dataclasses
import functools
import pathlib
import re
from typing import Optional

from chipbench import bench, trace

#: the program's span prefix (`repro.utils.trace.PREFIX`)
PREFIX = "repro/"
#: the label of host time inside no program span
OUTSIDE = "outside program spans"
_DEVICE0 = "/device:TPU:0"
_MODULES_LINE = "XLA Modules"
_FINGERPRINT = re.compile(r"\(\d+\)$")


@dataclasses.dataclass(frozen=True)
class Span:
    name: str                 # without the prefix
    start: int                # ns, clipped to the window
    end: int
    raw: tuple                # (start, end) as recorded
    counts: dict
    parent: Optional[int]     # index in `spans` of the span that holds it

    @property
    def inside(self) -> bool:
        """The span lies wholly inside the window."""
        return self.raw == (self.start, self.end)


@dataclasses.dataclass(frozen=True)
class ProgramTrace:
    window: tuple
    spans: list
    busy: list
    programs: dict

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def named(self, *names: str) -> list:
        return [s for s in self.spans if s.name in names]


def of(rec: dict) -> Optional[ProgramTrace]:
    """The traced window of this run, or None where the run was not traced
    or the program wrote no span into it."""
    if not rec.get("trace"):
        return None
    files = sorted(pathlib.Path(bench.TRACE_DIR).glob("**/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        return None
    pt = _parse(str(files[-1]), files[-1].stat().st_mtime_ns)
    return pt if pt.spans else None


@functools.lru_cache(maxsize=1)
def _parse(path: str, mtime_ns: int) -> ProgramTrace:
    del mtime_ns                       # part of the cache key only
    pd = trace.load(pathlib.Path(path).parent)
    windows, lines = [], []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = []
            for ev in line.events:
                if ev.name == trace.WINDOW_SPAN:
                    windows.append((int(ev.start_ns), int(ev.end_ns)))
                elif ev.name.startswith(PREFIX):
                    evs.append((int(ev.start_ns), int(ev.end_ns),
                                ev.name[len(PREFIX):], dict(ev.stats)))
            lines.append(evs)
    if len(windows) != 1:
        raise ValueError(f"expected one {trace.WINDOW_SPAN} span, found "
                         f"{len(windows)}")
    w0, w1 = windows[0]
    spans: list[Span] = []
    for evs in lines:
        stack: list[tuple[int, int]] = []          # (end, index in spans)
        for a, b, name, counts in sorted(evs, key=lambda e: (e[0], -e[1])):
            while stack and stack[-1][0] <= a:
                stack.pop()
            if b <= w0 or a >= w1:
                continue
            spans.append(Span(name, max(a, w0), min(b, w1), (a, b), counts,
                              stack[-1][1] if stack else None))
            stack.append((b, len(spans) - 1))
    busy, programs = [], {}
    for plane in pd.planes:
        if plane.name != _DEVICE0:
            continue
        for line in plane.lines:
            if line.name == trace.OPS_LINE:
                busy = trace.merge((int(ev.start_ns), int(ev.end_ns))
                                   for ev in line.events)
            elif line.name == _MODULES_LINE:
                for ev in line.events:
                    a, b = max(int(ev.start_ns), w0), min(int(ev.end_ns), w1)
                    if b > a:
                        name = _FINGERPRINT.sub("", ev.name)
                        programs[name] = (programs.get(name, 0.0)
                                          + (b - a) / 1e9)
    return ProgramTrace((w0, w1), spans, busy, programs)


def union_s(intervals) -> float:
    return sum(b - a for a, b in trace.merge(intervals)) / 1e9


def idle_intervals(pt: ProgramTrace) -> list[tuple[int, int]]:
    """The stretches of the window in which device 0 ran nothing."""
    w0, w1 = pt.window
    out, t = [], w0
    for a, b in pt.busy:
        if b <= w0 or a >= w1:
            continue
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if t < w1:
        out.append((t, w1))
    return out


def host_labels(pt: ProgramTrace) -> list[tuple[int, int, str]]:
    """The window cut into stretches, each named by the innermost program
    span the host was in (the one that started last), or OUTSIDE."""
    w0, w1 = pt.window
    cuts = sorted({w0, w1} | {s.start for s in pt.spans}
                  | {s.end for s in pt.spans})
    order = sorted(pt.spans, key=lambda s: (s.start, -s.end))
    out: list[list] = []
    active: list[Span] = []
    i = 0
    for x, y in zip(cuts, cuts[1:]):
        while i < len(order) and order[i].start <= x:
            active.append(order[i])
            i += 1
        active = [s for s in active if s.end > x]
        label = (max(active, key=lambda s: (s.start, -s.end)).name
                 if active else OUTSIDE)
        if out and out[-1][2] == label and out[-1][1] == x:
            out[-1][1] = y
        else:
            out.append([x, y, label])
    return [tuple(o) for o in out]


def idle_by_label(pt: ProgramTrace) -> list[tuple[tuple[int, int], dict]]:
    """Each idle stretch of device 0 with its ns by host label."""
    labels = host_labels(pt)
    out, j = [], 0
    for a, b in idle_intervals(pt):
        by: dict[str, int] = {}
        while j < len(labels) and labels[j][1] <= a:
            j += 1
        k = j
        while k < len(labels) and labels[k][0] < b:
            x, y, name = labels[k]
            ov = min(b, y) - max(a, x)
            if ov > 0:
                by[name] = by.get(name, 0) + ov
            k += 1
        out.append(((a, b), by))
    return out
