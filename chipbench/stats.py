"""Arithmetic the metric readers share: percentiles, window shares, and the
pieces of a run's records they read."""
from __future__ import annotations

import importlib
from typing import Iterable, Optional

import numpy as np


def percentile(xs: Iterable[float], q: float) -> Optional[float]:
    """The q-th percentile (linear interpolation, numpy's default), or None
    for an empty sample."""
    arr = np.asarray(list(xs), np.float64)
    return float(np.percentile(arr, q)) if arr.size else None


def window_s(rec: dict) -> float:
    t0, t1 = rec["window"]
    return t1 - t0


def in_window(rec: dict, t: float) -> bool:
    t0, t1 = rec["window"]
    return t0 <= t <= t1


def idle_share(rec: dict) -> Optional[float]:
    """Per cent of the traced window in which no operation ran on the
    device."""
    tr = rec.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def roofline(rec: dict, kernel: str) -> Optional[float]:
    """Per cent of the kernel's summed device time in the trace that the
    least time for the window's calls (counts/<kernel>.py over the peaks)
    takes. None where the trace holds no such kernel."""
    from chipbench import peaks, trace
    counts = importlib.import_module(f"chipbench.counts.{kernel}")
    busy = trace.kernel_seconds(rec.get("trace"), counts.TRACE_PATTERNS)
    if not busy or rec.get("peaks") is None:
        return None
    flops, nbytes = counts.window_work(rec)
    least, _ = peaks.least_seconds(flops, nbytes, rec["peaks"])
    return 100.0 * least / busy


def model_counts(rec: dict):
    return importlib.import_module(
        f"chipbench.counts.{rec['spec']['architecture']}")


def train_tokens_per_s(rec: dict) -> Optional[float]:
    steps = rec.get("steps")
    if not steps:
        return None
    t_open = rec["window"][0]
    return sum(n for _, _, n in steps) / (steps[-1][1] - t_open)


def served_times(rec: dict) -> list[float]:
    """Arrival times of every token produced in the window."""
    return [t for r in rec.get("all_requests", ()) for t in r.times
            if in_window(rec, t)]
