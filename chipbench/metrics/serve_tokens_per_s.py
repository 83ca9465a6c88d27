"""Served tokens per second: every output token that appeared in the
window, over the window."""
from chipbench import stats


def read(rec):
    if "all_requests" not in rec:
        return None
    return len(stats.served_times(rec)) / stats.window_s(rec)
