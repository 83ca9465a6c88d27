"""95th percentile of every gap between consecutive tokens of a request,
both inside the window. A gap that spans a prefill wave counts as it is."""
from chipbench import stats


def read(rec):
    if "all_requests" not in rec:
        return None
    gaps = []
    for r in rec["all_requests"]:
        ts = [t for t in r.times if stats.in_window(rec, t)]
        gaps.extend(b - a for a, b in zip(ts, ts[1:]))
    p = stats.percentile(gaps, 95)
    return None if p is None else 1e3 * p
