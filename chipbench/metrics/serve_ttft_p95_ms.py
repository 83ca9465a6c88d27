"""95th percentile, over every request sent in the window, of the time from
its sending to its first token, read from outside the engine. A request
that never produced a token counts with the time until the run stopped
waiting for it."""
from chipbench import stats


def read(rec):
    reqs = rec.get("requests")
    if reqs is None:
        return None
    ttft = [(r.times[0] if r.times else rec["drain_end"]) - r.t_send
            for r in reqs]
    p = stats.percentile(ttft, 95)
    return None if p is None else 1e3 * p
