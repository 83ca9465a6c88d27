"""Per cent of the window spent in engine ticks that ran a prefill wave
(decoding waits for them)."""
from chipbench import stats


def read(rec):
    if "ticks" not in rec:
        return None
    t0, t1 = rec["window"]
    busy = sum(min(b, t1) - max(a, t0) for kind, a, b, *_ in rec["ticks"]
               if kind == "prefill" and b > t0 and a < t1)
    return 100.0 * busy / stats.window_s(rec)
