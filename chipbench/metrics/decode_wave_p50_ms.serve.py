"""Median duration of the engine's decode waves in the traced window: its
`engine.decode_wave` spans (pack the slots, dispatch the step, wait for the
tokens, unpack them) that lie wholly inside the window."""
from chipbench import program_trace, stats


def read(rec):
    pt = program_trace.of(rec)
    if pt is None:
        return None
    return stats.percentile([(s.end - s.start) / 1e6
                             for s in pt.named("engine.decode_wave")
                             if s.inside], 50)
