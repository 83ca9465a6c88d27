"""Per cent of the prefill rows that carry a prompt token: the `tokens`
over the `padded` counts of the engine's `engine.prefill_group` and
`engine.prefill_chunk` spans that start in the traced window. A group runs
one row per request (`padded` = requests x prompt length); a chunk runs
`max_slots` rows of `chunk` tokens (`padded` = max_slots x chunk)."""
from chipbench import program_trace


def read(rec):
    pt = program_trace.of(rec)
    if pt is None:
        return None
    spans = [s for s in pt.named("engine.prefill_group",
                                 "engine.prefill_chunk")
             if s.raw[0] >= pt.window[0]]
    padded = sum(s.counts["padded"] for s in spans)
    if not padded:
        return None
    return 100.0 * sum(s.counts["tokens"] for s in spans) / padded
