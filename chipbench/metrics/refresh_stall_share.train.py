"""Per cent of the traced window the host spent in the index lifecycle's
refresh work: the union of its `index.refresh_dispatch` and
`index.refresh_complete` spans (the latter holds the validation gate),
clipped to the window."""
from chipbench import program_trace


def read(rec):
    pt = program_trace.of(rec)
    if pt is None:
        return None
    spans = pt.named("index.refresh_dispatch", "index.refresh_complete")
    return 100.0 * program_trace.union_s(
        (s.start, s.end) for s in spans) / pt.window_s
