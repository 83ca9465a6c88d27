"""Named host spans in the JAX profiler's trace.

`span(name, **counts)` marks a stretch of host work as `repro/<name>` in the
trace that `jax.profiler` records, on the same clock as the device's
operations; the counts (ints or short strings) ride on the event as its
stats. With no profiler running a span costs about a microsecond, so the
spans stay in the code unconditionally. To see them, record a run:

    with jax.profiler.trace("/tmp/repro-trace"):
        engine.run(requests)

and open the `.xplane.pb` under that directory in xprof (TensorBoard's
profile plugin) or Perfetto, or read it with `jax.profiler.ProfileData`.
A count known only at the end of the span is set on it with
`set_metadata(**counts)`.
"""
from __future__ import annotations

import jax

PREFIX = "repro/"


def span(name: str, **counts) -> jax.profiler.TraceAnnotation:
    return jax.profiler.TraceAnnotation(PREFIX + name, **counts)
