"""Per cent of the traced window in which device 0 ran nothing while the
host was inside one of the program's spans and not waiting on a device
result (`engine.device_wait`): the idle time the serving engine's host work
costs.

Also logs where all of the device's idle time went: seconds by the
innermost program span the host was in, `engine.device_wait`, or outside
program spans; the ten longest idle gaps, each named by the label that
covers most of it; and device seconds by program."""
from chipbench import bench, program_trace

WAIT = "engine.device_wait"


def read(rec):
    pt = program_trace.of(rec)
    if pt is None:
        return None
    gaps = program_trace.idle_by_label(pt)
    total: dict[str, int] = {}
    for _, by in gaps:
        for name, ns in by.items():
            total[name] = total.get(name, 0) + ns
    by_span = {name: ns / 1e9 for name, ns in
               sorted(total.items(), key=lambda kv: -kv[1])}
    longest = [((b - a) / 1e6, max(by, key=by.get)) for (a, b), by in
               sorted(gaps, key=lambda g: g[0][0] - g[0][1])[:10]]
    programs = dict(sorted(pt.programs.items(), key=lambda kv: -kv[1])[:10])
    bench.log(f"device idle by program span (s): {by_span}; longest gaps "
              f"(ms, span): {longest}; device seconds by program: "
              f"{programs}")
    host = sum(ns for name, ns in total.items()
               if name not in (program_trace.OUTSIDE, WAIT))
    return 100.0 * host / 1e9 / pt.window_s
