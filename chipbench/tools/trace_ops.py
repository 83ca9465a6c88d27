"""Print what a profiler trace of the window holds: its planes and lines,
the device operations that took most time with their long names, and
whether the host's window span and the device's events share a clock.
Use it to find the names a kernel shows under (counts/<kernel>.py keeps
them).

    python3 chipbench/tools/trace_ops.py chipbench/out/trace [top]
"""
import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from chipbench import trace  # noqa: E402


def main() -> None:
    pd = trace.load(pathlib.Path(sys.argv[1]))
    top = int(sys.argv[2]) if len(sys.argv) > 2 else 40
    for p in pd.planes:
        lines = [(ln.name, sum(1 for _ in ln.events)) for ln in p.lines]
        print("plane", p.name, lines)
    for p in pd.planes:
        if not p.name.startswith("/device:TPU:0"):
            continue
        for ln in p.lines:
            evs = list(ln.events)
            if evs:
                print("line", ln.name, "first", evs[0].start_ns, "last",
                      evs[-1].end_ns, "stats of first:",
                      [(k, str(v)[:120]) for k, v in evs[0].stats])
    red = trace.reduce(pd)
    print("window_s", red["window_s"], "busy_s", red["busy_s"])
    ops = sorted(red["ops"].items(), key=lambda kv: -kv[1]["seconds"])[:top]
    for name, o in ops:
        print(json.dumps([name, o["seconds"], o["count"],
                          (o.get("long_name") or "")[:300]]))
    print("idle_gaps", red["idle_gaps"])


if __name__ == "__main__":
    main()
