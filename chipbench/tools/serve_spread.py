"""Per-run records behind the serving cell's bounds: one run of a serving
cell exactly as `run.py` makes it, which also writes what lies behind its
TTFT tail to a JSON file; and the spreads over such files.

    python3 chipbench/tools/serve_spread.py run --workload <cell> \\
        --seed <n> --seconds <s> --out <file.json>
    python3 chipbench/tools/serve_spread.py summarize <file.json> ...

`run` prints the result line as `run.py` does (untraced) and writes: the
window's request count, TTFT p50/p90/p95/max over every request sent in the
window, `serve_tokens_per_s`, and for each request in the top tenth of TTFT
its prompt length and the tick that produced its first token (how many
first tokens it held and their prompt lengths). `summarize` groups the files
by the `set` in their names (`<set>-<seed>.json`) and prints each reading's
spread per set: the interquartile distance over the median, by
`statistics.quantiles(n=4)`.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

READINGS = ("attempted", "ttft_p50_ms", "ttft_p90_ms", "ttft_p95_ms",
            "ttft_max_ms", "serve_tokens_per_s", "setup_s")


def tail_record(rec: dict, line: dict) -> dict:
    """What a run's records say about its TTFT tail."""
    import numpy as np
    reqs = rec["requests"]
    ttft = np.array([(r.times[0] if r.times else rec["drain_end"]) - r.t_send
                     for r in reqs]) * 1e3
    ticks = {t1: (kind, t1 - t0) for kind, t0, t1, _, _ in rec["ticks"]}
    firsts: dict = {}
    for r in rec["all_requests"]:
        if r.times:
            firsts.setdefault(r.times[0], []).append(r.plen)
    p90 = float(np.percentile(ttft, 90))
    tail = []
    for r, t in sorted(zip(reqs, ttft), key=lambda x: -x[1]):
        if t < p90:
            break
        t_first = r.times[0] if r.times else None
        kind, dur = ticks.get(t_first, (None, None))
        tail.append({"rid": r.rid, "plen": r.plen, "ttft_ms": float(t),
                     "tick": kind, "tick_ms": None if dur is None
                     else 1e3 * dur,
                     "tick_plens": sorted(firsts.get(t_first, []))})
    m = line["metrics"]
    return {"correct": line["correct"], "attempted": line["attempted"],
            "failed": line["failed"],
            "ttft_p50_ms": float(np.percentile(ttft, 50)),
            "ttft_p90_ms": p90,
            "ttft_p95_ms": float(np.percentile(ttft, 95)),
            "ttft_max_ms": float(ttft.max()),
            "serve_tokens_per_s": m["serve_tokens_per_s"]["value"],
            "setup_s": m["setup_s"]["value"],
            "memory_peak_bytes": line["device"]["memory_peak_bytes"],
            "draw_mismatch": line["compared"]["draw_mismatch"]["value"],
            "tail": tail}


def spread(values: list) -> float:
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2


def summarize(paths: list) -> dict:
    sets: dict = {}
    for p in sorted(paths):
        name = pathlib.Path(p).stem
        sets.setdefault(name.split("-")[0], []).append(
            json.loads(pathlib.Path(p).read_text()))
    out = {}
    for name, runs in sets.items():
        out[name] = {k: {"values": [r[k] for r in runs],
                         "median": statistics.median(r[k] for r in runs),
                         "spread": spread([r[k] for r in runs])}
                     for k in READINGS}
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    sub = ap.add_subparsers(dest="cmd", required=True)
    r = sub.add_parser("run")
    r.add_argument("--workload", required=True)
    r.add_argument("--seed", type=int, required=True)
    r.add_argument("--seconds", type=float, required=True)
    r.add_argument("--out", required=True)
    s = sub.add_parser("summarize")
    s.add_argument("files", nargs="+")
    args = ap.parse_args()
    if args.cmd == "summarize":
        print(json.dumps(summarize(args.files), indent=1))
        return

    from chipbench import bench
    b = bench.load_json(bench.REPO / "BENCHMARK.json")
    cell = bench.load_cell(b, args.workload)
    dev = bench.device_check(cell.chips)
    bench.use_checkout_cache()
    ctx = bench.Ctx(cell=cell, seed=args.seed, seconds=args.seconds,
                    trace=False, t_process=T_PROCESS)
    line, rec = bench.run_cell(b, cell, ctx, dev)
    out = {"seed": args.seed, **tail_record(rec, json.loads(line))}
    path = pathlib.Path(args.out)
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(out, indent=1))
    print(line, flush=True)


if __name__ == "__main__":
    main()
