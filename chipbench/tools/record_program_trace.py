"""Record the small trace the program-span readers are tested on: inside
the window span, a tiny serving engine (the reduced paper-lm, 4 slots)
serves two requests of different prompt lengths to the end, the host then
sleeps 20 ms outside any program span, and one index refresh of the
engine's MIDX index runs through the lifecycle (dispatch, completion,
validation gate). Every program is compiled before the window opens.
The recording is trimmed to what the readers read (`trim`) and written to
chipbench/testdata/program.xplane.pb; run on the chip:

    python3 chipbench/tools/record_program_trace.py
"""
import json
import pathlib
import shutil
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2] / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from chipbench import bench, program_trace, trace  # noqa: E402


def trim(pd) -> bytes:
    """The trace as a serialized XSpace that keeps only the window span
    and the program's spans on the host, with their counts, and device 0's
    `XLA Ops` (each named by its short name) and `XLA Modules` lines: the
    HLO the profiler stores with each program would make the file
    megabytes."""
    def keep(plane, line, name):
        if plane.startswith("/host:"):
            return (name == trace.WINDOW_SPAN
                    or name.startswith(program_trace.PREFIX))
        return plane == "/device:TPU:0" and line in (trace.OPS_LINE,
                                                      "XLA Modules")

    out = []
    for pid, plane in enumerate(pd.planes, 1):
        events, stats, lines = {}, {}, []
        for lid, line in enumerate(plane.lines, 1):
            evs = []
            for ev in line.events:
                if not keep(plane.name, line.name, ev.name):
                    continue
                name = (trace.short_name(ev.name)
                        if line.name == trace.OPS_LINE else ev.name)
                mid = events.setdefault(name, len(events) + 1)
                a, b = int(ev.start_ns), int(ev.end_ns)
                st = " ".join(
                    f"stats {{ metadata_id: "
                    f"{stats.setdefault(k, len(stats) + 1)} "
                    + (f"int64_value: {v}" if isinstance(v, int)
                       else f"str_value: {json.dumps(str(v))}") + " }"
                    for k, v in ev.stats)
                evs.append(f"events {{ metadata_id: {mid} offset_ps: "
                           f"{a * 1000} duration_ps: {(b - a) * 1000} "
                           f"{st} }}")
            if evs:
                lines.append(f"lines {{ id: {lid} name: "
                             f"{json.dumps(line.name)} timestamp_ns: 0 "
                             + " ".join(evs) + " }")
        if not lines:
            continue
        out.append(
            f"planes {{ id: {pid} name: {json.dumps(plane.name)} "
            + " ".join(lines) + " "
            + " ".join(f"event_metadata {{ key: {i} value {{ id: {i} "
                       f"name: {json.dumps(n)} }} }}"
                       for n, i in events.items()) + " "
            + " ".join(f"stat_metadata {{ key: {i} value {{ id: {i} "
                       f"name: {json.dumps(n)} }} }}"
                       for n, i in stats.items()) + " }")
    from jax.profiler import ProfileData
    return ProfileData.text_proto_to_serialized_xspace("\n".join(out))


def main() -> None:
    from repro.configs import get_config
    from repro.index import IndexLifecycle
    from repro.launch.steps import make_refresh_step
    from repro.serve import Engine, Request

    cfg = get_config("paper-lm").reduced().with_serve(
        max_slots=4, page_size=4, max_seq=32)
    engine = Engine(cfg, init_key=jax.random.PRNGKey(0), head="midx")
    plens = (6, 11)
    engine.warmup(plens)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, tokens=rng.integers(0, cfg.vocab_size, p)
                    .astype(np.int32), max_new=3, seed=1)
            for i, p in enumerate(plens)]
    refresh = jax.jit(make_refresh_step(cfg, head_mode="midx"))
    jax.block_until_ready(refresh(engine.params, engine.index,
                                  jax.random.PRNGKey(1)))
    lifecycle = IndexLifecycle(refresh, every=1,
                               base_key=jax.random.PRNGKey(2))

    out = bench.BENCH_DIR / "out" / "record"
    shutil.rmtree(out, ignore_errors=True)
    # neither Python function events nor the programs' HLO: the file keeps
    # the spans and the device's operations, small enough to commit
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(str(out), profiler_options=opts)
    with jax.profiler.TraceAnnotation(bench.WINDOW_SPAN):
        engine.run(reqs)
        time.sleep(0.02)
        index, ev = lifecycle.step(0, engine.params, engine.index)
        jax.block_until_ready(index)
    jax.profiler.stop_trace()
    print(engine.stats.summary(), ev)
    dst = bench.BENCH_DIR / "testdata" / "program.xplane.pb"
    dst.write_bytes(trim(trace.load(out)))
    print(dst, dst.stat().st_size)


if __name__ == "__main__":
    main()
