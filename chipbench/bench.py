"""Harness of the on-chip benchmark: resolves one cell of `BENCHMARK.json` to
its files, runs it on the chip, and prints the one-line result.

A cell `<config>.<traffic>` is made of files found by name, so a later change
adds a cell, a configuration, a traffic mix or a metric by adding files and
entries, and edits nothing here:

  configs/<config>.json      the configuration as run: sizes, source, cut,
                             the program's settings, the `architecture`
  configs/<config>.ref.py    its plain float32 reference and weight maker
  traffic/<traffic>.json     the mix's parameters; `driver` names
                             drivers/<driver>.py, the general generator
  cells/<cell>.json          limits of the comparison that decides `correct`
  metrics/<metric>.py        `read(rec)` -> a number, or None where the run
                             has nothing to read
  counts/<name>.py           operations and bytes, computed from shapes

A driver's `run(ctx)` builds the system under test, checks what the timed
path produced against the reference, measures the window and returns its
records (`rec`); the metric readers turn those into numbers.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import pathlib
import sys
import time
from typing import Any, Optional

from chipbench.trace import SPAN_PREFIX, WINDOW_SPAN

BENCH_DIR = pathlib.Path(__file__).resolve().parent
REPO = BENCH_DIR.parent
TRACE_DIR = BENCH_DIR / "out" / "trace"


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path):
    """Import a file by path: config references and metric readers have
    names (`mamba2-370m.ref.py`, `step_mfu.train.py`) that are not
    identifiers."""
    if not path.is_file():
        raise FileNotFoundError(f"no such benchmark file: {path}")
    name = "chipbench_file_" + "".join(c if c.isalnum() else "_"
                                       for c in str(path))
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One entry of BENCHMARK.json's `workloads`, with its files loaded."""
    name: str
    chips: int
    spec: dict             # configs/<config>.json
    traffic: dict          # traffic/<traffic>.json
    limits: dict           # cells/<cell>.json
    bench_dir: pathlib.Path

    @property
    def reference(self):
        return load_module(self.bench_dir / "configs" /
                           f"{self.spec['name']}.ref.py")

    @property
    def driver(self):
        return load_module(self.bench_dir / "drivers" /
                           f"{self.traffic['driver']}.py")


def load_cell(bench: dict, name: str, bench_dir: pathlib.Path = BENCH_DIR
              ) -> Cell:
    for w in bench["workloads"]:
        if w["name"] == name:
            break
    else:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    spec = load_json(bench_dir / "configs" / f"{w['config']}.json")
    traffic = load_json(bench_dir / "traffic" / f"{w['traffic']}.json")
    limits = load_json(bench_dir / "cells" / f"{name}.json")
    return Cell(name, int(w["chips"]), spec, traffic, limits, bench_dir)


def metrics_of(bench: dict, cell: str, kind: str) -> list[dict]:
    """The metrics of `kind` ('end_to_end' | 'per_layer') this cell reports:
    those whose `workloads` list names it, and those without such a list."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


def use_checkout_cache() -> None:
    """The program on the path, and JAX's persistent compilation cache at
    `<checkout>/.jax_cache` whatever the environment says: a fixed path, so
    a cell's second run finds what its first compiled, and two checkouts
    never share compiled programs."""
    sys.path.insert(0, str(REPO / "src"))
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(REPO / ".jax_cache")
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()


def device_check(chips: int) -> dict:
    """The devices JAX found. No accelerator, or fewer chips than the cell
    asks for, ends the run before anything is built: a number from the CPU
    is never reported under a device metric's name."""
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if dev["platform"] != "tpu":
        raise SystemExit(f"no TPU: JAX found {dev['platform']}")
    if dev["count"] < chips:
        raise SystemExit(f"the cell needs {chips} chips, JAX found "
                         f"{dev['count']}")
    return dev


#: JAX's monitoring events, counted over the process: a jit traced anew, and
#: a program compiled (not found in the persistent cache)
_JIT_EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "traced",
               "/jax/core/compile/backend_compile_duration": "compiled"}
_jit_counts = {"traced": 0, "compiled": 0}
_listening = []


def jit_counts() -> dict:
    """How many jits were traced and programs compiled so far."""
    if not _listening:
        import jax

        def on_event(event, duration, **kw):
            if event in _JIT_EVENTS:
                _jit_counts[_JIT_EVENTS[event]] += 1

        jax.monitoring.register_event_duration_secs_listener(on_event)
        _listening.append(on_event)
    return dict(_jit_counts)


def memory_peak_bytes(chips: int) -> Optional[int]:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:chips]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


@dataclasses.dataclass
class Ctx:
    """What a driver gets: the cell, the run's arguments, and the window and
    span helpers that put its records on one clock."""
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    t_process: float                     # perf_counter at process start
    spans: list = dataclasses.field(default_factory=list)
    trace_dir: pathlib.Path = TRACE_DIR
    window: Optional[tuple] = None       # (t_open, t_close) perf_counter
    jit_in_window: Optional[dict] = None  # jits traced, programs compiled

    @contextlib.contextmanager
    def span(self, name: str):
        """A host span: kept as (name, t0, t1) and, in a traced run, written
        into the profiler's trace on the device's clock."""
        t0 = time.perf_counter()
        if self.trace:
            import jax
            with jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
                yield
        else:
            yield
        self.spans.append((name, t0, time.perf_counter()))

    def open_window(self) -> float:
        """Start of the measured window; the profiler starts here in a traced
        run, so the trace holds the window and nothing else."""
        if self.trace:
            import jax
            import shutil
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            self.trace_dir.mkdir(parents=True, exist_ok=True)
            jax.profiler.start_trace(str(self.trace_dir))
            self._window_span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
            self._window_span.__enter__()
        self._jit_open = jit_counts()
        t = time.perf_counter()
        self.window = (t, None)
        return t

    def close_window(self, t_close: Optional[float] = None) -> float:
        """End of the window. The caller has synced on the device, so every
        operation of the window is in the trace before it stops."""
        t = time.perf_counter() if t_close is None else t_close
        self.window = (self.window[0], t)
        now = jit_counts()
        self.jit_in_window = {k: now[k] - self._jit_open[k] for k in now}
        if self.trace:
            import jax
            self._window_span.__exit__(None, None, None)
            jax.profiler.stop_trace()
        return t

    def reduce_trace(self, chips: int) -> Optional[dict]:
        if not self.trace:
            return None
        from chipbench import trace as trace_mod
        return trace_mod.reduce(trace_mod.load(self.trace_dir),
                                chips=chips)


def compare(readings: dict, limits: dict) -> tuple[bool, dict]:
    """Each compared number beside its limit. A number that is missing or
    not finite fails; so does one above its limit."""
    import math
    out, ok = {}, True
    for name, lim in limits.items():
        v = readings.get(name)
        good = v is not None and math.isfinite(v) and v <= lim
        ok &= good
        out[name] = {"value": v, "limit": lim}
    return ok, out


def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: dict, device: dict, compared: dict,
                breakdown: Optional[dict] = None) -> str:
    """The contract's last line of standard output; `compared` comes last."""
    out: dict[str, Any] = {"correct": bool(correct),
                           "attempted": int(attempted),
                           "failed": int(failed), "metrics": metrics,
                           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["compared"] = compared
    return json.dumps(out)


def read_metrics(bench: dict, cell: Cell, kind: str, rec: dict,
                 bench_dir: pathlib.Path = BENCH_DIR) -> dict:
    """Run each metric's reader over the run's records. A reader that finds
    nothing returns None and the metric is left out of the line."""
    out = {}
    for m in metrics_of(bench, cell.name, kind):
        reader = load_module(bench_dir / "metrics" / f"{m['name']}.py")
        v = reader.read(rec)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def run_cell(bench: dict, cell: Cell, ctx: Ctx, dev: dict, *,
             peaks: Optional[dict] = None, fault=None) -> tuple[str, dict]:
    """Drive the cell, read its metrics, and build the result line. The
    peaks are the device kind's; the CPU tests pass their own, and a
    `fault` that breaks the timed path."""
    from chipbench import peaks as peaks_mod
    rec = cell.driver.run(ctx, fault=fault)
    rec["peaks"] = peaks if peaks is not None else peaks_mod.for_kind(
        dev["kind"])
    rec["chips"] = cell.chips
    kind = "per_layer" if ctx.trace else "end_to_end"
    metrics = read_metrics(bench, cell, kind, rec, cell.bench_dir)
    device = {**dev, "memory_peak_bytes": rec.get("memory_peak_bytes")}
    breakdown = None
    tr = rec.get("trace")
    if tr is not None:
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        breakdown = {"device_ops": tr["device_ops"],
                     "idle_gaps": tr["idle_gaps"]}
    log(f"inside the window: {ctx.jit_in_window}")
    correct, compared = compare(rec["readings"], cell.limits["limits"])
    correct &= bool(rec.get("sound", True))
    for name, c in compared.items():
        log(f"compared {name} = {c['value']!r} (limit {c['limit']!r})")
    line = result_line(correct=correct, attempted=rec["attempted"],
                       failed=rec["failed"], metrics=metrics, device=device,
                       compared=compared, breakdown=breakdown)
    return line, rec


def main(argv: Optional[list] = None,
         t_process: Optional[float] = None) -> None:
    import argparse
    ap = argparse.ArgumentParser(
        description="Run one cell of BENCHMARK.json on the chip.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    t_process = time.perf_counter() if t_process is None else t_process

    bench = load_json(REPO / "BENCHMARK.json")
    cell = load_cell(bench, args.workload)
    dev = device_check(cell.chips)
    use_checkout_cache()
    ctx = Ctx(cell=cell, seed=args.seed, seconds=args.seconds,
              trace=bool(args.trace), t_process=t_process)
    line, _ = run_cell(bench, cell, ctx, dev)
    print(line, flush=True)
