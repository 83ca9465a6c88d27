"""Record the small trace the trace reduction is tested on: a jitted matrix
product run three times inside the window span, each in a host span, with a
host-only sleep between the second and third so the device idles there.
Writes chipbench/testdata/window.xplane.pb; run on the chip:

    python3 chipbench/tools/record_trace.py
"""
import pathlib
import shutil
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from chipbench import bench  # noqa: E402


def main() -> None:
    out = bench.BENCH_DIR / "out" / "record"
    shutil.rmtree(out, ignore_errors=True)
    f = jax.jit(lambda x: jnp.tanh(x @ x))
    x = jnp.ones((2048, 2048), jnp.float32) / 2048
    f(x).block_until_ready()
    jax.profiler.start_trace(str(out))
    with jax.profiler.TraceAnnotation(bench.WINDOW_SPAN):
        for i in range(3):
            with jax.profiler.TraceAnnotation(bench.SPAN_PREFIX + f"call{i}"):
                f(x).block_until_ready()
            if i == 1:
                with jax.profiler.TraceAnnotation(bench.SPAN_PREFIX + "sleep"):
                    time.sleep(0.02)
    jax.profiler.stop_trace()
    src = sorted(out.glob("**/*.xplane.pb"))[-1]
    dst = bench.BENCH_DIR / "testdata" / "window.xplane.pb"
    shutil.copy(src, dst)
    print(dst, dst.stat().st_size)


if __name__ == "__main__":
    main()
