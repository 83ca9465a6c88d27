"""Run one cell of the on-chip benchmark.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout, on a machine that holds the chips the cell asks
for. Without a TPU it exits non-zero and prints no result. The last line of
standard output is the result as one JSON object; the numbers that decided
`correct`, each beside its limit, are the last lines of standard error.
"""
import time

T_PROCESS = time.perf_counter()

import pathlib  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from chipbench.bench import main  # noqa: E402

if __name__ == "__main__":
    main(t_process=T_PROCESS)
