#!/usr/bin/env python3
"""Run one cell of the chip benchmark and print its result line.

  python3 bench/run.py --workload <name> --seed <n> --seconds <s> \
      --trace <0|1>

`<name>` is a `workloads` entry of `BENCHMARK.json`.  With `--trace 0`
the result line holds the cell's end-to-end metrics, with `--trace 1`
its per-layer metrics, read from a profiler trace of part of the window.
The last stdout line is one JSON object (`correct`, `attempted`,
`failed`, `metrics`, `device`, with `--trace 1` also `breakdown`, and
last `check`: each number compared with its limit); the compared
numbers are also the last stderr lines.  Without a TPU, or with fewer
chips than the cell asks for, it exits non-zero and prints no result.
"""
import time

T_PROC0 = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t_proc0=T_PROC0))
