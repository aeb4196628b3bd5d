#!/usr/bin/env python3
"""Benchmark command: one cell of ``BENCHMARK.json`` on the chip.

    python3 hbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Exits non-zero, printing no result, when JAX's first device is not a TPU
or the cell needs more chips than JAX sees. Otherwise the last line of
standard output is the result as one JSON object (``hbench/bench.py``).
"""
import time

T_START = time.perf_counter()

import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from hbench import bench  # noqa: E402

if __name__ == "__main__":
    sys.exit(bench.main(sys.argv[1:], T_START))
