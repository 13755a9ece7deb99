#!/usr/bin/env python3
"""Run one benchmark cell on the chip this process finds.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic are looked up by name in
BENCHMARK.json and the files under bench/. With --trace 0 the last line of
standard output is the cell's end-to-end metrics; with --trace 1 it is its
per-layer metrics and a breakdown of device time, from a profiler trace of
the window. Without a TPU, or without the cell's chips, it exits nonzero
and prints no result.
"""
import sys
import time

T_START = time.perf_counter()

from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t_start=T_START))
