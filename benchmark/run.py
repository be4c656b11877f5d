#!/usr/bin/env python3
"""The benchmark's command (see BENCHMARK.json and benchmark/README.md):

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, no children. Exits non-zero and prints no result line without
a TPU whose kind is in ``benchmark/lib/peaks.py``.
"""

import time

_T_START = time.perf_counter()  # set-up runs from here

import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, ROOT)

if __name__ == "__main__":
    from benchmark.lib import harness

    sys.exit(harness.main(
        sys.argv[1:], root=ROOT, bench_dir=BENCH_DIR, t_start=_T_START
    ))
