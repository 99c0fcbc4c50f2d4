#!/usr/bin/env python3
"""Run one cell of the chip benchmark and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell is an entry of ``BENCHMARK.json``; its configuration, traffic mix,
driver and per-layer metrics are found by name under ``bench/`` (see
``bench/lib/harness.py``).  The run needs as many TPU chips as the cell
asks for and exits non-zero, printing no result, without them.  The last
line of standard output is the result as one JSON object; the last lines
of standard error are the numbers the correctness check compared, each
beside its limit.
"""
import os
import sys
import time

T0 = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)

from bench.lib.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t0=T0))
