#!/usr/bin/env python3
"""Run one cell of the benchmark on the chip this machine holds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cell is an entry of ``workloads`` in
``BENCHMARK.json``.  Exits non-zero, and prints no result, where JAX finds
no TPU or fewer chips than the cell asks for; it never falls back to the
CPU.  The last line of standard output is the result as one JSON object.
"""

import sys
import time
from pathlib import Path

T_START = time.perf_counter()
ROOT = Path(__file__).resolve().parents[1]
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))

if __name__ == "__main__":
    from bench import harness

    sys.exit(harness.main(t_start=T_START))
