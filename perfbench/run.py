#!/usr/bin/env python3
"""Run one benchmark cell once on the chip this machine holds.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The cell, its configuration, its traffic mix and its metrics are found
by name from BENCHMARK.json (perfbench/harness/spec.py).  The last line
of standard output is the result object; every number compared for
`correct` is printed beside its limit as the last lines of standard
error, and under `checks` in the result.  With no TPU the run exits 2
and prints no result.
"""

import time

T_PROCESS = time.perf_counter()  # set-up is timed from here

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent))

from harness import runner  # noqa: E402

if __name__ == "__main__":
    sys.exit(runner.main(sys.argv[1:], T_PROCESS))
