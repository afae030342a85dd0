#!/usr/bin/env python3
"""Run one benchmark cell traced and name its idle gaps by program stage.

    python3 perfbench/trace_stages.py --workload <name> --seed <n> \
        --seconds <s>

The run is `run.py ... --trace 1`, set-up, window, check and result
line alike, except that the result's `breakdown.idle_gaps` are named by
harness/stage_gaps.py (the innermost `bench.*` or `cess.*` span on the
window's thread) instead of by `bench.*` spans alone, and that standard
error gets a `stage_coincidence:` line: for each program stage, its
seconds in the traced window, the device's busy seconds within it, and
the seconds other threads' stages overlapped it.
"""

import time

T_PROCESS = time.perf_counter()  # set-up is timed from here

import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent))

from harness import runner, stage_gaps, trace  # noqa: E402

_summarize = trace.summarize


def summarize(planes, top: int = 10) -> dict:
    planes = list(planes)
    summary = _summarize(planes, top)
    summary["idle_gaps"] = stage_gaps.idle_gaps(planes, top)
    runner.log("stage_coincidence: "
               + json.dumps(stage_gaps.coincidence(planes)))
    return summary


if __name__ == "__main__":
    trace.summarize = summarize
    sys.exit(runner.main(sys.argv[1:] + ["--trace", "1"], T_PROCESS))
