"""Shared helpers for the benchmark's tests: put perfbench/ on the path,
and shrink a cell's configuration and traffic to sizes a CPU test can
run in seconds (the geometry's widths shrink too; the chip runs the
files as they are)."""

from __future__ import annotations

import copy
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "perfbench"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))


def tiny(config: dict, traffic: dict) -> tuple[dict, dict]:
    config, traffic = copy.deepcopy(config), copy.deepcopy(traffic)
    geo = config["geometry"]
    if traffic["driver"] == "verify":
        geo.update(chunks_per_fragment=16, sectors_per_chunk=4,
                   challenged_chunks=4)
        traffic.update(pool=12, mu_vectors=2,
                       probe={"batch": 8, "forged": 2},
                       sample={"window": 2, "probe_honest": 1})
        for c in traffic["calls"]:
            c["batch"] = ({"fixed": 8} if "fixed" in c["batch"]
                          else {"log_uniform": [2, 8], "strata": 3})
    else:
        geo.update(fragment_bytes=4096)
        traffic.update(files=2, segments_per_call=6,
                       lost_per_fragment=[2, 2, 2], sample_calls=2)
    return config, traffic
