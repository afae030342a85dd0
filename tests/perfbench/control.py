#!/usr/bin/env python3
"""Sound runs and control runs of one cell on the chip, at its own size.

    python3 tests/perfbench/control.py --workload <cell> --seeds a,b,c \
        --seconds <s>

For each seed, in one process: the cell's set-up, a window of `s`
seconds and the check (a sound run); then, on the same set-up, a window
and the check with the control switched on: for the verify cells a
verifier that accepts every batch (the pairing check skipped), for the
repair cells recovery as if the parity row were XOR.  Prints one JSON
line per run with every number compared.  The benchmark's own runs never
run this.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "perfbench"))
sys.path.insert(0, str(ROOT))

from harness import platform, runner, spec  # noqa: E402


@contextlib.contextmanager
def control(driver: str):
    if driver == "verify":
        from cess_tpu.ops import bls12_381

        saved, name = bls12_381, "pairing_check"
        orig = bls12_381.pairing_check
        bls12_381.pairing_check = lambda pairs: True
    else:
        from cess_tpu.ops import gf256, rs

        def xor_inverse(k, m, present):
            gen = np.concatenate([np.eye(k, dtype=np.uint8),
                                  np.ones((m, k), dtype=np.uint8)])
            return gf256.mat_inv(gen[np.asarray(present)])

        saved, name = rs, "_inv_cached"
        orig = rs._inv_cached
        rs._inv_cached = xor_inverse
    try:
        yield
    finally:
        setattr(saved, name, orig)


def reset(work) -> None:
    """Forget the last window's calls, keeping the set-up."""
    work.calls = [] if isinstance(work.calls, list) else 0
    if hasattr(work, "kept"):
        work.kept = []


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args()
    bench = spec.benchmark()
    cell = spec.cell(bench, args.workload)
    platform.compile_cache(ROOT)
    platform.native_core(ROOT)
    platform.chips(cell["chips"])
    drv_name = spec.traffic(cell["traffic"])["driver"]
    drv = spec.driver(drv_name)
    warmed = False
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx = runner.Context(root=ROOT, seed=seed, cell=cell,
                             config=spec.config(bench, cell["config"]),
                             traffic=spec.traffic(cell["traffic"]))
        if warmed and drv_name == "verify":
            # the chunk program is loaded; later seeds skip its warm-up
            from cess_tpu.proof.xla_backend import XlaBackend
            XlaBackend.warm_up = lambda self, params, n: False
        t0 = time.perf_counter()
        work = drv.setup(ctx)
        warmed = True
        setup_s = time.perf_counter() - t0
        for arm in ("sound", "control"):
            reset(work)
            with control(drv_name) if arm == "control" else \
                    contextlib.nullcontext():
                window_s, totals, units, _ = runner.measure(
                    work, args.seconds, ctx.span)
                res = work.check()
            print(json.dumps({
                "workload": args.workload, "seed": seed, "arm": arm,
                "correct": all(c["value"] <= c["limit"]
                               for c in res.checks.values()),
                "checks": res.checks, "attempted": res.attempted,
                "failed": res.failed, "window_s": window_s,
                "units": units, "setup_s": setup_s}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
