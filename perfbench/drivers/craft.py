#!/usr/bin/env python3
"""Proof-crafting worker for the verify driver.

    python3 perfbench/drivers/craft.py <job.json> <out>

Runs as a child process that never touches the chip (the driver starts
it with JAX_PLATFORMS=cpu, and nothing here imports JAX).  For each
fragment name of the job it writes the 48-byte sigma of an honest proof
under the job's challenge and the proof's mu vector
(reference/podr2_ref.py craft_sigma).  The chunk points come from the
program's native hash-to-curve, for speed; the reference verifier hashes
every point again itself, so a wrong point makes the crafted proof fail
the reference and the run come out not correct.
"""

import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE.parents[1]))

from reference import podr2_ref  # noqa: E402
from reference.bls12_381 import G1Point  # noqa: E402


def main() -> int:
    job = json.loads(Path(sys.argv[1]).read_text())
    from cess_tpu import native

    names = [bytes.fromhex(n) for n in job["names"]]
    indices = job["indices"]
    coeffs = podr2_ref.coefficients(bytes.fromhex(r) for r in job["randoms"])
    us = [G1Point(x, y) for x, y in native.hash_to_g1_batch(
        [podr2_ref.U_DST + j.to_bytes(4, "little")
         for j in range(job["sectors"])], podr2_ref.U_DST, threads=1)]
    mu_points = [podr2_ref.u_part(mu, us) for mu in job["mus"]]
    out = []
    for name, m in zip(names, job["mu_of"]):
        pts = native.hash_to_g1_batch(
            [podr2_ref.chunk_message(name, i) for i in indices],
            podr2_ref.H_DST, threads=1)
        h = [G1Point(x, y) for x, y in pts]
        out.append(podr2_ref.craft_sigma(job["sk"], h, coeffs, mu_points[m]))
    Path(sys.argv[2]).write_bytes(b"".join(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
