"""Protocol-geometry end-to-end: one prove → verify cycle at the REAL
fragment shape (n=1024 chunks × s=265 sectors, 47 challenged chunks —
reference geometry: primitives/common/src/lib.rs:61-62,
c-pallets/audit/src/lib.rs:906) through the xla backend.

Marked slow: several minutes of XLA compiles on the CPU test mesh.  Run
with RUN_SLOW=1 (CI) or `pytest --runslow`; the benchmark's verify cells
(perfbench/) exercise the same geometry on the real chip.
"""

import random

import pytest

from cess_tpu.ops import podr2
from cess_tpu.ops.podr2 import Challenge, Podr2Params
from cess_tpu.proof import CpuBackend, XlaBackend
from cess_tpu.proof.backend import ProveRequest

pytestmark = pytest.mark.slow


def test_prove_verify_cycle_at_protocol_geometry():
    params = Podr2Params()  # n=1024, s=265 — the real thing
    assert (params.n, params.s) == (1024, 265)
    sk, pk = podr2.keygen(b"proto-tee")
    rnd = random.Random(1024)
    indices = tuple(sorted(rnd.sample(range(params.n), 47)))
    challenge = Challenge(
        indices=indices,
        randoms=tuple(rnd.randbytes(20) for _ in indices),
    )

    name = b"proto-fragment"
    data = rnd.randbytes(params.fragment_bytes)  # a full 8 MiB fragment
    tags = podr2.tag_fragment(sk, name, data, params)

    backend = XlaBackend()
    req = ProveRequest(
        names=[name], tags=[tags], data=[data],
        challenge=challenge, params=params,
    )
    proofs = backend.prove_batch(req)
    assert len(proofs) == 1
    # the prover outputs match the host reference bit-for-bit
    host_proof = podr2.prove(tags, data, challenge, params)
    assert proofs[0].sigma == host_proof.sigma
    assert proofs[0].mu == host_proof.mu

    items = [(name, challenge, proofs[0])]
    assert backend.verify_batch(pk, items, b"proto-seed", params) == [True]
    assert CpuBackend().verify_batch(pk, items, b"proto-seed", params) == [
        True
    ]

    # corrupt one sector's μ → the xla backend must reject
    bad = podr2.Podr2Proof(proofs[0].sigma, list(proofs[0].mu))
    bad.mu[7] = (bad.mu[7] + 1) % podr2.R
    assert backend.verify_batch(
        pk, [(name, challenge, bad)], b"proto-seed", params
    ) == [False]
