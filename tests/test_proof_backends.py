"""ProofBackend parity tests: cpu and xla must be bit-identical."""

import pytest

from cess_tpu.ops import podr2
from cess_tpu.ops.bls12_381 import R
from cess_tpu.ops.podr2 import Challenge, Podr2Params, keygen, tag_fragment
from cess_tpu.proof import CpuBackend, XlaBackend, get_backend
from cess_tpu.proof.backend import ProveRequest

PARAMS = Podr2Params(n=8, s=4)
SK, PK = keygen(b"backend-tee")


def stage_samples(reg) -> dict:
    """{sample name: value} of a registry's unlabelled samples."""
    return {name: v for metric in reg.metrics()
            for name, labels, v in metric.samples() if not labels}


def make_challenge(indices, seed=b"x"):
    randoms = tuple(
        (seed + i.to_bytes(2, "little")).ljust(20, b"\x55") for i in indices
    )
    return Challenge(indices=tuple(indices), randoms=randoms)


@pytest.fixture(scope="module")
def fragments():
    out = []
    for k in range(4):
        name = f"frag-{k}".encode()
        data = bytes([(k * 37 + i) % 256 for i in range(PARAMS.fragment_bytes)])
        tags = tag_fragment(SK, name, data, PARAMS)
        out.append((name, data, tags))
    return out


@pytest.fixture(scope="module")
def proved(fragments):
    ch = make_challenge([0, 2, 5, 7])
    items = []
    for name, data, tags in fragments:
        proof = podr2.prove(tags, data, ch, PARAMS)
        items.append((name, ch, proof))
    return ch, items


class TestParity:
    def test_prove_batch_identical(self, fragments):
        ch = make_challenge([1, 3, 6])
        req = ProveRequest(
            names=[f[0] for f in fragments],
            tags=[f[2] for f in fragments],
            data=[f[1] for f in fragments],
            challenge=ch,
            params=PARAMS,
        )
        cpu_proofs = CpuBackend().prove_batch(req)
        xla_proofs = XlaBackend().prove_batch(req)
        for a, b in zip(cpu_proofs, xla_proofs):
            assert a.sigma == b.sigma
            assert a.mu == b.mu

    def test_verify_all_honest(self, proved):
        _, items = proved
        for backend in (CpuBackend(), XlaBackend()):
            assert backend.verify_batch(PK, items, b"round", PARAMS) == [True] * 4

    def test_verify_with_one_bad(self, proved):
        _, items = proved
        bad = list(items)
        name, ch, proof = bad[2]
        tampered = podr2.Podr2Proof(proof.sigma, list(proof.mu))
        tampered.mu[0] = (tampered.mu[0] + 1) % R
        bad[2] = (name, ch, tampered)
        cpu = CpuBackend().verify_batch(PK, bad, b"round", PARAMS)
        xla = XlaBackend().verify_batch(PK, bad, b"round", PARAMS)
        assert cpu == [True, True, False, True]
        assert cpu == xla

    def test_verify_all_bad(self, proved):
        _, items = proved
        bad = [
            (name, ch, podr2.Podr2Proof(p.sigma, [(m + 1) % R for m in p.mu]))
            for name, ch, p in items
        ]
        cpu = CpuBackend().verify_batch(PK, bad, b"s", PARAMS)
        xla = XlaBackend().verify_batch(PK, bad, b"s", PARAMS)
        assert cpu == [False] * 4 == xla

    def test_profile_stages_breakdown(self, proved):
        """The staged path's stage histograms: verdicts unchanged, every
        stage of the check observed, the front end once."""
        from cess_tpu.proof.xla_backend import proof_stage_registry

        _, items = proved
        reg = proof_stage_registry()
        before = stage_samples(reg)
        backend = XlaBackend()
        assert backend.verify_batch(PK, items, b"round", PARAMS) == (
            [True] * len(items)
        )
        after = stage_samples(reg)
        delta = {k: after[k] - before.get(k, 0.0) for k in after}
        seen = {k[len("cess_proof_stage_"):-len("_seconds_count")]
                for k, v in delta.items()
                if k.endswith("_seconds_count") and v > 0}
        assert seen == {
            "front_end", "sigma_decode", "host_prep", "u_fold",
            "sigma_fold", "chunk_program", "pairing",
        }
        assert delta["cess_proof_stage_front_end_seconds_count"] == 1
        assert delta["cess_proof_stage_pairing_seconds_sum"] > 0
        assert (delta["cess_proof_stage_front_end_seconds_sum"]
                <= delta["cess_proof_stage_host_prep_seconds_sum"])

    def test_empty_batch(self):
        for backend in (CpuBackend(), XlaBackend()):
            assert backend.verify_batch(PK, [], b"s", PARAMS) == []

    def test_get_backend(self):
        assert get_backend("cpu").name == "cpu"
        assert get_backend("xla").name == "xla"
        with pytest.raises(ValueError):
            get_backend("cuda")
