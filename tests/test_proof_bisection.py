"""The bisection tree's stages and counters against the plain reference.

`XlaBackend.verify_batch` marks each combined check below the root of
the bisection tree (`bisect`, span `cess.proof.bisect`, counter
`cess_proof_bisect_checks_total`) and each single-proof verify at a
failing leaf (`leaf`, `cess.proof.leaf`, `cess_proof_leaf_checks_total`).
Here 8 proofs carry 0, 1 or 2 forgeries of the two well-formed kinds
the `verify.failing` benchmark cell plants, through the fused path with
one-shape padding on and off and through the staged path; verdicts and
check counts are held to perfbench/reference/podr2_ref.py.

Both fused modes take chunks of one proof: every node of the tree then
runs one compiled chunk program, which the two modes share (each new
shape is minutes of compile on the CPU).
"""

import pytest

from cess_tpu.ops import glv, h2c
from cess_tpu.ops.bls12_381 import R
from cess_tpu.ops.podr2 import Podr2Proof
from cess_tpu.proof import XlaBackend, fused
from cess_tpu.proof.xla_backend import proof_stage_registry
from perfbench.reference import podr2_ref
from test_proof_hotpath import (  # noqa: F401  (proved10: a fixture)
    PARAMS,
    PK,
    SpanRecorder,
    proved10,
    stage_samples,
)

pytestmark = pytest.mark.proof_hotpath


def forged_batch(items, kind: str, positions) -> tuple[list, list]:
    """(batch, truth): the `verify.failing` cell's two well-formed
    forgeries at `positions`: another proof's σ, or one μ altered."""
    batch = list(items)
    for pos in positions:
        name, ch, proof = batch[pos]
        if kind == "sigma":
            other = items[(pos + 1) % len(items)][2].sigma
            batch[pos] = (name, ch, Podr2Proof(other, list(proof.mu)))
        else:
            mu = list(proof.mu)
            mu[0] = (mu[0] + 1) % R
            batch[pos] = (name, ch, Podr2Proof(proof.sigma, mu))
    return batch, [i not in positions for i in range(len(items))]


@pytest.fixture(params=["fused_one_shape", "fused_exact", "staged"])
def tree_backend(request, monkeypatch):
    """The fused path with one-shape padding on and off, and the staged
    path."""
    if request.param == "staged":
        return XlaBackend(fused=False)
    monkeypatch.setenv("CESS_FUSED_ONE_SHAPE",
                       "1" if request.param == "fused_one_shape" else "0")
    monkeypatch.setattr(fused, "CHUNK", 1)
    monkeypatch.setattr(h2c, "_MAP_TILE", 8)
    monkeypatch.setattr(glv, "_GLV_TILE", 8)
    return XlaBackend(fused=True)


@pytest.mark.parametrize("kind,positions", [
    ("none", ()), ("sigma", (5,)), ("sigma", (1, 6)), ("mu", (2,)),
    ("mu", (0, 7))])
def test_tree_counts_match_the_reference(proved10, tree_backend, kind,
                                         positions, monkeypatch):
    from cess_tpu.utils import stages

    batch, truth = forged_batch(proved10[:8], kind, positions)
    rec = SpanRecorder()
    monkeypatch.setattr(stages, "TraceAnnotation", rec)
    reg = proof_stage_registry()
    before = stage_samples(reg)
    verdicts = tree_backend.verify_batch(PK, batch, b"tree", PARAMS)
    after = stage_samples(reg)
    delta = {k: after[k] - before.get(k, 0.0) for k in after}

    ref = podr2_ref.verdicts(PK, [
        (name, list(ch.indices), list(ch.randoms), p.sigma, list(p.mu))
        for name, ch, p in batch], PARAMS.s, b"tree")
    assert verdicts == ref == truth
    forged = len(positions)
    checks = delta["cess_proof_checks"]
    assert checks == podr2_ref.bisection_checks(truth)
    below_root = checks - 1 if forged else 0
    assert delta["cess_proof_bisect_checks_total"] == below_root
    assert delta["cess_proof_stage_bisect_seconds_count"] == below_root
    assert len(rec.named("bisect")) == below_root
    assert delta["cess_proof_leaf_checks_total"] == forged
    assert delta["cess_proof_stage_leaf_seconds_count"] == forged
    assert len(rec.named("leaf")) == forged
    if forged:
        # a re-check encloses the stages of its own combined check
        s0, s1 = rec.named("bisect")[0][2:]
        assert any(s0 <= s[2] <= s[3] <= s1 for s in rec.named("pairing"))
