"""leaf_share.failing: percent of the window in single-proof verifies at
the failing leaves of the bisection tree
(cess_proof_stage_leaf_seconds, the span `cess.proof.leaf`)."""

from harness.readers import stage_share


def read(run):
    return stage_share(run, ["cess_proof_stage_leaf_seconds"])
