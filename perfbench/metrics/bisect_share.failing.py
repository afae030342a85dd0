"""bisect_share.failing: percent of the window in combined checks below
the root of the bisection tree, their own stages included
(cess_proof_stage_bisect_seconds, the span `cess.proof.bisect`)."""

from harness.readers import stage_share


def read(run):
    return stage_share(run, ["cess_proof_stage_bisect_seconds"])
