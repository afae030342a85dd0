"""launch_share.verify: percent of the window in the fused pipeline's
launches: each chunk's upload and asynchronous dispatch, and the partial
reduction's (cess_proof_stage_launch_seconds, the span
`cess.proof.launch`)."""

from harness.readers import stage_share


def read(run):
    return stage_share(run, ["cess_proof_stage_launch_seconds"])
