"""front_end_share.verify: percent of the window in the verify front end
on the calling thread: pk and σ decoding, the encode pass and the ρ
transcript (cess_proof_stage_front_end_seconds, the span
`cess.proof.front_end`)."""

from harness.readers import stage_share


def read(run):
    return stage_share(run, ["cess_proof_stage_front_end_seconds"])
