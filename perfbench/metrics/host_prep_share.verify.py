"""host_prep_share.verify: percent of the window in the verify front
end (cess_proof_stage_host_prep_seconds)."""

from harness.readers import stage_share


def read(run):
    return stage_share(run, ["cess_proof_stage_host_prep_seconds"])
