"""dispatch_wait_share.verify: percent of the window spent blocked on
device results after every chunk was in flight
(cess_proof_stage_dispatch_wait_seconds)."""

from harness.readers import stage_share


def read(run):
    return stage_share(run, ["cess_proof_stage_dispatch_wait_seconds"])
