"""check_constant_share.missions: percent of the window in the per-check
host tail: the u-side MSM and the host pairing
(cess_proof_stage_{u_fold,pairing}_seconds)."""

from harness.readers import stage_share


def read(run):
    return stage_share(run, ["cess_proof_stage_u_fold_seconds",
                             "cess_proof_stage_pairing_seconds"])
