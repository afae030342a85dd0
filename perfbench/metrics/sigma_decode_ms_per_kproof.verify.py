"""sigma_decode_ms_per_kproof.verify: milliseconds of σ decompression in
the verify front end (cess_proof_stage_sigma_decode_seconds, the span
`cess.proof.sigma_decode`) per 1000 σ decoded
(cess_proof_sigma_lanes_total), over the window.  A program without the
stage reads nothing."""


def read(run):
    secs = run.stages.get("cess_proof_stage_sigma_decode_seconds_sum")
    lanes = run.stages.get("cess_proof_sigma_lanes_total")
    if not run.stages.get("cess_proof_stage_sigma_decode_seconds_count") \
            or not lanes:
        return None
    return secs * 1e6 / lanes
