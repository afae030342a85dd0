"""prep_ms_per_kproof.verify: milliseconds of chunk packing on the
prefetch worker (cess_proof_stage_prep_seconds, the span
`cess.proof.prep`) per 1000 live proofs launched in fused chunks
(cess_proof_chunk_proofs_total), over the window."""


def read(run):
    secs = run.stages.get("cess_proof_stage_prep_seconds_sum")
    proofs = run.stages.get("cess_proof_chunk_proofs_total")
    if not run.stages.get("cess_proof_stage_prep_seconds_count") or not proofs:
        return None
    return secs * 1e6 / proofs
