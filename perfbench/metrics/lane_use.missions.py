"""lane_use.missions: percent of the fused chunks' proof slots that held
a live proof over the window (cess_proof_chunk_proofs_total over
cess_proof_chunk_slots_total); the rest is padding to the chunk shape."""


def read(run):
    slots = run.stages.get("cess_proof_chunk_slots_total")
    if not slots:
        return None
    return 100.0 * run.stages["cess_proof_chunk_proofs_total"] / slots
