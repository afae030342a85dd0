"""chunk_device_ms_per_kproof.verify: device milliseconds of the fused
chunk program (XLA modules of `_verify_chunk_device`, with its Pallas
kernels) per 1000 proofs verified in the traced part of the window."""

from harness.trace import module_seconds


def read(run):
    if run.trace is None or not run.trace_work.get("proofs"):
        return None
    secs, runs = module_seconds(run.trace, "_verify_chunk_device")
    if not runs:
        return None
    return secs * 1e6 / run.trace_work["proofs"]
