"""verify_proofs_per_s: proofs given verdicts per second of the window."""

from harness.readers import rate


def read(run):
    return rate(run, "proofs")
