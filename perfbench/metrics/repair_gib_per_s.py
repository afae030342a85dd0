"""repair_gib_per_s: GiB of segment data restored per second of the
window."""

from harness.readers import rate


def read(run):
    return rate(run, "bytes", float(1 << 30))
