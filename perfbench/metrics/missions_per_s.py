"""missions_per_s: per-miner missions (all their calls) drained per
second of the window."""

from harness.readers import rate


def read(run):
    return rate(run, "missions")
