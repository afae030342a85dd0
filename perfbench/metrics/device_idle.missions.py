"""device_idle: percent of the traced window in which no operation ran
on the device (profiler trace)."""

from harness.readers import idle_share


def read(run):
    return idle_share(run)
