"""Arithmetic shared by the metric readers (perfbench/metrics/)."""

from __future__ import annotations


def rate(run, key: str, scale: float = 1.0) -> float | None:
    """Work `key` completed per second of the window."""
    done = run.work.get(key)
    if not done:
        return None
    return done / scale / run.window_s


def stage_share(run, names: list[str]) -> float | None:
    """Percent of the window the program's named stage histograms
    observed, or None where none of them observed anything."""
    sums = [run.stages.get(f"{n}_sum") for n in names]
    counts = [run.stages.get(f"{n}_count") or 0 for n in names]
    if not any(counts):
        return None
    return 100.0 * sum(s or 0.0 for s in sums) / run.window_s


def idle_share(run) -> float | None:
    """Percent of the traced window in which no op ran on the device."""
    if run.trace is None or run.trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
