"""Stage marks of the data planes, on the profiler's clock.

The verify pipelines (proof/xla_backend.py, proof/fused.py) and the RS
stream (ops/rs.py RSStream) time each stage in a `with plane.stage(name)`
block.  One block does two things over one interval:

  * it opens `jax.profiler.TraceAnnotation("cess.<plane>.<stage>")`: a
    host span on the calling thread, on the profiler's clock (the
    mechanism the benchmark's `bench.*` spans use), so a device trace
    shows which program stage the host was in while the chip idled;
  * it observes the always-on histogram of that stage in the plane's
    process-wide registry (merged into node `system_metrics`).

A block that raises observes nothing: a batch the front end rejects
records no stage, unless a stage nested in the front end ended before
the rejection (σ decoding, then a μ out of range).  Outside a trace
the span costs one small object; CESS_STAGE_METRICS=0 turns every
block into a shared no-op context (no span, no observation) and every
count into a no-op.
"""

from __future__ import annotations

import contextlib
import os
import threading
import time

from jax.profiler import TraceAnnotation

ENABLED = os.environ.get(
    "CESS_STAGE_METRICS", "1") not in ("0", "false", "off")

BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
           0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 15.0, 60.0)

_OFF = contextlib.nullcontext()


class StagePlane:
    """One data plane's stage histograms (`histogram.format(stage)`,
    one per name in `names`) and unlabelled counters (`counters`: key ->
    (metric name, help)), in a registry of its own created on first use.
    node/metrics is imported lazily: the ops and proof packages must not
    import the node package at load."""

    def __init__(self, plane: str, names: tuple[str, ...], histogram: str,
                 help_: str, counters: dict[str, tuple[str, str]]) -> None:
        self.span_prefix = f"cess.{plane}."
        self.names = names
        self._histogram = histogram
        self._help = help_
        self._counter_specs = counters
        self._lock = threading.Lock()
        self._registry = None
        self._hists: dict = {}
        self._counters: dict = {}

    def registry(self):
        with self._lock:
            if self._registry is None:
                from ..node import metrics as m

                reg = m.Registry()
                for name in self.names:
                    self._hists[name] = m.Histogram(
                        self._histogram.format(name),
                        self._help.format(name),
                        buckets=BUCKETS, registry=reg)
                for key, (metric, help_) in self._counter_specs.items():
                    self._counters[key] = m.Counter(metric, help_, reg)
                self._registry = reg
        return self._registry

    def stage(self, name: str):
        """Context manager: span `cess.<plane>.<name>` and one observation
        of the stage's histogram over the block (none if it raises)."""
        if not ENABLED:
            return _OFF
        return _Stage(self, name)

    def observe(self, name: str, seconds: float) -> None:
        if self._registry is None:
            self.registry()
        self._hists[name].observe(seconds)

    def count(self, **amounts: float) -> None:
        """Add to the plane's counters, by key."""
        if not ENABLED:
            return
        if self._registry is None:
            self.registry()
        for key, amount in amounts.items():
            self._counters[key].inc(amount)


class _Stage:
    __slots__ = ("_plane", "_name", "_span", "_t0")

    def __init__(self, plane: StagePlane, name: str) -> None:
        self._plane = plane
        self._name = name

    def __enter__(self):
        self._span = TraceAnnotation(self._plane.span_prefix + self._name)
        self._span.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        seconds = time.perf_counter() - self._t0
        self._span.__exit__(exc_type, exc, tb)
        if exc_type is None:
            self._plane.observe(self._name, seconds)
        return False
