"""One run of one cell: set-up, the measured window, the check, the
result line.

Window rule: units of work (a call, or a mission of calls) run back to
back; none starts after the deadline; one in flight at the deadline
completes and counts.  A rate is the work of all completed units over
the time from the window's start to the last completion.

A traced run (--trace 1) traces the window's first units, until
TRACE_SECONDS have passed: the device trace of a verify call holds
about a million events per chunk program, and the profiler's buffer
keeps about nine chunk programs' worth.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from dataclasses import dataclass, field

from . import counters, platform, spec, trace

TRACE_SECONDS = 3.0


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@dataclass
class Context:
    """What a driver is given: the cell's files, the seed, and a span
    context for the host side of the trace."""

    root: object
    seed: int
    cell: dict
    config: dict
    traffic: dict
    log: object = log

    @staticmethod
    def span(name: str):
        import jax

        return jax.profiler.TraceAnnotation("bench." + name)


@dataclass
class Run:
    """What a metric reader is given."""

    setup_s: float
    window_s: float
    work: dict[str, float]
    units: int
    stages: dict[str, float]
    peaks: dict
    trace: dict | None = None
    trace_work: dict[str, float] = field(default_factory=dict)
    extra: dict = field(default_factory=dict)


def measure(work, seconds: float, span, trace_seconds=None):
    """(window seconds, summed work counts, units, traced) under the
    window rule.  With `trace_seconds`, the profiler traces the units
    that start in the window's first `trace_seconds`, inside a
    `bench.window` span; `traced` is then (trace directory, the work of
    the traced units)."""
    totals: dict[str, float] = {}
    traced = None
    units = 0
    start = time.perf_counter()
    deadline = start + seconds
    end = start
    with contextlib.ExitStack() as tracing:
        trace_dir = None
        if trace_seconds is not None:
            trace_dir = tracing.enter_context(trace.capture(True))
            tracing.enter_context(span("window"))
        while time.perf_counter() < deadline:
            for k, v in work.unit(units).items():
                totals[k] = totals.get(k, 0.0) + v
            units += 1
            end = time.perf_counter()
            if (trace_dir is not None and traced is None
                    and end - start >= trace_seconds):
                tracing.close()
                traced = (trace_dir, dict(totals))
        if trace_dir is not None and traced is None:
            tracing.close()
            traced = (trace_dir, dict(totals))
    return end - start, totals, units, traced


def parse(argv):
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv, t_process: float) -> int:
    args = parse(argv)
    bench = spec.benchmark()
    cell = spec.cell(bench, args.workload)
    ctx = Context(root=spec.ROOT, seed=args.seed, cell=cell,
                  config=spec.config(bench, cell["config"]),
                  traffic=spec.traffic(cell["traffic"]))
    kind = "per_layer" if args.trace else "end_to_end"
    wanted = spec.metrics_for(bench, cell["name"], kind)
    readers = {m["name"]: spec.reader(m["name"]) for m in wanted}

    cache = platform.compile_cache(spec.ROOT)
    platform.native_core(spec.ROOT)
    try:
        devices = platform.chips(cell["chips"])
    except platform.NoChip as e:
        log(f"refused: {e}")
        return 2
    device = platform.record(devices)
    peaks = spec.peaks(device["kind"])
    log(f"device: {json.dumps(device)} compile_cache={cache}")
    clock = platform.CompileClock(log)
    drv = spec.driver(ctx.traffic["driver"])
    return finish(args, ctx, drv, clock, devices, device, peaks, wanted,
                  readers, t_process)


def finish(args, ctx, drv, clock, devices, device, peaks, wanted, readers,
           t_process) -> int:
    """Set-up, window, check and result, given the footing (split from
    `main` so a test can drive a run without a chip)."""
    work = drv.setup(ctx)
    setup_s = time.perf_counter() - t_process
    log(f"setup: {setup_s:.3f}s, compile events {clock.count} "
        f"({clock.seconds:.1f}s)")

    before, events0 = counters.snapshot(), clock.count
    window_s, totals, units, traced = measure(
        work, args.seconds, ctx.span,
        TRACE_SECONDS if args.trace else None)
    stages = counters.delta(before, counters.snapshot())
    log(f"window: {window_s:.3f}s, {units} units, work {json.dumps(totals)}"
        f", compile events inside the window: {clock.count - events0}")
    summary, trace_work = None, {}
    if traced is not None:
        summary = trace.summarize(trace.load(traced[0]).planes)
        trace_work = traced[1]
        log(f"trace: {summary['window_s']:.3f}s traced, work "
            f"{json.dumps(trace_work)}; Pallas kernels that ran: "
            f"{summary['kernels']}")
    memory = platform.memory_peak(devices)

    result = work.check()
    run = Run(setup_s=setup_s, window_s=window_s, work=totals, units=units,
              stages=stages, peaks=peaks, trace=summary,
              trace_work=trace_work, extra=work.facts())
    metrics = {}
    for m in wanted:
        value = readers[m["name"]].read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    if summary is not None:
        device = dict(device, busy_s=summary["busy_s"],
                      window_s=summary["window_s"])
    device["memory_peak_bytes"] = memory
    correct = all(c["value"] <= c["limit"] for c in result.checks.values())
    line = {"correct": correct, "attempted": result.attempted,
            "failed": result.failed, "metrics": metrics, "device": device}
    if summary is not None:
        line["breakdown"] = {"device_ops": summary["device_ops"],
                             "idle_gaps": summary["idle_gaps"]}
    line["checks"] = result.checks
    for name, c in result.checks.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(line), flush=True)
    return 0
