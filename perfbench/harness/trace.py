"""Profiler trace of the measured window, reduced to plain numbers.

`capture` runs the JAX profiler around the window; `load` reads its
.xplane.pb (planes -> lines -> events with name, start_ns, duration_ns);
`summarize` reduces the planes in one pass, keeping no per-op events
(a verify window holds millions of them):

  window_s      the host span `bench.window`, on the trace's clock
  busy_s        union of the device's program (XLA module) executions
                inside the window, averaged over the chips
  modules       device seconds and executions per XLA module (program)
  ops           device seconds per op, named by its HLO instruction
                without the numeric suffix (`_glv_fold_pallas`, `fusion`)
  kernels       the ops that are Pallas kernels (tpu_custom_call)
  idle_gaps     the longest stretches of the window in which no program
                ran, each named by the innermost benchmark span
                (`bench.*`) the host was in at the gap's middle
"""

from __future__ import annotations

import contextlib
import glob
import os
import re
import shutil
import tempfile
from types import SimpleNamespace

WINDOW_SPAN = "bench.window"
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_MODULE_SUFFIX = re.compile(r"\(\d+\)$")
_OP_SUFFIX = re.compile(r"\.\d+$")


@contextlib.contextmanager
def capture(enabled: bool):
    """Yields the trace directory (None when off); the trace covers the
    body of the `with`."""
    if not enabled:
        yield None
        return
    import jax

    path = tempfile.mkdtemp(prefix="perfbench-trace-")
    jax.profiler.start_trace(path)
    try:
        yield path
    finally:
        jax.profiler.stop_trace()


def load(path: str):
    """The profile in the one .xplane.pb under `path` (the directory is
    removed afterwards); its `planes` go to `summarize`."""
    import jax

    try:
        files = glob.glob(os.path.join(path, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        if len(files) != 1:
            raise RuntimeError(f"expected one trace file, found {files}")
        return jax.profiler.ProfileData.from_file(files[0])
    finally:
        shutil.rmtree(path, ignore_errors=True)


def from_plain(trace: dict) -> list:
    """Planes from the plain form {"planes": [{"name", "lines": [{"name",
    "events": [[name, start_ns, duration_ns], ...]}]}]} of a fixture."""
    return [SimpleNamespace(name=p["name"], lines=[
        SimpleNamespace(name=line["name"], events=[
            SimpleNamespace(name=n, start_ns=s, duration_ns=d)
            for n, s, d in line["events"]])
        for line in p["lines"]]) for p in trace["planes"]]


def op_name(hlo: str) -> str:
    """`%_glv_fold_pallas.1 = (s32[...]) custom-call(...)` -> the
    instruction's name without its numeric suffix."""
    head = hlo.split(" = ", 1)[0].lstrip("%")
    return _OP_SUFFIX.sub("", head)


def _union(intervals) -> list[list[float]]:
    merged: list[list[float]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def summarize(planes, top: int = 10) -> dict:
    planes = list(planes)  # a profile yields its planes once
    host_spans = []
    for plane in planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        host_spans.append((e.name, e.start_ns,
                                           e.start_ns + e.duration_ns))
    windows = [(s, e) for n, s, e in host_spans if n == WINDOW_SPAN]
    if not windows:
        raise RuntimeError(f"the trace holds no {WINDOW_SPAN!r} span")
    lo, hi = windows[0]
    devices = [p for p in planes if _DEVICE_PLANE.match(p.name)]
    if not devices:
        raise RuntimeError("the trace holds no TPU device plane")

    busy_total = 0.0
    modules: dict[str, list[float]] = {}
    ops: dict[str, float] = {}
    kernels: set[str] = set()
    names: dict[str, tuple[str, bool]] = {}
    gaps: list[tuple[float, float]] = []
    for plane in devices:
        runs = []
        for line in plane.lines:
            if line.name == "XLA Modules":
                for e in line.events:
                    s, t = max(e.start_ns, lo), min(e.start_ns + e.duration_ns, hi)
                    if t <= s:
                        continue
                    runs.append((s, t))
                    acc = modules.setdefault(_MODULE_SUFFIX.sub("", e.name),
                                             [0.0, 0])
                    acc[0] += (t - s) / 1e9
                    acc[1] += 1
            elif line.name == "XLA Ops":
                for e in line.events:
                    s, t = max(e.start_ns, lo), min(e.start_ns + e.duration_ns, hi)
                    if t <= s:
                        continue
                    full = e.name
                    known = names.get(full)
                    if known is None:
                        known = names[full] = (op_name(full),
                                               "tpu_custom_call" in full)
                    ops[known[0]] = ops.get(known[0], 0.0) + (t - s) / 1e9
                    if known[1]:
                        kernels.add(known[0])
        busy = _union(runs)
        busy_total += sum(t - s for s, t in busy)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]

    def host_at(t: float) -> str:
        inner = [(e - s, n) for n, s, e in host_spans
                 if s <= t <= e and n != WINDOW_SPAN]
        return min(inner)[1] if inner else "bench.between_calls"

    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_total / len(devices) / 1e9,
        "chips": len(devices),
        "modules": {k: {"seconds": v[0], "runs": v[1]}
                    for k, v in modules.items()},
        "ops": ops,
        "kernels": sorted(kernels),
        "device_ops": sorted(([k, v] for k, v in ops.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": [[host_at((s + e) / 2), (e - s) / 1e9]
                      for s, e in longest],
    }


def module_seconds(summary: dict, program: str) -> tuple[float, int]:
    """(device seconds, executions) of the XLA modules whose name holds
    `program` (a jitted function's name)."""
    secs, runs = 0.0, 0
    for name, v in summary["modules"].items():
        if program in name:
            secs += v["seconds"]
            runs += v["runs"]
    return secs, runs
