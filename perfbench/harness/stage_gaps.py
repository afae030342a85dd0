"""Idle gaps of a traced window named by program stage.

`trace.summarize` names each idle gap by the innermost benchmark span
(`bench.*`) the host was in.  The program's stage marks are host spans
too (`cess.proof.<stage>`, `cess.rs.<stage>`, opened by the program's
utils/stages.py on the profiler's clock), so here a gap is named by the
innermost `bench.*` or `cess.*` span, at the gap's middle, on the host
line (thread) that holds `bench.window`.  Spans of other lines, such as
the fused pipeline's prefetch worker, never name a gap; `coincidence`
says what they and the device did during each stage of the window's
line.  With no such span a gap stays `bench.between_calls`.

Reads the same planes as `summarize` and changes none of its numbers.
"""

from __future__ import annotations

from .trace import _DEVICE_PLANE, WINDOW_SPAN, _union

_PREFIXES = ("bench.", "cess.")


def _spans(planes):
    """(host spans as (line, name, start, end), device busy unions per
    chip), in one pass over the planes."""
    spans, busy = [], []
    for pi, plane in enumerate(planes):
        if plane.name.startswith("/host:"):
            for li, line in enumerate(plane.lines):
                for e in line.events:
                    if e.name.startswith(_PREFIXES):
                        spans.append(((pi, li), e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
        elif _DEVICE_PLANE.match(plane.name):
            runs = []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    runs += [(e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events]
            busy.append(_union(runs))
    return spans, busy


def _window(spans):
    for line, name, s, e in spans:
        if name == WINDOW_SPAN:
            return line, s, e
    raise RuntimeError(f"the trace holds no {WINDOW_SPAN!r} span")


def _clip(intervals, lo, hi) -> list[list[float]]:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if min(e, hi) > max(s, lo)]


def _overlap(a, b) -> float:
    """Length of the intersection of two sorted, disjoint interval
    lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            total += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_gaps(planes, top: int = 10) -> list[list]:
    """The `top` longest stretches of the window in which no program ran
    on a chip, as [name, seconds], longest first."""
    spans, busy = _spans(list(planes))
    line, lo, hi = _window(spans)
    own = [(n, s, e) for ln, n, s, e in spans
           if ln == line and n != WINDOW_SPAN]
    gaps = []
    for runs in busy:
        edges = [lo] + [x for iv in _clip(runs, lo, hi) for x in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]

    def host_at(t: float) -> str:
        inner = [(e - s, n) for n, s, e in own if s <= t <= e]
        return min(inner)[1] if inner else "bench.between_calls"

    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return [[host_at((s + e) / 2), (e - s) / 1e9] for s, e in longest]


def coincidence(planes) -> dict:
    """For each program stage on the window's line, inside the window:
    {"seconds", "device_busy_s" (the first chip busy within it),
    "other_lines": {stage of another line: seconds it overlapped}}; and
    for each program stage of another line, {"seconds",
    "device_busy_s"}.  Stages of one name are merged first, so nested
    or repeated marks count once."""
    spans, busy = _spans(list(planes))
    line, lo, hi = _window(spans)
    device = _clip(busy[0], lo, hi) if busy else []
    mine: dict[str, list] = {}
    others: dict[str, list] = {}
    for ln, n, s, e in spans:
        if n.startswith("cess."):
            (mine if ln == line else others).setdefault(n, []).append((s, e))
    mine = {n: _union(_clip(v, lo, hi)) for n, v in mine.items()}
    others = {n: _union(_clip(v, lo, hi)) for n, v in others.items()}
    out = {}
    for n, ivs in sorted(mine.items()):
        out[n] = {"seconds": sum(e - s for s, e in ivs) / 1e9,
                  "device_busy_s": _overlap(ivs, device) / 1e9,
                  "other_lines": {o: _overlap(ivs, oivs) / 1e9
                                  for o, oivs in sorted(others.items())}}
    for n, ivs in sorted(others.items()):
        out["other_line:" + n] = {
            "seconds": sum(e - s for s, e in ivs) / 1e9,
            "device_busy_s": _overlap(ivs, device) / 1e9}
    return out
