"""The process's footing: compile cache, native core, the chip.

The compile cache lives where JAX_COMPILATION_CACHE_DIR points, when it
is set, and otherwise at <checkout>/.jax_cache, a fixed path (the path is
part of the cache key).  The program keeps its cache wherever that
variable points, so it is set here before JAX is imported.
"""

from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path


class NoChip(Exception):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def compile_cache(root: Path) -> str:
    """Point JAX's persistent cache at JAX_COMPILATION_CACHE_DIR, or at
    the checkout where it is unset; call before any jit compiles."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(root / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    import jax

    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # no size bound: eviction keeps access-time files beside the entries,
    # and an entry found without one breaks every later write
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_enable_xla_caches", "all")
    # Pallas kernel bodies carry source locations, which the cache key
    # hashes: without this a checkout at another path never hits.
    jax.config.update("jax_traceback_in_locations_limit", 0)
    return path


def native_core(root: Path) -> None:
    """Build the program's native core when it is missing (the first run
    in a checkout); later runs reuse it."""
    if (root / "cess_tpu" / "_native.so").exists():
        return
    done = subprocess.run(["make", "-C", str(root / "native")],
                          capture_output=True, text=True)
    if done.returncode:
        sys.stderr.write(done.stdout + done.stderr)
        raise RuntimeError("native core did not build")


def chips(n: int) -> list:
    """The devices to run on: JAX's TPUs, at least `n` of them."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's first device is {devices[0]}")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} chips, JAX has {len(devices)}")
    return devices


def record(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def memory_peak(devices) -> int | None:
    """Peak bytes in use on the fullest chip, where the backend says."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class CompileClock:
    """Compilations JAX made (trace, lower, backend compile or cache
    load), from jax.monitoring: their count and the union of their
    spans in seconds."""

    EVENTS = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self, log) -> None:
        import jax

        self.log = log
        self.spans: list[tuple[float, float]] = []
        jax.monitoring.register_event_time_span_listener(self._on)

    def _on(self, event: str, start: float, end: float, *,
            fun_name: str = "?", **_) -> None:
        if event not in self.EVENTS:
            return
        self.spans.append((start, end))
        if end - start > 5.0:
            self.log(f"  {event.rsplit('/', 1)[-1]} {fun_name}: "
                     f"{end - start:.1f}s")

    @property
    def count(self) -> int:
        return len(self.spans)

    @property
    def seconds(self) -> float:
        total, reach = 0.0, float("-inf")
        for start, end in sorted(self.spans):
            if end > reach:
                total += end - max(start, reach)
                reach = end
        return total
