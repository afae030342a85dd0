"""Find a cell's pieces by name.

BENCHMARK.json at the checkout root lists the configurations, cells and
metrics.  Each piece is a file of its own under perfbench/:

  configs/<config>.json   the configuration, as run
  traffic/<traffic>.json  a traffic mix: parameters, and the driver that
                          generates and serves it (drivers/<driver>.py)
  metrics/<metric>.py     one reader per metric, `read(run) -> float|None`
  peaks.json              the chip's published peaks, by device kind

A cell, configuration, traffic mix or metric is added by adding files
and entries; nothing here changes.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


class SpecError(Exception):
    """BENCHMARK.json or a file it names is missing or inconsistent."""


def load_json(path: Path):
    try:
        return json.loads(Path(path).read_text())
    except FileNotFoundError:
        raise SpecError(f"{path} is missing") from None


def benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SpecError(f"no workload {name!r} in BENCHMARK.json")


def config(bench: dict, name: str, bench_dir: Path = BENCH_DIR) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return load_json(bench_dir.parent / c["file"])
    raise SpecError(f"no config {name!r} in BENCHMARK.json")


def traffic(name: str, bench_dir: Path = BENCH_DIR) -> dict:
    return load_json(bench_dir / "traffic" / f"{name}.json")


def metrics_for(bench: dict, cell_name: str, kind: str) -> list[dict]:
    """The `end_to_end` or `per_layer` metrics a cell reports."""
    return [m for m in bench[kind]
            if cell_name in m.get("workloads", [cell_name])]


def _module(path: Path, name: str):
    if not path.exists():
        raise SpecError(f"{path} is missing")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str, bench_dir: Path = BENCH_DIR):
    return _module(bench_dir / "metrics" / f"{metric}.py",
                   "perfbench_metric_" + metric.replace(".", "_"))


def driver(name: str, bench_dir: Path = BENCH_DIR):
    return _module(bench_dir / "drivers" / f"{name}.py",
                   "perfbench_driver_" + name)


def peaks(device_kind: str, bench_dir: Path = BENCH_DIR) -> dict:
    table = load_json(bench_dir / "peaks.json")
    if device_kind not in table["devices"]:
        raise SpecError(f"no peaks for device kind {device_kind!r} in "
                        f"peaks.json")
    return table["devices"][device_kind]
