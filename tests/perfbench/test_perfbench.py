"""The benchmark's own checks, on the CPU in seconds: BENCHMARK.json's
names and references, the traffic generators, the trace reduction on a
recorded chip trace, the kernel cost function, the refusal of a host
with no TPU, and a cell added as files alone."""

from __future__ import annotations

import contextlib
import json
import random
import re
import shutil
import subprocess
import sys

import pytest

from perfbench_tiny import BENCH, ROOT, tiny

from harness import kernels, readers, spec, trace  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
FIXTURES = ROOT / "tests" / "perfbench" / "fixtures"


@pytest.fixture(scope="module")
def bench():
    return spec.benchmark(ROOT)


def test_keys_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    assert len(bench["command"]) <= 32
    for p in bench["paths"]:
        assert (ROOT / p).is_dir() and re.match(r"^[A-Za-z0-9_./-]{1,200}$", p)
    for w in bench["command"]:
        assert not w.startswith("/") and ".." not in w
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(
        1, len(bench["workloads"]) // 2)


def test_names_and_units(bench):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in bench[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append((group in ("end_to_end", "per_layer"), e["name"]))
            if "unit" in e:
                assert UNIT.match(e["unit"]), e["unit"]
                assert e["better"] in ("lower", "higher")
            for k in e.get("reduced", []):
                assert NAME.match(k)
            for text in (e.get("why"), e.get("layer"), e.get("source")):
                if text is not None:
                    assert 1 <= len(text) <= 200 and "\n" not in text
                    assert "\t" not in text
    assert len(names) == len(set(names))
    metric_names = [n for is_metric, n in names if is_metric]
    assert len(metric_names) == len(set(metric_names))
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert any(m["name"] == "setup_s" for m in bench["end_to_end"])


def test_cells_find_their_files(bench):
    for w in bench["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        cfg = spec.config(bench, w["config"])
        assert cfg["name"] == w["config"]
        tr = spec.traffic(w["traffic"])
        assert (BENCH / "drivers" / f"{tr['driver']}.py").exists()
        assert w["chips"] in (1, 4)
    files = [c["file"] for c in bench["configs"]]
    assert len(files) == len(set(files))
    for c in bench["configs"]:
        assert c["file"].startswith("perfbench/")
        assert any(w["config"] == c["name"] for w in bench["workloads"])


def test_every_metric_has_a_reader(bench):
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert hasattr(spec.reader(m["name"]), "read")


def test_each_cell_reports_setup_another_metric_and_a_layer(bench):
    for w in bench["workloads"]:
        e2e = {m["name"] for m in spec.metrics_for(bench, w["name"],
                                                   "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert spec.metrics_for(bench, w["name"], "per_layer")


def test_moves_is_reported_by_each_cell_of_the_metric(bench):
    e2e = {m["name"] for m in bench["end_to_end"]}
    layers = {}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        for cell in m.get("workloads", [w["name"] for w in
                                        bench["workloads"]]):
            reported = {x["name"] for x in spec.metrics_for(
                bench, cell, "end_to_end")}
            assert m["moves"] in reported, (m["name"], cell)
        layers.setdefault(m["layer"], set()).add(m["name"])
    perf = (ROOT / "PERF.md").read_text()
    for layer in layers:
        assert layer in perf, f"layer {layer!r} is not in PERF.md"


@pytest.mark.parametrize("traffic", ["bulk", "missions"])
def test_verify_batch_plan_is_seeded(traffic):
    drv = spec.driver("verify")
    tr = spec.traffic(traffic)

    def plan(seed):
        work = drv.VerifyWork.__new__(drv.VerifyWork)
        work.rnd = random.Random(seed)
        work.kinds = [drv.batch_sizes(c["batch"]) for c in tr["calls"]]
        work.perms = []
        return [work._size(u, k) for u in range(16)
                for k in range(len(work.kinds))]

    assert plan(2**31 + 5) == plan(2**31 + 5)
    a, b = plan(2**31 + 5), plan(2**31 + 6)
    assert sorted(a) == sorted(b)  # the same work on every seed
    if len(set(a)) > 1:
        assert a != b  # in another order


def test_log_uniform_strata():
    drv = spec.driver("verify")
    sizes = drv.batch_sizes({"log_uniform": [16, 1024], "strata": 8})
    assert len(sizes) == 8 and sizes == sorted(sizes)
    assert 16 < sizes[0] < sizes[-1] < 1024
    assert drv.batch_sizes({"fixed": 4096}) == [4096]


def test_forged_positions_fall_in_each_part():
    drv = spec.driver("verify")
    pos = drv.forged_positions(random.Random(1), 4096, 2)
    assert 0 <= pos[0] < 2048 <= pos[1] < 4096


def test_repair_files_are_seeded():
    drv = spec.driver("repair")
    bench = spec.benchmark(ROOT)
    cfg, tr = tiny(spec.config(bench, "storage_repair"),
                   spec.traffic("restoral"))

    class Ctx:
        config, traffic = cfg, tr

        def __init__(self, seed):
            self.seed = seed

        @staticmethod
        def log(msg):
            pass

        @staticmethod
        def span(name):
            return contextlib.nullcontext()

    a = drv.RepairWork(Ctx(2**31 + 3))
    b = drv.RepairWork(Ctx(2**31 + 3))
    c = drv.RepairWork(Ctx(2**31 + 4))
    assert a.present == b.present
    assert all((x == y).all() for x, y in zip(a.survivors, b.survivors))
    assert a.present != c.present
    assert not (a.data[0] == c.data[0]).all()
    lost = [sum(f not in p for p in a.present[0]) for f in range(3)]
    assert lost == [2, 2, 2]  # the same loss mix on every seed


def test_bitplane_cost_at_a_known_shape():
    ops, nbytes = kernels.bitplane_cost(64, 2, 8 << 20)
    assert ops == 2 * 64 * 16 * 16 * (8 << 20)  # 2.75e11
    assert nbytes == 2 * 64 * 2 * (8 << 20)  # 2 GiB: read and written
    peaks = spec.peaks("TPU v5 lite")
    floor, bound = kernels.roofline_floor(ops, nbytes, peaks,
                                          "int8_ops_per_s")
    assert bound == "bytes"
    assert floor == pytest.approx(nbytes / 819e9)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(spec.SpecError):
        spec.peaks("TPU v9 imaginary")


def test_trace_reduction_on_a_recorded_trace():
    recorded = json.loads((FIXTURES / "trace_repair.json").read_text())
    expect = json.loads((FIXTURES / "trace_repair_expect.json").read_text())
    s = trace.summarize(trace.from_plain(recorded))
    assert s["window_s"] == pytest.approx(expect["window_s"], rel=1e-9)
    assert s["busy_s"] == pytest.approx(expect["busy_s"], rel=1e-9)
    secs, runs = trace.module_seconds(s, "_matmul_gf_bitplane")
    assert runs == expect["bitplane_runs"]
    assert secs == pytest.approx(expect["bitplane_seconds"], rel=1e-9)
    assert s["idle_gaps"][0][1] == pytest.approx(expect["longest_gap_s"],
                                                 rel=1e-9)


def test_trace_reduction_by_hand():
    # two device ops overlapping, one gap, inside a 10 us window
    t = {"planes": [
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            ["bench.window", 1000.0, 10000.0],
            ["bench.run_batch", 1000.0, 4000.0]]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ["jit__matmul_gf_bitplane(7)", 1500.0, 3000.0],
                ["jit_other(3)", 8000.0, 1000.0]]},
            {"name": "XLA Ops", "events": [
                ["fusion.1", 1500.0, 2000.0], ["fusion.2", 3000.0, 1500.0],
                ["fusion.1", 8000.0, 1000.0]]}]}]}
    s = trace.summarize(trace.from_plain(t))
    assert s["window_s"] == pytest.approx(10e-6)
    assert s["busy_s"] == pytest.approx(4e-6)  # 1500..4500, 8000..9000
    run = type("R", (), {"trace": s})()
    assert readers.idle_share(run) == pytest.approx(60.0)
    assert trace.module_seconds(s, "_matmul_gf_bitplane") == (
        pytest.approx(3e-6), 1)
    gaps = dict((round(d * 1e9), n) for n, d in s["idle_gaps"])
    assert gaps == {3500: "bench.between_calls", 500: "bench.run_batch",
                    2000: "bench.between_calls"}


def test_refuses_a_host_with_no_tpu(tmp_path):
    """perfbench/run.py on this CPU host: exit code other than 0 and no
    result line."""
    done = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "repair.restoral", "--seed", str(2**31 + 9), "--seconds", "1",
         "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "HOME": str(tmp_path), "TMPDIR": str(tmp_path)})
    assert done.returncode != 0
    assert "correct" not in done.stdout
    assert "no TPU" in done.stderr


def test_a_cell_added_as_files_alone(tmp_path):
    """A new configuration, traffic mix and metric, given as new files
    and new entries only, are found by name."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((BENCH / "configs" / "storage_repair.json").read_text())
    cfg["name"] = "storage_repair_small"
    (root / "perfbench" / "configs" / "storage_repair_small.json").write_text(
        json.dumps(cfg))
    (root / "perfbench" / "traffic" / "single_pattern.json").write_text(
        json.dumps({"driver": "repair", "files": 1, "segments_per_call": 64,
                    "lost_per_fragment": [64, 0, 0], "sample_calls": 1}))
    (root / "perfbench" / "metrics" / "segments_per_call.repair.py"
     ).write_text("def read(run):\n    return run.work['segments'] / "
                  "run.units\n")
    bench["configs"].append({"name": "storage_repair_small",
                             "source": "https://github.com/omahs/cess",
                             "file": "perfbench/configs/"
                                     "storage_repair_small.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "repair.single_pattern",
                               "config": "storage_repair_small",
                               "traffic": "single_pattern", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "repair_gib_per_s":
            m["workloads"].append("repair.single_pattern")
    bench["per_layer"].append({"name": "segments_per_call.repair",
                               "unit": "segments", "better": "higher",
                               "source": "program_counter", "layer": "test",
                               "moves": "repair_gib_per_s",
                               "workloads": ["repair.single_pattern"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    b = spec.benchmark(root)
    cell = spec.cell(b, "repair.single_pattern")
    assert spec.config(b, cell["config"], root / "perfbench")["name"] == \
        "storage_repair_small"
    tr = spec.traffic(cell["traffic"], root / "perfbench")
    assert tr["lost_per_fragment"] == [64, 0, 0]
    per_layer = spec.metrics_for(b, cell["name"], "per_layer")
    assert [m["name"] for m in per_layer] == ["segments_per_call.repair"]
    mod = spec.reader("segments_per_call.repair", root / "perfbench")
    run = type("R", (), {"work": {"segments": 128.0}, "units": 2})()
    assert mod.read(run) == 64.0
    e2e = {m["name"] for m in spec.metrics_for(b, cell["name"],
                                               "end_to_end")}
    assert e2e == {"setup_s", "repair_gib_per_s"}
