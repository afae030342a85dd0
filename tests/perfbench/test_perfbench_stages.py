"""Idle gaps named by program stage (harness/stage_gaps.py) and the
per-layer metrics that read the program's stage spans and padding
counters, on the CPU in seconds."""

from __future__ import annotations

import argparse
import json
import time

import pytest

from perfbench_tiny import ROOT, tiny

from harness import platform, runner, spec, stage_gaps, trace  # noqa: E402

FIXTURES = ROOT / "tests" / "perfbench" / "fixtures"

NEW_METRICS = ("front_end_share.verify", "prep_ms_per_kproof.verify",
               "launch_share.verify", "lane_use.missions",
               "slab_use.repair", "host_copy_share.repair")


def by_hand():
    """A 10 us window on the caller's line; device busy 1500..4500 and
    8000..9000 ns, so idle 1000..1500, 4500..8000 and 9000..11000.  The
    caller is in `bench.run_batch` until 5000 with `cess.rs.pack` at
    4600..7000 and `cess.rs.gather` inside it at 4700..6000; a worker
    line's `cess.proof.prep` covers 8500..10500."""
    return {"planes": [
        {"name": "/host:CPU", "lines": [
            {"name": "python3", "events": [
                ["bench.window", 1000.0, 10000.0],
                ["bench.run_batch", 1000.0, 4000.0],
                ["cess.rs.pack", 4600.0, 2400.0],
                ["cess.rs.gather", 4700.0, 1300.0]]},
            {"name": "fused-prep_0", "events": [
                ["cess.proof.prep", 8500.0, 2000.0]]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ["jit__matmul_gf_bitplane(7)", 1500.0, 3000.0],
                ["jit_other(3)", 8000.0, 1000.0]]},
            {"name": "XLA Ops", "events": [
                ["fusion.1", 1500.0, 3000.0],
                ["fusion.1", 8000.0, 1000.0]]}]}]}


def test_stage_spans_name_gaps_on_the_window_line():
    planes = trace.from_plain(by_hand())
    gaps = {round(d * 1e9): n for n, d in stage_gaps.idle_gaps(planes)}
    # 4500..8000: middle 6250, inside cess.rs.pack but past gather;
    # 9000..11000: middle 10000 is under the worker's prep, which does
    # not name it; 1000..1500: inside bench.run_batch
    assert gaps == {3500: "cess.rs.pack", 2000: "bench.between_calls",
                    500: "bench.run_batch"}
    # the benchmark's own reduction ignores the program's spans
    s = trace.summarize(trace.from_plain(by_hand()))
    assert {round(d * 1e9): n for n, d in s["idle_gaps"]} == {
        3500: "bench.between_calls", 2000: "bench.between_calls",
        500: "bench.run_batch"}
    assert s["window_s"] == pytest.approx(10e-6)
    assert s["busy_s"] == pytest.approx(4e-6)


def test_innermost_stage_names_the_gap():
    t = by_hand()
    t["planes"][0]["lines"][0]["events"][3] = ["cess.rs.gather", 4700.0,
                                              2000.0]  # spans 6250
    gaps = dict((round(d * 1e9), n) for n, d in
                stage_gaps.idle_gaps(trace.from_plain(t)))
    assert gaps[3500] == "cess.rs.gather"


def test_coincidence_of_stages_with_device_and_worker():
    c = stage_gaps.coincidence(trace.from_plain(by_hand()))
    pack = c["cess.rs.pack"]
    assert pack["seconds"] == pytest.approx(2400e-9)
    assert pack["device_busy_s"] == 0.0
    assert pack["other_lines"] == {"cess.proof.prep": 0.0}
    worker = c["other_line:cess.proof.prep"]
    assert worker["seconds"] == pytest.approx(2000e-9)  # clipped at 11000
    assert worker["device_busy_s"] == pytest.approx(500e-9)  # 8500..9000


def test_recorded_trace_gaps_agree_with_summarize():
    recorded = json.loads((FIXTURES / "trace_repair.json").read_text())
    ours = stage_gaps.idle_gaps(trace.from_plain(recorded))
    theirs = trace.summarize(trace.from_plain(recorded))["idle_gaps"]
    assert [d for _, d in ours] == pytest.approx([d for _, d in theirs])


def test_new_metrics_are_silent_without_the_program_counters():
    """A program without the stage marks (an older checkout) reads
    nothing and raises nothing."""
    run = runner.Run(setup_s=1.0, window_s=30.0, work={}, units=1,
                     stages={"cess_proof_stage_host_prep_seconds_sum": 3.0,
                             "cess_proof_stage_host_prep_seconds_count": 4},
                     peaks={})
    for name in NEW_METRICS:
        assert spec.reader(name).read(run) is None, name


def test_verify_metrics_arithmetic():
    run = runner.Run(setup_s=1.0, window_s=20.0, work={}, units=1, peaks={},
                     stages={"cess_proof_stage_front_end_seconds_sum": 2.0,
                             "cess_proof_stage_front_end_seconds_count": 5,
                             "cess_proof_stage_launch_seconds_sum": 8.0,
                             "cess_proof_stage_launch_seconds_count": 25,
                             "cess_proof_stage_prep_seconds_sum": 4.0,
                             "cess_proof_stage_prep_seconds_count": 20,
                             "cess_proof_chunk_proofs_total": 8000.0,
                             "cess_proof_chunk_slots_total": 20480.0})
    read = {n: spec.reader(n).read(run) for n in NEW_METRICS[:4]}
    assert read == pytest.approx({
        "front_end_share.verify": 10.0, "launch_share.verify": 40.0,
        "prep_ms_per_kproof.verify": 500.0,
        "lane_use.missions": 100.0 * 8000 / 20480})


def test_tiny_restoral_reads_slab_use(capsys):
    """6 segments a call in 3 loss groups of 2, each padded to a slab of
    32: 6 of 96 slots live."""
    bench = spec.benchmark(ROOT)
    cell = spec.cell(bench, "repair.restoral")
    cfg, tr = tiny(spec.config(bench, cell["config"]),
                   spec.traffic(cell["traffic"]))
    ctx = runner.Context(root=ROOT, seed=2**31 + 77, cell=cell, config=cfg,
                         traffic=tr)
    wanted = [m for m in spec.metrics_for(bench, cell["name"], "per_layer")
              if m["name"] in NEW_METRICS]
    readers = {m["name"]: spec.reader(m["name"]) for m in wanted}
    import jax

    capsys.readouterr()
    rc = runner.finish(
        argparse.Namespace(seconds=0.5, trace=0), ctx,
        spec.driver(tr["driver"]), platform.CompileClock(lambda m: None),
        jax.devices(), {"platform": "cpu", "kind": "cpu", "count": 1},
        spec.peaks("TPU v5 lite"), wanted, readers, time.perf_counter())
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"]
    metrics = line["metrics"]
    assert set(metrics) == {"slab_use.repair", "host_copy_share.repair"}
    assert metrics["slab_use.repair"]["value"] == 6.25
    assert 0.0 < metrics["host_copy_share.repair"]["value"] < 100.0
