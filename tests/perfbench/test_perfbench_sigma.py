"""sigma_decode_ms_per_kproof.verify: σ decompression time per 1000 σ,
read from the program's `sigma_decode` stage and σ lane counter, and
silent on a program without them."""

from __future__ import annotations

import pytest

from perfbench_tiny import ROOT  # noqa: F401  (puts perfbench/ on the path)

from harness import runner, spec  # noqa: E402

METRIC = "sigma_decode_ms_per_kproof.verify"


def run_with(stages: dict) -> runner.Run:
    return runner.Run(setup_s=1.0, window_s=30.0, work={}, units=1,
                      stages=stages, peaks={})


def test_reads_ms_per_thousand_sigma():
    run = run_with({"cess_proof_stage_sigma_decode_seconds_sum": 0.4,
                    "cess_proof_stage_sigma_decode_seconds_count": 8,
                    "cess_proof_sigma_lanes_total": 32768.0,
                    "cess_proof_sigma_native_lanes_total": 32768.0})
    assert spec.reader(METRIC).read(run) == pytest.approx(0.4e6 / 32768)


@pytest.mark.parametrize("stages", [
    {},  # an older program: neither the stage nor the counter
    {"cess_proof_stage_front_end_seconds_sum": 2.0,
     "cess_proof_stage_front_end_seconds_count": 5},
    {"cess_proof_stage_sigma_decode_seconds_sum": 0.0,
     "cess_proof_stage_sigma_decode_seconds_count": 0,
     "cess_proof_sigma_lanes_total": 0.0},  # no verify call in the window
])
def test_silent_without_the_stage(stages):
    assert spec.reader(METRIC).read(run_with(stages)) is None


def test_declared_for_the_bulk_cell_only():
    bench = spec.benchmark(ROOT)
    (m,) = [m for m in bench["per_layer"] if m["name"] == METRIC]
    assert m["workloads"] == ["verify.bulk"]
    assert m["moves"] == "verify_proofs_per_s"
    assert m["layer"] == "verify front end"
