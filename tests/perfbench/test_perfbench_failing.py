"""The `verify.failing` cell on the CPU in seconds: its files are found
by name, a sound tiny run is `correct` and reads the bisection tree's
metrics, and the controls read not correct from the window alone."""

from __future__ import annotations

import argparse
import json
import math
import time

import pytest

from perfbench_tiny import ROOT, tiny

from harness import platform, runner, spec  # noqa: E402
from test_perfbench_faults import accept_every_batch, flip_one_verdict

CELL = "verify.failing"
NEW_METRICS = ("leaf_share.failing", "bisect_share.failing",
               "bisect_checks_per_mission.failing")


def tiny_failing(config: dict, traffic: dict) -> tuple[dict, dict]:
    """`tiny` shrinks traffic served by `verify`: shrink this cell's as
    such, then let `verify_failing` serve it again."""
    config, traffic = tiny(config, dict(traffic, driver="verify"))
    traffic["driver"] = "verify_failing"
    return config, traffic


def run_cell(capsys, control=None, seed: int = 2**31 + 211):
    """A tiny run: (result line, standard error, batch size).  With a
    `control`, the program is broken for the window and whole again for
    the check, and the probe carries no forgery."""
    bench = spec.benchmark(ROOT)
    cell = spec.cell(bench, CELL)
    cfg, tr = tiny_failing(spec.config(bench, cell["config"]),
                           spec.traffic(cell["traffic"]))
    ctx = runner.Context(root=ROOT, seed=seed, cell=cell, config=cfg,
                         traffic=tr)
    wanted = (spec.metrics_for(bench, CELL, "end_to_end")
              + spec.metrics_for(bench, CELL, "per_layer"))
    readers = {m["name"]: spec.reader(m["name"]) for m in wanted}
    drv = spec.driver(tr["driver"])
    broken = pytest.MonkeyPatch()
    if control is not None:
        tr["probe"]["forged"] = 0
        control(broken)
        check = drv.FailingWork.check

        def window_only(self):
            broken.undo()
            return check(self)

        drv.FailingWork.check = window_only
    import jax

    capsys.readouterr()
    try:
        rc = runner.finish(
            argparse.Namespace(seconds=0.5, trace=0), ctx, drv,
            platform.CompileClock(lambda m: None), jax.devices(),
            {"platform": "cpu", "kind": "cpu", "count": 1},
            spec.peaks("TPU v5 lite"), wanted, readers, time.perf_counter())
    finally:
        broken.undo()
    assert rc == 0
    out = capsys.readouterr()
    return (json.loads(out.out.strip().splitlines()[-1]), out.err,
            tr["calls"][0]["batch"]["fixed"])


def test_cell_finds_its_files():
    bench = spec.benchmark(ROOT)
    cell = spec.cell(bench, CELL)
    assert cell["chips"] == 1
    cfg = spec.config(bench, cell["config"])
    base = spec.config(bench, "tee_verifier")
    assert cfg["name"] == "tee_verifier_failing"
    for key in ("geometry", "published", "reduced", "guarantees",
                "limits"):
        assert cfg[key] == base[key], key
    tr = spec.traffic(cell["traffic"])
    assert tr["driver"] == "verify_failing"
    assert [c["forged"] for c in tr["calls"]] == [1]
    assert hasattr(spec.driver(tr["driver"]), "setup")
    e2e = {m["name"] for m in spec.metrics_for(bench, CELL, "end_to_end")}
    assert e2e == {"setup_s", "missions_per_s"}
    per_layer = [m["name"] for m in spec.metrics_for(bench, CELL,
                                                     "per_layer")]
    assert per_layer == list(NEW_METRICS)


def test_sound_run_is_correct_and_reads_the_tree(capsys):
    line, err, batch = run_cell(capsys)
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] >= 2
    metrics = line["metrics"]
    assert metrics["missions_per_s"]["value"] > 0
    for name in NEW_METRICS:
        assert metrics[name]["value"] is not None, name
    assert metrics["leaf_share.failing"]["value"] > 0
    assert metrics["bisect_share.failing"]["value"] > 0
    assert metrics["bisect_checks_per_mission.failing"]["value"] == (
        2 * math.log2(batch))
    # the program's combined checks equal a bisecting verifier's on
    # every call, and every forged proof of the window was sampled
    (log,) = [ln for ln in err.splitlines()
              if ln.startswith("verify_failing:")]
    calls = line["attempted"]
    assert f"equal on {calls} of {calls} calls" in log
    assert "forged in the window, all of them" in log


@pytest.mark.parametrize("control", [accept_every_batch, flip_one_verdict])
def test_control_is_not_correct_from_the_window(control, capsys):
    """The control is on for the window only and the probe carries no
    forgery, so only the window's calls can catch it: every one of them
    fails, and the probe does not."""
    line, _, _ = run_cell(capsys, control)
    assert not line["correct"], line["checks"]
    assert line["failed"] == line["attempted"] - 1 >= 1
