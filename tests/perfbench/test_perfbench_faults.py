"""The check that decides `correct`, against planted faults.

Each test drives the rest of a benchmark run (runner.finish: set-up, the
window, the check, the result line) at tiny sizes on the CPU, with the
look for a chip skipped and the timed path broken underneath, and sees
`correct` come out false; an unbroken run comes out true.  The controls
(a verifier that accepts every batch; XOR parity in place of the Cauchy
row) are the ones also run on the chip at the cells' own sizes
(tests/perfbench/control.py).
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np
import pytest

from perfbench_tiny import ROOT, tiny

from harness import platform, runner, spec  # noqa: E402


def run_cell(cell_name: str, capsys, seed: int = 2**31 + 101) -> dict:
    bench = spec.benchmark(ROOT)
    cell = spec.cell(bench, cell_name)
    cfg, tr = tiny(spec.config(bench, cell["config"]),
                   spec.traffic(cell["traffic"]))
    ctx = runner.Context(root=ROOT, seed=seed, cell=cell, config=cfg,
                         traffic=tr)
    wanted = spec.metrics_for(bench, cell_name, "end_to_end")
    readers = {m["name"]: spec.reader(m["name"]) for m in wanted}
    import jax

    capsys.readouterr()
    rc = runner.finish(
        argparse.Namespace(seconds=0.5, trace=0), ctx,
        spec.driver(tr["driver"]), platform.CompileClock(lambda m: None),
        jax.devices(), {"platform": "cpu", "kind": "cpu", "count": 1},
        spec.peaks("TPU v5 lite"), wanted, readers, time.perf_counter())
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


# ---------------------------------------------------------------- verify


def accept_every_batch(monkeypatch):
    """Control: the pairing check skipped, every batch accepted."""
    from cess_tpu.ops import bls12_381

    monkeypatch.setattr(bls12_381, "pairing_check", lambda pairs: True)


def flip_one_verdict(monkeypatch):
    """A verdict altered where the bisection produces it."""
    from cess_tpu.proof.backend import ProofBackend

    orig = ProofBackend._verdicts_by_bisection

    def flipped(self, *args, **kwargs):
        out = orig(self, *args, **kwargs)
        out[len(out) // 2] = not out[len(out) // 2]
        return out

    monkeypatch.setattr(ProofBackend, "_verdicts_by_bisection", flipped)


def check_half_the_batch(monkeypatch):
    """The combined check over the first half of the batch only."""
    from cess_tpu.proof.xla_backend import XlaBackend

    orig = XlaBackend._combined_check

    def half(self, pk, items, seed, params):
        return orig(self, pk, items[: max(1, len(items) // 2)], seed, params)

    monkeypatch.setattr(XlaBackend, "_combined_check", half)


def test_verify_sound_run_is_correct(capsys):
    line = run_cell("verify.missions", capsys)
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] >= 2
    assert list(line)[-1] == "checks"


@pytest.mark.parametrize("fault", [accept_every_batch, flip_one_verdict,
                                   check_half_the_batch])
def test_verify_fault_is_not_correct(fault, monkeypatch, capsys):
    fault(monkeypatch)
    line = run_cell("verify.bulk", capsys)
    assert not line["correct"], line["checks"]


# ---------------------------------------------------------------- repair


def xor_parity(monkeypatch):
    """Control: recovery as if the parity row were all ones (XOR)."""
    from cess_tpu.ops import gf256, rs

    def xor_inverse(k, m, present):
        gen = np.concatenate([np.eye(k, dtype=np.uint8),
                              np.ones((m, k), dtype=np.uint8)])
        return gf256.mat_inv(gen[np.asarray(present)])

    monkeypatch.setattr(rs, "_inv_cached", xor_inverse)


def alter_one_byte(monkeypatch):
    """One recovered byte altered where the stream produces it."""
    from cess_tpu.ops import rs

    orig = rs.RSStream.run_batch

    def altered(self, batch):
        out = orig(self, batch)
        out[0, 0, 0] ^= 1
        return out

    monkeypatch.setattr(rs.RSStream, "run_batch", altered)


def recover_half_the_segments(monkeypatch):
    """Each group's second half of segments left out of the recovery."""
    from cess_tpu.ops import rs

    orig = rs.RSStream._stream_slabs

    def half(self, mat, batch, out, idx):
        out[idx] = 0
        return orig(self, mat, batch, out, idx[: max(1, len(idx) // 2)])

    monkeypatch.setattr(rs.RSStream, "_stream_slabs", half)


def return_survivors(monkeypatch):
    """The survivors handed back unchanged, as if recovered."""
    from cess_tpu.ops import rs

    monkeypatch.setattr(rs.RSStream, "run_batch",
                        lambda self, batch: np.array(batch))


def test_repair_sound_run_is_correct(capsys):
    line = run_cell("repair.restoral", capsys)
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] >= 2


@pytest.mark.parametrize("fault", [xor_parity, alter_one_byte,
                                   recover_half_the_segments,
                                   return_survivors])
def test_repair_fault_is_not_correct(fault, monkeypatch, capsys):
    fault(monkeypatch)
    line = run_cell("repair.restoral", capsys)
    assert not line["correct"], line["checks"]
