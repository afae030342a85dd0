"""Repair traffic: lost fragments recovered through RSStream.run_batch.

Serves a storage node's restoral calls as `chain/node.py recover_file`
makes them: one `RSStream(code, present=per-segment survivors)
.run_batch(survivors)` per file, from host memory.  The traffic file sets:

  files              files held in host memory; calls cycle over them
  segments_per_call  segments of one file (one call)
  lost_per_fragment  how many segments of a file lose each fragment
                     (data 0, data 1, parity ...): every seed does the
                     same work, with the segments that lose each drawn
                     from the seed
  sample_calls       calls whose output is kept, by a seeded reservoir,
                     and compared once the window has closed

Segment data is random bytes from the seed; parity comes from the
reference encoder.  The kept outputs are compared byte for byte with the
reference decoder's recovery of the same survivors and with the original
data.
"""

from __future__ import annotations

import random

import numpy as np

from harness.checks import Checked, number
from reference import gf256_ref


def setup(ctx):
    return RepairWork(ctx)


class RepairWork:
    def __init__(self, ctx) -> None:
        from cess_tpu.ops import rs

        self.ctx = ctx
        cfg, tr = ctx.config, ctx.traffic
        geo = cfg["geometry"]
        self.k, self.m = geo["data_fragments"], geo["parity_fragments"]
        self.n = geo["fragment_bytes"]
        self.limits = cfg["limits"]
        b = int(tr["segments_per_call"])
        lost = [int(x) for x in tr["lost_per_fragment"]]
        if sum(lost) != b or len(lost) != self.k + self.m:
            raise ValueError("lost_per_fragment must cover every segment "
                             "and name every fragment")
        rng = np.random.default_rng(ctx.seed & (2**64 - 1))
        self.data, self.present, self.survivors = [], [], []
        with ctx.span("make_files"):
            for _ in range(int(tr["files"])):
                data = np.frombuffer(rng.bytes(b * self.k * self.n),
                                     dtype=np.uint8).reshape(b, self.k, self.n)
                which = rng.permutation(np.repeat(np.arange(self.k + self.m),
                                                  lost))
                present = [[f for f in range(self.k + self.m) if f != int(x)]
                           for x in which]
                surv = np.empty((b, self.k, self.n), dtype=np.uint8)
                for s in range(b):
                    parity = gf256_ref.encode(data[s], self.k, self.m)
                    shards = list(data[s]) + list(parity)
                    for r, f in enumerate(present[s][: self.k]):
                        surv[s, r] = shards[f]
                self.data.append(data)
                self.present.append(present)
                self.survivors.append(surv)
        self.code = rs.segment_code()
        self.rs = rs
        self.keep = int(tr["sample_calls"])
        self.pick = random.Random(ctx.seed)
        self.kept: list[tuple[int, np.ndarray]] = []
        self.calls = 0
        with ctx.span("warm_call"):
            self._recover(0)
        ctx.log(f"repair: {len(self.data)} files of {b} segments, RS"
                f"({self.k},{self.m}) path {self.code.path}")

    def _recover(self, call: int) -> np.ndarray:
        f = call % len(self.data)
        stream = self.rs.RSStream(self.code, present=self.present[f])
        with self.ctx.span("run_batch"):
            return stream.run_batch(self.survivors[f])

    def unit(self, i: int) -> dict:
        out = self._recover(i)
        self.calls += 1
        # seeded reservoir of `keep` calls
        if i < self.keep:
            self.kept.append((i, out))
        else:
            j = self.pick.randrange(i + 1)
            if j < self.keep:
                self.kept[j] = (i, out)
        return {"bytes": float(out.nbytes), "segments": out.shape[0],
                "calls": 1}

    def facts(self) -> dict:
        return {"data_fragments": self.k, "fragment_bytes": self.n}

    def check(self) -> Checked:
        off_ref = off_orig = failed = 0
        with self.ctx.span("reference"):
            for i, out in self.kept:
                f = i % len(self.data)
                bad = 0
                for s in range(out.shape[0]):
                    ref = gf256_ref.decode(self.survivors[f][s],
                                           self.present[f][s], self.k, self.m)
                    off_ref += not np.array_equal(out[s], ref)
                    off_orig += not np.array_equal(out[s], self.data[f][s])
                    bad += not np.array_equal(out[s], ref)
                failed += bad > 0
        self.ctx.log(f"repair: compared calls {[i for i, _ in self.kept]}")
        lim = self.limits
        return Checked(
            checks={
                "segments_off_reference": number(off_ref, lim["segments_off_reference"]),
                "segments_off_original": number(off_orig, lim["segments_off_original"]),
            },
            attempted=self.calls, failed=failed)
