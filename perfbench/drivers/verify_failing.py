"""Verify traffic with a failing miner: missions whose batch carries a
forged proof, through XlaBackend.verify_batch.

The traffic file and the work are drivers/verify.py's (`VerifyWork`),
with two additions:

  calls[].forged  forged proofs in each call of a unit, one in each equal
                  part of the batch at a seeded position; the kind
                  (another proof's sigma, or one mu altered: both
                  well-formed, as a miner without the data would submit)
                  alternates by unit
  sample          every forged proof of the window's calls joins the
                  reference's sample, beside the window, probe-honest
                  and probe-forged picks

Set-up serves one call of each kind with its forgeries, untimed, after
the honest warm calls, so the window's first mission does not pay the
tree's first re-checks and leaf.  The seconds of each mission are
printed for reading.

A forged proof sends the call down the bisection tree: one combined
check at the root, re-checks below it, and a single-proof verify at each
failing leaf.  The combined checks the program made on each call are
printed beside those a bisecting verifier makes, and the share of the
window's fused chunk slots that held a live proof, for reading, not for
`correct`.
"""

from __future__ import annotations

import random
import time

from harness import counters, spec
from harness.checks import Checked, number
from reference import podr2_ref

verify = spec.driver("verify")


def setup(ctx):
    return FailingWork(ctx)


class FailingWork(verify.VerifyWork):
    def __init__(self, ctx) -> None:
        self.kind_offset = 0
        self.forged = [int(c.get("forged", 0)) for c in ctx.traffic["calls"]]
        self.unit_s: list[float] = []
        super().__init__(ctx)
        # the warm calls are honest: one call of each kind with its
        # forgeries takes the tree's re-checks and leaf out of the window
        with ctx.span("warm_tree"):
            for k, forged in enumerate(self.forged):
                if forged:
                    self._serve(self._draw(max(self.kinds[k]), forged),
                                record=False)
        ctx.log(f"verify_failing warm: untimed calls with forgeries "
                f"{[f for f in self.forged if f]}")

    def _forge(self, item, k: int):
        return super()._forge(item, k + self.kind_offset)

    def _serve(self, call: dict, record: bool = True) -> int:
        c0 = counters.snapshot()
        n = super()._serve(call, record)
        c1 = counters.snapshot()
        call["lanes"] = [c1.get(k, 0.0) - c0.get(k, 0.0) for k in (
            "cess_proof_chunk_proofs_total", "cess_proof_chunk_slots_total")]
        return n

    def unit(self, i: int) -> dict:
        t0 = time.perf_counter()
        self.kind_offset = i
        proofs = 0
        for k, forged in enumerate(self.forged):
            proofs += self._serve(self._draw(self._size(i, k), forged))
        out = {"proofs": proofs, "calls": len(self.kinds)}
        if self.unit_name != "call":
            out[self.unit_name + "s"] = 1
        self.unit_s.append(time.perf_counter() - t0)
        return out

    def check(self) -> Checked:
        tr = self.ctx.traffic
        window = list(self.calls)
        self.kind_offset = 0
        probe = self._draw(int(tr["probe"]["batch"]),
                           int(tr["probe"]["forged"]))

        pick = random.Random(self.ctx.seed ^ 0x5EED)
        sample = []  # (call, position)
        for _ in range(min(int(tr["sample"]["window"]), len(window))):
            c = pick.choice(window)
            sample.append((c, pick.randrange(len(c["items"]))))
        forged = [(c, p) for c in window
                  for p, t in enumerate(c["truth"]) if not t]
        sample += forged
        honest = [p for p, t in enumerate(probe["truth"]) if t]
        sample += [(probe, p) for p in
                   pick.sample(honest, int(tr["sample"]["probe_honest"]))]
        sample += [(probe, p) for p, t in enumerate(probe["truth"]) if not t]
        # the reference verifies the sample in a process of its own while
        # the probe call runs
        reference = verify.Reference(
            self.pk, [c["items"][p] for c, p in sample], self.s,
            b"%d" % self.ctx.seed)
        try:
            with self.ctx.span("probe"):
                self._serve(probe)
        finally:
            ref = reference.wait()
        calls = window + [probe]

        off_truth = sum(v != t for c in calls
                        for v, t in zip(c["verdicts"], c["truth"]))
        off_ref = sum(c["verdicts"][p] != r for (c, p), r in zip(sample, ref))
        bisecting = [podr2_ref.bisection_checks(c["truth"]) for c in calls]
        self.ctx.log(
            f"verify_failing: {len(calls)} calls checked, sample of "
            f"{len(sample)} proofs ({len(forged)} forged in the window, all "
            f"of them), reference verdicts {sum(ref)} true / {len(ref)}; "
            f"combined checks {sum(c['checks'] for c in calls):.0f} (a "
            f"bisecting verifier: {sum(bisecting)}), equal on "
            f"{sum(c['checks'] == b for c, b in zip(calls, bisecting))} of "
            f"{len(calls)} calls")
        live, slots = (sum(c["lanes"][j] for c in window) for j in (0, 1))
        if slots:
            self.ctx.log(f"verify_failing: the window's fused chunks held "
                         f"{live:.0f} live proofs in {slots:.0f} slots "
                         f"({100 * live / slots:.2f} % lane use)")

        self.ctx.log("verify_failing units: seconds "
                     + " ".join(f"{s:.3f}" for s in self.unit_s))

        failed = sum(any(v != t for v, t in zip(c["verdicts"], c["truth"]))
                     for c in calls)
        lim = self.limits
        return Checked(
            checks={
                "verdicts_off_truth": number(off_truth,
                                             lim["verdicts_off_truth"]),
                "verdicts_off_reference": number(
                    off_ref, lim["verdicts_off_reference"]),
            },
            attempted=len(calls), failed=failed)
