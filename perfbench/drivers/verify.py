"""Verify traffic: audit-round proofs through XlaBackend.verify_batch.

Serves a TEE worker's verify calls as `node/client.py verify_missions`
makes them: one `proof.get_backend("xla").verify_batch` per miner batch,
in auto mode (the fused chunk program on a TPU), after the worker's
`warm_up` at boot.  The traffic file sets:

  pool            proofs crafted in set-up (distinct fragment names, one
                  TEE key, one round challenge); calls draw from it
  mu_vectors      distinct nonzero mu vectors the pool's proofs carry
  unit            "call", or a name ("mission") for a unit of `calls`
  calls           the calls of one unit: name and batch size, either
                  {"fixed": n} or {"log_uniform": [lo, hi], "strata": k}
                  (k sizes at the strata's geometric midpoints; every
                  block of k units takes each size once, in a seeded
                  order, so every seed does the same work)
  probe           after the window: one call of `batch` proofs with
                  `forged` forgeries, one in each equal part of the batch
  sample          proofs the reference verifies: `window` drawn from the
                  window's calls, `probe_honest` from the probe, plus
                  every forged proof of the probe

Each call takes a fresh round seed.  Verdicts are compared with what was
planted and with the reference's verdicts on the sample; the combined
checks the program made are printed beside those a bisecting verifier
makes, for reading, not for `correct`.
"""

from __future__ import annotations

import json
import os
import random
import subprocess
import sys
import tempfile
from pathlib import Path

from harness import counters
from harness.checks import Checked, number
from reference import podr2_ref
from reference.bls12_381 import R

HERE = Path(__file__).resolve().parent
_S_BYTES = 48
_MAX_WORKERS = 12  # a one-chip host has 13 cores


def setup(ctx):
    return VerifyWork(ctx)


def batch_sizes(spec: dict) -> list[int]:
    if "fixed" in spec:
        return [int(spec["fixed"])]
    lo, hi = spec["log_uniform"]
    k = int(spec["strata"])
    return [round(lo * (hi / lo) ** ((j + 0.5) / k)) for j in range(k)]


def forged_positions(rnd: random.Random, size: int, count: int) -> list[int]:
    """One seeded position in each of `count` equal parts of the batch."""
    if not count:
        return []
    edges = [size * p // count for p in range(count + 1)]
    return [rnd.randrange(edges[p], edges[p + 1]) for p in range(count)]


class Crafter:
    """Crafts the pool's sigmas in child processes that never touch the
    chip, while the parent warms the verifier up."""

    def __init__(self, job: dict, names: list[bytes], mu_of: list[int]):
        self.tmp = tempfile.TemporaryDirectory(prefix="perfbench-craft-")
        cores = len(os.sched_getaffinity(0))
        workers = max(1, min(len(names), cores - 2, _MAX_WORKERS))
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        self.parts = []
        for w in range(workers):
            lo = len(names) * w // workers
            hi = len(names) * (w + 1) // workers
            spec = dict(job, names=[n.hex() for n in names[lo:hi]],
                        mu_of=mu_of[lo:hi])
            src = Path(self.tmp.name) / f"job{w}.json"
            out = Path(self.tmp.name) / f"out{w}"
            src.write_text(json.dumps(spec))
            proc = subprocess.Popen(
                [sys.executable, str(HERE / "craft.py"), str(src), str(out)],
                env=env)
            self.parts.append((proc, out, hi - lo))

    def wait(self) -> list[bytes]:
        sigmas: list[bytes] = []
        try:
            for proc, out, n in self.parts:
                if proc.wait() != 0:
                    raise RuntimeError(f"crafting worker exited {proc.returncode}")
                blob = out.read_bytes()
                sigmas += [blob[i:i + _S_BYTES]
                           for i in range(0, n * _S_BYTES, _S_BYTES)]
        finally:
            for proc, _, _ in self.parts:
                if proc.poll() is None:
                    proc.kill()
                    proc.wait()
            self.tmp.cleanup()
        return sigmas


class Reference:
    """The reference verifier's verdicts on a sample, computed in a child
    process (it never touches the chip) while the parent goes on."""

    def __init__(self, pk: bytes, items, s: int, seed: bytes) -> None:
        self.tmp = tempfile.TemporaryDirectory(prefix="perfbench-ref-")
        job = Path(self.tmp.name) / "job.json"
        self.out = Path(self.tmp.name) / "out.json"
        job.write_text(json.dumps({
            "pk": pk.hex(), "s": s, "seed": seed.hex(),
            "items": [[n.hex(), list(ch.indices), [r.hex() for r in ch.randoms],
                       p.sigma.hex(), list(p.mu)] for n, ch, p in items]}))
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "reference.podr2_ref", str(job),
             str(self.out)],
            cwd=str(HERE.parent), env=dict(os.environ, JAX_PLATFORMS="cpu"))

    def wait(self) -> list[bool]:
        try:
            if self.proc.wait() != 0:
                raise RuntimeError(f"reference exited {self.proc.returncode}")
            return json.loads(self.out.read_text())
        finally:
            if self.proc.poll() is None:
                self.proc.kill()
                self.proc.wait()
            self.tmp.cleanup()


class VerifyWork:
    def __init__(self, ctx) -> None:
        from cess_tpu import proof
        from cess_tpu.ops.podr2 import Challenge, Podr2Params

        self.ctx = ctx
        cfg, tr = ctx.config, ctx.traffic
        geo = cfg["geometry"]
        n, s = geo["chunks_per_fragment"], geo["sectors_per_chunk"]
        chal = geo["challenged_chunks"]
        self.s = s
        self.limits = cfg["limits"]
        rnd = self.rnd = random.Random(ctx.seed)
        sk, self.pk = podr2_ref.keygen(b"perfbench-tee-%d" % ctx.seed)
        indices = tuple(sorted(rnd.sample(range(n), chal)))
        randoms = tuple(rnd.randbytes(geo["coefficient_bytes"])
                        for _ in indices)
        pool = int(tr["pool"])
        self.names = [b"perfbench-%d-frag-%08d" % (ctx.seed, i)
                      for i in range(pool)]
        self.mus = [[rnd.randrange(1, R) for _ in range(s)]
                    for _ in range(int(tr["mu_vectors"]))]
        self.mu_of = [rnd.randrange(len(self.mus)) for _ in range(pool)]
        crafter = Crafter({"sk": sk, "indices": list(indices),
                           "randoms": [r.hex() for r in randoms],
                           "sectors": s, "mus": self.mus},
                          self.names, self.mu_of)

        self.params = Podr2Params(n=n, s=s)
        self.challenge = Challenge(indices, randoms)
        self.backend = proof.get_backend("xla")
        try:
            with ctx.span("warm_up"):
                warmed = self.backend.warm_up(self.params, chal)
        finally:
            self.sigmas = crafter.wait()
        ctx.log(f"verify: warm_up compiled a program: {warmed}; crafted "
                f"{pool} proofs")
        from cess_tpu.ops.podr2 import Podr2Proof

        self.items = [(nm, self.challenge,
                       Podr2Proof(sg, list(self.mus[m])))
                      for nm, sg, m in zip(self.names, self.sigmas, self.mu_of)]

        self.kinds = [batch_sizes(c["batch"]) for c in tr["calls"]]
        self.unit_name = tr["unit"]
        self.perms: list[list[list[int]]] = []
        self.calls: list[dict] = []
        self.n_calls = 0
        warm = sorted({z for sizes in self.kinds for z in
                       (min(sizes), max(sizes))})
        with ctx.span("warm_calls"):
            for size in warm:
                self._serve(self._draw(size), record=False)
        ctx.log(f"verify: untimed calls at batch sizes {warm}")

    # -- traffic --------------------------------------------------------

    def _forge(self, item, k: int):
        """Alternately another proof's sigma, and one mu altered."""
        from cess_tpu.ops.podr2 import Podr2Proof

        name, ch, p = item
        if k % 2 == 0:
            other = self.sigmas[(self.names.index(name) + 1) % len(self.names)]
            return name, ch, Podr2Proof(other, list(p.mu))
        mu = list(p.mu)
        j = self.rnd.randrange(len(mu))
        mu[j] = (mu[j] + self.rnd.randrange(1, R)) % R
        return name, ch, Podr2Proof(p.sigma, mu)

    def _draw(self, size: int, forged: int = 0) -> dict:
        idx = self.rnd.sample(range(len(self.names)), size)
        items = [self.items[b] for b in idx]
        where = forged_positions(self.rnd, size, forged)
        for k, pos in enumerate(where):
            items[pos] = self._forge(items[pos], k)
        truth = [True] * size
        for pos in where:
            truth[pos] = False
        self.n_calls += 1
        seed = b"perfbench-round-%d-%d" % (self.ctx.seed, self.n_calls)
        return {"items": items, "truth": truth, "seed": seed}

    def _size(self, unit: int, kind: int) -> int:
        sizes = self.kinds[kind]
        block = unit // len(sizes)
        while len(self.perms) <= block:
            self.perms.append([self.rnd.sample(range(len(z)), len(z))
                               for z in self.kinds])
        return sizes[self.perms[block][kind][unit % len(sizes)]]

    def _serve(self, call: dict, record: bool = True) -> int:
        c0 = counters.snapshot().get("cess_proof_checks", 0.0)
        with self.ctx.span(f"verify_batch.{len(call['items'])}"):
            call["verdicts"] = self.backend.verify_batch(
                self.pk, call["items"], call["seed"], self.params)
        call["checks"] = counters.snapshot().get("cess_proof_checks", 0.0) - c0
        if record:
            self.calls.append(call)
        return len(call["items"])

    def unit(self, i: int) -> dict:
        proofs = 0
        for k in range(len(self.kinds)):
            size = self._size(i, k)
            proofs += self._serve(self._draw(size))
        out = {"proofs": proofs, "calls": len(self.kinds)}
        if self.unit_name != "call":
            out[self.unit_name + "s"] = 1
        return out

    def facts(self) -> dict:
        return {}

    # -- check ----------------------------------------------------------

    def check(self) -> Checked:
        tr = self.ctx.traffic
        window = list(self.calls)
        probe_spec = tr["probe"]
        probe = self._draw(int(probe_spec["batch"]), int(probe_spec["forged"]))

        pick = random.Random(self.ctx.seed ^ 0x5EED)
        sample = []  # (call, position)
        for _ in range(min(int(tr["sample"]["window"]), len(window))):
            c = pick.choice(window)
            sample.append((c, pick.randrange(len(c["items"]))))
        honest = [p for p, t in enumerate(probe["truth"]) if t]
        sample += [(probe, p) for p in
                   pick.sample(honest, int(tr["sample"]["probe_honest"]))]
        sample += [(probe, p) for p, t in enumerate(probe["truth"]) if not t]
        # the reference verifies the sample in a process of its own while
        # the probe call runs
        reference = Reference(self.pk, [c["items"][p] for c, p in sample],
                              self.s, b"%d" % self.ctx.seed)
        try:
            with self.ctx.span("probe"):
                self._serve(probe)
        finally:
            ref = reference.wait()
        calls = window + [probe]

        off_truth = sum(v != t for c in calls
                        for v, t in zip(c["verdicts"], c["truth"]))
        off_ref = sum(c["verdicts"][p] != r for (c, p), r in zip(sample, ref))
        self.ctx.log(f"verify: {len(calls)} calls checked, sample of "
                     f"{len(sample)} proofs, reference verdicts "
                     f"{sum(ref)} true / {len(ref)}; combined checks "
                     f"{sum(c['checks'] for c in calls):.0f} (a bisecting "
                     f"verifier: {sum(podr2_ref.bisection_checks(c['truth']) for c in calls)})")

        failed = sum(any(v != t for v, t in zip(c["verdicts"], c["truth"]))
                     for c in calls)
        lim = self.limits
        return Checked(
            checks={
                "verdicts_off_truth": number(off_truth, lim["verdicts_off_truth"]),
                "verdicts_off_reference": number(off_ref, lim["verdicts_off_reference"]),
            },
            attempted=len(calls), failed=failed)
