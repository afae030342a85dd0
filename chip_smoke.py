#!/usr/bin/env python3
"""Chip smoke: the storage-proof pipeline end to end on one TPU chip.

One process (a process that has touched JAX holds the chip), driven
through the entry points a TEE worker, a storage node and a validator
call, with every input generated from `--seed`:

  preflight  build the native core from the committed sources; refuse to
             go on unless JAX's first device is a TPU.
  verify     one audit round: >= 256 proofs at protocol geometry
             (1024-chunk x 265-sector fragments, 47 challenged chunks,
             distinct fragment names), crafted as the benchmark crafts them,
             with planted forgeries, verified through
             proof.get_backend("xla") in its auto mode (the fused Pallas
             pipeline on a TPU), whose warm_up compiles the fused
             program before the proofs exist, as a booting worker's.  The verdicts must equal the planted
             truth, and CpuBackend's verdicts on a subset holding a
             forgery.
  repair     >= 1 GiB of 16 MiB RS(2,1) segments: encode on device, drop
             one shard per segment, recover through rs.RSStream from host
             memory, compare every byte with the originals.
  bls        bls_agg.batch_verify_signatures on device: an honest batch
             passes, a batch with one forged member fails.

`--chips 4` runs only the sharded paths (XlaBackend on a 4-device mesh
against the single-device verdicts, batch-sharded RS recovery against
the host reference) and nothing else.

Each phase prints its wall time with compile time (trace + lower +
backend compile, from jax.monitoring) apart from a warm rerun, and the
kernel path it took: the Pallas kernels are named from the compiled
text of the programs that ran.  Any failed check raises, so the run
exits non-zero; only a run in which every check held prints the last
line `{"ok": true, "device": {...}}`.
"""

from __future__ import annotations

import argparse
import base64
import json
import random
import re
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# Protocol geometry of the verify phase (perfbench/configs/tee_verifier.json).
N_PROOFS = 256  # one fused CHUNK pads to 1024 lanes; >= 256 proofs
N_FORGED = 2
N_CHALLENGED = 47  # c-pallets/audit/src/lib.rs:906 — 47 of 1024 chunks
CPU_SUBSET = 4  # proofs around the forged run, checked against CpuBackend
# Segment repair: 64 x 16 MiB segments = 1 GiB of data.
N_SEGMENTS = 64
FRAGMENT_BYTES = 8 << 20
# BLS batch: signatures over distinct messages under a few keys.
N_SIGS = 64
N_KEYS = 4
# --chips 4: two proofs per device, each with nonzero μ, and one RS
# segment per device (one batch size keeps the four-chip call to the
# fewest compiles).
MESH_PROOFS = 8
MESH_MU_SECTORS = 2
MESH_SEGMENTS = 4


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


# ------------------------------------------------------------- telemetry


class CompileClock:
    """Wall seconds JAX spent tracing, lowering and compiling (or loading
    from the persistent cache): the union of those events' time spans
    from jax.monitoring, so a kernel traced inside an outer jit's trace
    is counted once."""

    EVENTS = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self) -> None:
        import jax

        self.spans: list[tuple[float, float]] = []
        jax.monitoring.register_event_time_span_listener(self._on)

    def _on(self, event: str, start: float, end: float, *,
            fun_name: str = "?", **_) -> None:
        if event not in self.EVENTS or end - start < 0.01:
            return  # eager host-side jnp ops trace in microseconds
        self.spans.append((start, end))
        if end - start > 5.0:  # a timed-out run still shows its compiles
            log(f"  {event.rsplit('/', 1)[-1]} {fun_name}: "
                f"{end - start:.1f}s")

    @property
    def seconds(self) -> float:
        total, reach = 0.0, float("-inf")
        for start, end in sorted(self.spans):
            if end > reach:
                total += end - max(start, reach)
                reach = end
        return total


class Spy:
    """Stands in for a module-level jitted program while in a `with`
    block and keeps the arguments of its first eager call per shape, so
    the compiled text of each program that ran can be read back
    (lowering the same arguments again hits JAX's in-memory executable
    cache)."""

    def __init__(self, module, attr: str) -> None:
        self.module, self.attr = module, attr
        self.fn = getattr(module, attr)
        self.calls: dict = {}

    def __enter__(self):
        setattr(self.module, self.attr, self)
        return self

    def __exit__(self, *exc) -> None:
        setattr(self.module, self.attr, self.fn)

    def __getattr__(self, name: str):
        return getattr(self.fn, name)  # .trace / .lower of the program

    def __call__(self, *args, **kwargs):
        import jax

        if not any(isinstance(a, jax.core.Tracer) for a in args):
            key = (
                tuple((getattr(a, "shape", None), str(getattr(a, "dtype", "")))
                      for a in args),
                tuple(sorted(kwargs.items())),
            )
            self.calls.setdefault(key, (args, kwargs))
        return self.fn(*args, **kwargs)

    def kernels(self) -> list[list[str]]:
        """Pallas kernel names in each recorded program's compiled text."""
        return [
            pallas_kernels(self.fn.lower(*a, **kw).compile().as_text())
            for a, kw in self.calls.values()
        ]


def pallas_kernels(hlo_text: str) -> list[str]:
    """Names of the Mosaic kernels (tpu_custom_call) in compiled HLO
    text; the name lives in the serialized kernel body."""
    names = []
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = re.search(r'"body":"([A-Za-z0-9+/=]+)"', line)
        raw = base64.b64decode(m.group(1)) if m else b""
        k = re.search(rb"_[A-Za-z0-9]+_tile_kernel", raw)
        names.append(k.group(0).decode() if k else "unnamed")
    return names


def timed(clock: CompileClock, fn):
    """(result, wall seconds, compile seconds) of one call."""
    c0, t0 = clock.seconds, time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0, clock.seconds - c0


# ------------------------------------------------------------- preflight


def preflight(chips: int) -> dict:
    """Build the native core, turn on the compile cache, require a TPU.
    Returns the device record of the last line."""
    build = subprocess.run(
        ["make", "-C", str(ROOT / "native"), "-B"],
        capture_output=True, text=True,
    )
    if build.returncode:
        sys.stderr.write(build.stdout + build.stderr)
    check(build.returncode == 0, "native core did not build")
    from cess_tpu import native

    check(native.load() is not None, "native core built but does not load")

    from cess_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    log(f"preflight: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)} compile_cache={cache_dir}")
    check(dev.platform == "tpu", f"no TPU: JAX's first device is {dev}")
    # the last line's count is the devices the phases use
    check(len(devices) == chips,
          f"--chips {chips} needs a host with {chips} devices, JAX has "
          f"{len(devices)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


# ---------------------------------------------------------------- verify


def audit_round(seed: int, n_proofs: int, n_forged: int,
                n_challenged: int, params, *, mu_sectors: int = 0):
    """(pk, items, truth, round seed, forged): one audit round's proofs
    under one challenge, valid proofs crafted as the benchmark's verify
    driver crafts them (podr2.zero_data_sigmas: σ = Π H^{sk·v_c}, μ = 0), with
    `n_forged` forgeries planted at a seeded position — alternately
    another fragment's σ and a μ that does not match the tags.  With
    `mu_sectors`, every honest proof instead carries that many seeded
    nonzero μ_j, σ multiplied up by Π u_j^{sk·μ_j} to match."""
    from cess_tpu.ops import podr2
    from cess_tpu.ops.bls12_381 import R, G1Point
    from cess_tpu.ops.podr2 import Challenge, Podr2Proof

    rnd = random.Random(seed)
    sk, pk = podr2.keygen(b"chip-smoke-tee-%d" % seed)
    indices = tuple(sorted(rnd.sample(range(params.n), n_challenged)))
    challenge = Challenge(
        indices=indices, randoms=tuple(rnd.randbytes(20) for _ in indices)
    )
    names = [b"smoke-%d-frag-%08d" % (seed, i) for i in range(n_proofs)]
    sigmas = podr2.zero_data_sigmas(sk, names, challenge)
    mus = [[0] * params.s for _ in names]
    if mu_sectors:
        us = podr2.u_generators(params.s)
        for b, mu in enumerate(mus):
            acc = G1Point.infinity()
            for j in rnd.sample(range(params.s), mu_sectors):
                mu[j] = rnd.randrange(1, R)
                acc = acc + us[j].mul(mu[j])
            sigmas[b] = sigmas[b] + acc.mul(sk)
    items = [
        (nm, challenge, Podr2Proof(s.to_bytes(), mu))
        for nm, s, mu in zip(names, sigmas, mus)
    ]
    # one miner's consecutive proofs, aligned so bisection isolates the
    # run in log2(n) levels
    first = n_forged * rnd.randrange(n_proofs // max(n_forged, 1))
    forged = list(range(first, first + n_forged))
    for k, i in enumerate(forged):
        if k % 2 == 0:
            name, ch, p = items[i]
            other = items[(i + 1) % n_proofs][2].sigma
            items[i] = (name, ch, Podr2Proof(other, list(p.mu)))
        else:
            items[i] = forge_mu(items[i], rnd, params)
    truth = [i not in forged for i in range(n_proofs)]
    return pk, items, truth, b"chip-smoke-round-%d" % seed, forged


def forge_mu(item, rnd: random.Random, params):
    """The proof with one μ_j replaced by a seeded value that does not
    match its tags."""
    from cess_tpu.ops.bls12_381 import R
    from cess_tpu.ops.podr2 import Podr2Proof

    name, ch, p = item
    mu = list(p.mu)
    j = rnd.randrange(params.s)
    mu[j] = (mu[j] + rnd.randrange(1, R)) % R
    return name, ch, Podr2Proof(p.sigma, mu)


def phase_verify(seed: int, clock: CompileClock, *, n_proofs=N_PROOFS,
                 n_forged=N_FORGED, n_challenged=N_CHALLENGED,
                 cpu_subset=CPU_SUBSET, params=None) -> dict:
    import jax

    from cess_tpu import proof
    from cess_tpu.ops import g1, podr2
    from cess_tpu.ops.podr2 import Podr2Params
    from cess_tpu.proof import fused

    params = params or Podr2Params()
    with Spy(fused, "_verify_chunk_device") as chunk_spy, \
            Spy(g1, "_msm_kernel") as msm_spy:
        # the TEE worker boots: its one-shape chunk program compiles
        # before the round's proofs exist
        backend = proof.get_backend("xla")
        before = fused.COMPILE_COUNTS["verify_chunk"]
        warmed, boot_s, boot_c = timed(
            clock, lambda: backend.warm_up(params, n_challenged))
        log(f"verify: warm_up compiled={warmed} in {boot_s:.1f}s "
            f"(compile {boot_c:.1f}s)")
        (pk, items, truth, rseed, forged), craft_s, craft_c = timed(
            clock, lambda: audit_round(seed, n_proofs, n_forged,
                                       n_challenged, params))
        log(f"verify: crafted {n_proofs} proofs in {craft_s:.1f}s "
            f"(compile {craft_c:.1f}s)")

        def run():
            podr2.chunk_point.cache_clear()  # the verifier re-derives H
            return backend.verify_batch(pk, items, rseed, params)

        cold, cold_s, cold_c = timed(clock, run)
        log(f"verify: cold verify_batch {cold_s:.1f}s "
            f"(compile {cold_c:.1f}s)")
        check(cold == truth, f"verdicts {_diff(cold, truth)} differ from "
              f"the planted truth (forged at {forged})")
        warm, warm_s, warm_c = timed(clock, run)
        check(warm == truth, "warm verdicts differ from the planted truth")
        traces = fused.COMPILE_COUNTS["verify_chunk"] - before

        # CpuBackend on a subset around the first forgery
        lo = max(0, min(forged[0] - cpu_subset // 2, n_proofs - cpu_subset))
        sub = items[lo : lo + cpu_subset]
        cpu, cpu_s, _ = timed(clock, lambda: proof.get_backend(
            "cpu").verify_batch(pk, sub, rseed, params))
        xla_sub = backend.verify_batch(pk, sub, rseed, params)
        check(cpu == truth[lo : lo + cpu_subset] and xla_sub == cpu,
              f"CpuBackend {cpu} / XlaBackend {xla_sub} disagree with "
              f"the truth {truth[lo : lo + cpu_subset]} on proofs {lo}..")
        kernels = {"verify_chunk": chunk_spy.kernels(),
                   "msm": msm_spy.kernels()}

    fused_ran = bool(chunk_spy.calls)
    chunk_kernels = {k for ks in kernels["verify_chunk"] for k in ks}
    if jax.default_backend() == "tpu":
        check(warmed, "XlaBackend auto mode had no fused program to warm")
        check(fused_ran, "XlaBackend auto mode did not take the fused path")
        check({"_map_tile_kernel", "_glv_tile_kernel"} <= chunk_kernels,
              f"fused verify ran without its Pallas kernels "
              f"({sorted(chunk_kernels)}): the lane count took the per-op "
              f"XLA path")
        check(all("_ladder_tile_kernel" in ks for ks in kernels["msm"]),
              f"an MSM took the per-op XLA ladder: {kernels['msm']}")
        check(traces <= 1, f"fused chunk program traced {traces}x "
              f"(warm-up included)")
    return {
        "proofs": n_proofs, "forged_at": forged,
        "geometry": f"{params.n}x{params.s}/{n_challenged}",
        "verdicts_match_truth": True, "cpu_subset": [lo, cpu_subset],
        "warm_up": warmed, "warm_up_s": boot_s, "warm_up_compile_s": boot_c,
        "craft_s": craft_s, "craft_compile_s": craft_c,
        "cold_s": cold_s, "cold_compile_s": cold_c,
        "warm_s": warm_s, "warm_compile_s": warm_c,
        "cpu_subset_s": cpu_s, "chunk_traces": traces,
        "path": "fused" if fused_ran else "staged",
        "one_shape": fused._one_shape(), "chunk": fused.CHUNK,
        "kernels": kernels,
    }


def _diff(got: list, want: list) -> list[int]:
    return [i for i, (a, b) in enumerate(zip(got, want)) if a != b]


# ---------------------------------------------------------------- repair


def segments(seed: int, n_segments: int, fragment_bytes: int):
    """(data (B, 2, n) uint8, per-segment survivor lists): seeded segment
    data, and one dropped shard per segment."""
    import numpy as np

    rng = np.random.default_rng(seed)
    data = rng.integers(0, 256, size=(n_segments, 2, fragment_bytes),
                        dtype=np.uint8)
    lost = rng.integers(0, 3, size=n_segments)
    present = [[s for s in range(3) if s != int(x)] for x in lost]
    return data, present


def survivors_of(data, parity, present):
    import numpy as np

    shards = np.concatenate([data, parity], axis=1)  # (B, 3, n)
    return np.stack([shards[b, p] for b, p in enumerate(present)])


def phase_repair(seed: int, clock: CompileClock, *,
                 n_segments=N_SEGMENTS, fragment_bytes=FRAGMENT_BYTES) -> dict:
    import numpy as np

    from cess_tpu.ops import rs

    t0 = time.perf_counter()
    data, present = segments(seed, n_segments, fragment_bytes)
    gen_s = time.perf_counter() - t0
    code = rs.segment_code()
    parity, enc_s, enc_c = timed(
        clock, lambda: rs.RSStream(code).run_batch(data))
    survivors = survivors_of(data, parity, present)
    del parity

    def recover():
        return rs.RSStream(code, present=present).run_batch(survivors)

    cold, cold_s, cold_c = timed(clock, recover)
    bad = [b for b in range(n_segments)
           if not np.array_equal(cold[b], data[b])]
    check(not bad, f"RSStream recovered segments {bad} wrong")
    del cold
    warm, warm_s, warm_c = timed(clock, recover)
    check(np.array_equal(warm, data), "warm RSStream recovery differs")
    gib = data.nbytes / (1 << 30)
    return {
        "segments": n_segments, "segment_mib": 2 * fragment_bytes / (1 << 20),
        "gib": gib, "patterns": sorted({tuple(p) for p in present}),
        "datagen_s": gen_s, "encode_s": enc_s, "encode_compile_s": enc_c,
        "cold_s": cold_s, "cold_compile_s": cold_c,
        "warm_s": warm_s, "warm_compile_s": warm_c,
        "path": code.path, "donation": rs._donate_ok(),
        "slab": rs.SLAB, "byte_exact": True,
    }


# ------------------------------------------------------------------- bls


def phase_bls(seed: int, clock: CompileClock, *, n_sigs=N_SIGS,
              n_keys=N_KEYS) -> dict:
    import jax

    from cess_tpu.ops import bls12_381 as bls
    from cess_tpu.ops import bls_agg, g1

    rnd = random.Random(seed)
    sks = [bls.keygen(b"chip-smoke-key-%d-%d" % (seed, k))
           for k in range(n_keys)]
    pks = [bls.sk_to_pk(sk) for sk in sks]
    triples = []
    for i in range(n_sigs):
        k = rnd.randrange(n_keys)
        msg = b"chip-smoke-msg-%d-%d" % (seed, i)
        triples.append((pks[k], msg, bls.sign(sks[k], msg)))
    forged_at = rnd.randrange(n_sigs)
    pk, msg, _ = triples[forged_at]
    k = pks.index(pk)
    forged = list(triples)
    forged[forged_at] = (pk, msg, bls.sign(sks[k], msg + b"/forged"))

    bseed = b"chip-smoke-bls-%d" % seed
    with Spy(g1, "_msm_kernel") as spy:
        honest, cold_s, cold_c = timed(
            clock, lambda: bls_agg.batch_verify_signatures(triples, bseed))
        check(honest, "honest BLS batch rejected")
        sunk, warm_s, warm_c = timed(
            clock, lambda: bls_agg.batch_verify_signatures(forged, bseed))
        check(not sunk,
              f"BLS batch with a forged member (#{forged_at}) passed")
        kernels = spy.kernels()
    if jax.default_backend() == "tpu":
        check(all("_ladder_tile_kernel" in ks for ks in kernels),
              f"a BLS fold took the per-op XLA ladder: {kernels}")
    return {
        "signatures": n_sigs, "keys": n_keys, "forged_at": forged_at,
        "cold_s": cold_s, "cold_compile_s": cold_c,
        "warm_s": warm_s, "warm_compile_s": warm_c,
        "kernels": kernels,
    }


# ------------------------------------------------------------- --chips 4


def phase_mesh_verify(seed: int, clock: CompileClock, n_dev: int, *,
                      n_proofs=MESH_PROOFS, mu_sectors=MESH_MU_SECTORS,
                      n_challenged=N_CHALLENGED, params=None) -> dict:
    """XlaBackend on an n_dev mesh (the sharded μ combine,
    parallel.verify) against one device on the same seeded batch.

    Every honest proof carries nonzero μ, so each device's shard adds to
    the combined exponents: a combine that dropped a shard, or summed
    one device's, fails the combined check (the root of verify_batch's
    bisection) on the honest batch.  verify_batch alone would not show
    it: its bisection ends in host checks of single proofs.  One forged
    μ sits on a shard other than device 0's, and the batch holding it
    must fail the combined check on the mesh as on one device (checked
    at the root only: bisecting would compile every MSM again at each
    smaller batch size)."""
    from cess_tpu.ops.podr2 import Podr2Params
    from cess_tpu.parallel import make_mesh
    from cess_tpu.parallel import verify as sharded
    from cess_tpu.proof import XlaBackend

    params = params or Podr2Params()
    mesh = make_mesh(n_dev)
    check(mesh.devices.size == n_dev
          and len({d.id for d in mesh.devices.flat}) == n_dev,
          f"make_mesh({n_dev}) built {mesh.devices}")
    per_dev = n_proofs // n_dev
    check(per_dev >= 2 and per_dev * n_dev == n_proofs,
          f"{n_proofs} proofs do not give {n_dev} devices two or more each")
    pk, items, truth, rseed, _ = audit_round(
        seed, n_proofs, 0, n_challenged, params, mu_sectors=mu_sectors)
    rnd = random.Random(seed)
    at = rnd.randrange(per_dev, n_proofs)  # off device 0's shard
    forged = list(items)
    forged[at] = forge_mu(items[at], rnd, params)

    build, spread = sharded._combine_fn, []

    def recording(m):
        fn = build(m)

        def run(*args):
            out = fn(*args)
            spread.append(len(out.sharding.device_set))
            return out
        return run

    # H points hash on the host (native) in both arms: the mesh shards
    # the μ combine, and the SSWU map kernel's minutes of compile would
    # only repeat what the one-chip run covers.
    meshed = XlaBackend(mesh=mesh, device_h2c=False)
    single = XlaBackend(fused=False, device_h2c=False)
    sharded._combine_fn = recording
    try:
        mesh_v, mesh_s, mesh_c = timed(
            clock, lambda: meshed.verify_batch(pk, items, rseed, params))
        mesh_root = [meshed._combined_check(pk, batch, rseed, params)
                     for batch in (items, forged)]
    finally:
        sharded._combine_fn = build
    one_v, one_s, one_c = timed(
        clock, lambda: single.verify_batch(pk, items, rseed, params))
    one_root = [single._combined_check(pk, batch, rseed, params)
                for batch in (items, forged)]
    check(mesh_v == one_v == truth,
          f"honest batch: mesh {mesh_v} / one device {one_v} / truth {truth}")
    check(mesh_root == one_root == [True, False],
          f"combined checks of the honest batch and of the batch forged "
          f"at proof {at} (device {at // per_dev}): mesh {mesh_root} / "
          f"one device {one_root}, want [True, False]")
    check(len(spread) == 3 and set(spread) == {n_dev},
          f"sharded combines ran on {spread} devices, not 3 x {n_dev}")
    return {
        "proofs": n_proofs, "per_device": per_dev, "mu_sectors": mu_sectors,
        "forged_at": at, "forged_device": at // per_dev,
        "mesh_devices": [d.id for d in mesh.devices.flat],
        "combine_devices": spread, "combined_checks": mesh_root,
        "mesh_s": mesh_s, "mesh_compile_s": mesh_c,
        "single_s": one_s, "single_compile_s": one_c,
    }


def phase_mesh_repair(seed: int, clock: CompileClock, n_dev: int, *,
                      n_segments=MESH_SEGMENTS,
                      fragment_bytes=FRAGMENT_BYTES) -> dict:
    """Batch-sharded RS recovery (rs._sharded_batch_fn) against the host
    reference, byte for byte."""
    import numpy as np

    from cess_tpu.ops import gf256, rs
    from cess_tpu.parallel import make_mesh

    mesh = make_mesh(n_dev)
    data, _ = segments(seed, n_segments, fragment_bytes)
    code = rs.segment_code()
    present = [1, 2]  # data shard 0 lost on every segment
    parity = np.stack([gf256.rs_encode_ref(d, 2, 1) for d in data])
    survivors = np.concatenate([data[:, 1:], parity], axis=1)
    out, cold_s, cold_c = timed(
        clock, lambda: code.reconstruct_batch(survivors, present, mesh=mesh))
    spread = len(out.sharding.device_set)
    check(spread == n_dev, f"recovery ran on {spread} devices, not {n_dev}")
    got = np.asarray(out)
    ref = np.stack([gf256.rs_decode_ref(s, present, 2, 1) for s in survivors])
    check(np.array_equal(got, ref) and np.array_equal(got, data),
          "sharded RS recovery differs from the host reference")
    return {
        "segments": n_segments, "devices": spread, "path": code.path,
        "cold_s": cold_s, "cold_compile_s": cold_c, "byte_exact": True,
    }


# ------------------------------------------------------------------ main


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    t_start = time.perf_counter()
    device = preflight(args.chips)
    clock = CompileClock()
    if args.chips == 4:
        phases = [
            ("mesh_repair", lambda: phase_mesh_repair(args.seed, clock, 4)),
            ("mesh_verify", lambda: phase_mesh_verify(args.seed, clock, 4)),
        ]
    else:
        phases = [
            ("verify", lambda: phase_verify(args.seed, clock)),
            ("repair", lambda: phase_repair(args.seed, clock)),
            ("bls", lambda: phase_bls(args.seed, clock)),
        ]
    for name, run in phases:
        t0 = time.perf_counter()
        log(f"phase {name}: start at {t0 - t_start:.1f}s")
        report = run()
        report["wall_s"] = time.perf_counter() - t0
        log(f"phase {name}: {json.dumps(report)}")
    log(f"total_s: {time.perf_counter() - t_start}")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
