"""XLA ProofBackend — the TPU data-plane path.

Work split (SURVEY.md §2 "distributed communication backend" item — keep
the hot data plane on device, control on host):

 * μ aggregation over challenged sectors (prove) and the ρ-weighted batch
   combination (verify) run on TPU as base-128 limb matmuls (ops/fr.py).
 * Every G1 multi-scalar multiplication — the verify equation's σ^ρ fold,
   its H/u products, and the prover's σ fold — runs on TPU through the
   complete-formula limb kernels in ops/g1.py.
 * Only the two pairings per combined check (O(1) per batch) and the
   hash-to-curve points stay host-side (ops/bls12_381.py).

Verdicts are bit-identical to CpuBackend: the combined equation uses the
same ρ derivation (ops/podr2.py batch_rho) and the device group math is
bit-identical to the host fold (tests/test_g1.py); the H-side product is
associated as Π_b (Π_c H_{b,c}^{v_c})^{ρ_b}, the same group element as
the host's flat Π_{b,c} H_{b,c}^{ρ_b v_c}.

Capability match: the reference's pairing-side verify
(utils/verify-bls-signatures/src/lib.rs:85-100) and the audit pallet's
declared verification seam (c-pallets/audit/src/lib.rs:484).
"""

from __future__ import annotations

import os
import time as _time

import jax
import numpy as np

from ..ops import bls12_381 as bls
from ..ops import fr, g1, h2c, podr2
from ..ops.bls12_381 import G1Point
from ..ops.podr2 import Challenge, Podr2Params, Podr2Proof
from ..utils.stages import StagePlane
from .backend import ProofBackend, ProveRequest, VerifyItem

# Fragment-axis chunk for prove_batch: bounds host staging + HBM footprint
# (47×265×36 limb bytes ≈ 448 KB per fragment).
_PROVE_CHUNK = 1024

# Challenge coefficients are 20-byte randoms (audit/src/lib.rs:916-924);
# batch weights ρ are 128-bit by construction (podr2.batch_rho).
_COEFF_BITS = 160
_RHO_BITS = 128
# Coefficients arrive at the MSM multiplied by the effective cofactor
# (ops/h2c.py cofactor-folding contract): 160 + 64 bits.
_COEFF_HEFF_BITS = _COEFF_BITS + 64

# Below this many (proof, chunk) pairs the host native hash-to-curve
# (native/blsmap.cpp, ~0.6 ms/pair) beats paying a device map compile +
# padded launch; above it the device SSWU path (ops/h2c.py) wins and
# scales.  Verdicts are bit-identical either way (tests/test_h2c.py).
_DEVICE_H2C_MIN_PAIRS = 256


# ------------------------------------------------------- stage telemetry
#
# Always-on per-stage histograms of the combined check, in a
# process-wide registry of their own so any host embedding a backend
# (node RPC, TEE client, the benchmark) exposes them without threading
# a registry through the proof API; the node's `system_metrics` merges
# this registry into its exposition (node/rpc.py).  Each stage is also
# a profiler span `cess.proof.<stage>` over the same interval
# (utils/stages.py), so a device trace names the stage the host was in.
#
# BOTH verify pipelines mark `front_end` (decode, encode pass, ρ
# transcript; nested in the first `host_prep`) with `sigma_decode` (σ
# decompression) inside it, and `host_prep`, `u_fold`, `pairing`; both
# count the σ decoded and those whose roots came from the native core.
# The staged path below adds sigma_fold and chunk_program (the H-side
# folds, real work); the fused single-program path
# (proof/fused.py combined_check_fused) adds `prep` (one chunk's
# packing, on the prefetch worker), `launch` (upload + asynchronous
# dispatch of each chunk, and of the partial reduction) and
# `dispatch_wait` (the block on device results after every chunk is in
# flight).  The fused path also counts each launched chunk's live
# proofs and padded slots.  Around the checks, `verify_batch` marks
# the bisection tree a failing batch opens: `bisect` encloses each
# combined check below the root (its own stages nest inside it) and
# `leaf` each single-proof verify at a failing leaf, and counts both;
# an all-honest batch, checked at the root alone, marks neither.
# docs/perf.md explains how to read the split.
#
# Overhead guard: each stage costs two perf_counter calls, one span
# object and one locked histogram observe — microseconds against
# stages that cost milliseconds (tests/test_telemetry.py measures it).
# CESS_STAGE_METRICS=0 switches spans, histograms and counters off for
# A/B measurement.

STAGE_NAMES = ("front_end", "sigma_decode", "host_prep", "prep", "launch",
               "u_fold", "sigma_fold", "chunk_program", "dispatch_wait",
               "pairing", "bisect", "leaf")

PROOF_STAGES = StagePlane(
    "proof", STAGE_NAMES, "cess_proof_stage_{}_seconds",
    "combined-check {} stage time", {
        "proofs": ("cess_proofs_verified",
                   "proof items covered by combined checks"),
        "checks": ("cess_proof_checks",
                   "combined pairing checks executed"),
        "seconds": ("cess_proof_verify_seconds_total",
                    "wall-clock seconds spent in combined checks"),
        "chunk_proofs": ("cess_proof_chunk_proofs_total",
                         "live proofs in launched fused chunks"),
        "chunk_slots": ("cess_proof_chunk_slots_total",
                        "padded proof slots of launched fused chunks"),
        "sigma_lanes": ("cess_proof_sigma_lanes_total",
                        "sigma points decoded by the verify front end"),
        "sigma_native_lanes": ("cess_proof_sigma_native_lanes_total",
                               "decoded sigma points whose square roots "
                               "came from the native core"),
        "bisect_checks": ("cess_proof_bisect_checks_total",
                          "combined checks below the root of the "
                          "bisection tree"),
        "leaf_checks": ("cess_proof_leaf_checks_total",
                        "single-proof verifications at failing leaves"),
    })


def proof_stage_registry():
    """The process-wide metrics registry for the proof data plane."""
    return PROOF_STAGES.registry()


def _subgroup_ok(points, device: bool | None = None) -> bool:
    """True iff every point is in the r-order subgroup (or ∞) — the
    shared deferred-subgroup gate behind g1_decompress_batch(
    check_subgroup=False) on the staged verify and prove paths.

    device=None is auto: ONE batched device [r]-chain (ops/glv.py
    subgroup_mask) on a real TPU, where the whole batch costs
    microseconds per point; the per-point host ladder on CPU hosts,
    where the emulated chain measured ~3× SLOWER than the ladder
    (10.7 vs 3.3 ms/point at 1024 lanes) — the same auto shape as
    device_h2c.  CESS_DEVICE_SUBGROUP=1/0 forces either way (tests
    force the device wiring on the CPU mesh).  Both routes are
    bit-identical (tests/test_fused TestGlv subgroup_mask matrix)."""
    if not points:
        return True
    if device is None:
        env = os.environ.get("CESS_DEVICE_SUBGROUP")
        if env is not None:
            device = env not in ("0", "false", "off")
        else:
            device = jax.default_backend() == "tpu"
    if not device:
        return all(p.in_subgroup() for p in points)
    import jax.numpy as jnp

    from ..ops import glv
    from ..ops.bls12_381 import G1Point as _G1
    from .fused import pack_points_limbs

    # pow2 ∞-pad with an 8-lane floor: tiny batches (single-proof
    # bisection leaves, 1-item RPC verifies) share one compiled mask
    # shape instead of one per batch size
    m = max(8, 1 << max(0, (len(points) - 1).bit_length()))
    X, Y, Z = pack_points_limbs(
        list(points) + [_G1.infinity()] * (m - len(points))
    )
    mask = np.asarray(
        glv.subgroup_mask(jnp.asarray(X), jnp.asarray(Y), jnp.asarray(Z))
    )
    return bool(np.all(mask == 1))


class XlaBackend(ProofBackend):
    """mesh: optional jax.sharding.Mesh over the proof-batch axis.  When
    given, the ρ-weighted μ combination runs through the sharded data
    plane (parallel/verify.py: shard_map + psum over ICI) instead of the
    single-device kernel — bit-identical verdicts either way
    (tests/test_parallel.py)."""

    name = "xla"

    def __init__(
        self,
        mesh=None,
        device_h2c: bool | None = None,
        fused: bool | None = None,
    ) -> None:
        self.mesh = mesh
        # fused: None = auto (the single-program GLV pipeline of
        # proof/fused.py on a real TPU); True/False force it — tests
        # force True to exercise the fused path on the CPU mesh.
        # Verdicts are bit-identical either way (tests/test_fused.py).
        # The fused pipeline is single-device: forcing it alongside a
        # mesh would silently ignore the sharded data plane the caller
        # asked for, so the combination is rejected outright.
        if fused and mesh is not None:
            raise ValueError(
                "fused=True is single-device and incompatible with a "
                "mesh; use fused=None/False on meshed backends"
            )
        self.fused = fused
        # device_h2c: None = auto (device SSWU only on a real TPU, where
        # the fused Pallas map wins); True/False force it — tests force
        # True to exercise the wiring on the CPU mesh.  On CPU the
        # emulated-limb map is slower than the native host hash, so auto
        # keeps CPU-only hosts on the native path at every batch size.
        self.device_h2c = device_h2c
        # H-point memo for one verify_batch call: the bisection tree
        # re-visits identical (name, index) pairs across overlapping
        # subsets; hash each pair once (the cached-chunk_point role of
        # the host path, scoped to the call so memory stays bounded).
        self._h_memo: dict[tuple[bytes, int], object] = {}

    def _use_fused(self) -> bool:
        return (
            self.fused
            if self.fused is not None
            else jax.default_backend() == "tpu"
        ) and self.mesh is None

    def warm_up(self, params: Podr2Params, n_challenged: int) -> bool:
        """Compile the fused chunk program for rounds of at most
        `n_challenged`-chunk challenges before the first one arrives
        (minutes on a TPU; proof/fused.py precompile).  Returns whether
        there was a program to compile: not off the fused path, nor when
        chunk shapes follow each batch (no one-shape padding)."""
        from . import fused

        if not (self._use_fused() and fused._one_shape()):
            return False
        fused.precompile(n_challenged, params)
        return True

    def _chunk_points(self, pairs: list[tuple[bytes, int]]) -> list:
        missing = [p for p in pairs if p not in self._h_memo]
        if missing:
            for p, pt in zip(missing, podr2.chunk_points_batch(missing)):
                self._h_memo[p] = pt
        return [self._h_memo[p] for p in pairs]

    # ------------------------------------------------------------ verify

    def _h_inner_fold_device(self, items: list[VerifyItem]) -> list[G1Point]:
        """Per-item Π_c H(name‖i_c)^{v_c}, entirely on device: host XMD →
        device SSWU map (uncleared points) → grouped MSM with v_c·h_eff
        scalars ([v·h_eff]Q = [v]([h_eff]Q), so the result is the cleared
        fold — tests/test_h2c.py TestCofactorFolding)."""
        import jax.numpy as jnp

        B = len(items)
        names = [name for name, _, _ in items]
        # zip-truncation semantics, exactly like the host reference's
        # `zip(coefficients(), indices)` (ops/podr2.py _rhs_point /
        # batch_verify): a challenge with mismatched index/random list
        # lengths contributes min(len) pairs on every backend.
        counts = [
            min(len(ch.indices), len(ch.randoms)) for _, ch, _ in items
        ]
        name_ids = np.repeat(np.arange(B, dtype=np.uint32), counts)
        indices = np.concatenate(
            [
                np.asarray(ch.indices[:c], dtype=np.uint64)
                for (_, ch, _), c in zip(items, counts)
            ]
        )
        (X, Y, Z), n = h2c.hash_pairs_device(
            names, name_ids, indices, podr2.H_DST
        )

        # grouped layout: pad each item's chunk row to a power-of-two
        # width, and the item count to a power of two (dead lanes get
        # scalar 0, which the ladder turns into an ∞ contribution
        # regardless of the gathered point).
        g = 1 << max(0, (max(counts) - 1).bit_length())
        Bp = 1 << max(0, (B - 1).bit_length())
        lane_map = np.zeros((Bp, g), dtype=np.int32)
        slimbs = np.zeros((Bp, g, g1.R_LIMBS), dtype=np.int32)
        limb_cache: dict[int, np.ndarray] = {}

        def limbs_of(v: int) -> np.ndarray:
            row = limb_cache.get(v)
            if row is None:
                row = g1.scalars_to_digits([v], g1.R_LIMBS)[:, 0]
                limb_cache[v] = row
            return row

        pos = 0
        for b, ((_, ch, _), cnt) in enumerate(zip(items, counts)):
            coeffs = ch.coefficients()[:cnt]
            for k, v in enumerate(coeffs):
                lane_map[b, k] = pos + k
                slimbs[b, k] = limbs_of(v * h2c.H_EFF)
            pos += cnt

        flat = lane_map.reshape(-1)
        Xg = jnp.take(X, jnp.asarray(flat), axis=1)
        Yg = jnp.take(Y, jnp.asarray(flat), axis=1)
        Zg = jnp.take(Z, jnp.asarray(flat), axis=1)
        s = jnp.asarray(slimbs.reshape(Bp * g, g1.R_LIMBS).T)
        rX, rY, rZ = g1._msm_kernel(
            Xg, Yg, Zg, s, bits=_COEFF_HEFF_BITS, group=g
        )
        return g1.projective_to_points(
            np.asarray(rX).T[:B], np.asarray(rY).T[:B], np.asarray(rZ).T[:B]
        )

    def _combined_check(
        self,
        pk: bytes,
        items: list[VerifyItem],
        seed: bytes,
        params: Podr2Params,
    ) -> bool:
        """One pairing equation for the whole batch, with every group fold
        on device:

          e(Π_b σ_b^{ρ_b}, −g2) · e(Π_b (Π_c H_{b,c}^{v_c})^{ρ_b}
                                     · Π_j u_j^{Σ_b ρ_b μ_bj}, pk) == 1
        """
        if not items:
            return True
        if self._use_fused():
            from .fused import combined_check_fused

            return combined_check_fused(pk, items, seed, params)
        from . import frontend

        # Every stage below ends in host materialization (g1.msm /
        # limbs_to_ints return host values, pairing_check is host), so
        # each stage's interval is its real cost — a stage changed to
        # return a device-resident array must block on it inside its
        # block, or the cost migrates to the next stage.  A rejected
        # front end raises and observes nothing.
        stage = PROOF_STAGES.stage
        check_t0 = _time.perf_counter()
        try:
            with stage("host_prep"):
                # batched σ decompression with the subgroup test
                # deferred: the per-σ host ladder (~3 ms each) becomes
                # ONE device [r]-chain over the whole batch below — same
                # rejection set (tests/test_proof_backends.py
                # non-subgroup/tampered matrix).
                with stage("front_end"):
                    fe = frontend.front_end(pk, items, seed, params)
                # μ limbs come from the SAME encode pass as the
                # transcript — a numpy word unpack instead of B·S
                # per-limb Python loops.
                mu_limbs = frontend.mu_limbs(fe.mu_words)  # (B, S, 37)
        except frontend.Rejected:
            return False
        rhos = fe.rhos

        # σ subgroup gate: the test deferred from decompression runs as
        # one batched device [r]-chain (ops/glv.py subgroup_mask —
        # bit-identical to the host in_subgroup ladder, tests/test_fused
        # TestGlv), ∞-padded to a power of two ([r]∞ = ∞ passes).
        with stage("sigma_fold"):
            sub_ok = _subgroup_ok(fe.sigmas)
        if not sub_ok:
            return False

        # u-side exponents Σ_b ρ_b μ_bj: device limb matmul (ops/fr.py) —
        # sharded over the mesh when one is configured (ρ=0 row padding
        # contributes nothing to the combination).
        with stage("u_fold"):
            if self.mesh is not None:
                from ..parallel import combine_mu_sharded, pad_batch_rows

                n_dev = self.mesh.devices.size
                rho_limbs = pad_batch_rows(
                    frontend.rho_limbs7(rhos), n_dev
                )
                mu_limbs = pad_batch_rows(mu_limbs, n_dev)
                exps = fr.limbs_to_ints(
                    combine_mu_sharded(self.mesh, rho_limbs, mu_limbs)
                )
            else:
                exps = fr.limbs_to_ints(fr.combine_mu(rhos, mu_limbs))

        # σ-side: Π σ_b^{ρ_b} — one flat MSM over the batch.
        with stage("sigma_fold"):
            lhs = g1.msm(fe.sigmas, rhos, bits=_RHO_BITS)

        with stage("chunk_program"):
            rhs = g1.msm(self._h_inner_fold(items), rhos, bits=_RHO_BITS)

        # u-side: Π_j u_j^{e_j} over the global sector generators.
        with stage("u_fold"):
            us = list(podr2.u_generators(params.s))
            rhs = rhs + g1.msm(us, exps)

        with stage("pairing"):
            verdict = bls.pairing_check(
                [(lhs, -bls.G2_GENERATOR), (rhs, fe.pk_point)]
            )
        PROOF_STAGES.count(checks=1, proofs=len(items),
                           seconds=_time.perf_counter() - check_t0)
        return verdict

    def _h_inner_fold(self, items: list[VerifyItem]) -> list[G1Point]:
        """H-side: per-item Π_c H^{v_c} (grouped MSM over the challenged
        chunk points).  At batch scale the random-oracle points are
        hashed ON DEVICE (ops/h2c.py: host XMD → device SSWU) and stay
        device-resident into the MSM, with the effective cofactor folded
        into the coefficients."""
        n_pairs = sum(len(ch.indices) for _, ch, _ in items)
        use_device = (
            self.device_h2c
            if self.device_h2c is not None
            else jax.default_backend() == "tpu"
        )
        if use_device and n_pairs >= _DEVICE_H2C_MIN_PAIRS:
            return self._h_inner_fold_device(items)
        # same zip-truncation semantics as the host reference and the
        # device branch
        counts = [
            min(len(ch.indices), len(ch.randoms)) for _, ch, _ in items
        ]
        flat_pairs = [
            (name, i)
            for (name, ch, _), c in zip(items, counts)
            for i in ch.indices[:c]
        ]
        flat_pts = self._chunk_points(flat_pairs)
        h_pts = []
        pos = 0
        for c in counts:
            h_pts.append(flat_pts[pos : pos + c])
            pos += c
        h_coeffs = [
            list(ch.coefficients()[:c])
            for (_, ch, _), c in zip(items, counts)
        ]
        return g1.msm_grouped(h_pts, h_coeffs, bits=_COEFF_BITS)

    def verify_batch(
        self,
        pk: bytes,
        items: list[VerifyItem],
        seed: bytes,
        params: Podr2Params,
    ) -> list[bool]:
        stage = PROOF_STAGES.stage

        def batch_check(pk_, subset, seed_, params_):
            # only the root of the bisection tree holds every item; each
            # other check re-checks a strictly smaller subset below it
            if len(subset) == len(items):
                return self._combined_check(pk_, subset, seed_, params_)
            with stage("bisect"):
                verdict = self._combined_check(pk_, subset, seed_, params_)
            PROOF_STAGES.count(bisect_checks=1)
            return verdict

        def single_check(pk_, item, params_):
            name, challenge, proof = item
            with stage("leaf"):
                verdict = podr2.verify(pk_, name, challenge, proof,
                                       s=params_.s)
            PROOF_STAGES.count(leaf_checks=1)
            return verdict

        self._h_memo = {}
        try:
            return self._verdicts_by_bisection(
                pk, items, seed, params, batch_check, single_check
            )
        finally:
            self._h_memo = {}

    # ------------------------------------------------------------ prove

    def prove_batch(self, request: ProveRequest) -> list[Podr2Proof]:
        """μ on device (challenged sectors only — 47/1024 of the data moves
        to HBM); σ = Π_c tag_{i_c}^{v_c} per fragment as one grouped MSM.
        Tag decompression is batched (ops/bls12_381.g1_decompress_batch)
        with the subgroup test deferred to one device [r]-chain per chunk
        — the per-tag host ladder cost ~3 ms × 47 tags × fragment; the
        rejection set (ValueError on any malformed or non-subgroup tag)
        matches the host reference's per-tag from_bytes."""
        params = request.params
        challenge = request.challenge
        coeffs = challenge.coefficients()

        proofs: list[Podr2Proof] = []
        for start in range(0, len(request.data), _PROVE_CHUNK):
            chunk_data = request.data[start : start + _PROVE_CHUNK]
            chunk_tags = request.tags[start : start + _PROVE_CHUNK]
            # Challenged rows only — 47/1024 of the fragment bytes move.
            batches = []
            for data in chunk_data:
                matrix = podr2.fragment_sectors(data, params)
                rows = [matrix[i] for i in challenge.indices]
                batches.append(fr.sectors_to_limbs(rows))
            sector_limbs = np.stack(batches)
            mu_all = fr.mu_aggregate(coeffs, sector_limbs)  # (n, S, 37)

            flat = bls.g1_decompress_batch(
                [tags[i] for tags in chunk_tags for i in challenge.indices],
                check_subgroup=False,
            )
            self._require_subgroup(flat)
            k = len(challenge.indices)
            tag_pts = [
                flat[b * k : (b + 1) * k] for b in range(len(chunk_tags))
            ]
            sigmas = g1.msm_grouped(
                tag_pts,
                [list(coeffs)] * len(tag_pts),
                bits=_COEFF_BITS,
            )
            for b, sigma in enumerate(sigmas):
                mu = fr.limbs_to_ints(mu_all[b])
                proofs.append(Podr2Proof(sigma.to_bytes(), mu))
        return proofs

    @staticmethod
    def _require_subgroup(points: list[G1Point]) -> None:
        """Raises the scalar path's 'point not in G1 subgroup'
        ValueError if any point fails the batched device check."""
        if points and not _subgroup_ok(points):
            raise ValueError("point not in G1 subgroup")
