"""Verify-front-end hot path: bit-identity of the vectorized batch
forms with the scalar reference, and the fused pipeline's one-shape
compile invariant.

The `proof_hotpath` marker runs as its own CI gate: these are the
seams where a vectorization bug would silently diverge consensus
verdicts (docs/perf.md).  Sorts after the tier-1 truncation point like
the other device-program suites; the small geometries keep every
compile tiny on the CPU mesh.
"""

import hashlib
import random
import threading
import time

import numpy as np
import pytest

from cess_tpu.ops import fr, g1, glv, h2c, podr2
from cess_tpu.ops import bls12_381 as bls
from cess_tpu.ops.bls12_381 import G1Point, G1_GENERATOR, P, R
from cess_tpu.ops.podr2 import (
    BatchItem,
    Challenge,
    Podr2Params,
    Podr2Proof,
    keygen,
    tag_fragment,
)
from cess_tpu.proof import CpuBackend, XlaBackend, frontend, fused

pytestmark = pytest.mark.proof_hotpath

RND = random.Random(0x1207)


def stage_samples(reg) -> dict:
    """{sample name: value} of a registry's unlabelled samples."""
    return {name: v for metric in reg.metrics()
            for name, labels, v in metric.samples() if not labels}


def _scalar_decompress(blob, check_subgroup):
    if check_subgroup:
        return G1Point.from_bytes(blob)
    return bls.g1_decompress_unchecked(blob)


def _compress_raw(x: int, y_large: bool) -> bytes:
    raw = bytearray(x.to_bytes(48, "big"))
    raw[0] |= 0x80
    if y_large:
        raw[0] |= 0x20
    return bytes(raw)


def _nonresidue_blob() -> bytes:
    rnd = random.Random(41)
    while True:
        x = rnd.getrandbits(380) % P
        if bls.fp_sqrt((x * x % P * x + 4) % P) is None:
            return _compress_raw(x, False)


def _nonsubgroup_blob() -> bytes:
    rnd = random.Random(43)
    while True:
        p = bls.map_to_curve_g1(rnd.getrandbits(300) % P)
        if not p.is_infinity() and not p.in_subgroup():
            return _compress_raw(p.x, p.y > P - p.y)


@pytest.fixture(scope="module")
def native_core():
    """The native core, built from the current sources (make does
    nothing when the library is up to date)."""
    from cess_tpu import native

    assert native.build(), "native build failed (g++ required)"
    native.load.cache_clear()
    assert native.load() is not None


@pytest.fixture(params=["native", "fallback"])
def roots(request, monkeypatch):
    """Where the square roots run: the native core's batch call, or the
    pow() loop taken when no core loads."""
    from cess_tpu import native

    if request.param == "native":
        request.getfixturevalue("native_core")
    else:
        monkeypatch.setattr(native, "load", lambda: None)
    return request.param


class TestDecompressBatch:
    """g1_decompress_batch must reject exactly the blobs the scalar
    path rejects and return identical points otherwise — both flags,
    infinity, non-residue x, malformed encodings, and (checked mode)
    non-subgroup points — whichever path computes the roots."""

    def valid_blobs(self):
        pts = [G1_GENERATOR.mul(RND.getrandbits(200)) for _ in range(12)]
        blobs = [p.to_bytes() for p in pts]
        blobs += [(-p).to_bytes() for p in pts[:6]]  # other sign flag
        blobs.append(G1Point.infinity().to_bytes())
        return blobs

    def test_valid_batch_identity(self, roots):
        blobs = self.valid_blobs()
        for check in (True, False):
            native_lanes = []
            got = bls.g1_decompress_batch(blobs, check_subgroup=check,
                                          on_native=native_lanes.append)
            want = [_scalar_decompress(b, check) for b in blobs]
            assert got == want
            # every lane but the point at infinity takes a root
            assert native_lanes == ([18] if roots == "native" else [])

    @pytest.mark.parametrize(
        "blob",
        [
            b"\x00" * 48,                      # uncompressed flag clear
            b"\xc0" + b"\x01" + bytes(46),     # dirty infinity payload
            b"\xe0" + bytes(47),               # infinity + sign flag
            _compress_raw(P, False),           # x ≥ p
            _compress_raw(P + 1, True),
            _nonresidue_blob(),                # x³+4 a non-residue
            bytes(47),                         # short
            bytes(49),                         # long
            b"",
        ],
    )
    def test_rejects_exactly_the_scalar_set(self, blob, roots):
        for check in (True, False):
            with pytest.raises(ValueError):
                _scalar_decompress(blob, check)
            with pytest.raises(ValueError):
                bls.g1_decompress_batch([blob], check_subgroup=check)
            # and inside a batch of valid blobs
            with pytest.raises(ValueError):
                bls.g1_decompress_batch(
                    self.valid_blobs() + [blob], check_subgroup=check
                )

    def test_subgroup_flag(self, roots):
        blob = _nonsubgroup_blob()
        with pytest.raises(ValueError):
            G1Point.from_bytes(blob)
        with pytest.raises(ValueError):
            bls.g1_decompress_batch([blob], check_subgroup=True)
        # unchecked mode matches g1_decompress_unchecked bit for bit
        got = bls.g1_decompress_batch([blob], check_subgroup=False)[0]
        assert got == bls.g1_decompress_unchecked(blob)

    def test_fp_sqrt_batch_identity(self, roots):
        rnd = random.Random(0x5A)
        squares = [pow(rnd.getrandbits(400), 2, P) for _ in range(32)]
        vals = ([0, 1, P - 1, 4] + squares
                + [rnd.getrandbits(400) % P for _ in range(32)]
                + [v + P for v in squares[:2]])  # unreduced
        want = [bls.fp_sqrt(v) for v in vals]
        found = [y for y in want if y]
        assert None in want  # seeded non-residues
        # roots of either sign class (y > p - y and not), returned as
        # pow() returns them
        assert {y > P - y for y in found} == {True, False}
        assert bls.fp_sqrt_batch(vals) == want
        assert (bls._native_roots(vals[:4], None) is not None) == (
            roots == "native")


class TestVectorizedPacking:
    """Byte-identity of the vectorized transcript/μ/ρ packing with the
    scalar loop forms they replaced."""

    def _items(self, s=4, n=5):
        ch = Challenge(
            indices=(1, 4, 9),
            randoms=(b"a" * 20, b"b" * 20, b"c" * 20),
        )
        ragged = Challenge(indices=(2, 6, 7), randoms=(b"x" * 20, b"y" * 20))
        items = []
        for i in range(n):
            mu = [RND.getrandbits(250) % R for _ in range(s)]
            items.append(
                BatchItem(
                    b"hp-%d" % i, ch if i % 2 else ragged,
                    Podr2Proof(bytes(48), mu),
                )
            )
        return items

    def test_transcript_byte_identity(self):
        items = self._items()

        def loop_transcript(seed, its):
            h = hashlib.blake2b(digest_size=32)
            h.update(podr2.RHO_DST)
            h.update(seed)
            for it in its:
                h.update(hashlib.sha256(it.name).digest())
                for i, v in zip(it.challenge.indices, it.challenge.randoms):
                    h.update(i.to_bytes(4, "little"))
                    h.update(v)
                h.update(it.proof.encode())
            return h.digest()

        assert podr2.batch_transcript(b"s", items) == loop_transcript(
            b"s", items
        )
        encs = [it.proof.encode() for it in items]
        assert podr2.batch_transcript(
            b"s", items, encodings=encs
        ) == loop_transcript(b"s", items)

    def test_rho_byte_identity(self):
        tr = hashlib.blake2b(b"t", digest_size=32).digest()

        def loop_rho(transcript, count):
            out = []
            for b in range(count):
                d = hashlib.blake2b(
                    podr2.RHO_DST + transcript + b.to_bytes(8, "little"),
                    digest_size=16,
                ).digest()
                out.append(int.from_bytes(d, "little") | 1)
            return out

        assert podr2.batch_rho(tr, 9) == loop_rho(tr, 9)

    def test_pack_mu_words_identity(self):
        mus = [[RND.getrandbits(250) for _ in range(7)] for _ in range(3)]
        want = np.zeros((3, 7, 8), dtype="<u4")
        for b, row in enumerate(mus):
            for s, m in enumerate(row):
                want[b, s] = np.frombuffer(
                    m.to_bytes(32, "little"), dtype="<u4"
                )
        assert np.array_equal(fused.pack_mu_words(mus), want)

    def test_words_to_limbs_identity(self):
        xs = [RND.getrandbits(255) % R for _ in range(40)] + [0, 1, R - 1]
        w = fr.ints_to_words(xs, 32)
        assert np.array_equal(
            fr.words_to_limbs(w, fr.LIMB_BITS, fr.NLIMBS, np.int8),
            fr.ints_to_limbs(xs, fr.NLIMBS),
        )
        assert np.array_equal(
            fr.words_to_limbs(w, g1.LIMB_BITS, g1.R_LIMBS, np.int32),
            g1.scalars_to_limbs(xs),
        )
        rhos = [RND.getrandbits(128) | 1 for _ in range(11)]
        assert np.array_equal(
            frontend.rho_digits(rhos), g1.scalars_to_limbs(rhos).T
        )
        assert np.array_equal(
            frontend.rho_limbs7(rhos), fr.ints_to_limbs(rhos, 19)
        )

    def test_mu_range_word_compare(self):
        def words_of(vals):
            buf = b"".join(v.to_bytes(32, "little") for v in vals)
            return np.frombuffer(buf, "<u4").reshape(1, len(vals), 8)

        assert frontend.mu_in_range(words_of([0, 1, R - 1]))
        assert not frontend.mu_in_range(words_of([R]))
        assert not frontend.mu_in_range(words_of([R + 1]))
        assert not frontend.mu_in_range(words_of([2**256 - 1]))
        assert not frontend.mu_in_range(words_of([5, R, 7]))

    def test_encode_proofs_rejects_unencodable(self):
        ok = [(b"n", None, Podr2Proof(bytes(48), [1, 2]))]
        assert frontend.encode_proofs(ok) is not None
        for bad_mu in ([-1, 2], [2**256, 2]):
            bad = [(b"n", None, Podr2Proof(bytes(48), bad_mu))]
            assert frontend.encode_proofs(bad) is None


PARAMS = Podr2Params(n=8, s=6)  # s=6: a chunk-program shape unique to
SK, PK = keygen(b"hotpath-tee")  # this file (the counter test needs a
                                 # first-compile baseline of exactly 1)


@pytest.fixture(scope="module")
def proved10():
    indices = (0, 2, 5)
    ch = Challenge(
        indices=indices,
        randoms=tuple(
            (b"hp" + i.to_bytes(2, "little")).ljust(20, b"\x77")
            for i in indices
        ),
    )
    items = []
    for k in range(10):
        name = f"hp-frag-{k}".encode()
        data = bytes(
            [(k * 13 + i) % 256 for i in range(PARAMS.fragment_bytes)]
        )
        tags = tag_fragment(SK, name, data, PARAMS)
        items.append((name, ch, podr2.prove(tags, data, ch, PARAMS)))
    return items


@pytest.fixture
def one_shape(monkeypatch):
    """Force the one-shape pad with a tiny CHUNK so a 10-proof batch is
    3 chunks (4+4+2 → all padded to 4) and device programs stay small
    on the CPU mesh."""
    monkeypatch.setenv("CESS_FUSED_ONE_SHAPE", "1")
    monkeypatch.setattr(fused, "CHUNK", 4)
    monkeypatch.setattr(h2c, "_MAP_TILE", 8)
    monkeypatch.setattr(glv, "_GLV_TILE", 8)


class TestOneShapeCompile:
    def test_multichunk_compiles_once_and_bisects(
        self, proved10, one_shape
    ):
        """Acceptance: the compile counter proves _verify_chunk_device
        traces exactly once across a multi-chunk verify_batch (padded
        shapes), and bisection over a tampered batch reuses the same
        executable with verdicts bit-identical to CpuBackend."""
        backend = XlaBackend(fused=True)
        before = fused.COMPILE_COUNTS["verify_chunk"]
        assert backend.verify_batch(
            PK, proved10, b"shape", PARAMS
        ) == [True] * 10
        after_honest = fused.COMPILE_COUNTS["verify_chunk"]
        assert after_honest - before == 1, (
            "3 padded chunks must share one chunk-program trace"
        )

        # tampered proof in the middle chunk: the bisection tree issues
        # combined checks at every subset size — same shape, no retrace
        bad = list(proved10)
        name, ch, proof = bad[5]
        t = Podr2Proof(proof.sigma, list(proof.mu))
        t.mu[0] = (t.mu[0] + 1) % R
        bad[5] = (name, ch, t)
        cpu = CpuBackend().verify_batch(PK, bad, b"shape", PARAMS)
        fus = backend.verify_batch(PK, bad, b"shape", PARAMS)
        assert cpu == fus
        assert cpu == [True] * 5 + [False] + [True] * 4
        assert fused.COMPILE_COUNTS["verify_chunk"] == after_honest, (
            "bisection subsets must reuse the one-shape executable"
        )

    def test_bad_sigma_isolated_across_chunks(self, proved10, one_shape):
        bad = list(proved10)
        name, ch, proof = bad[7]
        bad[7] = (name, ch, Podr2Proof(b"\x00" * 48, list(proof.mu)))
        cpu = CpuBackend().verify_batch(PK, bad, b"enc", PARAMS)
        fus = XlaBackend(fused=True).verify_batch(PK, bad, b"enc", PARAMS)
        assert cpu == fus
        assert cpu == [True] * 7 + [False] + [True] * 2

    def test_non_subgroup_sigma_across_chunks(self, proved10, one_shape):
        bad = list(proved10)
        name, ch, proof = bad[2]
        bad[2] = (name, ch, Podr2Proof(_nonsubgroup_blob(), list(proof.mu)))
        cpu = CpuBackend().verify_batch(PK, bad, b"sub", PARAMS)
        fus = XlaBackend(fused=True).verify_batch(PK, bad, b"sub", PARAMS)
        assert cpu == fus
        assert cpu == [True, True, False] + [True] * 7

    def test_warm_up_compiles_what_rounds_dispatch_to(
        self, proved10, one_shape
    ):
        """XlaBackend.warm_up builds the chunk program from shapes alone;
        a round of that challenge geometry then neither traces nor lowers
        it again (its packed arguments match the precompiled signature)."""
        import jax

        jax.clear_caches()  # the test above ran this very shape
        backend = XlaBackend(fused=True)
        before = fused.COMPILE_COUNTS["verify_chunk"]
        assert backend.warm_up(PARAMS, 3)
        assert fused.COMPILE_COUNTS["verify_chunk"] - before == 1
        lowered = []

        def on(event, secs, *, fun_name="", **_):
            if event.endswith("jaxpr_to_mlir_module_duration"):
                lowered.append(fun_name)

        jax.monitoring.register_event_duration_secs_listener(on)
        try:
            assert backend.verify_batch(
                PK, proved10, b"warm", PARAMS
            ) == [True] * 10
        finally:
            from jax._src import monitoring

            monitoring.unregister_event_duration_listener(on)
        assert fused.COMPILE_COUNTS["verify_chunk"] - before == 1
        assert not [f for f in lowered if "verify_chunk" in f]
        assert not XlaBackend(fused=False).warm_up(PARAMS, 3)


class TestStagedPathParity:
    """The staged (non-fused) path with the vectorized front-end and
    the deferred device subgroup gate stays bit-identical to the CPU
    reference."""

    def test_staged_non_subgroup_sigma(self, proved10):
        bad = list(proved10[:4])
        name, ch, proof = bad[1]
        bad[1] = (name, ch, Podr2Proof(_nonsubgroup_blob(), list(proof.mu)))
        cpu = CpuBackend().verify_batch(PK, bad, b"sg", PARAMS)
        xla = XlaBackend(fused=False).verify_batch(PK, bad, b"sg", PARAMS)
        assert cpu == xla == [True, False, True, True]

    def test_staged_fused_same_stage_names(self, proved10):
        from cess_tpu.proof.xla_backend import (
            STAGE_NAMES,
            proof_stage_registry,
        )

        reg = proof_stage_registry()

        def observed(backend):
            before = stage_samples(reg)
            assert backend.verify_batch(PK, proved10[:2], b"st", PARAMS) == (
                [True, True]
            )
            after = stage_samples(reg)
            return {n for n in STAGE_NAMES
                    if after[f"cess_proof_stage_{n}_seconds_count"]
                    > before.get(f"cess_proof_stage_{n}_seconds_count", 0)}

        staged = observed(XlaBackend(fused=False))
        fusedb = observed(XlaBackend(fused=True))
        assert {"front_end", "host_prep", "u_fold", "pairing"} <= (
            staged & fusedb)
        # an all-honest batch is checked at the root alone
        assert not {"bisect", "leaf"} & (staged | fusedb)
        assert "dispatch_wait" in fusedb and "launch" in fusedb
        assert "prep" in fusedb and "chunk_program" not in fusedb
        assert "chunk_program" in staged and "launch" not in staged


class SpanRecorder:
    """Stands in for jax.profiler.TraceAnnotation: records (name,
    thread, start, end) of every stage span."""

    def __init__(self):
        self.spans = []
        self.lock = threading.Lock()

    def __call__(self, name):
        rec = self

        class Span:
            def __enter__(self):
                self.t0 = time.perf_counter()

            def __exit__(self, *exc):
                with rec.lock:
                    rec.spans.append((name, threading.current_thread().name,
                                      self.t0, time.perf_counter()))

        return Span()

    def named(self, stage):
        return [s for s in self.spans if s[0] == "cess.proof." + stage]


class TestStageSpans:
    """The fused pipeline's stage spans and counters, on a two-chunk
    batch of 7 proofs (chunks of 4 and 3, both padded to 4 slots)."""

    def test_fused_two_chunk_spans(self, proved10, one_shape, monkeypatch):
        from cess_tpu.proof.xla_backend import proof_stage_registry
        from cess_tpu.utils import stages

        rec = SpanRecorder()
        monkeypatch.setattr(stages, "TraceAnnotation", rec)
        reg = proof_stage_registry()
        before = stage_samples(reg)
        assert XlaBackend(fused=True).verify_batch(
            PK, proved10[:7], b"spans", PARAMS) == [True] * 7
        after = stage_samples(reg)
        delta = {k: after[k] - before.get(k, 0.0) for k in after}

        counts = {s: delta[f"cess_proof_stage_{s}_seconds_count"]
                  for s in ("front_end", "sigma_decode", "host_prep",
                            "prep", "launch", "dispatch_wait", "u_fold",
                            "pairing")}
        assert counts == {"front_end": 1, "sigma_decode": 1,
                          "host_prep": 2, "prep": 2, "launch": 3,
                          "dispatch_wait": 1, "u_fold": 1, "pairing": 1}
        for stage, n in counts.items():
            assert len(rec.named(stage)) == n, stage
        assert delta["cess_proof_chunk_proofs_total"] == 7
        assert delta["cess_proof_chunk_slots_total"] == 8

        caller = threading.current_thread().name
        (fe,) = rec.named("front_end")
        (sd,) = rec.named("sigma_decode")
        first_prep = min(rec.named("host_prep"), key=lambda s: s[2])
        assert fe[1] == sd[1] == first_prep[1] == caller
        assert first_prep[2] <= fe[2] <= sd[2] <= sd[3] <= fe[3] <= (
            first_prep[3])
        assert delta["cess_proof_sigma_lanes_total"] == 7
        workers = {s[1] for s in rec.named("prep")}
        assert len(workers) == 1 and caller not in workers
        for stage in ("host_prep", "launch", "dispatch_wait", "u_fold",
                      "pairing"):
            assert {s[1] for s in rec.named(stage)} == {caller}, stage

    def test_fused_sigma_roots_all_native(self, proved10, one_shape,
                                          native_core):
        """With the core built every σ root of a fused check comes from
        it: sigma_native_lanes equals sigma_lanes."""
        from cess_tpu.proof.xla_backend import proof_stage_registry

        reg = proof_stage_registry()
        before = stage_samples(reg)
        assert XlaBackend(fused=True).verify_batch(
            PK, proved10[:7], b"native", PARAMS) == [True] * 7
        after = stage_samples(reg)
        delta = {k: after[k] - before.get(k, 0.0) for k in after}
        assert delta["cess_proof_sigma_lanes_total"] == 7
        assert delta["cess_proof_sigma_native_lanes_total"] == 7
        assert delta["cess_proof_stage_sigma_decode_seconds_count"] == 1

    def test_rejected_batch_observes_nothing(self, proved10, monkeypatch):
        from cess_tpu.proof.xla_backend import proof_stage_registry
        from cess_tpu.utils import stages

        rec = SpanRecorder()
        monkeypatch.setattr(stages, "TraceAnnotation", rec)
        name, ch, proof = proved10[0]
        bad = [(name, ch, Podr2Proof(proof.sigma, list(proof.mu)[:-1]))]
        reg = proof_stage_registry()
        before = stage_samples(reg)
        assert not fused.combined_check_fused(PK, bad, b"rej", PARAMS)
        after = stage_samples(reg)
        assert {k: v for k, v in after.items()
                if v != before.get(k, 0.0)} == {}
        # the spans were opened and closed, but no stage was observed
        assert {s[0] for s in rec.spans} == {"cess.proof.host_prep",
                                             "cess.proof.front_end"}
