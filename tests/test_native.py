"""Native chaincore bit-identity tests (builds the library if needed)."""

import hashlib
import os
import random

import numpy as np
import pytest

from cess_tpu import native
from cess_tpu.ops import gf256
from cess_tpu.utils import codec
from cess_tpu.utils.rng import ProtocolRng


@pytest.fixture(scope="module")
def lib():
    if native.load() is None:
        assert native.build(), "native build failed (g++ required)"
        native.load.cache_clear()
    lib = native.load()
    assert lib is not None
    return lib


class TestHashes:
    def test_sha256_matches_hashlib(self, lib):
        for data in (b"", b"abc", b"x" * 1000, os.urandom(12345)):
            assert native.sha256(data) == hashlib.sha256(data).digest()

    def test_blake2b_matches_hashlib(self, lib):
        for data in (b"", b"abc", b"y" * 129, os.urandom(4096)):
            assert (
                native.blake2b(data)
                == hashlib.blake2b(data, digest_size=32).digest()
            )
            assert native.blake2b(data, 64) == hashlib.blake2b(data).digest()

    def test_block_boundaries(self, lib):
        # SHA-256: 55/56/64-byte padding boundaries; BLAKE2b: 128/129.
        for n in (55, 56, 63, 64, 65, 127, 128, 129, 256):
            data = bytes(range(256))[:n] * 1
            assert native.sha256(data) == hashlib.sha256(data).digest()
            assert (
                native.blake2b(data)
                == hashlib.blake2b(data, digest_size=32).digest()
            )


class TestRng:
    def test_stream_matches_python(self, lib):
        for seed, dom, n in (
            (b"seed", 0, 100),
            (b"", 7, 33),
            (os.urandom(32), 2**63, 200),
            (b"q", 2**64 - 1, 1),
        ):
            assert native.rng_stream(seed, dom, n) == ProtocolRng(
                seed, dom
            ).take(n)


class TestCompact:
    def test_roundtrip_matches_python(self, lib):
        for v in (0, 1, 63, 64, 2**14 - 1, 2**14, 2**30 - 1, 2**30,
                  2**40, 2**64 - 1):
            enc = native.compact_encode(v)
            assert enc == codec.encode_compact(v)
            assert native.compact_decode(enc) == (v, len(enc))

    def test_rejects_noncanonical(self, lib):
        # 64 encoded in 4-byte mode is non-canonical.
        bad = ((64 << 2) | 0b10).to_bytes(4, "little")
        with pytest.raises(ValueError):
            native.compact_decode(bad)


class TestRs:
    @pytest.mark.parametrize("k,m", [(2, 1), (12, 4), (5, 3)])
    def test_encode_matches_reference(self, lib, k, m):
        rng = np.random.default_rng(42)
        data = rng.integers(0, 256, size=(k, 2048), dtype=np.uint8)
        parity = native.rs_encode(k, m, [bytes(r) for r in data])
        ref = gf256.rs_encode_ref(data, k, m)
        assert parity == [bytes(r) for r in ref]

    @pytest.mark.parametrize("k,m", [(2, 1), (12, 4)])
    def test_reconstruct_any_k(self, lib, k, m):
        rng = np.random.default_rng(43)
        data = rng.integers(0, 256, size=(k, 512), dtype=np.uint8)
        parity = native.rs_encode(k, m, [bytes(r) for r in data])
        shards = [bytes(r) for r in data] + parity
        # Worst case: all parity + tail of data.
        present = list(range(m, k + m))[-k:]
        rec = native.rs_reconstruct(
            k, m, [shards[i] for i in present], present
        )
        assert rec == [bytes(r) for r in data]

    def test_matches_jax_kernel(self, lib):
        """Native RS and the TPU bitplane kernel agree."""
        from cess_tpu.ops.rs import RSCode

        rng = np.random.default_rng(44)
        data = rng.integers(0, 256, size=(12, 1024), dtype=np.uint8)
        native_parity = native.rs_encode(12, 4, [bytes(r) for r in data])
        jax_parity = np.asarray(RSCode(12, 4).encode(data))
        assert [bytes(r) for r in jax_parity] == native_parity


class TestBlsMap:
    """native/blsmap.cpp hash-to-curve vs the host reference — the
    random-oracle batch path must be bit-identical (capability match:
    utils/verify-bls-signatures/src/lib.rs:23-31)."""

    def test_hash_batch_bit_identity(self, lib):
        from cess_tpu import native
        from cess_tpu.ops import bls12_381 as bls

        msgs = [b"frag/%d" % i for i in range(6)] + [b"", b"\x00" * 64]
        got = native.hash_to_g1_batch(msgs, bls.DST_G1)
        for m, (x, y) in zip(msgs, got):
            want = bls.hash_to_g1(m)
            assert (x, y) == (want.x, want.y)

    def test_chunk_points_batch_matches_single(self, lib):
        from cess_tpu.ops import podr2

        pairs = [(b"name-%d" % (i % 3), i * 7) for i in range(8)]
        batch = podr2.chunk_points_batch(pairs)
        singles = [podr2.chunk_point(n, i) for n, i in pairs]
        assert batch == singles

    @pytest.mark.parametrize("n", [11, 41])  # below and above 2 * 8 lanes
    def test_decompress_roots_threads_agree(self, lib, n):
        """The decompression export gives the same y and status on one
        thread and on eight, in both modes, and the pow() loop's values:
        curve lanes (x³ + 4, root the sign names) and raw radicands."""
        from cess_tpu.ops import bls12_381 as bls

        P = bls.P
        rnd = random.Random(n)
        xs = [rnd.getrandbits(381) % P for _ in range(n - 1)] + [0]
        signs = [bool(rnd.getrandbits(1)) for _ in xs]
        want = []
        for x, large in zip(xs, signs):
            y = bls.fp_sqrt((x**3 + 4) % P)
            if y is not None and large != (y > P - y):
                y = P - y
            want.append(y)
        assert None in want and any(want)
        for threads in (1, 8):
            assert native.g1_decompress_batch(xs, signs, threads) == want
            assert native.g1_decompress_batch(xs, None, threads) == [
                bls.fp_sqrt(x) for x in xs]

    def test_stale_library_falls_back(self, lib, monkeypatch):
        """A core built before the decompression export: the roots come
        from the pow() loop, with no error and no native lanes."""
        from cess_tpu.ops import bls12_381 as bls

        class Stale:
            def __getattr__(self, name):
                if name == "cess_blsmap_g1_decompress_batch":
                    raise AttributeError(name)
                return getattr(lib, name)

        monkeypatch.setattr(native, "load", lambda: Stale())
        blobs = [bls.G1_GENERATOR.mul(k).to_bytes() for k in (3, 5, 7)]
        native_lanes = []
        got = bls.g1_decompress_batch(blobs, check_subgroup=False,
                                      on_native=native_lanes.append)
        assert got == [bls.g1_decompress_unchecked(b) for b in blobs]
        assert native_lanes == []
        vals = [4, 5, bls.P - 1]
        assert bls.fp_sqrt_batch(vals) == [bls.fp_sqrt(v) for v in vals]
