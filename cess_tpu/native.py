"""ctypes bindings for the chaincore native library (native/chaincore.cpp).

The native core carries the host-side deterministic primitives (hashing,
protocol RNG, SCALE compact codec, GF(2^8) Reed-Solomon) in C++ — the role
the reference delegates to native Rust/C (e.g. the vendored ring crypto,
reference: utils/ring).  Python remains the source of truth; every binding
is tested bit-identical against the pure-Python implementation.

`load()` returns None when the library hasn't been built (`make -C native`),
so the framework degrades gracefully to the Python paths.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
from functools import lru_cache

_SO_PATH = os.path.join(os.path.dirname(__file__), "_native.so")
_NATIVE_DIR = os.path.join(os.path.dirname(__file__), os.pardir, "native")


def build(quiet: bool = True) -> bool:
    """Invoke the Makefile; returns True if the library is present after."""
    try:
        subprocess.run(
            ["make", "-C", _NATIVE_DIR],
            check=True,
            capture_output=quiet,
        )
    except (OSError, subprocess.CalledProcessError):
        return False
    return os.path.exists(_SO_PATH)


@lru_cache(maxsize=1)
def load() -> "ctypes.CDLL | None":
    if not os.path.exists(_SO_PATH):
        return None
    lib = ctypes.CDLL(_SO_PATH)
    lib.cess_sha256.argtypes = [
        ctypes.c_char_p, ctypes.c_size_t, ctypes.c_char_p,
    ]
    lib.cess_blake2b.argtypes = [
        ctypes.c_char_p, ctypes.c_size_t, ctypes.c_char_p, ctypes.c_uint,
    ]
    lib.cess_rng_stream.argtypes = [
        ctypes.c_char_p, ctypes.c_size_t, ctypes.c_uint64,
        ctypes.c_char_p, ctypes.c_size_t,
    ]
    lib.cess_compact_encode.argtypes = [ctypes.c_uint64, ctypes.c_char_p]
    lib.cess_compact_encode.restype = ctypes.c_size_t
    lib.cess_compact_decode.argtypes = [
        ctypes.c_char_p, ctypes.c_size_t, ctypes.POINTER(ctypes.c_uint64),
    ]
    lib.cess_compact_decode.restype = ctypes.c_size_t
    lib.cess_rs_encode.argtypes = [
        ctypes.c_uint, ctypes.c_uint, ctypes.c_size_t,
        ctypes.c_char_p, ctypes.c_char_p,
    ]
    lib.cess_rs_encode.restype = ctypes.c_int
    lib.cess_rs_reconstruct.argtypes = [
        ctypes.c_uint, ctypes.c_uint, ctypes.c_size_t, ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_uint32), ctypes.c_char_p,
    ]
    lib.cess_rs_reconstruct.restype = ctypes.c_int
    lib.cess_abi_version.restype = ctypes.c_uint
    return lib


# ---------------------------------------------------------------- wrappers


def sha256(data: bytes) -> bytes:
    lib = load()
    assert lib is not None, "native library not built (make -C native)"
    out = ctypes.create_string_buffer(32)
    lib.cess_sha256(data, len(data), out)
    return out.raw


def blake2b(data: bytes, digest_size: int = 32) -> bytes:
    lib = load()
    assert lib is not None, "native library not built (make -C native)"
    out = ctypes.create_string_buffer(digest_size)
    lib.cess_blake2b(data, len(data), out, digest_size)
    return out.raw


def rng_stream(seed: bytes, domain: int, n: int) -> bytes:
    lib = load()
    assert lib is not None, "native library not built (make -C native)"
    out = ctypes.create_string_buffer(n)
    lib.cess_rng_stream(seed, len(seed), domain, out, n)
    return out.raw


def compact_encode(value: int) -> bytes:
    lib = load()
    assert lib is not None, "native library not built (make -C native)"
    out = ctypes.create_string_buffer(9)
    n = lib.cess_compact_encode(value, out)
    return out.raw[:n]


def compact_decode(data: bytes) -> tuple[int, int]:
    """Returns (value, consumed); raises ValueError on malformed input."""
    lib = load()
    assert lib is not None, "native library not built (make -C native)"
    out = ctypes.c_uint64()
    n = lib.cess_compact_decode(data, len(data), ctypes.byref(out))
    if n == 0:
        raise ValueError("malformed or non-canonical compact encoding")
    return out.value, n


def rs_encode(k: int, m: int, data_shards: list[bytes]) -> list[bytes]:
    lib = load()
    assert lib is not None, "native library not built (make -C native)"
    shard_len = len(data_shards[0])
    assert len(data_shards) == k
    assert all(len(s) == shard_len for s in data_shards)
    parity = ctypes.create_string_buffer(m * shard_len)
    rc = lib.cess_rs_encode(k, m, shard_len, b"".join(data_shards), parity)
    if rc != 0:
        raise ValueError("rs_encode failed")
    return [
        parity.raw[i * shard_len : (i + 1) * shard_len] for i in range(m)
    ]


def rs_reconstruct(
    k: int, m: int, shards: list[bytes], present: list[int]
) -> list[bytes]:
    lib = load()
    assert lib is not None, "native library not built (make -C native)"
    shard_len = len(shards[0])
    arr = (ctypes.c_uint32 * k)(*present[:k])
    out = ctypes.create_string_buffer(k * shard_len)
    rc = lib.cess_rs_reconstruct(
        k, m, shard_len, b"".join(shards[:k]), arr, out
    )
    if rc != 0:
        raise ValueError("rs_reconstruct failed")
    return [out.raw[i * shard_len : (i + 1) * shard_len] for i in range(k)]


# ---------------------------------------------------------------- BLS hash

_BLSMAP_READY = False


def _blsmap_lib():
    lib = load()
    assert lib is not None, "native library not built (make -C native)"
    if not hasattr(lib.cess_blsmap_init, "_configured"):
        lib.cess_blsmap_init.argtypes = [
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p,
            ctypes.c_uint64,
            ctypes.c_char_p, ctypes.c_uint64,
            ctypes.c_char_p, ctypes.c_uint64,
            ctypes.c_char_p, ctypes.c_uint64,
            ctypes.c_char_p, ctypes.c_uint64,
            ctypes.c_uint64,
        ]
        lib.cess_blsmap_init.restype = ctypes.c_int
        lib.cess_blsmap_hash_g1_batch.argtypes = [
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_uint64),
            ctypes.c_uint64, ctypes.c_char_p, ctypes.c_uint64,
            ctypes.c_char_p, ctypes.c_uint64,
        ]
        lib.cess_blsmap_hash_g1_batch.restype = ctypes.c_int
        lib.cess_blsmap_xmd_u_batch.argtypes = [
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_uint64),
            ctypes.c_uint64, ctypes.c_char_p, ctypes.c_uint64,
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_uint64,
        ]
        lib.cess_blsmap_xmd_u_batch.restype = ctypes.c_int
        lib.cess_blsmap_xmd_u_indexed.argtypes = [
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_uint64),
            ctypes.c_uint64, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_uint64, ctypes.c_char_p, ctypes.c_uint64,
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_uint64,
        ]
        lib.cess_blsmap_xmd_u_indexed.restype = ctypes.c_int
        lib.cess_blsmap_init._configured = True
    return lib


def blsmap_init() -> None:
    """Feed the derived SSWU/isogeny constants (ops/_sswu_g1.py) and the
    curve parameters into the native hash-to-curve kernel."""
    global _BLSMAP_READY
    if _BLSMAP_READY:
        return
    from .ops import _sswu_g1, bls12_381 as bls

    lib = _blsmap_lib()

    def be48(x: int) -> bytes:
        return x.to_bytes(48, "big")

    def vec(coeffs: list[int]) -> bytes:
        return b"".join(be48(c) for c in coeffs)

    rc = lib.cess_blsmap_init(
        be48(bls.P), be48(_sswu_g1.A_PRIME), be48(_sswu_g1.B_PRIME),
        _sswu_g1.Z_SSWU,
        vec(_sswu_g1.X_NUM), len(_sswu_g1.X_NUM),
        vec(_sswu_g1.X_DEN), len(_sswu_g1.X_DEN),
        vec(_sswu_g1.Y_NUM), len(_sswu_g1.Y_NUM),
        vec(_sswu_g1.Y_DEN), len(_sswu_g1.Y_DEN),
        bls.H_EFF_G1,
    )
    if rc != 0:
        raise RuntimeError(f"cess_blsmap_init failed: {rc}")
    _BLSMAP_READY = True


def hash_to_g1_batch(
    msgs: list[bytes], dst: bytes, threads: int = 8
) -> list[tuple[int, int]]:
    """Batched hash-to-G1 (affine (x, y) ints), bit-identical to the host
    reference ops/bls12_381.hash_to_g1 (tests/test_native.py).  Runs the
    xmd/SSWU/isogeny/cofactor pipeline in native threads with the GIL
    released — the verifier's random-oracle workhorse."""
    blsmap_init()
    lib = _blsmap_lib()
    assert all(len(m) <= 1024 for m in msgs), "message too long"
    assert len(dst) <= 255
    blob = b"".join(msgs)
    offs = (ctypes.c_uint64 * (len(msgs) + 1))()
    acc = 0
    for i, m in enumerate(msgs):
        offs[i] = acc
        acc += len(m)
    offs[len(msgs)] = acc
    out = ctypes.create_string_buffer(96 * len(msgs))
    rc = lib.cess_blsmap_hash_g1_batch(
        blob, offs, len(msgs), dst, len(dst), out, threads
    )
    if rc != 0:
        raise RuntimeError(f"hash_g1_batch failed: {rc}")
    res = []
    for i in range(len(msgs)):
        chunk = out.raw[96 * i : 96 * (i + 1)]
        res.append(
            (int.from_bytes(chunk[:48], "big"), int.from_bytes(chunk[48:], "big"))
        )
    return res


def xmd_u_batch(msgs: list[bytes], dst: bytes, threads: int = 1):
    """expand_message_xmd + hash_to_field only — the host front half of
    the DEVICE hash-to-curve path (ops/h2c.py).  Returns
    (u: np.uint8 (N, 2, 48) canonical big-endian field elements,
     flags: np.uint8 (N,)) with flag bits
    (sgn0(u0), sswu_exceptional(u0), sgn0(u1), sswu_exceptional(u1))
    in bits 0..3 — the predicates the device kernel takes as inputs."""
    import numpy as np

    blsmap_init()
    lib = _blsmap_lib()
    assert all(len(m) <= 1024 for m in msgs), "message too long"
    assert len(dst) <= 255
    blob = b"".join(msgs)
    offs = (ctypes.c_uint64 * (len(msgs) + 1))()
    acc = 0
    for i, m in enumerate(msgs):
        offs[i] = acc
        acc += len(m)
    offs[len(msgs)] = acc
    out_u = ctypes.create_string_buffer(96 * len(msgs))
    out_f = ctypes.create_string_buffer(len(msgs))
    rc = lib.cess_blsmap_xmd_u_batch(
        blob, offs, len(msgs), dst, len(dst), out_u, out_f, threads
    )
    if rc != 0:
        raise RuntimeError(f"xmd_u_batch failed: {rc}")
    u = np.frombuffer(out_u.raw, dtype=np.uint8).reshape(len(msgs), 2, 48)
    flags = np.frombuffer(out_f.raw, dtype=np.uint8)
    return u, flags


def xmd_u_indexed(names: list[bytes], name_ids, indices, dst: bytes,
                  threads: int = 1):
    """xmd_u_batch for messages of the podr2 chunk-point framing
    name ‖ '/' ‖ LE64(index), assembled natively: `name_ids` (uint32) and
    `indices` (uint64) are parallel arrays selecting (names[id], index)
    per output row — Python never builds the per-pair byte strings."""
    import numpy as np

    blsmap_init()
    lib = _blsmap_lib()
    assert all(len(m) <= 1000 for m in names), "name too long"
    assert len(dst) <= 255
    name_ids = np.ascontiguousarray(name_ids, dtype=np.uint32)
    indices = np.ascontiguousarray(indices, dtype=np.uint64)
    n = len(name_ids)
    assert len(indices) == n
    blob = b"".join(names)
    offs = (ctypes.c_uint64 * (len(names) + 1))()
    acc = 0
    for i, m in enumerate(names):
        offs[i] = acc
        acc += len(m)
    offs[len(names)] = acc
    out_u = ctypes.create_string_buffer(96 * n)
    out_f = ctypes.create_string_buffer(max(n, 1))
    rc = lib.cess_blsmap_xmd_u_indexed(
        blob, offs, len(names),
        name_ids.ctypes.data, indices.ctypes.data, n,
        dst, len(dst), out_u, out_f, threads,
    )
    if rc != 0:
        raise RuntimeError(f"xmd_u_indexed failed: {rc}")
    u = np.frombuffer(out_u.raw, dtype=np.uint8).reshape(n, 2, 48)
    flags = np.frombuffer(out_f.raw, dtype=np.uint8)[:n]
    return u, flags


def g1_decompress_batch(xs: list[int], signs: list[bool] | None,
                        threads: int = 8) -> list[int | None]:
    """Square roots for compressed-G1 decompression, in native threads
    with the GIL released: per canonical x, the y of the point
    (x, y) on y² = x³ + 4 whose sign flag (y > p − y) is the lane's
    `signs` entry, or None where x³ + 4 has no root.  With `signs`
    None, `xs` are canonical radicands a and each result is
    a^((p+1)/4) where that is a root (fp_sqrt's value).  Bit-identical
    to the pow() loop in ops/bls12_381.py (tests/test_native.py)."""
    blsmap_init()
    lib = _blsmap_lib()
    # typed here, not in _blsmap_lib: a core built before this export
    # raises AttributeError for this call alone, and the caller falls back
    fn = lib.cess_blsmap_g1_decompress_batch
    fn.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_uint64,
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_uint64,
    ]
    fn.restype = ctypes.c_int
    n = len(xs)
    if signs is not None and len(signs) != n:
        raise ValueError("one sign per x coordinate")
    out_y = ctypes.create_string_buffer(48 * n)
    status = ctypes.create_string_buffer(n)
    rc = fn(b"".join(x.to_bytes(48, "big") for x in xs),
            None if signs is None else bytes(map(bool, signs)),
            n, out_y, status, threads)
    if rc != 0:
        raise RuntimeError(f"g1_decompress_batch failed: {rc}")
    ys = out_y.raw
    return [None if bad else int.from_bytes(ys[48 * i : 48 * i + 48], "big")
            for i, bad in enumerate(status.raw)]
