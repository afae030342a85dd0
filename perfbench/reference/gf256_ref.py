"""Plain Reed-Solomon over GF(2^8) for the repair cells' reference.

Field: primitive polynomial x^8+x^4+x^3+x^2+1 (0x11D).  Code: systematic
generator [I_k; C] with the Cauchy parity rows C[j, i] = 1 / ((k+j) xor i),
so any k of the k+m shards determine the data.  Encoding and decoding
are table lookups in numpy; nothing is imported from the program.
"""

from __future__ import annotations

import numpy as np

PRIM_POLY = 0x11D


def _tables() -> tuple[np.ndarray, np.ndarray]:
    exp = np.zeros(512, dtype=np.int32)
    log = np.zeros(256, dtype=np.int32)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= PRIM_POLY
    exp[255:510] = exp[:255]
    return exp, log


EXP, LOG = _tables()


def mul(a: int, b: int) -> int:
    if a == 0 or b == 0:
        return 0
    return int(EXP[LOG[a] + LOG[b]])


def inv(a: int) -> int:
    if a == 0:
        raise ZeroDivisionError("GF(256) inverse of zero")
    return int(EXP[255 - LOG[a]])


def generator(k: int, m: int) -> list[list[int]]:
    """(k+m) x k systematic generator rows."""
    rows = [[int(i == c) for c in range(k)] for i in range(k)]
    rows += [[inv((k + j) ^ i) for i in range(k)] for j in range(m)]
    return rows


def invert(mat: list[list[int]]) -> list[list[int]]:
    """Gauss-Jordan inverse of a square matrix over GF(256)."""
    n = len(mat)
    aug = [list(row) + [int(i == c) for c in range(n)]
           for i, row in enumerate(mat)]
    for col in range(n):
        piv = next(r for r in range(col, n) if aug[r][col])
        aug[col], aug[piv] = aug[piv], aug[col]
        f = inv(aug[col][col])
        aug[col] = [mul(f, x) for x in aug[col]]
        for r in range(n):
            if r != col and aug[r][col]:
                g = aug[r][col]
                aug[r] = [x ^ mul(g, y) for x, y in zip(aug[r], aug[col])]
    return [row[n:] for row in aug]


def _scale_table(c: int) -> np.ndarray:
    return np.array([mul(c, x) for x in range(256)], dtype=np.uint8)


def apply(mat: list[list[int]], rows: np.ndarray) -> np.ndarray:
    """mat (r x k) times rows (k, n) uint8 over GF(256) -> (r, n)."""
    out = np.zeros((len(mat), rows.shape[1]), dtype=np.uint8)
    for i, coeffs in enumerate(mat):
        for c, row in zip(coeffs, rows):
            if c:
                out[i] ^= _scale_table(c)[row]
    return out


def encode(data: np.ndarray, k: int, m: int) -> np.ndarray:
    """data (k, n) -> parity (m, n)."""
    return apply(generator(k, m)[k:], data)


def decode(survivors: np.ndarray, present: list[int], k: int, m: int):
    """The k data shards from the k survivor rows listed in `present`
    (global shard indices, data shards first)."""
    gen = generator(k, m)
    return apply(invert([gen[p] for p in present[:k]]), survivors[:k])
