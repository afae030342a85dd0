"""Plain PoDR2 reference: crafting and per-proof verdicts.

The scheme (Shacham-Waters over BLS12-381, as the program defines it):

  u_j      = hash_to_g1(U_DST || j, U_DST)            sector generators
  H(n, i)  = hash_to_g1(n || "/" || i, H_DST)         chunk points
  v_c      = int(random_c, little-endian)             challenge coefficients
  proof    = (sigma, mu_1..mu_s)
  valid   <=> e(sigma, g2) == e(prod_c H(n, i_c)^{v_c} * prod_j u_j^{mu_j}, pk)

Verdicts are those of a verifier that checks items one by one; batches
are folded with this module's own random weights and bisected, which
gives the same verdicts with overwhelming probability.  Nothing here
imports the program: an item is a plain tuple
(name, indices, randoms, sigma bytes, mu list).
"""

from __future__ import annotations

import hashlib
from functools import lru_cache

from . import bls12_381 as bls
from .bls12_381 import G1Point, G2Point, R

U_DST = b"cess/podr2/u/v1"
H_DST = b"cess/podr2/h/v1"


def keygen(seed: bytes) -> tuple[int, bytes]:
    """(sk, compressed pk in G2) from a seed."""
    sk = bls.keygen(b"podr2" + seed)
    return sk, (bls.G2_GENERATOR.mul(sk)).to_bytes()


def chunk_message(name: bytes, index: int) -> bytes:
    return name + b"/" + index.to_bytes(8, "little")


@lru_cache(maxsize=1 << 14)
def chunk_point(name: bytes, index: int) -> G1Point:
    return bls.hash_to_g1(chunk_message(name, index), H_DST)


@lru_cache(maxsize=4)
def u_generators(s: int) -> tuple[G1Point, ...]:
    return tuple(
        bls.hash_to_g1(U_DST + j.to_bytes(4, "little"), U_DST)
        for j in range(s)
    )


def coefficients(randoms) -> list[int]:
    return [int.from_bytes(v, "little") for v in randoms]


def u_part(mu: list[int], us) -> G1Point:
    """prod_j u_j^{mu_j}."""
    acc = G1Point.infinity()
    for u, m in zip(us, mu):
        if m:
            acc = acc + u.mul(m)
    return acc


def craft_sigma(sk: int, h_points, coeffs, mu_point: G1Point) -> bytes:
    """sigma = (prod_c H_c^{v_c} * prod_j u_j^{mu_j})^sk, compressed: the
    proof an honest prover gives over data whose challenged sectors
    combine to mu."""
    acc = G1Point.infinity()
    for h, v in zip(h_points, coeffs):
        acc = acc + h.mul(v)
    return (acc + mu_point).mul(sk).to_bytes()


def _decode(pk: bytes, item):
    """(pk point, sigma point) or None where an encoding is invalid."""
    pk_pt, sigma = _g2(pk), _g1(item[3])
    return None if pk_pt is None or sigma is None else (pk_pt, sigma)


@lru_cache(maxsize=4)
def _g2(data: bytes):
    try:
        return G2Point.from_bytes(data)
    except ValueError:
        return None


@lru_cache(maxsize=1 << 12)
def _g1(data: bytes):
    try:
        return G1Point.from_bytes(data)
    except ValueError:
        return None


def _h_side(item) -> G1Point:
    return _h_point(item[0], tuple(item[1]), tuple(item[2]))


@lru_cache(maxsize=1 << 12)
def _h_point(name: bytes, indices: tuple, randoms: tuple) -> G1Point:
    """prod_c H(name, i_c)^{v_c}."""
    acc = G1Point.infinity()
    for i, v in zip(indices, coefficients(randoms)):
        acc = acc + chunk_point(name, i).mul(v)
    return acc


@lru_cache(maxsize=64)
def _u_side(mu: tuple, s: int) -> G1Point:
    """prod_j u_j^{mu_j} (proofs that share a mu vector share it)."""
    return u_part(list(mu), u_generators(s))


def _mu_ok(item, s: int) -> bool:
    mu = item[4]
    return len(mu) == s and all(0 <= m < R for m in mu)


def verify_one(pk: bytes, item, s: int) -> bool:
    dec = _decode(pk, item)
    if dec is None or not _mu_ok(item, s):
        return False
    pk_pt, sigma = dec
    rhs = _h_side(item) + _u_side(tuple(item[4]), s)
    return bls.pairing_check([(sigma, -bls.G2_GENERATOR), (rhs, pk_pt)])


def batch_ok(pk: bytes, items, s: int, seed: bytes) -> bool:
    """One check for all items, with 128-bit weights drawn from `seed`
    and the items (a forged item passes with probability 2^-128)."""
    decs = [_decode(pk, it) for it in items]
    if any(d is None for d in decs) or not all(_mu_ok(it, s) for it in items):
        return False
    pk_pt = decs[0][0]
    h = hashlib.sha256(b"perfbench-ref-weights" + seed)
    for it in items:
        h.update(it[0] + it[3])
    root = h.digest()
    weights = [
        int.from_bytes(hashlib.sha256(root + b.to_bytes(4, "little"))
                       .digest()[:16], "little") | 1
        for b in range(len(items))
    ]
    # prod_b (H_b * U_b)^{w_b}, U_b = prod_j u_j^{mu_bj}: the same element
    # as prod_b H_b^{w_b} * prod_j u_j^{sum_b w_b mu_bj}
    lhs = G1Point.infinity()
    rhs = G1Point.infinity()
    for (_, sigma), it, w in zip(decs, items, weights):
        lhs = lhs + sigma.mul(w)
        rhs = rhs + (_h_side(it) + _u_side(tuple(it[4]), s)).mul(w)
    return bls.pairing_check([(lhs, -bls.G2_GENERATOR), (rhs, pk_pt)])


def verdicts(pk: bytes, items, s: int, seed: bytes = b"") -> list[bool]:
    """Per-item verdicts: one batch check per node of a bisection, and a
    single check at each failing leaf."""
    out = [False] * len(items)

    def visit(lo: int, hi: int, depth: int) -> None:
        sub = items[lo:hi]
        if batch_ok(pk, sub, s, seed + bytes([depth, lo % 256])):
            out[lo:hi] = [True] * (hi - lo)
        elif hi - lo == 1:
            out[lo] = verify_one(pk, items[lo], s)
        else:
            mid = (lo + hi) // 2
            visit(lo, mid, depth + 1)
            visit(mid, hi, depth + 1)

    if items:
        visit(0, len(items), 0)
    return out


def bisection_checks(truth: list[bool]) -> int:
    """Combined checks a verifier that bisects on failure makes over a
    batch with these per-item verdicts: one per node of the tree it
    visits (a failing single item takes its combined check too)."""
    def visit(lo: int, hi: int) -> int:
        if all(truth[lo:hi]) or hi - lo == 1:
            return 1
        mid = (lo + hi) // 2
        return 1 + visit(lo, mid) + visit(mid, hi)

    return visit(0, len(truth)) if truth else 0


def main(argv) -> int:
    """`python3 -m reference.podr2_ref <job.json> <out.json>`: verdicts
    for the job's items, in a process of its own."""
    import json

    job = json.loads(open(argv[0]).read())
    items = [(bytes.fromhex(n), ix, [bytes.fromhex(r) for r in rs],
              bytes.fromhex(sg), mu) for n, ix, rs, sg, mu in job["items"]]
    out = verdicts(bytes.fromhex(job["pk"]), items, job["s"],
                   bytes.fromhex(job["seed"]))
    with open(argv[1], "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main(sys.argv[1:]))
