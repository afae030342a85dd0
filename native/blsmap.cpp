// BLS12-381 G1 hash-to-curve (RFC 9380 SSWU suite) — native batch path.
//
// Role: the verifier must evaluate the random oracle H(name ‖ index) for
// every challenged chunk (cess_tpu/ops/podr2.py chunk_point); at
// north-star scale that is millions of hash-to-curve evaluations, far
// too slow for Python big-ints.  This file provides a threaded batch
// kernel: expand_message_xmd (SHA-256, shared with chaincore.cpp's
// compressor), simplified SWU onto the 11-isogenous curve, the isogeny
// back to E, and effective-cofactor clearing — bit-identical to the
// host reference cess_tpu/ops/bls12_381.hash_to_g1 (asserted in
// tests/test_native.py).
//
// Every curve constant (p, A', B', Z, the isogeny coefficient arrays,
// h_eff) is INJECTED at init time from the Python side, which derives
// them (tools/derive_sswu.py); nothing numeric is transcribed here.
// Montgomery parameters (R², -p⁻¹ mod 2⁶⁴) are computed at init.
//
// Capability match: the reference's hash-to-G1 inside
// utils/verify-bls-signatures/src/lib.rs:23-31 (ic_verify_bls_signature
// hash_to_point) and the IAS-side BLS check at
// primitives/enclave-verify/src/lib.rs:230-235.

#include <cstdint>
#include <cstring>
#include <thread>
#include <vector>

#if defined(_WIN32)
#define CESS_EXPORT extern "C" __declspec(dllexport)
#else
#define CESS_EXPORT extern "C" __attribute__((visibility("default")))
#endif

// sha256() from chaincore.cpp (same translation unit set, internal linkage
// there — so re-declare a tiny local copy hook instead).  chaincore keeps
// its sha256 in an anonymous namespace; we export a thin wrapper from it:
extern "C" void cess_sha256(const uint8_t* data, size_t len, uint8_t out[32]);

namespace blsmap {

typedef unsigned __int128 u128;

constexpr int NL = 6;  // 6 × 64-bit limbs hold 381-bit p

struct Fp {
  uint64_t v[NL];
};

// ----------------------------------------------------------- bignum core

static Fp P;             // modulus (little-endian limbs)
static uint64_t PINV;    // -p^-1 mod 2^64
static Fp R2;            // 2^768 mod p (to-Montgomery factor)
static Fp ONE_M;         // 1 in Montgomery form

static inline bool geq(const Fp& a, const Fp& b) {
  for (int i = NL - 1; i >= 0; --i) {
    if (a.v[i] != b.v[i]) return a.v[i] > b.v[i];
  }
  return true;
}

static inline void sub_nocheck(Fp& a, const Fp& b) {
  u128 borrow = 0;
  for (int i = 0; i < NL; ++i) {
    u128 cur = (u128)a.v[i] - b.v[i] - borrow;
    a.v[i] = (uint64_t)cur;
    borrow = (cur >> 64) ? 1 : 0;
  }
}

static inline void add_mod(const Fp& a, const Fp& b, Fp& out) {
  u128 carry = 0;
  for (int i = 0; i < NL; ++i) {
    u128 cur = (u128)a.v[i] + b.v[i] + (uint64_t)carry;
    out.v[i] = (uint64_t)cur;
    carry = cur >> 64;
  }
  if (carry || geq(out, P)) sub_nocheck(out, P);
}

static inline void sub_mod(const Fp& a, const Fp& b, Fp& out) {
  Fp tmp = a;
  if (!geq(tmp, b)) {
    // a + p - b
    u128 carry = 0;
    for (int i = 0; i < NL; ++i) {
      u128 cur = (u128)tmp.v[i] + P.v[i] + (uint64_t)carry;
      tmp.v[i] = (uint64_t)cur;
      carry = cur >> 64;
    }
  }
  sub_nocheck(tmp, b);
  out = tmp;
}

// CIOS Montgomery multiplication (interleaved multiply + reduce).
static inline void mont_mul(const Fp& a, const Fp& b, Fp& out) {
  uint64_t t[NL + 2] = {0};
  for (int i = 0; i < NL; ++i) {
    u128 c = 0;
    const uint64_t ai = a.v[i];
#pragma GCC unroll 6
    for (int j = 0; j < NL; ++j) {
      u128 cur = (u128)t[j] + (u128)ai * b.v[j] + (uint64_t)c;
      t[j] = (uint64_t)cur;
      c = cur >> 64;
    }
    u128 cur = (u128)t[NL] + (uint64_t)c;
    t[NL] = (uint64_t)cur;
    t[NL + 1] = (uint64_t)(cur >> 64);

    const uint64_t m = t[0] * PINV;
    cur = (u128)t[0] + (u128)m * P.v[0];
    c = cur >> 64;
#pragma GCC unroll 5
    for (int j = 1; j < NL; ++j) {
      cur = (u128)t[j] + (u128)m * P.v[j] + (uint64_t)c;
      t[j - 1] = (uint64_t)cur;
      c = cur >> 64;
    }
    cur = (u128)t[NL] + (uint64_t)c;
    t[NL - 1] = (uint64_t)cur;
    t[NL] = t[NL + 1] + (uint64_t)(cur >> 64);
  }
  Fp r;
  for (int i = 0; i < NL; ++i) r.v[i] = t[i];
  if (t[NL] || geq(r, P)) sub_nocheck(r, P);
  out = r;
}

static inline void mont_sqr(const Fp& a, Fp& out) { mont_mul(a, a, out); }

static void to_mont(const Fp& a, Fp& out) { mont_mul(a, R2, out); }
static void from_mont(const Fp& a, Fp& out) {
  Fp one = {{1, 0, 0, 0, 0, 0}};
  mont_mul(a, one, out);
}

// pow with big-endian byte exponent, base in Montgomery form.
static void mont_pow(const Fp& base, const uint8_t* exp, size_t exp_len,
                     Fp& out) {
  Fp acc = ONE_M;
  for (size_t i = 0; i < exp_len; ++i) {
    uint8_t byte = exp[i];
    for (int b = 7; b >= 0; --b) {
      mont_sqr(acc, acc);
      if ((byte >> b) & 1) mont_mul(acc, base, acc);
    }
  }
  out = acc;
}

static bool is_zero(const Fp& a) {
  for (int i = 0; i < NL; ++i)
    if (a.v[i]) return false;
  return true;
}

static bool eq(const Fp& a, const Fp& b) {
  for (int i = 0; i < NL; ++i)
    if (a.v[i] != b.v[i]) return false;
  return true;
}

// 64 big-endian bytes → canonical PLAIN-domain Fp (hash_to_field's hot
// shape): u = hi·2^384 + lo, and mont_mul(hi, R2) = hi·R²·R⁻¹ = hi·R =
// hi·2^384 mod p directly in the plain domain — one Montgomery multiply,
// no round-trips.  Shared by the full hash path and the device-offload
// front half so the parsing/reduction can never diverge.
static void bytes_be64_to_fp_plain(const uint8_t* in, Fp& out_plain) {
  Fp hi = {{0}}, lo, t;
  uint64_t h1 = 0, h0 = 0;
  for (int k = 0; k < 8; ++k) h1 = (h1 << 8) | in[k];
  for (int k = 8; k < 16; ++k) h0 = (h0 << 8) | in[k];
  hi.v[0] = h0;
  hi.v[1] = h1;
  for (int i = 0; i < NL; ++i) {
    uint64_t limb = 0;
    for (int k = 0; k < 8; ++k) limb = (limb << 8) | in[16 + 40 - 8 * i + k];
    lo.v[i] = limb;
  }
  while (geq(lo, P)) sub_nocheck(lo, P);  // lo < 2^384 < 10p: ≤ 10 rounds
  mont_mul(hi, R2, t);  // = hi·2^384 mod p, plain domain
  add_mod(t, lo, out_plain);
}

static void fp_to_bytes_be(const Fp& a, uint8_t out[48]) {
  for (int i = 0; i < NL; ++i) {
    uint64_t limb = a.v[NL - 1 - i];
    for (int k = 0; k < 8; ++k)
      out[i * 8 + k] = (uint8_t)(limb >> (56 - 8 * k));
  }
}

// ----------------------------------------------------------- parameters

static Fp A_M, B_M, Z_M;       // E' SSWU parameters (Montgomery)
static Fp NEG_B_OVER_A;        // -B/A
static Fp B_OVER_ZA;           // B/(Z*A)
static Fp EXC_CMP;             // (-1/Z)·R^{-1}: u is SSWU-exceptional iff
                               // mont_mul(u,u) == EXC_CMP (both sides /R)
static Fp FOUR_M;              // E: y^2 = x^3 + 4
static uint64_t H_EFF;         // effective cofactor (64-bit)
static std::vector<Fp> XNUM, XDEN, YNUM, YDEN;  // isogeny (Montgomery)
static uint8_t SQRT_EXP[48];   // (p+1)/4 big-endian
static uint8_t INV_EXP[48];    // p-2 big-endian
static bool INITED = false;

static void exp_from_p(uint8_t out[48], int add, int shift) {
  // out = (p + add) >> shift, big-endian 48 bytes (add may be negative;
  // p's low limb is large enough that no borrow propagates)
  uint64_t limbs[NL];
  std::memcpy(limbs, P.v, sizeof(limbs));
  if (add >= 0) {
    u128 carry = (u128)(uint64_t)add;
    for (int i = 0; i < NL && carry; ++i) {
      u128 cur = (u128)limbs[i] + (uint64_t)carry;
      limbs[i] = (uint64_t)cur;
      carry = cur >> 64;
    }
  } else {
    uint64_t sub = (uint64_t)(-add);
    if (limbs[0] >= sub) {
      limbs[0] -= sub;
    } else {
      limbs[0] -= sub;  // wraps
      for (int i = 1; i < NL; ++i) {
        if (limbs[i]--) break;
      }
    }
  }
  for (int s = 0; s < shift; ++s) {
    uint64_t c = 0;
    for (int i = NL - 1; i >= 0; --i) {
      uint64_t nc = limbs[i] & 1;
      limbs[i] = (limbs[i] >> 1) | (c << 63);
      c = nc;
    }
  }
  Fp tmp;
  std::memcpy(tmp.v, limbs, sizeof(limbs));
  fp_to_bytes_be(tmp, out);
}

static void mont_inv(const Fp& a, Fp& out) {
  mont_pow(a, INV_EXP, 48, out);
}

// ----------------------------------------------------------- curve (E)

struct Jac {
  Fp x, y, z;  // Montgomery; infinity <=> z == 0
};

static void jac_dbl(const Jac& p, Jac& out) {
  if (is_zero(p.z)) {
    out = p;
    return;
  }
  Fp a, b, c, d, e, f, t;
  mont_sqr(p.x, a);                 // A = X^2
  mont_sqr(p.y, b);                 // B = Y^2
  mont_sqr(b, c);                   // C = B^2
  add_mod(p.x, b, t);
  mont_sqr(t, d);
  sub_mod(d, a, d);
  sub_mod(d, c, d);
  add_mod(d, d, d);                 // D = 2((X+B)^2 - A - C)
  add_mod(a, a, e);
  add_mod(e, a, e);                 // E = 3A
  mont_sqr(e, f);                   // F = E^2
  Jac r;
  sub_mod(f, d, r.x);
  sub_mod(r.x, d, r.x);             // X3 = F - 2D
  Fp c8;
  add_mod(c, c, c8);
  add_mod(c8, c8, c8);
  add_mod(c8, c8, c8);              // 8C
  sub_mod(d, r.x, t);
  mont_mul(e, t, r.y);
  sub_mod(r.y, c8, r.y);            // Y3 = E(D - X3) - 8C
  mont_mul(p.y, p.z, t);
  add_mod(t, t, r.z);               // Z3 = 2YZ
  out = r;
}

static void jac_add(const Jac& p, const Jac& q, Jac& out) {
  if (is_zero(p.z)) {
    out = q;
    return;
  }
  if (is_zero(q.z)) {
    out = p;
    return;
  }
  Fp z1z1, z2z2, u1, u2, s1, s2, h, r, t;
  mont_sqr(p.z, z1z1);
  mont_sqr(q.z, z2z2);
  mont_mul(p.x, z2z2, u1);
  mont_mul(q.x, z1z1, u2);
  mont_mul(p.y, q.z, t);
  mont_mul(t, z2z2, s1);
  mont_mul(q.y, p.z, t);
  mont_mul(t, z1z1, s2);
  sub_mod(u2, u1, h);
  sub_mod(s2, s1, r);
  if (is_zero(h)) {
    if (is_zero(r)) {
      jac_dbl(p, out);
      return;
    }
    out.x = ONE_M;
    out.y = ONE_M;
    std::memset(out.z.v, 0, sizeof(out.z.v));
    return;
  }
  Fp i, j, v;
  add_mod(h, h, t);
  mont_sqr(t, i);                   // I = (2H)^2
  mont_mul(h, i, j);                // J = H*I
  add_mod(r, r, r);                 // r = 2(S2-S1)
  mont_mul(u1, i, v);               // V = U1*I
  Jac o;
  mont_sqr(r, o.x);
  sub_mod(o.x, j, o.x);
  sub_mod(o.x, v, o.x);
  sub_mod(o.x, v, o.x);             // X3 = r^2 - J - 2V
  sub_mod(v, o.x, t);
  mont_mul(r, t, o.y);
  mont_mul(s1, j, t);
  sub_mod(o.y, t, o.y);
  sub_mod(o.y, t, o.y);             // Y3 = r(V-X3) - 2 S1 J
  add_mod(p.z, q.z, t);
  mont_sqr(t, o.z);
  sub_mod(o.z, z1z1, o.z);
  sub_mod(o.z, z2z2, o.z);
  mont_mul(o.z, h, o.z);            // Z3 = ((Z1+Z2)^2 - Z1Z1 - Z2Z2) H
  out = o;
}

static void jac_mul_u64(const Jac& p, uint64_t k, Jac& out) {
  Jac acc;
  acc.x = ONE_M;
  acc.y = ONE_M;
  std::memset(acc.z.v, 0, sizeof(acc.z.v));
  bool started = false;
  for (int b = 63; b >= 0; --b) {
    if (started) jac_dbl(acc, acc);
    if ((k >> b) & 1) {
      if (started) {
        jac_add(acc, p, acc);
      } else {
        acc = p;
        started = true;
      }
    }
  }
  out = acc;
}

static void jac_to_affine(const Jac& p, Fp& x, Fp& y) {
  Fp zinv, z2, z3;
  mont_inv(p.z, zinv);
  mont_sqr(zinv, z2);
  mont_mul(z2, zinv, z3);
  mont_mul(p.x, z2, x);
  mont_mul(p.y, z3, y);
}

// ----------------------------------------------------------- SSWU + iso

static int parity(const Fp& a_mont) {
  Fp plain;
  from_mont(a_mont, plain);
  return (int)(plain.v[0] & 1);
}

static void sswu_map(const Fp& u_mont, int u_parity, Fp& x_out, Fp& y_out) {
  Fp u2, tv, d, x1, gx, y, t;
  mont_sqr(u_mont, u2);
  mont_mul(Z_M, u2, tv);            // tv = Z u^2
  mont_sqr(tv, d);
  add_mod(d, tv, d);                // d = Z^2 u^4 + Z u^2
  if (is_zero(d)) {
    x1 = B_OVER_ZA;
  } else {
    Fp dinv;
    mont_inv(d, dinv);
    add_mod(dinv, ONE_M, t);
    mont_mul(NEG_B_OVER_A, t, x1);  // (-B/A)(1 + 1/d)
  }
  // gx = x1^3 + A x1 + B
  Fp x1sq;
  mont_sqr(x1, x1sq);
  mont_mul(x1sq, x1, gx);
  mont_mul(A_M, x1, t);
  add_mod(gx, t, gx);
  add_mod(gx, B_M, gx);
  mont_pow(gx, SQRT_EXP, 48, y);
  Fp ysq;
  mont_sqr(y, ysq);
  if (!eq(ysq, gx)) {
    Fp x2, gx2;
    mont_mul(tv, x1, x2);           // x2 = Z u^2 x1
    Fp x2sq;
    mont_sqr(x2, x2sq);
    mont_mul(x2sq, x2, gx2);
    mont_mul(A_M, x2, t);
    add_mod(gx2, t, gx2);
    add_mod(gx2, B_M, gx2);
    mont_pow(gx2, SQRT_EXP, 48, y);
    x1 = x2;
  }
  if (parity(y) != u_parity) {
    Fp zero = {{0}};
    sub_mod(zero, y, y);
  }
  x_out = x1;
  y_out = y;
}

static void horner(const std::vector<Fp>& c, const Fp& x, Fp& out) {
  Fp acc = c.back();
  for (int i = (int)c.size() - 2; i >= 0; --i) {
    mont_mul(acc, x, acc);
    add_mod(acc, c[i], acc);
  }
  out = acc;
}

static bool iso_map(const Fp& x, const Fp& y, Fp& xo, Fp& yo) {
  Fp xn, xd, yn, yd;
  horner(XNUM, x, xn);
  horner(XDEN, x, xd);
  horner(YNUM, x, yn);
  horner(YDEN, x, yd);
  if (is_zero(xd) || is_zero(yd)) return false;  // kernel → infinity
  Fp prod, inv, t;
  mont_mul(xd, yd, prod);
  mont_inv(prod, inv);
  mont_mul(xn, yd, t);
  mont_mul(t, inv, xo);
  mont_mul(yn, xd, t);
  mont_mul(t, inv, t);
  mont_mul(y, t, yo);
  return true;
}

// ----------------------------------------------------------- xmd + hash

static void expand_xmd(const uint8_t* msg, size_t msg_len, const uint8_t* dst,
                       size_t dst_len, uint8_t out[128]) {
  // RFC 9380 §5.3.1, SHA-256, len_in_bytes = 128 (two 64-byte elements)
  uint8_t buf[64 + 1024 + 2 + 1 + 256 + 1];
  size_t off = 0;
  std::memset(buf, 0, 64);
  off = 64;
  std::memcpy(buf + off, msg, msg_len);
  off += msg_len;
  buf[off++] = 0;
  buf[off++] = 128;
  buf[off++] = 0;
  std::memcpy(buf + off, dst, dst_len);
  off += dst_len;
  buf[off++] = (uint8_t)dst_len;
  uint8_t b0[32];
  cess_sha256(buf, off, b0);

  uint8_t bi[32];
  uint8_t block[32 + 1 + 256 + 1];
  // b1 = H(b0 || 1 || dst')
  std::memcpy(block, b0, 32);
  block[32] = 1;
  std::memcpy(block + 33, dst, dst_len);
  block[33 + dst_len] = (uint8_t)dst_len;
  cess_sha256(block, 34 + dst_len, bi);
  std::memcpy(out, bi, 32);
  for (int i = 2; i <= 4; ++i) {
    for (int k = 0; k < 32; ++k) block[k] = b0[k] ^ bi[k];
    block[32] = (uint8_t)i;
    std::memcpy(block + 33, dst, dst_len);
    block[33 + dst_len] = (uint8_t)dst_len;
    cess_sha256(block, 34 + dst_len, bi);
    std::memcpy(out + 32 * (i - 1), bi, 32);
  }
}

// 128 uniform bytes → two canonical big-endian u values + predicate
// flags, with two mont_muls per element: mont_mul(hi, R2) computes
// hi·2^384 mod p directly in the plain domain, and the exceptional test
// compares mont_mul(u, u) = u²·R^{-1} against the precomputed
// (-1/Z)·R^{-1} (tv2 = Z²u⁴ + Zu² ≡ 0 ⟺ u = 0 or u² = −1/Z).
static uint8_t u_pair_from_uniform(const uint8_t uniform[128],
                                   uint8_t out_u[96]) {
  uint8_t flags = 0;
  for (int e = 0; e < 2; ++e) {
    Fp u, usq;
    bytes_be64_to_fp_plain(uniform + 64 * e, u);
    fp_to_bytes_be(u, out_u + 48 * e);
    if (u.v[0] & 1) flags |= (uint8_t)(1u << (2 * e));
    mont_mul(u, u, usq);  // = u²·R^{-1}
    if (eq(usq, EXC_CMP) || is_zero(u))
      flags |= (uint8_t)(1u << (2 * e + 1));
  }
  return flags;
}

static void hash_one(const uint8_t* msg, size_t msg_len, const uint8_t* dst,
                     size_t dst_len, uint8_t out[96]) {
  uint8_t uniform[128];
  expand_xmd(msg, msg_len, dst, dst_len, uniform);
  Jac acc;
  std::memset(acc.z.v, 0, sizeof(acc.z.v));
  acc.x = ONE_M;
  acc.y = ONE_M;
  for (int e = 0; e < 2; ++e) {
    Fp u, um;
    bytes_be64_to_fp_plain(uniform + 64 * e, u);
    to_mont(u, um);
    int up = (int)(u.v[0] & 1);
    Fp sx, sy, ex, ey;
    sswu_map(um, up, sx, sy);
    if (!iso_map(sx, sy, ex, ey)) continue;  // point at infinity: skip add
    Jac pt;
    pt.x = ex;
    pt.y = ey;
    pt.z = ONE_M;
    Jac sum;
    jac_add(acc, pt, sum);
    acc = sum;
  }
  Jac cleared;
  jac_mul_u64(acc, H_EFF, cleared);
  if (is_zero(cleared.z)) {
    std::memset(out, 0, 96);  // infinity marker (all-zero x,y)
    return;
  }
  Fp ax, ay, axp, ayp;
  jac_to_affine(cleared, ax, ay);
  from_mont(ax, axp);
  from_mont(ay, ayp);
  fp_to_bytes_be(axp, out);
  fp_to_bytes_be(ayp, out + 48);
}

// ----------------------------------------------------------- decompression

// One lane of cess_blsmap_g1_decompress_batch: y = a^((p+1)/4) for
// a = x³ + 4 (curve) or a = x itself, the check y² = a, then the root
// the sign names.  Returns 1 where a has no root.
static uint8_t sqrt_lane(const uint8_t in[48], bool curve, int sign,
                         uint8_t out[48]) {
  Fp a, am, y, ysq;
  for (int i = 0; i < NL; ++i) {
    uint64_t limb = 0;
    for (int k = 0; k < 8; ++k) limb = (limb << 8) | in[48 - 8 * (i + 1) + k];
    a.v[i] = limb;
  }
  to_mont(a, am);
  if (curve) {
    Fp x2;
    mont_sqr(am, x2);
    mont_mul(x2, am, x2);
    add_mod(x2, FOUR_M, am);
  }
  mont_pow(am, SQRT_EXP, 48, y);
  mont_sqr(y, ysq);
  if (!eq(ysq, am)) {
    std::memset(out, 0, 48);
    return 1;
  }
  from_mont(y, y);
  if (sign >= 0) {
    Fp neg = P;
    sub_nocheck(neg, y);  // p − y, as the scalar path computes it
    if ((sign != 0) != !geq(neg, y)) y = neg;
  }
  fp_to_bytes_be(y, out);
  return 0;
}

// Runs work(lo, hi) over [0, n): on the calling thread below two lanes a
// thread, so a small batch pays no thread start-up; else in n_threads
// threads of near-equal slices.
template <class Work>
static void split_lanes(uint64_t n, uint64_t n_threads, const Work& work) {
  if (n_threads <= 1 || n < 2 * n_threads) {
    work(0, n);
    return;
  }
  std::vector<std::thread> pool;
  uint64_t chunk = (n + n_threads - 1) / n_threads;
  for (uint64_t t = 0; t < n_threads; ++t) {
    uint64_t lo = t * chunk;
    uint64_t hi = lo + chunk < n ? lo + chunk : n;
    if (lo >= hi) break;
    pool.emplace_back(work, lo, hi);
  }
  for (auto& th : pool) th.join();
}

}  // namespace blsmap

// ----------------------------------------------------------- exports

CESS_EXPORT int cess_blsmap_init(
    const uint8_t* p48, const uint8_t* a48, const uint8_t* b48,
    uint64_t z_small, const uint8_t* xnum, uint64_t n_xnum,
    const uint8_t* xden, uint64_t n_xden, const uint8_t* ynum,
    uint64_t n_ynum, const uint8_t* yden, uint64_t n_yden, uint64_t h_eff) {
  using namespace blsmap;
  // parse big-endian p into little-endian limbs
  for (int i = 0; i < NL; ++i) {
    uint64_t limb = 0;
    for (int k = 0; k < 8; ++k) limb = (limb << 8) | p48[48 - 8 * (i + 1) + k];
    P.v[i] = limb;
  }
  if (!(P.v[0] & 1)) return 1;  // p must be odd
  // PINV = -p^{-1} mod 2^64 (Newton)
  uint64_t inv = 1;
  for (int k = 0; k < 6; ++k) inv *= 2 - P.v[0] * inv;
  PINV = (uint64_t)(0 - inv);
  // R2 = 2^768 mod p by repeated doubling of 1 … start from R mod p:
  Fp acc = {{1, 0, 0, 0, 0, 0}};
  for (int i = 0; i < 2 * NL * 64; ++i) add_mod(acc, acc, acc);
  R2 = acc;
  Fp one = {{1, 0, 0, 0, 0, 0}};
  to_mont(one, ONE_M);
  exp_from_p(SQRT_EXP, 1, 2);
  exp_from_p(INV_EXP, -2, 0);

  auto load = [](const uint8_t* b, Fp& out) {
    Fp plain;
    for (int i = 0; i < NL; ++i) {
      uint64_t limb = 0;
      for (int k = 0; k < 8; ++k) limb = (limb << 8) | b[48 - 8 * (i + 1) + k];
      plain.v[i] = limb;
    }
    to_mont(plain, out);
  };
  load(a48, A_M);
  load(b48, B_M);
  Fp zp = {{z_small, 0, 0, 0, 0, 0}};
  to_mont(zp, Z_M);
  Fp four = {{4, 0, 0, 0, 0, 0}};
  to_mont(four, FOUR_M);
  H_EFF = h_eff;

  // -B/A and B/(Z A)
  Fp ainv, za, zainv, zero = {{0}};
  mont_inv(A_M, ainv);
  mont_mul(B_M, ainv, NEG_B_OVER_A);
  sub_mod(zero, NEG_B_OVER_A, NEG_B_OVER_A);
  mont_mul(Z_M, A_M, za);
  mont_inv(za, zainv);
  mont_mul(B_M, zainv, B_OVER_ZA);
  {
    // EXC_CMP = (-1/Z)·R^{-1}: from_mont twice takes Z^{-1}·R down to
    // Z^{-1}·R^{-1}, then negate mod p.
    Fp zinv, t, zero = {{0}};
    mont_inv(Z_M, zinv);        // Z^{-1}·R
    from_mont(zinv, t);         // Z^{-1}
    from_mont(t, t);            // Z^{-1}·R^{-1}
    sub_mod(zero, t, EXC_CMP);  // −Z^{-1}·R^{-1}
  }

  auto load_vec = [&](const uint8_t* b, uint64_t n, std::vector<Fp>& out) {
    out.resize(n);
    for (uint64_t i = 0; i < n; ++i) load(b + 48 * i, out[i]);
  };
  load_vec(xnum, n_xnum, XNUM);
  load_vec(xden, n_xden, XDEN);
  load_vec(ynum, n_ynum, YNUM);
  load_vec(yden, n_yden, YDEN);
  INITED = true;
  return 0;
}

// Device-offload front half: expand_message_xmd + hash_to_field only.
// The TPU runs the SSWU map itself (cess_tpu/ops/h2c.py); the host
// supplies, per message, the two reduced field elements u0, u1
// (canonical big-endian 48 B each) plus the predicate bits the device
// kernel cannot derive from loose limbs without a canonical pass it
// would rather skip:
//   bit0: sgn0(u0)   bit1: sswu-exceptional(u0)  [Z²u⁴ + Zu² ≡ 0]
//   bit2: sgn0(u1)   bit3: sswu-exceptional(u1)
CESS_EXPORT int cess_blsmap_xmd_u_batch(
    const uint8_t* msgs, const uint64_t* offsets, uint64_t n,
    const uint8_t* dst, uint64_t dst_len, uint8_t* out_u,
    uint8_t* out_flags, uint64_t n_threads) {
  using namespace blsmap;
  if (!INITED) return 1;
  if (dst_len > 255) return 2;
  for (uint64_t i = 0; i < n; ++i) {
    if (offsets[i + 1] - offsets[i] > 1024) return 3;  // xmd buffer bound
  }
  auto work = [&](uint64_t lo, uint64_t hi) {
    for (uint64_t i = lo; i < hi; ++i) {
      const uint8_t* msg = msgs + offsets[i];
      size_t len = (size_t)(offsets[i + 1] - offsets[i]);
      uint8_t uniform[128];
      expand_xmd(msg, len, dst, dst_len, uniform);
      out_flags[i] = u_pair_from_uniform(uniform, out_u + 96 * i);
    }
  };
  split_lanes(n, n_threads, work);
  return 0;
}

// Indexed variant: messages are name ‖ '/' ‖ LE64(index) — the podr2
// chunk-point framing (cess_tpu/ops/podr2.py chunk_point) — assembled
// here so Python never materialises millions of byte strings.
CESS_EXPORT int cess_blsmap_xmd_u_indexed(
    const uint8_t* names, const uint64_t* name_offsets, uint64_t n_names,
    const uint32_t* name_ids, const uint64_t* indices, uint64_t n,
    const uint8_t* dst, uint64_t dst_len, uint8_t* out_u,
    uint8_t* out_flags, uint64_t n_threads) {
  using namespace blsmap;
  if (!INITED) return 1;
  if (dst_len > 255) return 2;
  for (uint64_t k = 0; k < n_names; ++k) {
    if (name_offsets[k + 1] - name_offsets[k] > 1000) return 3;
  }
  for (uint64_t i = 0; i < n; ++i) {
    if (name_ids[i] >= n_names) return 4;
  }
  auto work = [&](uint64_t lo, uint64_t hi) {
    uint8_t msg[1024 + 9];
    for (uint64_t i = lo; i < hi; ++i) {
      const uint64_t k = name_ids[i];
      const size_t nlen =
          (size_t)(name_offsets[k + 1] - name_offsets[k]);
      std::memcpy(msg, names + name_offsets[k], nlen);
      msg[nlen] = '/';
      uint64_t idx = indices[i];
      for (int b = 0; b < 8; ++b) msg[nlen + 1 + b] = (uint8_t)(idx >> (8 * b));
      uint8_t uniform[128];
      expand_xmd(msg, nlen + 9, dst, dst_len, uniform);
      out_flags[i] = u_pair_from_uniform(uniform, out_u + 96 * i);
    }
  };
  split_lanes(n, n_threads, work);
  return 0;
}

CESS_EXPORT int cess_blsmap_hash_g1_batch(
    const uint8_t* msgs, const uint64_t* offsets, uint64_t n,
    const uint8_t* dst, uint64_t dst_len, uint8_t* out, uint64_t n_threads) {
  using namespace blsmap;
  if (!INITED) return 1;
  if (dst_len > 255) return 2;
  for (uint64_t i = 0; i < n; ++i) {
    if (offsets[i + 1] - offsets[i] > 1024) return 3;  // xmd buffer bound
  }
  auto work = [&](uint64_t lo, uint64_t hi) {
    for (uint64_t i = lo; i < hi; ++i) {
      const uint8_t* msg = msgs + offsets[i];
      size_t len = (size_t)(offsets[i + 1] - offsets[i]);
      hash_one(msg, len, dst, dst_len, out + 96 * i);
    }
  };
  split_lanes(n, n_threads, work);
  return 0;
}

// Compressed-G1 decompression's square roots (cess_tpu/ops/bls12_381.py
// g1_decompress_batch, which validates the encodings first).  `xs` holds
// n canonical big-endian x coordinates, flag bits stripped; per lane
// y = (x³ + 4)^((p+1)/4), status 1 where y² ≠ x³ + 4 (x is on no point),
// and out_y the root the lane's sign byte names: p − y where the sign
// disagrees with y > p − y (the ZCash flag rule).  With `signs` null the
// inputs are the radicands themselves and out_y the root
// a^((p+1)/4) as is: fp_sqrt_batch's form.
CESS_EXPORT int cess_blsmap_g1_decompress_batch(
    const uint8_t* xs, const uint8_t* signs, uint64_t n, uint8_t* out_y,
    uint8_t* status, uint64_t n_threads) {
  using namespace blsmap;
  if (!INITED) return 1;
  auto work = [&](uint64_t lo, uint64_t hi) {
    for (uint64_t i = lo; i < hi; ++i) {
      status[i] = sqrt_lane(xs + 48 * i, signs != nullptr,
                            signs ? signs[i] : -1, out_y + 48 * i);
    }
  };
  split_lanes(n, n_threads, work);
  return 0;
}
