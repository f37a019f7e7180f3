// BLS12-381 batch signature verification — host CPU path.
//
// Role: (1) the MEASURED same-host baseline for bench.py (replaces the
// round-2 hard-coded blst estimate — VERDICT round 2, "what's missing" #2)
// and (2) the small-batch / odd-shape fallback verifier the beacon node
// routes gossip-latency work to (SURVEY.md §2.7 item 1; the reference
// links Supranational blst for this role, crypto/bls/src/impls/blst.rs:36-118).
//
// This is a from-scratch C++ port of OUR pure-Python oracle
// (lighthouse_tpu/crypto/bls/{fields,curves,pairing,hash_to_curve}.py):
// same tower convention (Fp2=Fp[u]/(u^2+1), Fp6=Fp2[v]/(v^3-(1+u)),
// Fp12=Fp6[w]/(w^2-v)), same batch equation
//     prod_i e([r_i] agg_pk_i, H(m_i)) * e(-g1, sum_i [r_i] sig_i) == 1,
// same h2c ciphersuite (BLS12381G2_XMD:SHA-256_SSWU_RO_POP_).
// Differences from the oracle are performance-only: 6x64 Montgomery
// arithmetic with __int128 CIOS, Jacobian group law, Montgomery batch
// inversion across the Miller-loop line denominators, and the x-chain
// final exponentiation (the same chain the device kernel uses,
// ops/pairing.py — verified there against the generic exponent).
//
// Single-threaded by design: the box the driver measures on has one core,
// and the baseline number should be the honest one-core figure.

#include <cstdint>
#include <cstring>
#include <vector>

// ---------------------------------------------------------------------------
// SHA-256 (compact, public-domain-style from FIPS 180-4)
// ---------------------------------------------------------------------------

namespace sha256 {

static const uint32_t K[64] = {
    0x428a2f98, 0x71374491, 0xb5c0fbcf, 0xe9b5dba5, 0x3956c25b, 0x59f111f1,
    0x923f82a4, 0xab1c5ed5, 0xd807aa98, 0x12835b01, 0x243185be, 0x550c7dc3,
    0x72be5d74, 0x80deb1fe, 0x9bdc06a7, 0xc19bf174, 0xe49b69c1, 0xefbe4786,
    0x0fc19dc6, 0x240ca1cc, 0x2de92c6f, 0x4a7484aa, 0x5cb0a9dc, 0x76f988da,
    0x983e5152, 0xa831c66d, 0xb00327c8, 0xbf597fc7, 0xc6e00bf3, 0xd5a79147,
    0x06ca6351, 0x14292967, 0x27b70a85, 0x2e1b2138, 0x4d2c6dfc, 0x53380d13,
    0x650a7354, 0x766a0abb, 0x81c2c92e, 0x92722c85, 0xa2bfe8a1, 0xa81a664b,
    0xc24b8b70, 0xc76c51a3, 0xd192e819, 0xd6990624, 0xf40e3585, 0x106aa070,
    0x19a4c116, 0x1e376c08, 0x2748774c, 0x34b0bcb5, 0x391c0cb3, 0x4ed8aa4a,
    0x5b9cca4f, 0x682e6ff3, 0x748f82ee, 0x78a5636f, 0x84c87814, 0x8cc70208,
    0x90befffa, 0xa4506ceb, 0xbef9a3f7, 0xc67178f2};

struct Ctx {
  uint32_t h[8];
  uint8_t buf[64];
  uint64_t len;
  size_t fill;
};

static inline uint32_t rotr(uint32_t x, int n) {
  return (x >> n) | (x << (32 - n));
}

static void init(Ctx* c) {
  static const uint32_t H0[8] = {0x6a09e667, 0xbb67ae85, 0x3c6ef372,
                                 0xa54ff53a, 0x510e527f, 0x9b05688c,
                                 0x1f83d9ab, 0x5be0cd19};
  memcpy(c->h, H0, sizeof(H0));
  c->len = 0;
  c->fill = 0;
}

static void block(Ctx* c, const uint8_t* p) {
  uint32_t w[64];
  for (int i = 0; i < 16; i++)
    w[i] = (uint32_t(p[4 * i]) << 24) | (uint32_t(p[4 * i + 1]) << 16) |
           (uint32_t(p[4 * i + 2]) << 8) | uint32_t(p[4 * i + 3]);
  for (int i = 16; i < 64; i++) {
    uint32_t s0 = rotr(w[i - 15], 7) ^ rotr(w[i - 15], 18) ^ (w[i - 15] >> 3);
    uint32_t s1 = rotr(w[i - 2], 17) ^ rotr(w[i - 2], 19) ^ (w[i - 2] >> 10);
    w[i] = w[i - 16] + s0 + w[i - 7] + s1;
  }
  uint32_t a = c->h[0], b = c->h[1], cc = c->h[2], d = c->h[3], e = c->h[4],
           f = c->h[5], g = c->h[6], h = c->h[7];
  for (int i = 0; i < 64; i++) {
    uint32_t S1 = rotr(e, 6) ^ rotr(e, 11) ^ rotr(e, 25);
    uint32_t ch = (e & f) ^ (~e & g);
    uint32_t t1 = h + S1 + ch + K[i] + w[i];
    uint32_t S0 = rotr(a, 2) ^ rotr(a, 13) ^ rotr(a, 22);
    uint32_t maj = (a & b) ^ (a & cc) ^ (b & cc);
    uint32_t t2 = S0 + maj;
    h = g; g = f; f = e; e = d + t1;
    d = cc; cc = b; b = a; a = t1 + t2;
  }
  c->h[0] += a; c->h[1] += b; c->h[2] += cc; c->h[3] += d;
  c->h[4] += e; c->h[5] += f; c->h[6] += g; c->h[7] += h;
}

static void update(Ctx* c, const uint8_t* p, size_t n) {
  c->len += n;
  while (n) {
    size_t take = 64 - c->fill;
    if (take > n) take = n;
    memcpy(c->buf + c->fill, p, take);
    c->fill += take;
    p += take;
    n -= take;
    if (c->fill == 64) {
      block(c, c->buf);
      c->fill = 0;
    }
  }
}

static void final(Ctx* c, uint8_t out[32]) {
  uint64_t bits = c->len * 8;
  uint8_t pad = 0x80;
  update(c, &pad, 1);
  uint8_t z = 0;
  while (c->fill != 56) update(c, &z, 1);
  uint8_t lenb[8];
  for (int i = 0; i < 8; i++) lenb[i] = uint8_t(bits >> (56 - 8 * i));
  update(c, lenb, 8);
  for (int i = 0; i < 8; i++) {
    out[4 * i] = uint8_t(c->h[i] >> 24);
    out[4 * i + 1] = uint8_t(c->h[i] >> 16);
    out[4 * i + 2] = uint8_t(c->h[i] >> 8);
    out[4 * i + 3] = uint8_t(c->h[i]);
  }
}

static void digest(const uint8_t* p, size_t n, uint8_t out[32]) {
  Ctx c;
  init(&c);
  update(&c, p, n);
  final(&c, out);
}

}  // namespace sha256

// ---------------------------------------------------------------------------
// Fp: 6x64-bit Montgomery arithmetic, R = 2^384
// ---------------------------------------------------------------------------

typedef unsigned __int128 u128;

static const uint64_t P_LIMBS[6] = {
    0xb9feffffffffaaabULL, 0x1eabfffeb153ffffULL, 0x6730d2a0f6b0f624ULL,
    0x64774b84f38512bfULL, 0x4b1ba7b6434bacd7ULL, 0x1a0111ea397fe69aULL};

static uint64_t N0;            // -p^-1 mod 2^64
static uint64_t R2_LIMBS[6];   // 2^768 mod p (to-Montgomery factor)

struct fp {
  uint64_t l[6];
};

static inline bool fp_raw_ge(const uint64_t* a, const uint64_t* b) {
  for (int i = 5; i >= 0; i--) {
    if (a[i] != b[i]) return a[i] > b[i];
  }
  return true;
}

static inline void fp_raw_sub(uint64_t* r, const uint64_t* a,
                              const uint64_t* b) {
  u128 borrow = 0;
  for (int i = 0; i < 6; i++) {
    u128 d = (u128)a[i] - b[i] - (uint64_t)borrow;
    r[i] = (uint64_t)d;
    borrow = (d >> 64) ? 1 : 0;
  }
}

static inline fp fp_add(const fp& a, const fp& b) {
  fp r;
  u128 c = 0;
  for (int i = 0; i < 6; i++) {
    c += (u128)a.l[i] + b.l[i];
    r.l[i] = (uint64_t)c;
    c >>= 64;
  }
  if (c || fp_raw_ge(r.l, P_LIMBS)) {
    uint64_t t[6];
    fp_raw_sub(t, r.l, P_LIMBS);
    memcpy(r.l, t, sizeof(t));
  }
  return r;
}

static inline fp fp_sub(const fp& a, const fp& b) {
  fp r;
  u128 borrow = 0;
  for (int i = 0; i < 6; i++) {
    u128 d = (u128)a.l[i] - b.l[i] - (uint64_t)borrow;
    r.l[i] = (uint64_t)d;
    borrow = (d >> 64) ? 1 : 0;
  }
  if (borrow) {
    u128 c = 0;
    for (int i = 0; i < 6; i++) {
      c += (u128)r.l[i] + P_LIMBS[i];
      r.l[i] = (uint64_t)c;
      c >>= 64;
    }
  }
  return r;
}

static inline bool fp_is_zero(const fp& a) {
  uint64_t acc = 0;
  for (int i = 0; i < 6; i++) acc |= a.l[i];
  return acc == 0;
}

static inline fp fp_neg(const fp& a) {
  if (fp_is_zero(a)) return a;
  fp r;
  fp_raw_sub(r.l, P_LIMBS, a.l);
  return r;
}

static inline bool fp_eq(const fp& a, const fp& b) {
  uint64_t acc = 0;
  for (int i = 0; i < 6; i++) acc |= a.l[i] ^ b.l[i];
  return acc == 0;
}

// CIOS Montgomery multiplication.
static fp fp_mul(const fp& a, const fp& b) {
  uint64_t T[8] = {0, 0, 0, 0, 0, 0, 0, 0};
  for (int i = 0; i < 6; i++) {
    u128 c = 0;
    for (int j = 0; j < 6; j++) {
      c = (u128)a.l[j] * b.l[i] + T[j] + (uint64_t)c;
      T[j] = (uint64_t)c;
      c >>= 64;
    }
    c = (u128)T[6] + (uint64_t)c;
    T[6] = (uint64_t)c;
    T[7] = (uint64_t)(c >> 64);
    uint64_t m = T[0] * N0;
    c = (u128)m * P_LIMBS[0] + T[0];
    c >>= 64;
    for (int j = 1; j < 6; j++) {
      c = (u128)m * P_LIMBS[j] + T[j] + (uint64_t)c;
      T[j - 1] = (uint64_t)c;
      c >>= 64;
    }
    c = (u128)T[6] + (uint64_t)c;
    T[5] = (uint64_t)c;
    T[6] = T[7] + (uint64_t)(c >> 64);
  }
  fp r;
  memcpy(r.l, T, 6 * sizeof(uint64_t));
  if (T[6] || fp_raw_ge(r.l, P_LIMBS)) {
    uint64_t t[6];
    fp_raw_sub(t, r.l, P_LIMBS);
    memcpy(r.l, t, sizeof(t));
  }
  return r;
}

static inline fp fp_sqr(const fp& a) { return fp_mul(a, a); }

static fp FP_ZERO;
static fp FP_ONE;  // R mod p (Montgomery one)

static fp fp_from_raw(const uint64_t* limbs) {
  fp t;
  memcpy(t.l, limbs, sizeof(t.l));
  fp r2;
  memcpy(r2.l, R2_LIMBS, sizeof(r2.l));
  return fp_mul(t, r2);  // a * R^2 * R^-1 = a*R
}

static void fp_to_raw(const fp& a, uint64_t* out) {
  fp one_raw;
  memset(one_raw.l, 0, sizeof(one_raw.l));
  one_raw.l[0] = 1;
  fp r = fp_mul(a, one_raw);  // a*R * 1 * R^-1 = a
  memcpy(out, r.l, sizeof(r.l));
}

// 48-byte big-endian -> Montgomery fp. Returns false if >= p.
static bool fp_from_be(const uint8_t* be, fp* out) {
  uint64_t raw[6];
  for (int i = 0; i < 6; i++) {
    uint64_t v = 0;
    for (int j = 0; j < 8; j++) v = (v << 8) | be[(5 - i) * 8 + j];
    raw[i] = v;
  }
  if (fp_raw_ge(raw, P_LIMBS)) return false;
  *out = fp_from_raw(raw);
  return true;
}

static void fp_to_be(const fp& a, uint8_t* be) {
  uint64_t raw[6];
  fp_to_raw(a, raw);
  for (int i = 0; i < 6; i++)
    for (int j = 0; j < 8; j++) be[(5 - i) * 8 + j] = uint8_t(raw[i] >> (56 - 8 * j));
}

static inline fp fp_mul_small(const fp& a, uint64_t k) {
  // k is tiny (2, 3, 8, 12...): repeated addition tree.
  fp r = FP_ZERO;
  fp base = a;
  while (k) {
    if (k & 1) r = fp_add(r, base);
    base = fp_add(base, base);
    k >>= 1;
  }
  return r;
}

// Exponentiation by a big-endian byte exponent.
static fp fp_pow_be(const fp& a, const uint8_t* e, size_t n) {
  fp r = FP_ONE;
  bool started = false;
  for (size_t i = 0; i < n; i++) {
    for (int b = 7; b >= 0; b--) {
      if (started) r = fp_sqr(r);
      if ((e[i] >> b) & 1) {
        if (started) r = fp_mul(r, a);
        else { r = a; started = true; }
      }
    }
  }
  return started ? r : FP_ONE;
}

static uint8_t P_MINUS_2_BE[48];
static uint8_t P_MINUS_1_OVER_2_BE[48];

static fp fp_inv(const fp& a) { return fp_pow_be(a, P_MINUS_2_BE, 48); }

static bool fp_is_square(const fp& a) {
  if (fp_is_zero(a)) return true;
  fp l = fp_pow_be(a, P_MINUS_1_OVER_2_BE, 48);
  return fp_eq(l, FP_ONE);
}

static bool fp_sgn0(const fp& a) {
  uint64_t raw[6];
  fp_to_raw(a, raw);
  return raw[0] & 1;
}

static bool fp_is_lex_largest(const fp& y) {
  // y > (p-1)/2
  uint64_t raw[6];
  fp_to_raw(y, raw);
  uint64_t half[6];  // (p-1)/2
  u128 borrow = 0;
  uint64_t pm1[6];
  memcpy(pm1, P_LIMBS, sizeof(pm1));
  pm1[0] -= 1;  // p is odd, no borrow
  (void)borrow;
  for (int i = 0; i < 6; i++) {
    half[i] = pm1[i] >> 1;
    if (i < 5) half[i] |= pm1[i + 1] << 63;
  }
  // raw > half ?
  for (int i = 5; i >= 0; i--) {
    if (raw[i] != half[i]) return raw[i] > half[i];
  }
  return false;
}

// ---------------------------------------------------------------------------
// Fp2 = Fp[u] / (u^2 + 1)
// ---------------------------------------------------------------------------

struct fp2 {
  fp c0, c1;
};

static fp2 FP2_ZERO_C, FP2_ONE_C;

static inline fp2 add(const fp2& a, const fp2& b) {
  return {fp_add(a.c0, b.c0), fp_add(a.c1, b.c1)};
}
static inline fp2 sub(const fp2& a, const fp2& b) {
  return {fp_sub(a.c0, b.c0), fp_sub(a.c1, b.c1)};
}
static inline fp2 neg(const fp2& a) { return {fp_neg(a.c0), fp_neg(a.c1)}; }
static inline fp2 conj(const fp2& a) { return {a.c0, fp_neg(a.c1)}; }
static inline fp2 mul(const fp2& a, const fp2& b) {
  fp t0 = fp_mul(a.c0, b.c0);
  fp t1 = fp_mul(a.c1, b.c1);
  fp s = fp_mul(fp_add(a.c0, a.c1), fp_add(b.c0, b.c1));
  return {fp_sub(t0, t1), fp_sub(fp_sub(s, t0), t1)};
}
static inline fp2 sqr(const fp2& a) {
  fp s = fp_mul(fp_add(a.c0, a.c1), fp_sub(a.c0, a.c1));
  fp t = fp_mul(a.c0, a.c1);
  return {s, fp_add(t, t)};
}
static inline fp2 mul_small(const fp2& a, uint64_t k) {
  return {fp_mul_small(a.c0, k), fp_mul_small(a.c1, k)};
}
static inline bool is_zero(const fp2& a) {
  return fp_is_zero(a.c0) && fp_is_zero(a.c1);
}
static inline bool eq(const fp2& a, const fp2& b) {
  return fp_eq(a.c0, b.c0) && fp_eq(a.c1, b.c1);
}
static fp2 inv(const fp2& a) {
  fp norm = fp_add(fp_sqr(a.c0), fp_sqr(a.c1));
  fp ni = fp_inv(norm);
  return {fp_mul(a.c0, ni), fp_neg(fp_mul(a.c1, ni))};
}
// (a0 + a1 u) * (1 + u)
static inline fp2 mul_by_xi(const fp2& a) {
  return {fp_sub(a.c0, a.c1), fp_add(a.c0, a.c1)};
}

static fp2 fp2_pow_be(const fp2& a, const uint8_t* e, size_t n) {
  fp2 r = FP2_ONE_C;
  bool started = false;
  for (size_t i = 0; i < n; i++) {
    for (int b = 7; b >= 0; b--) {
      if (started) r = sqr(r);
      if ((e[i] >> b) & 1) {
        if (started) r = mul(r, a);
        else { r = a; started = true; }
      }
    }
  }
  return started ? r : FP2_ONE_C;
}

static bool fp2_sgn0(const fp2& a) {
  bool s0 = fp_sgn0(a.c0);
  bool z0 = fp_is_zero(a.c0);
  bool s1 = fp_sgn0(a.c1);
  return s0 | (z0 & s1);
}

static bool fp2_is_lex_largest(const fp2& y) {
  if (!fp_is_zero(y.c1)) return fp_is_lex_largest(y.c1);
  return fp_is_lex_largest(y.c0);
}

// Fp2 square root via two Fp square roots (p ≡ 3 mod 4 so
// sqrt_fp(a) = a^((p+1)/4)): for a = a0 + a1 u with a1 != 0, let
// s = sqrt(a0^2 + a1^2) (the norm is a square when a is), d = (a0+s)/2
// or (a0-s)/2 (whichever is a square; 4d^2 - a1^2 = 4 a0 d), then
// sqrt(a) = x0 + (a1 / 2x0) u with x0 = sqrt(d). Much cheaper than the
// oracle's 762-bit Tonelli–Shanks (two or three ~381-bit Fp pows and no
// inversion, instead of 762-bit Fp2 pows) and verified against it by
// construction: we check r^2 == a before returning.
static uint8_t P_PLUS_1_OVER_4_BE[48];
static uint8_t P_MINUS_3_OVER_4_BE[48];

static bool fp_sqrt(const fp& a, fp* out) {
  fp c = fp_pow_be(a, P_PLUS_1_OVER_4_BE, 48);
  if (!fp_eq(fp_sqr(c), a)) return false;
  *out = c;
  return true;
}

static fp FP_HALF;  // 1/2 mod p

static bool fp2_sqrt(const fp2& a, fp2* out) {
  if (is_zero(a)) {
    *out = FP2_ZERO_C;
    return true;
  }
  if (fp_is_zero(a.c1)) {
    fp r;
    if (fp_sqrt(a.c0, &r)) {
      *out = {r, FP_ZERO};
      return true;
    }
    if (fp_sqrt(fp_neg(a.c0), &r)) {
      *out = {FP_ZERO, r};  // (r u)^2 = -r^2
      return true;
    }
    return false;
  }
  fp norm = fp_add(fp_sqr(a.c0), fp_sqr(a.c1));
  fp s;
  if (!fp_sqrt(norm, &s)) return false;  // norm non-square: a non-square
  // x0 = d^((p+1)/4) = c*d with c = d^((p-3)/4); when x0^2 == d (d is
  // nonzero here, as a1 is), c*x0 = d^((p-1)/2) = 1: c is 1/x0, no inverse.
  fp d = fp_mul(fp_add(a.c0, s), FP_HALF);
  fp c = fp_pow_be(d, P_MINUS_3_OVER_4_BE, 48);
  fp x0 = fp_mul(c, d);
  if (!fp_eq(fp_sqr(x0), d)) {
    d = fp_mul(fp_sub(a.c0, s), FP_HALF);
    c = fp_pow_be(d, P_MINUS_3_OVER_4_BE, 48);
    x0 = fp_mul(c, d);
    if (!fp_eq(fp_sqr(x0), d)) return false;
  }
  fp x1 = fp_mul(fp_mul(a.c1, c), FP_HALF);
  fp2 r = {x0, x1};
  if (!eq(sqr(r), a)) return false;
  *out = r;
  return true;
}

// ---------------------------------------------------------------------------
// Fp6 = Fp2[v] / (v^3 - (1+u)),  Fp12 = Fp6[w] / (w^2 - v)
// ---------------------------------------------------------------------------

struct fp6 {
  fp2 c0, c1, c2;
};
struct fp12 {
  fp6 c0, c1;
};

static fp6 FP6_ZERO_C, FP6_ONE_C;
static fp12 FP12_ONE_C;

static inline fp6 add(const fp6& a, const fp6& b) {
  return {add(a.c0, b.c0), add(a.c1, b.c1), add(a.c2, b.c2)};
}
static inline fp6 sub(const fp6& a, const fp6& b) {
  return {sub(a.c0, b.c0), sub(a.c1, b.c1), sub(a.c2, b.c2)};
}
static inline fp6 neg(const fp6& a) {
  return {neg(a.c0), neg(a.c1), neg(a.c2)};
}
static fp6 mul(const fp6& a, const fp6& b) {
  fp2 t0 = mul(a.c0, b.c0);
  fp2 t1 = mul(a.c1, b.c1);
  fp2 t2 = mul(a.c2, b.c2);
  fp2 c0 = add(t0, mul_by_xi(sub(mul(add(a.c1, a.c2), add(b.c1, b.c2)),
                                 add(t1, t2))));
  fp2 c1 = add(sub(mul(add(a.c0, a.c1), add(b.c0, b.c1)), add(t0, t1)),
               mul_by_xi(t2));
  fp2 c2 = add(sub(mul(add(a.c0, a.c2), add(b.c0, b.c2)), add(t0, t2)), t1);
  return {c0, c1, c2};
}
static inline fp6 mul_by_v(const fp6& a) {
  return {mul_by_xi(a.c2), a.c0, a.c1};
}
static fp6 inv(const fp6& a) {
  fp2 c0 = sub(sqr(a.c0), mul_by_xi(mul(a.c1, a.c2)));
  fp2 c1 = sub(mul_by_xi(sqr(a.c2)), mul(a.c0, a.c1));
  fp2 c2 = sub(sqr(a.c1), mul(a.c0, a.c2));
  fp2 t = add(mul_by_xi(add(mul(a.c2, c1), mul(a.c1, c2))), mul(a.c0, c0));
  fp2 ti = inv(t);
  return {mul(c0, ti), mul(c1, ti), mul(c2, ti)};
}

static fp12 mul(const fp12& a, const fp12& b) {
  fp6 t0 = mul(a.c0, b.c0);
  fp6 t1 = mul(a.c1, b.c1);
  fp6 c0 = add(t0, mul_by_v(t1));
  fp6 c1 = sub(mul(add(a.c0, a.c1), add(b.c0, b.c1)), add(t0, t1));
  return {c0, c1};
}
static inline fp12 sqr(const fp12& a) { return mul(a, a); }
static inline fp12 conj(const fp12& a) { return {a.c0, neg(a.c1)}; }
static fp12 inv(const fp12& a) {
  fp6 t = sub(mul(a.c0, a.c0), mul_by_v(mul(a.c1, a.c1)));
  fp6 ti = inv(t);
  return {mul(a.c0, ti), neg(mul(a.c1, ti))};
}
static bool is_one(const fp12& a) {
  return eq(a.c0.c0, FP2_ONE_C) && is_zero(a.c0.c1) && is_zero(a.c0.c2) &&
         is_zero(a.c1.c0) && is_zero(a.c1.c1) && is_zero(a.c1.c2);
}

// Frobenius: gamma[j] = xi^(j*(p-1)/6); computed at init.
static fp2 GAMMA1[6];

static fp12 frob(const fp12& a) {
  fp2 e0 = conj(a.c0.c0);
  fp2 e1 = mul(conj(a.c0.c1), GAMMA1[2]);
  fp2 e2 = mul(conj(a.c0.c2), GAMMA1[4]);
  fp2 f0 = mul(conj(a.c1.c0), GAMMA1[1]);
  fp2 f1 = mul(conj(a.c1.c1), GAMMA1[3]);
  fp2 f2 = mul(conj(a.c1.c2), GAMMA1[5]);
  return {{e0, e1, e2}, {f0, f1, f2}};
}
static fp12 frob_n(const fp12& a, int n) {
  fp12 r = a;
  for (int i = 0; i < n; i++) r = frob(r);
  return r;
}

// f^e for positive big-endian byte exponent (generic square-and-multiply).
static fp12 fp12_pow_be(const fp12& a, const uint8_t* e, size_t n) {
  fp12 r = FP12_ONE_C;
  bool started = false;
  for (size_t i = 0; i < n; i++) {
    for (int b = 7; b >= 0; b--) {
      if (started) r = sqr(r);
      if ((e[i] >> b) & 1) {
        if (started) r = mul(r, a);
        else { r = a; started = true; }
      }
    }
  }
  return started ? r : FP12_ONE_C;
}

// ---------------------------------------------------------------------------
// Generic Jacobian EC over F in {fp, fp2} (port of oracle curves.py)
// ---------------------------------------------------------------------------

static inline fp field_one(const fp*) { return FP_ONE; }
static inline fp2 field_one(const fp2*) { return FP2_ONE_C; }
static inline fp field_zero(const fp*) { return FP_ZERO; }
static inline fp2 field_zero(const fp2*) { return FP2_ZERO_C; }
static inline fp add(const fp& a, const fp& b) { return fp_add(a, b); }
static inline fp sub(const fp& a, const fp& b) { return fp_sub(a, b); }
static inline fp mul(const fp& a, const fp& b) { return fp_mul(a, b); }
static inline fp sqr_f(const fp& a) { return fp_sqr(a); }
static inline fp2 sqr_f(const fp2& a) { return sqr(a); }
static inline fp neg_f(const fp& a) { return fp_neg(a); }
static inline fp2 neg_f(const fp2& a) { return neg(a); }
static inline fp mul_small_f(const fp& a, uint64_t k) { return fp_mul_small(a, k); }
static inline fp2 mul_small_f(const fp2& a, uint64_t k) { return mul_small(a, k); }
static inline bool is_zero_f(const fp& a) { return fp_is_zero(a); }
static inline bool is_zero_f(const fp2& a) { return is_zero(a); }
static inline bool eq_f(const fp& a, const fp& b) { return fp_eq(a, b); }
static inline bool eq_f(const fp2& a, const fp2& b) { return eq(a, b); }
static inline fp inv_f(const fp& a) { return fp_inv(a); }
static inline fp2 inv_f(const fp2& a) { return inv(a); }

template <typename F>
struct jac {
  F X, Y, Z;
};

template <typename F>
static jac<F> jac_infinity() {
  F* tag = nullptr;
  return {field_one(tag), field_one(tag), field_zero(tag)};
}

template <typename F>
static bool jac_is_infinity(const jac<F>& p) {
  return is_zero_f(p.Z);
}

template <typename F>
static jac<F> jac_double(const jac<F>& p) {
  if (is_zero_f(p.Z) || is_zero_f(p.Y)) return jac_infinity<F>();
  F A = sqr_f(p.X);
  F B = sqr_f(p.Y);
  F C = sqr_f(B);
  F D = mul_small_f(sub(sub(sqr_f(add(p.X, B)), A), C), 2);
  F E = mul_small_f(A, 3);
  F Fv = sqr_f(E);
  F X3 = sub(Fv, mul_small_f(D, 2));
  F Y3 = sub(mul(E, sub(D, X3)), mul_small_f(C, 8));
  F Z3 = mul(mul_small_f(p.Y, 2), p.Z);
  return {X3, Y3, Z3};
}

template <typename F>
static jac<F> jac_add(const jac<F>& p1, const jac<F>& p2) {
  if (is_zero_f(p1.Z)) return p2;
  if (is_zero_f(p2.Z)) return p1;
  F Z1Z1 = sqr_f(p1.Z);
  F Z2Z2 = sqr_f(p2.Z);
  F U1 = mul(p1.X, Z2Z2);
  F U2 = mul(p2.X, Z1Z1);
  F S1 = mul(mul(p1.Y, p2.Z), Z2Z2);
  F S2 = mul(mul(p2.Y, p1.Z), Z1Z1);
  if (eq_f(U1, U2)) {
    if (eq_f(S1, S2)) return jac_double(p1);
    return jac_infinity<F>();
  }
  F H = sub(U2, U1);
  F I = sqr_f(mul_small_f(H, 2));
  F J = mul(H, I);
  F rr = mul_small_f(sub(S2, S1), 2);
  F V = mul(U1, I);
  F X3 = sub(sub(sqr_f(rr), J), mul_small_f(V, 2));
  F Y3 = sub(mul(rr, sub(V, X3)), mul_small_f(mul(S1, J), 2));
  F Z3 = mul(sub(sub(sqr_f(add(p1.Z, p2.Z)), Z1Z1), Z2Z2), H);
  return {X3, Y3, Z3};
}

template <typename F>
static jac<F> jac_neg(const jac<F>& p) {
  return {p.X, neg_f(p.Y), p.Z};
}

// Scalar multiplication, little-endian 64-bit limbs.
template <typename F>
static jac<F> jac_mul(const jac<F>& p, const uint64_t* k, int nk) {
  jac<F> acc = jac_infinity<F>();
  jac<F> addp = p;
  for (int i = 0; i < nk; i++) {
    uint64_t w = k[i];
    for (int b = 0; b < 64; b++) {
      if (w & 1) acc = jac_add(acc, addp);
      w >>= 1;
      // Skip the final doubling chain once no bits remain anywhere above.
      addp = jac_double(addp);
    }
  }
  return acc;
}

template <typename F>
static void jac_to_affine(const jac<F>& p, F* x, F* y, bool* inf) {
  if (is_zero_f(p.Z)) {
    *inf = true;
    return;
  }
  *inf = false;
  F zi = inv_f(p.Z);
  F zi2 = sqr_f(zi);
  *x = mul(p.X, zi2);
  *y = mul(p.Y, mul(zi2, zi));
}

// Jacobian equality without inversions: X1 Z2^2 == X2 Z1^2, Y1 Z2^3 == Y2 Z1^3.
template <typename F>
static bool jac_eq(const jac<F>& a, const jac<F>& b) {
  bool ia = is_zero_f(a.Z), ib = is_zero_f(b.Z);
  if (ia || ib) return ia == ib;
  F za2 = sqr_f(a.Z), zb2 = sqr_f(b.Z);
  if (!eq_f(mul(a.X, zb2), mul(b.X, za2))) return false;
  return eq_f(mul(a.Y, mul(zb2, b.Z)), mul(b.Y, mul(za2, a.Z)));
}

// ---------------------------------------------------------------------------
// Curve constants / init
// ---------------------------------------------------------------------------

static const uint64_t BLS_X_ABS_U64 = 0xd201000000010000ULL;

static fp2 B2_COEFF;    // 4*(1+u)
static fp B1_COEFF;     // 4
static jac<fp> NEG_G1;  // -(G1 generator), Montgomery affine as Z=1 jacobian
static fp2 PSI_CX, PSI_CY;

// SSWU / isogeny constants (RFC 9380 §8.8.2 + App E.3, same values as
// our constants.py; hex big-endian).
static fp2 SSWU_A, SSWU_B, SSWU_Z;
static fp2 ISO_XN[4], ISO_XD[3], ISO_YN[4], ISO_YD[4];

static const char* G1_GEN_X_HEX =
    "17f1d3a73197d7942695638c4fa9ac0fc3688c4f9774b905a14e3a3f171bac586c55e83f"
    "f97a1aeffb3af00adb22c6bb";
static const char* G1_GEN_Y_HEX =
    "08b3f481e3aaa0f1a09e30ed741d8ae4fcf5e095d5d00af600db18cb2c04b3edd03cc744"
    "a2888ae40caa232946c5e7e1";

struct Fp2Hex {
  const char* c0;
  const char* c1;
};

// 3-isogeny coefficient tables (ascending degree), values from RFC 9380
// Appendix E.3 (mirrored in lighthouse_tpu/crypto/bls/constants.py where
// they are structurally cross-validated by tests).
static const Fp2Hex ISO_XN_HEX[4] = {
    {"5c759507e8e333ebb5b7a9a47d7ed8532c52d39fd3a042a88b58423c50ae15d5c2638e343d9c71c6238aaaaaaaa97d6",
     "5c759507e8e333ebb5b7a9a47d7ed8532c52d39fd3a042a88b58423c50ae15d5c2638e343d9c71c6238aaaaaaaa97d6"},
    {"0",
     "11560bf17baa99bc32126fced787c88f984f87adf7ae0c7f9a208c6b4f20a4181472aaa9cb8d555526a9ffffffffc71a"},
    {"11560bf17baa99bc32126fced787c88f984f87adf7ae0c7f9a208c6b4f20a4181472aaa9cb8d555526a9ffffffffc71e",
     "8ab05f8bdd54cde190937e76bc3e447cc27c3d6fbd7063fcd104635a790520c0a395554e5c6aaaa9354ffffffffe38d"},
    {"171d6541fa38ccfaed6dea691f5fb614cb14b4e7f4e810aa22d6108f142b85757098e38d0f671c7188e2aaaaaaaa5ed1",
     "0"},
};
static const Fp2Hex ISO_XD_HEX[3] = {
    {"0",
     "1a0111ea397fe69a4b1ba7b6434bacd764774b84f38512bf6730d2a0f6b0f6241eabfffeb153ffffb9feffffffffaa63"},
    {"c",
     "1a0111ea397fe69a4b1ba7b6434bacd764774b84f38512bf6730d2a0f6b0f6241eabfffeb153ffffb9feffffffffaa9f"},
    {"1", "0"},
};
static const Fp2Hex ISO_YN_HEX[4] = {
    {"1530477c7ab4113b59a4c18b076d11930f7da5d4a07f649bf54439d87d27e500fc8c25ebf8c92f6812cfc71c71c6d706",
     "1530477c7ab4113b59a4c18b076d11930f7da5d4a07f649bf54439d87d27e500fc8c25ebf8c92f6812cfc71c71c6d706"},
    {"0",
     "5c759507e8e333ebb5b7a9a47d7ed8532c52d39fd3a042a88b58423c50ae15d5c2638e343d9c71c6238aaaaaaaa97be"},
    {"11560bf17baa99bc32126fced787c88f984f87adf7ae0c7f9a208c6b4f20a4181472aaa9cb8d555526a9ffffffffc71c",
     "8ab05f8bdd54cde190937e76bc3e447cc27c3d6fbd7063fcd104635a790520c0a395554e5c6aaaa9354ffffffffe38f"},
    {"124c9ad43b6cf79bfbf7043de3811ad0761b0f37a1e26286b0e977c69aa274524e79097a56dc4bd9e1b371c71c718b10",
     "0"},
};
static const Fp2Hex ISO_YD_HEX[4] = {
    {"1a0111ea397fe69a4b1ba7b6434bacd764774b84f38512bf6730d2a0f6b0f6241eabfffeb153ffffb9feffffffffa8fb",
     "1a0111ea397fe69a4b1ba7b6434bacd764774b84f38512bf6730d2a0f6b0f6241eabfffeb153ffffb9feffffffffa8fb"},
    {"0",
     "1a0111ea397fe69a4b1ba7b6434bacd764774b84f38512bf6730d2a0f6b0f6241eabfffeb153ffffb9feffffffffa9d3"},
    {"12",
     "1a0111ea397fe69a4b1ba7b6434bacd764774b84f38512bf6730d2a0f6b0f6241eabfffeb153ffffb9feffffffffaa99"},
    {"1", "0"},
};

static fp fp_from_hex(const char* h) {
  uint8_t be[48];
  memset(be, 0, sizeof(be));
  size_t n = strlen(h);
  // right-align hex nibbles
  for (size_t i = 0; i < n; i++) {
    char c = h[n - 1 - i];
    uint8_t v = (c >= '0' && c <= '9') ? c - '0'
               : (c >= 'a' && c <= 'f') ? c - 'a' + 10
               : c - 'A' + 10;
    be[47 - i / 2] |= (i % 2) ? (v << 4) : v;
  }
  fp out;
  fp_from_be(be, &out);
  return out;
}

static fp2 fp2_from_hex(const Fp2Hex& h) {
  return {fp_from_hex(h.c0), fp_from_hex(h.c1)};
}

// Hard-part exponent e = (|x|+1)^2 / 3 (the x-chain decomposition
// e*(x+p)*(x^2+p^2-1)+1 = (p^4-p^2+1)/r; verified in ops/pairing.py).
static uint8_t E_EXP_BE[16];

static void compute_e_exp() {
  u128 z1 = (u128)BLS_X_ABS_U64 + 1;
  u128 sq = z1 * z1;  // fits: (2^63.8)^2 < 2^128
  u128 e = sq / 3;
  for (int i = 0; i < 16; i++) E_EXP_BE[15 - i] = uint8_t(e >> (8 * i));
}

static bool INIT_DONE = false;

extern "C" int blscpu_init() {
  if (INIT_DONE) return 0;
  // n0 = -p^-1 mod 2^64 (Newton).
  uint64_t pinv = 1;
  for (int i = 0; i < 6; i++) pinv *= 2 - P_LIMBS[0] * pinv;
  N0 = ~pinv + 1;  // -pinv
  // R2 = 2^768 mod p by repeated doubling of (2^384 mod p)... start from
  // 1 and double 768 times (straightforward, init-only).
  uint64_t acc[6] = {1, 0, 0, 0, 0, 0};
  for (int d = 0; d < 768; d++) {
    // acc <<= 1 mod p
    uint64_t carry = 0;
    for (int i = 0; i < 6; i++) {
      uint64_t nc = acc[i] >> 63;
      acc[i] = (acc[i] << 1) | carry;
      carry = nc;
    }
    if (carry || fp_raw_ge(acc, P_LIMBS)) {
      uint64_t t[6];
      fp_raw_sub(t, acc, P_LIMBS);
      memcpy(acc, t, sizeof(t));
    }
  }
  memcpy(R2_LIMBS, acc, sizeof(acc));
  memset(FP_ZERO.l, 0, sizeof(FP_ZERO.l));
  {
    uint64_t one_raw[6] = {1, 0, 0, 0, 0, 0};
    FP_ONE = fp_from_raw(one_raw);
  }
  FP2_ZERO_C = {FP_ZERO, FP_ZERO};
  FP2_ONE_C = {FP_ONE, FP_ZERO};
  FP6_ZERO_C = {FP2_ZERO_C, FP2_ZERO_C, FP2_ZERO_C};
  FP6_ONE_C = {FP2_ONE_C, FP2_ZERO_C, FP2_ZERO_C};
  FP12_ONE_C = {FP6_ONE_C, FP6_ZERO_C};

  // p-2, (p-1)/2 as big-endian bytes.
  {
    uint64_t pm2[6];
    memcpy(pm2, P_LIMBS, sizeof(pm2));
    pm2[0] -= 2;
    uint64_t ph[6];
    uint64_t pm1[6];
    memcpy(pm1, P_LIMBS, sizeof(pm1));
    pm1[0] -= 1;
    for (int i = 0; i < 6; i++) {
      ph[i] = pm1[i] >> 1;
      if (i < 5) ph[i] |= pm1[i + 1] << 63;
    }
    // (p+1)/4: p ≡ 3 mod 4, so (p+1)/4 = (p-1)/2 - (p-3)/4... compute
    // directly: (p+1) >> 2 (p+1 = ...aaac, no carry out of the top limb).
    uint64_t pp1[6];
    memcpy(pp1, P_LIMBS, sizeof(pp1));
    pp1[0] += 1;
    uint64_t pq[6];
    for (int i = 0; i < 6; i++) {
      pq[i] = pp1[i] >> 2;
      if (i < 5) pq[i] |= pp1[i + 1] << 62;
    }
    for (int i = 0; i < 6; i++)
      for (int j = 0; j < 8; j++) {
        P_MINUS_2_BE[47 - (8 * i + j)] = uint8_t(pm2[i] >> (8 * j));
        P_MINUS_1_OVER_2_BE[47 - (8 * i + j)] = uint8_t(ph[i] >> (8 * j));
        P_PLUS_1_OVER_4_BE[47 - (8 * i + j)] = uint8_t(pq[i] >> (8 * j));
        P_MINUS_3_OVER_4_BE[47 - (8 * i + j)] =
            uint8_t((pq[i] - (i == 0)) >> (8 * j));  // low limb odd
      }
  }
  compute_e_exp();
  FP_HALF = fp_inv(fp_mul_small(FP_ONE, 2));

  fp2 xi = {FP_ONE, FP_ONE};

  // GAMMA1[j] = xi^(j*(p-1)/6): gamma1 = xi^((p-1)/6), then products.
  {
    // (p-1)/6 via division by 6 (p-1 divisible by 6).
    uint64_t pm1[6];
    memcpy(pm1, P_LIMBS, sizeof(pm1));
    pm1[0] -= 1;
    uint64_t q[6];
    u128 rem = 0;
    for (int i = 5; i >= 0; i--) {
      u128 cur = (rem << 64) | pm1[i];
      q[i] = (uint64_t)(cur / 6);
      rem = cur % 6;
    }
    uint8_t e_be[48];
    for (int i = 0; i < 6; i++)
      for (int j = 0; j < 8; j++)
        e_be[47 - (8 * i + j)] = uint8_t(q[i] >> (8 * j));
    GAMMA1[0] = FP2_ONE_C;
    GAMMA1[1] = fp2_pow_be(xi, e_be, 48);
    for (int j = 2; j < 6; j++) GAMMA1[j] = mul(GAMMA1[j - 1], GAMMA1[1]);
  }
  PSI_CX = inv(GAMMA1[2]);  // 1 / xi^((p-1)/3)
  PSI_CY = inv(GAMMA1[3]);  // 1 / xi^((p-1)/2)

  B1_COEFF = fp_mul_small(FP_ONE, 4);
  B2_COEFF = {fp_mul_small(FP_ONE, 4), fp_mul_small(FP_ONE, 4)};

  SSWU_A = {FP_ZERO, fp_mul_small(FP_ONE, 240)};
  SSWU_B = {fp_mul_small(FP_ONE, 1012), fp_mul_small(FP_ONE, 1012)};
  SSWU_Z = {fp_neg(fp_mul_small(FP_ONE, 2)), fp_neg(FP_ONE)};

  for (int i = 0; i < 4; i++) ISO_XN[i] = fp2_from_hex(ISO_XN_HEX[i]);
  for (int i = 0; i < 3; i++) ISO_XD[i] = fp2_from_hex(ISO_XD_HEX[i]);
  for (int i = 0; i < 4; i++) ISO_YN[i] = fp2_from_hex(ISO_YN_HEX[i]);
  for (int i = 0; i < 4; i++) ISO_YD[i] = fp2_from_hex(ISO_YD_HEX[i]);

  {
    fp gx = fp_from_hex(G1_GEN_X_HEX);
    fp gy = fp_from_hex(G1_GEN_Y_HEX);
    NEG_G1 = {gx, fp_neg(gy), FP_ONE};
  }
  INIT_DONE = true;
  return 0;
}

// ---------------------------------------------------------------------------
// psi endomorphism + subgroup / cofactor machinery (oracle curves.py)
// ---------------------------------------------------------------------------

static jac<fp2> g2_psi(const jac<fp2>& p) {
  // Affine: psi(x, y) = (cx*conj(x), cy*conj(y)); on Jacobian coordinates
  // conjugate X, Y, Z and scale X/Y (conj is a field automorphism).
  return {mul(PSI_CX, conj(p.X)), mul(PSI_CY, conj(p.Y)), conj(p.Z)};
}

static bool g2_on_curve_affine(const fp2& x, const fp2& y) {
  fp2 lhs = sqr(y);
  fp2 rhs = add(mul(sqr(x), x), B2_COEFF);
  return eq(lhs, rhs);
}

static bool g1_on_curve_affine(const fp& x, const fp& y) {
  fp lhs = fp_sqr(y);
  fp rhs = fp_add(fp_mul(fp_sqr(x), x), B1_COEFF);
  return fp_eq(lhs, rhs);
}

// P in G2 iff psi(P) == [x]P (x negative: psi(P) == -[|x|]P) — Bowe's
// check, the same boolean as blst's (oracle curves.py g2_in_subgroup).
static bool g2_in_subgroup(const jac<fp2>& p) {
  if (jac_is_infinity(p)) return true;
  uint64_t k[1] = {BLS_X_ABS_U64};
  jac<fp2> xp = jac_mul(p, k, 1);
  return jac_eq(g2_psi(p), jac_neg(xp));
}

// [z]P for the sparse BLS parameter z = |x| (Hamming weight 6):
// 64 doublings + 6 additions.
static jac<fp2> g2_mul_z(const jac<fp2>& p) {
  jac<fp2> acc = jac_infinity<fp2>();
  jac<fp2> addp = p;
  uint64_t z = BLS_X_ABS_U64;
  while (z) {
    if (z & 1) acc = jac_add(acc, addp);
    z >>= 1;
    if (z) addp = jac_double(addp);
  }
  return acc;
}

// Clear cofactor via the psi decomposition
// [x^2-x-1]P + [x-1]psi(P) + psi^2([2]P), x = -z:
//   = [z]([z]P) + [z]P - P - [z]psi(P) - psi(P) + psi^2([2]P)
// — every scalar multiply rides the weight-6 z chain
// (cross-validated against h_eff in tests/test_bls_curves.py and against
// the oracle's generic h_eff multiply in tests/test_native_bls.py).
static jac<fp2> g2_clear_cofactor(const jac<fp2>& p) {
  jac<fp2> zp = g2_mul_z(p);
  jac<fp2> a = jac_add(jac_add(g2_mul_z(zp), zp), jac_neg(p));
  jac<fp2> psip = g2_psi(p);
  jac<fp2> b = jac_neg(jac_add(g2_mul_z(psip), psip));
  jac<fp2> c = g2_psi(g2_psi(jac_double(p)));
  return jac_add(jac_add(a, b), c);
}

// ---------------------------------------------------------------------------
// hash_to_curve (RFC 9380, BLS12381G2_XMD:SHA-256_SSWU_RO_POP_)
// ---------------------------------------------------------------------------

static const char DST[] = "BLS_SIG_BLS12381G2_XMD:SHA-256_SSWU_RO_POP_";
static const size_t DST_LEN = sizeof(DST) - 1;

static void expand_message_xmd(const uint8_t* msg, size_t msg_len,
                               const uint8_t* dst, size_t dst_len,
                               uint8_t* out, size_t len_in_bytes) {
  // ell <= 255 enforced by caller (256 bytes here -> ell = 8);
  // dst_len <= 255 (RFC 9380 §5.3.3 long-DST hashing is the caller's
  // job; every ciphersuite DST we use is short).
  size_t ell = (len_in_bytes + 31) / 32;
  uint8_t b0[32];
  {
    sha256::Ctx c;
    sha256::init(&c);
    uint8_t zpad[64] = {0};
    sha256::update(&c, zpad, 64);
    sha256::update(&c, msg, msg_len);
    uint8_t lib[2] = {uint8_t(len_in_bytes >> 8), uint8_t(len_in_bytes)};
    sha256::update(&c, lib, 2);
    uint8_t zero = 0;
    sha256::update(&c, &zero, 1);
    sha256::update(&c, dst, dst_len);
    uint8_t dlen = (uint8_t)dst_len;
    sha256::update(&c, &dlen, 1);
    sha256::final(&c, b0);
  }
  uint8_t bi[32];
  for (size_t i = 1; i <= ell; i++) {
    sha256::Ctx c;
    sha256::init(&c);
    if (i == 1) {
      sha256::update(&c, b0, 32);
    } else {
      uint8_t x[32];
      for (int j = 0; j < 32; j++) x[j] = b0[j] ^ bi[j];
      sha256::update(&c, x, 32);
    }
    uint8_t idx = (uint8_t)i;
    sha256::update(&c, &idx, 1);
    sha256::update(&c, dst, dst_len);
    uint8_t dlen = (uint8_t)dst_len;
    sha256::update(&c, &dlen, 1);
    sha256::final(&c, bi);
    size_t off = (i - 1) * 32;
    size_t take = len_in_bytes - off < 32 ? len_in_bytes - off : 32;
    memcpy(out + off, bi, take);
  }
}

// 64-byte big-endian -> fp (mod p): reduce a 512-bit value.
static fp fp_from_be64_mod(const uint8_t* be) {
  // Split v = hi*2^128 + lo384? Simpler: Horner over bytes in Montgomery
  // domain: acc = acc*256 + byte. 64 iterations of cheap ops (init-free).
  fp acc = FP_ZERO;
  fp b256 = fp_mul_small(FP_ONE, 256);
  for (int i = 0; i < 64; i++) {
    acc = fp_mul(acc, b256);
    acc = fp_add(acc, fp_mul_small(FP_ONE, be[i]));
  }
  return acc;
}

static void sswu_g2(const fp2& u, fp2* xo, fp2* yo) {
  fp2 zu2 = mul(SSWU_Z, sqr(u));
  fp2 tv = add(sqr(zu2), zu2);
  fp2 x1;
  if (is_zero(tv)) {
    x1 = mul(SSWU_B, inv(mul(SSWU_Z, SSWU_A)));
  } else {
    x1 = mul(mul(neg(SSWU_B), inv(SSWU_A)), add(FP2_ONE_C, inv(tv)));
  }
  fp2 gx1 = add(mul(add(sqr(x1), SSWU_A), x1), SSWU_B);
  fp2 y1;
  fp2 x, y;
  if (fp2_sqrt(gx1, &y1)) {
    x = x1;
    y = y1;
  } else {
    fp2 x2 = mul(zu2, x1);
    fp2 gx2 = add(mul(add(sqr(x2), SSWU_A), x2), SSWU_B);
    fp2 y2;
    fp2_sqrt(gx2, &y2);  // guaranteed square when gx1 is not
    x = x2;
    y = y2;
  }
  if (fp2_sgn0(u) != fp2_sgn0(y)) y = neg(y);
  *xo = x;
  *yo = y;
}

static fp2 horner(const fp2* coeffs, int n, const fp2& x) {
  fp2 acc = coeffs[n - 1];
  for (int i = n - 2; i >= 0; i--) acc = add(mul(acc, x), coeffs[i]);
  return acc;
}

// E2' point -> E2 (3-isogeny); returns infinity when x hits the kernel.
static jac<fp2> iso_map(const fp2& x, const fp2& y) {
  fp2 xn = horner(ISO_XN, 4, x);
  fp2 xd = horner(ISO_XD, 3, x);
  fp2 yn = horner(ISO_YN, 4, x);
  fp2 yd = horner(ISO_YD, 4, x);
  if (is_zero(xd) || is_zero(yd)) return jac_infinity<fp2>();
  // Jacobian embedding without inversions: with Z = xd*yd,
  // X = xn/xd -> xn*yd * Z / ... use (X, Y, Z) = (xn*xd*yd^2? ) —
  // simplest correct: x_aff = xn/xd, y_aff = y*yn/yd. Set Z = xd*yd,
  // then X = x_aff*Z^2 = xn*xd*yd^2, Y = y_aff*Z^3 = y*yn*xd^3*yd^2.
  fp2 Z = mul(xd, yd);
  fp2 yd2 = sqr(yd);
  fp2 X = mul(mul(xn, xd), yd2);
  fp2 xd2 = sqr(xd);
  fp2 Y = mul(mul(mul(y, yn), mul(xd2, xd)), yd2);
  return {X, Y, Z};
}

static jac<fp2> hash_to_g2_jac_dst(const uint8_t* msg, size_t msg_len,
                                   const uint8_t* dst, size_t dst_len) {
  uint8_t uni[256];
  expand_message_xmd(msg, msg_len, dst, dst_len, uni, 256);
  fp2 u0 = {fp_from_be64_mod(uni), fp_from_be64_mod(uni + 64)};
  fp2 u1 = {fp_from_be64_mod(uni + 128), fp_from_be64_mod(uni + 192)};
  fp2 x0, y0, x1, y1;
  sswu_g2(u0, &x0, &y0);
  sswu_g2(u1, &x1, &y1);
  jac<fp2> q0 = iso_map(x0, y0);
  jac<fp2> q1 = iso_map(x1, y1);
  return g2_clear_cofactor(jac_add(q0, q1));
}

static jac<fp2> hash_to_g2_jac(const uint8_t* msg, size_t msg_len) {
  return hash_to_g2_jac_dst(msg, msg_len, (const uint8_t*)DST, DST_LEN);
}

// ---------------------------------------------------------------------------
// Pairing: multi-Miller loop (affine steps + Montgomery batch inversion)
// ---------------------------------------------------------------------------

// Batch inversion (Montgomery's trick) over fp2.
static void fp2_batch_inv(std::vector<fp2>& v) {
  size_t n = v.size();
  if (n == 0) return;
  std::vector<fp2> prefix(n);
  fp2 acc = FP2_ONE_C;
  for (size_t i = 0; i < n; i++) {
    prefix[i] = acc;
    acc = mul(acc, v[i]);
  }
  fp2 ainv = inv(acc);
  for (size_t i = n; i-- > 0;) {
    fp2 vi = v[i];
    v[i] = mul(ainv, prefix[i]);
    ainv = mul(ainv, vi);
  }
}

struct MillerPair {
  fp px, py;      // G1 affine
  fp2 qx, qy;     // G2 affine (twist coords)
  fp2 tx, ty;     // running T
};

// Sparse line value: a = xi*py (w^0 slot), b = slope*xt - yt (w^3 slot,
// i.e. v^1 of the w-part), c = -slope*px (w^5 slot, v^2 of the w-part).
struct LineVal {
  fp2 a, b, c;
};

static LineVal line_value(const fp2& xt, const fp2& yt, const fp2& slope,
                          const fp& px, const fp& py) {
  fp2 a = {fp_mul(FP_ONE, py), fp_mul(FP_ONE, py)};  // (1+u)*py
  fp2 b = sub(mul(slope, xt), yt);
  fp2 ns = neg(slope);
  fp2 c = {fp_mul(ns.c0, px), fp_mul(ns.c1, px)};
  return {a, b, c};
}

// f * line, exploiting the ((a,0,0),(0,b,c)) sparsity: 13 fp2 muls
// instead of the 18 of a generic fp12 multiply. Derivation: with
// l0 = (a,0,0), l1 = (0,b,c):
//   t0 = f0*l0 = (f00 a, f01 a, f02 a)                       (3 muls)
//   t1 = f1*l1 : (g0,g1,g2)*(0,b,c) = (xi*(g1 c + g2 b),
//                 xi*(g2 c) + g0 b, g0 c + g1 b)             (6 muls)
//   c1 = (f0+f1)(l0+l1) - t0 - t1, with l0+l1 = (a,b,c):
//        computed via the same sparse shape plus the extra a-column
//        folded in as s*(a) on each coefficient... generic 6-mul
//        Karatsuba fp6 would redo b,c work, so expand directly:
//        (s0,s1,s2)*(a,b,c) with s = f0+f1 — schoolbook sparse using
//        only 4 additional muls for the a-column after reusing the
//        b/c structure costs the same as a fresh 6-mul Karatsuba;
//        we just do the 6-mul Karatsuba fp6 mul (well-tested path).
static fp12 mul_by_line(const fp12& f, const LineVal& l) {
  fp6 l0 = {l.a, FP2_ZERO_C, FP2_ZERO_C};
  fp6 l1 = {FP2_ZERO_C, l.b, l.c};
  // t0 = f0 * l0 (3 muls)
  fp6 t0 = {mul(f.c0.c0, l.a), mul(f.c0.c1, l.a), mul(f.c0.c2, l.a)};
  // t1 = f1 * l1 (6 muls, sparse first column)
  const fp2& g0 = f.c1.c0;
  const fp2& g1 = f.c1.c1;
  const fp2& g2 = f.c1.c2;
  fp6 t1 = {mul_by_xi(add(mul(g1, l.c), mul(g2, l.b))),
            add(mul_by_xi(mul(g2, l.c)), mul(g0, l.b)),
            add(mul(g0, l.c), mul(g1, l.b))};
  fp6 c0 = add(t0, mul_by_v(t1));
  fp6 c1 = sub(mul(add(f.c0, f.c1), add(l0, l1)), add(t0, t1));
  return {c0, c1};
}

static fp12 multi_miller_loop(std::vector<MillerPair>& pairs) {
  if (pairs.empty()) return FP12_ONE_C;
  fp12 acc = FP12_ONE_C;
  uint64_t x = BLS_X_ABS_U64;
  int nbits = 64 - __builtin_clzll(x);
  std::vector<fp2> denoms(pairs.size());
  for (int i = nbits - 2; i >= 0; i--) {
    acc = sqr(acc);
    // Doubling step for every pair: slope = 3 xt^2 / (2 yt).
    for (size_t j = 0; j < pairs.size(); j++)
      denoms[j] = mul_small(pairs[j].ty, 2);
    fp2_batch_inv(denoms);
    for (size_t j = 0; j < pairs.size(); j++) {
      MillerPair& pr = pairs[j];
      fp2 slope = mul(mul_small(sqr(pr.tx), 3), denoms[j]);
      acc = mul_by_line(acc, line_value(pr.tx, pr.ty, slope, pr.px, pr.py));
      fp2 x3 = sub(sqr(slope), mul_small(pr.tx, 2));
      fp2 y3 = sub(mul(slope, sub(pr.tx, x3)), pr.ty);
      pr.tx = x3;
      pr.ty = y3;
    }
    if ((x >> i) & 1) {
      for (size_t j = 0; j < pairs.size(); j++)
        denoms[j] = sub(pairs[j].qx, pairs[j].tx);
      fp2_batch_inv(denoms);
      for (size_t j = 0; j < pairs.size(); j++) {
        MillerPair& pr = pairs[j];
        fp2 slope = mul(sub(pr.qy, pr.ty), denoms[j]);
        acc = mul_by_line(acc, line_value(pr.tx, pr.ty, slope, pr.px, pr.py));
        fp2 x3 = sub(sub(sqr(slope), pr.tx), pr.qx);
        fp2 y3 = sub(mul(slope, sub(pr.tx, x3)), pr.ty);
        pr.tx = x3;
        pr.ty = y3;
      }
    }
  }
  return conj(acc);  // x < 0
}

static fp12 fp12_pow_abs_x(const fp12& f) {
  uint8_t be[8];
  for (int i = 0; i < 8; i++) be[7 - i] = uint8_t(BLS_X_ABS_U64 >> (8 * i));
  return fp12_pow_be(f, be, 8);
}

static fp12 final_exponentiation(const fp12& f) {
  fp12 t = mul(conj(f), inv(f));
  t = mul(frob_n(t, 2), t);
  fp12 g1 = fp12_pow_be(t, E_EXP_BE, 16);
  fp12 g2 = mul(conj(fp12_pow_abs_x(g1)), frob(g1));
  fp12 g2x2 = fp12_pow_abs_x(fp12_pow_abs_x(g2));
  fp12 g3 = mul(mul(g2x2, frob_n(g2, 2)), conj(g2));
  return mul(g3, t);
}

// ---------------------------------------------------------------------------
// Public ABI
// ---------------------------------------------------------------------------

// Point ABI: G1 affine = 96 bytes (X||Y big-endian, 48 each); G2 affine =
// 192 bytes (X0||X1||Y0||Y1). Infinity carried as separate flag bytes.

static bool read_g1(const uint8_t* b, bool inf, jac<fp>* out) {
  if (inf) {
    *out = jac_infinity<fp>();
    return true;
  }
  fp x, y;
  if (!fp_from_be(b, &x) || !fp_from_be(b + 48, &y)) return false;
  if (!g1_on_curve_affine(x, y)) return false;
  *out = {x, y, FP_ONE};
  return true;
}

static bool read_g2(const uint8_t* b, bool inf, jac<fp2>* out) {
  if (inf) {
    *out = jac_infinity<fp2>();
    return true;
  }
  fp2 x, y;
  if (!fp_from_be(b, &x.c0) || !fp_from_be(b + 48, &x.c1) ||
      !fp_from_be(b + 96, &y.c0) || !fp_from_be(b + 144, &y.c1))
    return false;
  if (!g2_on_curve_affine(x, y)) return false;
  *out = {x, y, FP2_ONE_C};
  return true;
}

// Batch verify, blst semantics (see ops/backend.py module docstring):
//   prod_i e([r_i] agg_pk_i, H(m_i)) * e(-g1, sum_i [r_i] sig_i) == 1
// msgs: n*32; pks: concatenated 96-byte G1 affine, counts in pk_counts;
// sigs: n*192 G2 affine; sig_inf: n flags; sig_checked: n flags (skip the
// subgroup check where the caller already paid it); scalars: n nonzero
// 64-bit weights. Returns 1 valid / 0 invalid / -1 malformed input.
extern "C" int blscpu_verify_batch(const uint8_t* msgs, const uint8_t* pks,
                                   const uint32_t* pk_counts,
                                   const uint8_t* sigs, const uint8_t* sig_inf,
                                   const uint8_t* sig_checked,
                                   const uint64_t* scalars, uint32_t n) {
  blscpu_init();
  if (n == 0) return 0;
  std::vector<MillerPair> pairs;
  pairs.reserve(n + 1);
  jac<fp2> sig_sum = jac_infinity<fp2>();
  size_t pk_off = 0;
  for (uint32_t i = 0; i < n; i++) {
    if (pk_counts[i] == 0) return 0;        // empty signing_keys rejects
    if (sig_inf[i]) return 0;               // infinity signature rejects
    jac<fp2> sig;
    if (!read_g2(sigs + (size_t)i * 192, false, &sig)) return -1;
    if (!sig_checked[i] && !g2_in_subgroup(sig)) return 0;
    jac<fp> agg = jac_infinity<fp>();
    for (uint32_t k = 0; k < pk_counts[i]; k++) {
      jac<fp> pk;
      if (!read_g1(pks + (pk_off + k) * 96, false, &pk)) return -1;
      agg = jac_add(agg, pk);
    }
    pk_off += pk_counts[i];
    if (jac_is_infinity(agg)) return 0;     // infinity aggregate rejects
    uint64_t r[1] = {scalars[i]};
    jac<fp> wagg = jac_mul(agg, r, 1);
    sig_sum = jac_add(sig_sum, jac_mul(sig, r, 1));
    jac<fp2> h = hash_to_g2_jac(msgs + (size_t)i * 32, 32);
    MillerPair mp;
    bool inf;
    jac_to_affine(wagg, &mp.px, &mp.py, &inf);
    if (inf) continue;  // weighted aggregate at infinity: r*agg == O
    fp2 hx, hy;
    jac_to_affine(h, &hx, &hy, &inf);
    if (inf) continue;  // H(m) infinity: contributes 1
    mp.qx = hx;
    mp.qy = hy;
    mp.tx = hx;
    mp.ty = hy;
    pairs.push_back(mp);
  }
  {
    MillerPair mp;
    bool inf;
    jac_to_affine(NEG_G1, &mp.px, &mp.py, &inf);
    fp2 sx, sy;
    jac_to_affine(sig_sum, &sx, &sy, &inf);
    if (!inf) {
      mp.qx = sx;
      mp.qy = sy;
      mp.tx = sx;
      mp.ty = sy;
      pairs.push_back(mp);
    }
  }
  fp12 m = multi_miller_loop(pairs);
  return is_one(final_exponentiation(m)) ? 1 : 0;
}

// Single-set verify (the gossip-latency path): k pubkeys, one message.
extern "C" int blscpu_verify_one(const uint8_t* msg, const uint8_t* pks,
                                 uint32_t k, const uint8_t* sig,
                                 uint8_t sig_is_inf, uint8_t sig_checked) {
  uint32_t counts[1] = {k};
  uint8_t inf[1] = {sig_is_inf};
  uint8_t chk[1] = {sig_checked};
  uint64_t sc[1] = {1};
  return blscpu_verify_batch(msg, pks, counts, sig, inf, chk, sc, 1);
}

// hash_to_g2 for KAT cross-checks: out = 192-byte affine (X0,X1,Y0,Y1)
// big-endian; returns 1, or 0 if the result is infinity (never for RO).
extern "C" int blscpu_hash_to_g2_dst(const uint8_t* msg, uint32_t msg_len,
                                     const uint8_t* dst, uint32_t dst_len,
                                     uint8_t* out192) {
  blscpu_init();
  jac<fp2> h = hash_to_g2_jac_dst(msg, msg_len, dst, dst_len);
  fp2 x, y;
  bool inf;
  jac_to_affine(h, &x, &y, &inf);
  if (inf) return 0;
  fp_to_be(x.c0, out192);
  fp_to_be(x.c1, out192 + 48);
  fp_to_be(y.c0, out192 + 96);
  fp_to_be(y.c1, out192 + 144);
  return 1;
}

extern "C" int blscpu_hash_to_g2(const uint8_t* msg, uint32_t msg_len,
                                 uint8_t* out192) {
  blscpu_init();
  jac<fp2> h = hash_to_g2_jac(msg, msg_len);
  fp2 x, y;
  bool inf;
  jac_to_affine(h, &x, &y, &inf);
  if (inf) return 0;
  fp_to_be(x.c0, out192);
  fp_to_be(x.c1, out192 + 48);
  fp_to_be(y.c0, out192 + 96);
  fp_to_be(y.c1, out192 + 144);
  return 1;
}

// G2 decompression, ZCash layout: in96 = x1 (flag bits in the top three
// of byte 0) then x0, big-endian. Flags, length and infinity are the
// caller's; no subgroup check. out192 = affine (X0,X1,Y0,Y1) big-endian.
// Returns 1, -1 when x0 or x1 >= p, -2 when x is not on the curve.
extern "C" int blscpu_g2_decompress(const uint8_t* in96, uint8_t* out192) {
  blscpu_init();
  uint8_t x1_be[48];
  memcpy(x1_be, in96, 48);
  x1_be[0] &= 0x1F;
  fp2 x;
  if (!fp_from_be(x1_be, &x.c1) || !fp_from_be(in96 + 48, &x.c0)) return -1;
  fp2 y;
  if (!fp2_sqrt(add(mul(sqr(x), x), B2_COEFF), &y)) return -2;
  if (fp2_is_lex_largest(y) != bool(in96[0] & 0x20)) y = neg(y);
  fp_to_be(x.c0, out192);
  fp_to_be(x.c1, out192 + 48);
  fp_to_be(y.c0, out192 + 96);
  fp_to_be(y.c1, out192 + 144);
  return 1;
}

// G2 subgroup check on an affine point (Signature.from_bytes, parity tests).
extern "C" int blscpu_g2_in_subgroup(const uint8_t* pt192, uint8_t inf) {
  blscpu_init();
  jac<fp2> q;
  if (!read_g2(pt192, inf, &q)) return -1;
  return g2_in_subgroup(q) ? 1 : 0;
}
