// ed25519prep — the host half of a batched ed25519 verify
// (tpubft/ops/ed25519.py prepare_batch): for each item the challenge
// h = SHA-512(R || A || M) mod L, the canonicality checks s < L, A_y < p and
// R_y < p (A_y and R_y with the sign bit masked off), and the kernel's
// operands, written straight into the caller's column-major arrays:
//
//   s_win, h_win  (64, stride) int32  4-bit windows, low nibble first
//   a_y, r_y      (nl, stride) int32  limb j = bits w[j] .. w[j+1]-1
//   a_sign, r_sign (stride,)   int32  bit 255 of A and of R
//   host_valid    (stride,)    uint8  shaped and all three checks pass
//
// An item that is not shaped (signature not 64 bytes or key not 32) or that
// fails a check gets 0 in every column, signs included, so the kernel runs
// on benign values. The limb offsets w[0..nl] come from the caller
// (ops/f25519.W), so the two never drift.
//
// Portable SHA-512 (FIPS 180-4) and a 512-bit -> mod L reduction by folding
// at 2^252 (2^252 = -c mod L, c < 2^125), 64-bit limbs with __int128.
//
// C ABI only — consumed via ctypes from tpubft/ops/ed25519.py.

#include <cstdint>
#include <cstring>

namespace {

const uint64_t K[80] = {
    0x428a2f98d728ae22ULL, 0x7137449123ef65cdULL, 0xb5c0fbcfec4d3b2fULL,
    0xe9b5dba58189dbbcULL, 0x3956c25bf348b538ULL, 0x59f111f1b605d019ULL,
    0x923f82a4af194f9bULL, 0xab1c5ed5da6d8118ULL, 0xd807aa98a3030242ULL,
    0x12835b0145706fbeULL, 0x243185be4ee4b28cULL, 0x550c7dc3d5ffb4e2ULL,
    0x72be5d74f27b896fULL, 0x80deb1fe3b1696b1ULL, 0x9bdc06a725c71235ULL,
    0xc19bf174cf692694ULL, 0xe49b69c19ef14ad2ULL, 0xefbe4786384f25e3ULL,
    0x0fc19dc68b8cd5b5ULL, 0x240ca1cc77ac9c65ULL, 0x2de92c6f592b0275ULL,
    0x4a7484aa6ea6e483ULL, 0x5cb0a9dcbd41fbd4ULL, 0x76f988da831153b5ULL,
    0x983e5152ee66dfabULL, 0xa831c66d2db43210ULL, 0xb00327c898fb213fULL,
    0xbf597fc7beef0ee4ULL, 0xc6e00bf33da88fc2ULL, 0xd5a79147930aa725ULL,
    0x06ca6351e003826fULL, 0x142929670a0e6e70ULL, 0x27b70a8546d22ffcULL,
    0x2e1b21385c26c926ULL, 0x4d2c6dfc5ac42aedULL, 0x53380d139d95b3dfULL,
    0x650a73548baf63deULL, 0x766a0abb3c77b2a8ULL, 0x81c2c92e47edaee6ULL,
    0x92722c851482353bULL, 0xa2bfe8a14cf10364ULL, 0xa81a664bbc423001ULL,
    0xc24b8b70d0f89791ULL, 0xc76c51a30654be30ULL, 0xd192e819d6ef5218ULL,
    0xd69906245565a910ULL, 0xf40e35855771202aULL, 0x106aa07032bbd1b8ULL,
    0x19a4c116b8d2d0c8ULL, 0x1e376c085141ab53ULL, 0x2748774cdf8eeb99ULL,
    0x34b0bcb5e19b48a8ULL, 0x391c0cb3c5c95a63ULL, 0x4ed8aa4ae3418acbULL,
    0x5b9cca4f7763e373ULL, 0x682e6ff3d6b2b8a3ULL, 0x748f82ee5defb2fcULL,
    0x78a5636f43172f60ULL, 0x84c87814a1f0ab72ULL, 0x8cc702081a6439ecULL,
    0x90befffa23631e28ULL, 0xa4506cebde82bde9ULL, 0xbef9a3f7b2c67915ULL,
    0xc67178f2e372532bULL, 0xca273eceea26619cULL, 0xd186b8c721c0c207ULL,
    0xeada7dd6cde0eb1eULL, 0xf57d4f7fee6ed178ULL, 0x06f067aa72176fbaULL,
    0x0a637dc5a2c898a6ULL, 0x113f9804bef90daeULL, 0x1b710b35131c471bULL,
    0x28db77f523047d84ULL, 0x32caab7b40c72493ULL, 0x3c9ebe0a15c9bebcULL,
    0x431d67c49c100d4cULL, 0x4cc5d4becb3e42b6ULL, 0x597f299cfc657e2aULL,
    0x5fcb6fab3ad6faecULL, 0x6c44198c4a475817ULL};

const uint64_t H0[8] = {
    0x6a09e667f3bcc908ULL, 0xbb67ae8584caa73bULL, 0x3c6ef372fe94f82bULL,
    0xa54ff53a5f1d36f1ULL, 0x510e527fade682d1ULL, 0x9b05688c2b3e6c1fULL,
    0x1f83d9abfb41bd6bULL, 0x5be0cd19137e2179ULL};

inline uint64_t rotr(uint64_t x, int n) { return (x >> n) | (x << (64 - n)); }

inline uint64_t load_be64(const uint8_t* p) {
  uint64_t v = 0;
  for (int i = 0; i < 8; i++) v = v << 8 | p[i];
  return v;
}

inline uint64_t load_le64(const uint8_t* p) {
  uint64_t v = 0;
  for (int i = 7; i >= 0; i--) v = v << 8 | p[i];
  return v;
}

void compress(uint64_t h[8], const uint8_t* p) {
  uint64_t w[80];
  for (int i = 0; i < 16; i++) w[i] = load_be64(p + 8 * i);
  for (int i = 16; i < 80; i++) {
    uint64_t s0 = rotr(w[i - 15], 1) ^ rotr(w[i - 15], 8) ^ (w[i - 15] >> 7);
    uint64_t s1 = rotr(w[i - 2], 19) ^ rotr(w[i - 2], 61) ^ (w[i - 2] >> 6);
    w[i] = w[i - 16] + s0 + w[i - 7] + s1;
  }
  uint64_t a = h[0], b = h[1], c = h[2], d = h[3];
  uint64_t e = h[4], f = h[5], g = h[6], k = h[7];
  for (int i = 0; i < 80; i++) {
    uint64_t t1 = k + (rotr(e, 14) ^ rotr(e, 18) ^ rotr(e, 41)) +
                  ((e & f) ^ (~e & g)) + K[i] + w[i];
    uint64_t t2 = (rotr(a, 28) ^ rotr(a, 34) ^ rotr(a, 39)) +
                  ((a & b) ^ (a & c) ^ (b & c));
    k = g;
    g = f;
    f = e;
    e = d + t1;
    d = c;
    c = b;
    b = a;
    a = t1 + t2;
  }
  h[0] += a;
  h[1] += b;
  h[2] += c;
  h[3] += d;
  h[4] += e;
  h[5] += f;
  h[6] += g;
  h[7] += k;
}

struct Sha512 {
  uint64_t h[8];
  uint8_t buf[128];
  size_t used = 0;
  uint64_t total = 0;

  Sha512() { memcpy(h, H0, sizeof h); }

  void update(const uint8_t* p, size_t n) {
    total += n;
    if (used) {
      size_t take = n < 128 - used ? n : 128 - used;
      memcpy(buf + used, p, take);
      used += take;
      p += take;
      n -= take;
      if (used < 128) return;
      compress(h, buf);
      used = 0;
    }
    for (; n >= 128; p += 128, n -= 128) compress(h, p);
    memcpy(buf, p, n);
    used = n;
  }

  void final(uint8_t out[64]) {
    uint64_t bits = total * 8;
    buf[used++] = 0x80;
    if (used > 112) {
      memset(buf + used, 0, 128 - used);
      compress(h, buf);
      used = 0;
    }
    memset(buf + used, 0, 120 - used);     // the length's high word is 0
    for (int i = 0; i < 8; i++) buf[120 + i] = (uint8_t)(bits >> (56 - 8 * i));
    compress(h, buf);
    for (int i = 0; i < 8; i++)
      for (int j = 0; j < 8; j++) out[8 * i + j] = (uint8_t)(h[i] >> (56 - 8 * j));
  }
};

// ---------------- scalars mod L = 2^252 + c ----------------

const uint64_t C[2] = {0x5812631a5cf5d3edULL, 0x14def9dea2f79cd6ULL};
const uint64_t Lw[4] = {C[0], C[1], 0, 0x1000000000000000ULL};
const uint64_t MASK60 = (1ULL << 60) - 1;

// r[0 .. na+1] = a[0 .. na-1] * c
void mul_c(const uint64_t* a, int na, uint64_t* r) {
  for (int i = 0; i < na + 2; i++) r[i] = 0;
  for (int i = 0; i < na; i++) {
    unsigned __int128 carry = 0;
    for (int j = 0; j < 2; j++) {
      unsigned __int128 t = (unsigned __int128)a[i] * C[j] + r[i + j] + carry;
      r[i + j] = (uint64_t)t;
      carry = t >> 64;
    }
    r[i + 2] = (uint64_t)carry;
  }
}

// lo = v mod 2^252 (4 limbs); hi = v >> 252 (n - 3 limbs)
void split252(const uint64_t* v, int n, uint64_t lo[4], uint64_t* hi) {
  lo[0] = v[0];
  lo[1] = v[1];
  lo[2] = v[2];
  lo[3] = v[3] & MASK60;
  for (int i = 0; i + 3 < n; i++)
    hi[i] = (v[i + 3] >> 60) | (i + 4 < n ? v[i + 4] << 4 : 0);
}

bool geq_l(const uint64_t z[4]) {
  for (int i = 3; i >= 0; i--)
    if (z[i] != Lw[i]) return z[i] > Lw[i];
  return true;
}

// x (64 bytes, little-endian) mod L -> 32 bytes, little-endian.
// With x = hi0 2^252 + lo0, hi0 c = hi1 2^252 + lo1, hi1 c = hi2 2^252 + lo2:
// x = lo0 - lo1 + lo2 - hi2 c (mod L), each of lo* below 2^252 and hi2 c
// below 2^131, so z = that + 2L lies in (0, 5L).
void sc_reduce(const uint8_t in[64], uint8_t out[32]) {
  uint64_t x[8], lo0[4], hi0[5], t[7], lo1[4], hi1[4], u[5], lo2[4], hi2[2];
  uint64_t v[3];
  for (int i = 0; i < 8; i++) x[i] = load_le64(in + 8 * i);
  split252(x, 8, lo0, hi0);               // hi0 < 2^260: 5 limbs
  mul_c(hi0, 5, t);                       // < 2^385: 7 limbs
  split252(t, 7, lo1, hi1);               // hi1 < 2^133: 3 limbs (+1 spare)
  mul_c(hi1, 3, u);                       // < 2^258: 5 limbs
  split252(u, 5, lo2, hi2);               // hi2 < 2^6
  mul_c(hi2, 1, v);                       // < 2^131
  uint64_t z[4];
  __int128 acc = 0;
  for (int i = 0; i < 4; i++) {
    acc += (__int128)lo0[i] + lo2[i] + 2 * (__int128)Lw[i] - lo1[i] -
           (i < 3 ? v[i] : 0);
    z[i] = (uint64_t)acc;
    acc >>= 64;                           // arithmetic: a borrow is -1
  }
  while (geq_l(z)) {
    unsigned __int128 borrow = 0;
    for (int i = 0; i < 4; i++) {
      unsigned __int128 d = (unsigned __int128)z[i] - Lw[i] - borrow;
      z[i] = (uint64_t)d;
      borrow = (d >> 64) & 1;
    }
  }
  for (int i = 0; i < 4; i++)
    for (int j = 0; j < 8; j++) out[8 * i + j] = (uint8_t)(z[i] >> (8 * j));
}

// ---------------- the per-item checks and operands ----------------

const uint8_t L_LE[32] = {0xed, 0xd3, 0xf5, 0x5c, 0x1a, 0x63, 0x12, 0x58,
                          0xd6, 0x9c, 0xf7, 0xa2, 0xde, 0xf9, 0xde, 0x14,
                          0,    0,    0,    0,    0,    0,    0,    0,
                          0,    0,    0,    0,    0,    0,    0,    0x10};
const uint8_t P_LE[32] = {0xed, 0xff, 0xff, 0xff, 0xff, 0xff, 0xff, 0xff,
                          0xff, 0xff, 0xff, 0xff, 0xff, 0xff, 0xff, 0xff,
                          0xff, 0xff, 0xff, 0xff, 0xff, 0xff, 0xff, 0xff,
                          0xff, 0xff, 0xff, 0xff, 0xff, 0xff, 0xff, 0x7f};

bool lt_le(const uint8_t* a, const uint8_t* bound) {
  for (int i = 31; i >= 0; i--)
    if (a[i] != bound[i]) return a[i] < bound[i];
  return false;
}

inline void windows(const uint8_t* v, int32_t* col, int64_t stride) {
  for (int k = 0; k < 32; k++) {
    col[(2 * k) * stride] = v[k] & 15;
    col[(2 * k + 1) * stride] = v[k] >> 4;
  }
}

inline void limbs(const uint8_t* v, const int32_t* w, int nl, int32_t* col,
                  int64_t stride) {
  for (int j = 0; j < nl; j++) {
    int b = w[j] >> 3, sh = w[j] & 7, width = w[j + 1] - w[j];
    uint32_t word = v[b];
    if (b + 1 < 32) word |= (uint32_t)v[b + 1] << 8;
    if (b + 2 < 32) word |= (uint32_t)v[b + 2] << 16;
    col[j * stride] = (int32_t)((word >> sh) & ((1u << width) - 1));
  }
}

}  // namespace

extern "C" {

void ed25519prep_sha512(const uint8_t* msg, uint64_t len, uint8_t out[64]) {
  Sha512 s;
  s.update(msg, len);
  s.final(out);
}

void ed25519prep_sc_reduce(const uint8_t in[64], uint8_t out[32]) {
  sc_reduce(in, out);
}

// Items i0 .. i1-1 into columns i0 .. i1-1 of the outputs (row length
// `stride`). Message i is msgs[offs[i] .. offs[i+1]); signature i is
// sigs[64 i ..], key i pks[32 i ..], read only where shaped[i].
// Returns 0, or -1 (nothing written) when w does not describe limbs of
// 1 .. 16 bits inside 256.
int ed25519prep_run(int64_t i0, int64_t i1, int64_t stride,
                    const uint8_t* msgs, const uint64_t* offs,
                    const uint8_t* sigs, const uint8_t* pks,
                    const uint8_t* shaped, const int32_t* w, int32_t nl,
                    int32_t* s_win, int32_t* h_win, int32_t* a_y,
                    int32_t* a_sign, int32_t* r_y, int32_t* r_sign,
                    uint8_t* host_valid) {
  if (w[0] != 0 || w[nl] > 256) return -1;
  for (int j = 0; j < nl; j++)
    if (w[j + 1] - w[j] < 1 || w[j + 1] - w[j] > 16) return -1;
  static const uint8_t zero[32] = {0};
  for (int64_t i = i0; i < i1; i++) {
    const uint8_t* sig = sigs + 64 * i;
    const uint8_t* pk = pks + 32 * i;
    uint8_t a[32], r[32], digest[64], h[32];
    bool ok = shaped[i] != 0;
    if (ok) {
      memcpy(a, pk, 32);
      a[31] &= 0x7f;
      memcpy(r, sig, 32);
      r[31] &= 0x7f;
      ok = lt_le(sig + 32, L_LE) && lt_le(a, P_LE) && lt_le(r, P_LE);
    }
    if (ok) {
      Sha512 s;
      s.update(sig, 32);
      s.update(pk, 32);
      s.update(msgs + offs[i], offs[i + 1] - offs[i]);
      s.final(digest);
      sc_reduce(digest, h);
    }
    windows(ok ? sig + 32 : zero, s_win + i, stride);
    windows(ok ? h : zero, h_win + i, stride);
    limbs(ok ? a : zero, w, nl, a_y + i, stride);
    limbs(ok ? r : zero, w, nl, r_y + i, stride);
    a_sign[i] = ok ? pk[31] >> 7 : 0;
    r_sign[i] = ok ? sig[31] >> 7 : 0;
    host_valid[i] = ok;
  }
  return 0;
}

}  // extern "C"
