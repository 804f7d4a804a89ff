// smtwalk — the sparse merkle tree's level-synchronous walk, for a batch
// narrower than the device SHA-256 tier (tpubft/kvbc/sparse_merkle.py
// chooses). The caller has hashed the keys to their paths and read, through
// its own read view, every sibling that is not provably the default; this
// walks the changed leaves up to the root and returns the rows the walk
// stages, already in WriteBatch's wire encoding (kvlog.cpp, and
// tpubft/storage/interfaces.py WriteBatch.encode):
//
//   repeat{ u8 op(1=put,2=del) | u32le klen | key | [u32le vlen | val] }
//
// Row order, row for row what the Python level loop stages:
//   per leaf, caller's order:  leaf row  [, leaf archive row]
//   depth 256, caller's order: node row  [, node archive row]
//   depth 255..0, ascending:   node row  [, node archive row]
// A node that hashes to its depth's default is a delete (only non-default
// nodes are stored) and its archive row's value is empty. Archive rows are
// written only when version > 0; their key is the row's key + u64be version.
//
// Beside the payload comes an index, four u32 a row: key start, key end,
// value start, value end (offsets into the payload; 0, 0 for a delete — a
// value never starts at 0), so the caller's read-your-writes overlay is fed
// from slices and no row is parsed twice.
//
// C ABI only — consumed via ctypes from tpubft/kvbc/sparse_merkle.py.

#include <algorithm>
#include <cstdint>
#include <cstdlib>
#include <cstring>
#include <vector>

#if defined(__x86_64__)
#include <cpuid.h>
#include <immintrin.h>
#endif

namespace {

constexpr int kDepth = 256;

const uint32_t K[64] = {
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

inline uint32_t rotr(uint32_t x, int n) { return (x >> n) | (x << (32 - n)); }

void compress_plain(uint32_t s[8], const uint8_t* p) {
  uint32_t w[64];
  for (int i = 0; i < 16; i++)
    w[i] = (uint32_t)p[4 * i] << 24 | (uint32_t)p[4 * i + 1] << 16 |
           (uint32_t)p[4 * i + 2] << 8 | (uint32_t)p[4 * i + 3];
  for (int i = 16; i < 64; i++) {
    uint32_t s0 = rotr(w[i - 15], 7) ^ rotr(w[i - 15], 18) ^ (w[i - 15] >> 3);
    uint32_t s1 = rotr(w[i - 2], 17) ^ rotr(w[i - 2], 19) ^ (w[i - 2] >> 10);
    w[i] = w[i - 16] + s0 + w[i - 7] + s1;
  }
  uint32_t a = s[0], b = s[1], c = s[2], d = s[3], e = s[4], f = s[5],
           g = s[6], h = s[7];
  for (int i = 0; i < 64; i++) {
    uint32_t t1 = h + (rotr(e, 6) ^ rotr(e, 11) ^ rotr(e, 25)) +
                  ((e & f) ^ (~e & g)) + K[i] + w[i];
    uint32_t t2 = (rotr(a, 2) ^ rotr(a, 13) ^ rotr(a, 22)) +
                  ((a & b) ^ (a & c) ^ (b & c));
    h = g; g = f; f = e; e = d + t1; d = c; c = b; b = a; a = t1 + t2;
  }
  s[0] += a; s[1] += b; s[2] += c; s[3] += d;
  s[4] += e; s[5] += f; s[6] += g; s[7] += h;
}

#if defined(__x86_64__)
// The CPU's own SHA-256 rounds, four to a group: m[] holds the sixteen
// newest schedule words, msg1/msg2 extend them.
__attribute__((target("sha,sse4.1,ssse3")))
void compress_shani(uint32_t s[8], const uint8_t* p) {
  const __m128i swap = _mm_set_epi64x(0x0c0d0e0f08090a0bULL,
                                      0x0405060700010203ULL);
  __m128i tmp = _mm_loadu_si128((const __m128i*)&s[0]);
  __m128i st1 = _mm_loadu_si128((const __m128i*)&s[4]);
  tmp = _mm_shuffle_epi32(tmp, 0xB1);            // CDAB
  st1 = _mm_shuffle_epi32(st1, 0x1B);            // EFGH
  __m128i st0 = _mm_alignr_epi8(tmp, st1, 8);    // ABEF
  st1 = _mm_blend_epi16(st1, tmp, 0xF0);         // CDGH
  const __m128i save0 = st0, save1 = st1;
  __m128i m[4];
#pragma GCC unroll 16
  for (int g = 0; g < 16; g++) {
    if (g < 4)
      m[g] = _mm_shuffle_epi8(
          _mm_loadu_si128((const __m128i*)(p + 16 * g)), swap);
    __m128i msg = _mm_add_epi32(
        m[g & 3], _mm_loadu_si128((const __m128i*)&K[4 * g]));
    st1 = _mm_sha256rnds2_epu32(st1, st0, msg);
    if (g >= 3 && g < 15) {
      tmp = _mm_alignr_epi8(m[g & 3], m[(g + 3) & 3], 4);
      m[(g + 1) & 3] = _mm_sha256msg2_epu32(
          _mm_add_epi32(m[(g + 1) & 3], tmp), m[g & 3]);
    }
    st0 = _mm_sha256rnds2_epu32(st0, st1, _mm_shuffle_epi32(msg, 0x0E));
    if (g >= 1 && g < 13)
      m[(g + 3) & 3] = _mm_sha256msg1_epu32(m[(g + 3) & 3], m[g & 3]);
  }
  st0 = _mm_add_epi32(st0, save0);
  st1 = _mm_add_epi32(st1, save1);
  tmp = _mm_shuffle_epi32(st0, 0x1B);            // FEBA
  st1 = _mm_shuffle_epi32(st1, 0xB1);            // DCHG
  st0 = _mm_blend_epi16(tmp, st1, 0xF0);         // DCBA
  st1 = _mm_alignr_epi8(st1, tmp, 8);            // HGFE
  _mm_storeu_si128((__m128i*)&s[0], st0);
  _mm_storeu_si128((__m128i*)&s[4], st1);
}

bool cpu_has_sha() {
  unsigned a, b, c, d;
  if (!__get_cpuid(1, &a, &b, &c, &d)) return false;
  if (!(c & (1u << 9)) || !(c & (1u << 19))) return false;  // ssse3, sse4.1
  if (!__get_cpuid_count(7, 0, &a, &b, &c, &d)) return false;
  return b & (1u << 29);
}
#endif

void (*compress)(uint32_t[8], const uint8_t*) = compress_plain;

void sha256(const uint8_t* data, size_t len, uint8_t out[32]) {
  uint32_t s[8] = {0x6a09e667, 0xbb67ae85, 0x3c6ef372, 0xa54ff53a,
                   0x510e527f, 0x9b05688c, 0x1f83d9ab, 0x5be0cd19};
  size_t off = 0;
  for (; off + 64 <= len; off += 64) compress(s, data + off);
  uint8_t tail[128] = {0};
  size_t rest = len - off;
  memcpy(tail, data + off, rest);
  tail[rest] = 0x80;
  size_t tlen = rest + 9 <= 64 ? 64 : 128;
  uint64_t bits = (uint64_t)len * 8;
  for (int i = 0; i < 8; i++) tail[tlen - 1 - i] = (uint8_t)(bits >> (8 * i));
  compress(s, tail);
  if (tlen == 128) compress(s, tail + 64);
  for (int i = 0; i < 8; i++) {
    out[4 * i] = s[i] >> 24; out[4 * i + 1] = s[i] >> 16;
    out[4 * i + 2] = s[i] >> 8; out[4 * i + 3] = s[i];
  }
}

// default (empty-subtree) hash per depth, and the hash engine chosen once:
// the CPU's rounds only where they give the plain rounds' digest
struct Tables {
  uint8_t defaults[kDepth + 1][32];
  Tables() {
#if defined(__x86_64__)
    if (cpu_has_sha()) {
      uint8_t msg[150], a[32], b[32];
      for (int i = 0; i < 150; i++) msg[i] = (uint8_t)(i * 7 + 1);
      bool same = true;
      for (size_t len : {0, 3, 55, 56, 65, 119, 150}) {
        compress = compress_plain;
        sha256(msg, len, a);
        compress = compress_shani;
        sha256(msg, len, b);
        same = same && memcmp(a, b, 32) == 0;
      }
      compress = same ? compress_shani : compress_plain;
    }
#endif
    memset(defaults[kDepth], 0, 32);
    uint8_t msg[65] = {0x01};
    for (int d = kDepth - 1; d >= 0; d--) {
      memcpy(msg + 1, defaults[d + 1], 32);
      memcpy(msg + 33, defaults[d + 1], 32);
      sha256(msg, 65, defaults[d]);
    }
  }
};

const Tables& tables() {
  static const Tables t;
  return t;
}

struct Node {
  uint8_t pre[32];   // the path's leading `depth` bits, the rest zero
  uint8_t hash[32];
};

struct Prefix { const uint8_t* p; uint32_t len; };

// One walk's output: the payload and, per row, its four index words.
struct Rows {
  std::vector<uint8_t> out;
  std::vector<uint32_t> index;

  void u32(uint32_t v) {
    uint8_t b[4] = {(uint8_t)v, (uint8_t)(v >> 8), (uint8_t)(v >> 16),
                    (uint8_t)(v >> 24)};
    out.insert(out.end(), b, b + 4);
  }

  // key = family prefix + body [+ version]; val == nullptr deletes
  void row(const Prefix& fam, const uint8_t* body, uint32_t body_len,
           const uint8_t* ver, const uint8_t* val, uint32_t vlen) {
    uint32_t klen = fam.len + body_len + (ver ? 8 : 0);
    out.push_back(val ? 1 : 2);
    u32(klen);
    index.push_back((uint32_t)out.size());
    out.insert(out.end(), fam.p, fam.p + fam.len);
    out.insert(out.end(), body, body + body_len);
    if (ver) out.insert(out.end(), ver, ver + 8);
    index.push_back((uint32_t)out.size());
    if (val) {
      u32(vlen);
      index.push_back((uint32_t)out.size());
      out.insert(out.end(), val, val + vlen);
      index.push_back((uint32_t)out.size());
    } else {
      index.push_back(0);
      index.push_back(0);
    }
  }
};

// physical node key: depth (2B big-endian) + the leading `depth` bits
uint32_t node_key(int depth, const uint8_t pre[32], uint8_t key[34]) {
  uint32_t nbytes = (depth + 7) / 8;
  key[0] = (uint8_t)(depth >> 8);
  key[1] = (uint8_t)depth;
  memcpy(key + 2, pre, nbytes);
  return 2 + nbytes;
}

void node_rows(Rows& rows, const Prefix& live, const Prefix& arch, int depth,
               const Node& n, const uint8_t* ver) {
  uint8_t key[34];
  uint32_t klen = node_key(depth, n.pre, key);
  bool is_default = memcmp(n.hash, tables().defaults[depth], 32) == 0;
  rows.row(live, key, klen, nullptr, is_default ? nullptr : n.hash, 32);
  if (ver) rows.row(arch, key, klen, ver, n.hash, is_default ? 0 : 32);
}

}  // namespace

extern "C" {

// paths: n x 32, distinct, in the caller's order. values / value_lens: the
// leaves' value hashes, concatenated; a length of -1 deletes the leaf.
// sib_keys / sib_vals: the siblings the caller read — node keys (2B depth +
// prefix) back to back, deepest level first and ascending within a level,
// 32 bytes of hash each; a sibling that is not among them is its depth's
// default. prefixes / prefix_lens: the physical-key prefixes of the node,
// leaf, node-archive and leaf-archive families, back to back.
// Returns 0, or -1 for arguments that are no batch (n == 0, a path twice),
// -2 for a sibling the walk never met (wrong key or order), -3 out of
// memory. On 0: root (32 bytes), defaults_used (single-child parents whose
// sibling was taken as the default), and payload / index, which the caller
// gives back to smt_free.
int smt_walk(const uint8_t* paths, const uint8_t* values,
             const int32_t* value_lens, uint32_t n,
             const uint8_t* sib_keys, uint32_t sib_keys_len,
             const uint8_t* sib_vals, uint32_t n_sibs, uint64_t version,
             const uint8_t* prefixes, const uint32_t* prefix_lens,
             uint8_t* root, uint32_t* defaults_used,
             uint8_t** payload, uint32_t* payload_len,
             uint32_t** index, uint32_t* n_rows) {
  if (n == 0) return -1;
  const Tables& t = tables();
  Prefix fam[4];
  for (uint32_t i = 0, off = 0; i < 4; off += prefix_lens[i++])
    fam[i] = {prefixes + off, prefix_lens[i]};
  const Prefix &f_node = fam[0], &f_leaf = fam[1], &f_arch = fam[2],
               &f_leaf_arch = fam[3];
  uint8_t verb[8];
  for (int i = 0; i < 8; i++) verb[i] = (uint8_t)(version >> (8 * (7 - i)));
  const uint8_t* ver = version ? verb : nullptr;

  Rows rows;
  size_t key_max = 34 + 8 + std::max(std::max(fam[0].len, fam[1].len),
                                     std::max(fam[2].len, fam[3].len));
  rows.out.reserve(((size_t)n + kDepth) * 2 * (key_max + 41));
  rows.index.reserve(((size_t)n + kDepth) * 2 * 4);

  // leaf level
  std::vector<Node> cur(n), up;
  std::vector<uint8_t> msg;
  const uint8_t* val = values;
  for (uint32_t i = 0; i < n; i++) {
    const uint8_t* path = paths + 32 * (size_t)i;
    int32_t vlen = value_lens[i];
    memcpy(cur[i].pre, path, 32);
    if (vlen < 0) {
      memset(cur[i].hash, 0, 32);
      rows.row(f_leaf, path, 32, nullptr, nullptr, 0);
      if (ver) rows.row(f_leaf_arch, path, 32, ver, path, 0);
    } else {
      msg.assign(1, 0x00);
      msg.insert(msg.end(), path, path + 32);
      msg.insert(msg.end(), val, val + vlen);
      sha256(msg.data(), msg.size(), cur[i].hash);
      rows.row(f_leaf, path, 32, nullptr, val, vlen);
      if (ver) rows.row(f_leaf_arch, path, 32, ver, val, vlen);
      val += vlen;
    }
  }
  for (const Node& leaf : cur)
    node_rows(rows, f_node, f_arch, kDepth, leaf, ver);
  std::sort(cur.begin(), cur.end(), [](const Node& a, const Node& b) {
    return memcmp(a.pre, b.pre, 32) < 0;
  });
  for (uint32_t i = 1; i < n; i++)
    if (memcmp(cur[i - 1].pre, cur[i].pre, 32) == 0) return -1;

  // ascend: a level's changed nodes pair up under their parents
  uint32_t sib_at = 0, sib_off = 0, defaulted = 0;
  uint8_t inner[65] = {0x01}, want[34];
  for (int depth = kDepth; depth > 0; depth--) {
    const int byte = (depth - 1) >> 3;
    const uint8_t bit = 0x80 >> ((depth - 1) & 7);
    up.clear();
    for (size_t i = 0; i < cur.size();) {
      const Node& c = cur[i];
      const bool is_right = c.pre[byte] & bit;
      Node parent;
      memcpy(parent.pre, c.pre, 32);
      parent.pre[byte] &= (uint8_t)~bit;
      const uint8_t *left, *right;
      if (!is_right && i + 1 < cur.size() &&
          (cur[i + 1].pre[byte] & bit) &&
          memcmp(cur[i + 1].pre, c.pre, byte) == 0 &&
          (cur[i + 1].pre[byte] & (uint8_t)~bit) == c.pre[byte]) {
        left = c.hash;
        right = cur[i + 1].hash;
        i += 2;
      } else {
        uint8_t sib[32];
        memcpy(sib, c.pre, 32);
        sib[byte] ^= bit;
        uint32_t klen = node_key(depth, sib, want);
        const uint8_t* other = t.defaults[depth];
        if (sib_at < n_sibs && sib_off + klen <= sib_keys_len &&
            memcmp(sib_keys + sib_off, want, klen) == 0) {
          other = sib_vals + 32 * (size_t)sib_at++;
          sib_off += klen;
        } else {
          defaulted++;
        }
        left = is_right ? other : c.hash;
        right = is_right ? c.hash : other;
        i += 1;
      }
      memcpy(inner + 1, left, 32);
      memcpy(inner + 33, right, 32);
      sha256(inner, 65, parent.hash);
      up.push_back(parent);
    }
    for (const Node& p : up)
      node_rows(rows, f_node, f_arch, depth - 1, p, ver);
    cur.swap(up);
  }
  if (sib_at != n_sibs || sib_off != sib_keys_len) return -2;

  uint8_t* out = (uint8_t*)malloc(rows.out.size());
  uint32_t* idx = (uint32_t*)malloc(rows.index.size() * sizeof(uint32_t));
  if (!out || !idx || rows.out.size() > 0xFFFFFFFFu) {
    free(out);
    free(idx);
    return -3;
  }
  memcpy(out, rows.out.data(), rows.out.size());
  memcpy(idx, rows.index.data(), rows.index.size() * sizeof(uint32_t));
  memcpy(root, cur[0].hash, 32);
  *defaults_used = defaulted;
  *payload = out;
  *payload_len = (uint32_t)rows.out.size();
  *index = idx;
  *n_rows = (uint32_t)(rows.index.size() / 4);
  return 0;
}

void smt_free(void* p) { free(p); }

}  // extern "C"
