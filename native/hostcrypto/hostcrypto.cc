// Host crypto hot loops, batched behind a C ABI for ctypes
// (kaspa_tpu/crypto/hostcrypto.py loads the library; where it cannot be
// built the callers keep their Python paths):
//
// - chacha20_keystream_batch: ChaCha20 keystream expansion for muhash
//   elements.  The reference expands each element with rand_chacha
//   (crypto/muhash/src/lib.rs:152-168) in native Rust; this is the
//   equivalent for the framework's host side (djb variant: 64-bit counter
//   from 0, nonce 0), batched over N keys (crypto/chacha.py; numpy rounds
//   otherwise).
// - secp_lift_x_batch: the modular square root that lifts a public key's x
//   to its curve point (BIP340 lift_x / a compressed key's y), once a verify
//   batch instead of a 256-bit pow() per job in the interpreter
//   (crypto/secp.py's batch builders; eclib.lift_x otherwise, which stays
//   the oracle).
//
// No entry allocates or keeps state: any number of threads may call at once.

#include <cstdint>
#include <cstring>

namespace {

inline uint32_t rotl(uint32_t x, int n) { return (x << n) | (x >> (32 - n)); }

#define QR(a, b, c, d)                                                                                   \
  a += b; d ^= a; d = rotl(d, 16);                                                                       \
  c += d; b ^= c; b = rotl(b, 12);                                                                       \
  a += b; d ^= a; d = rotl(d, 8);                                                                        \
  c += d; b ^= c; b = rotl(b, 7);

void chacha_block(const uint32_t key[8], uint64_t counter, uint8_t out[64]) {
  uint32_t init[16] = {0x61707865u, 0x3320646eu, 0x79622d32u, 0x6b206574u,
                       key[0], key[1], key[2], key[3], key[4], key[5], key[6], key[7],
                       static_cast<uint32_t>(counter), static_cast<uint32_t>(counter >> 32), 0u, 0u};
  uint32_t x[16];
  memcpy(x, init, sizeof(x));
  for (int i = 0; i < 10; i++) {
    QR(x[0], x[4], x[8], x[12])
    QR(x[1], x[5], x[9], x[13])
    QR(x[2], x[6], x[10], x[14])
    QR(x[3], x[7], x[11], x[15])
    QR(x[0], x[5], x[10], x[15])
    QR(x[1], x[6], x[11], x[12])
    QR(x[2], x[7], x[8], x[13])
    QR(x[3], x[4], x[9], x[14])
  }
  for (int i = 0; i < 16; i++) {
    uint32_t v = x[i] + init[i];
    out[4 * i + 0] = static_cast<uint8_t>(v);
    out[4 * i + 1] = static_cast<uint8_t>(v >> 8);
    out[4 * i + 2] = static_cast<uint8_t>(v >> 16);
    out[4 * i + 3] = static_cast<uint8_t>(v >> 24);
  }
}

// ---- the field of secp256k1: p = 2^256 - 2^32 - 977, four 64-bit limbs ----

typedef unsigned __int128 u128;

struct Fe {
  uint64_t v[4];  // little-endian limbs, always fully reduced (< p)
};

const uint64_t P0 = 0xFFFFFFFEFFFFFC2FULL;  // p's lowest limb; the other three are all ones
const uint64_t PC = 0x1000003D1ULL;         // 2^256 mod p

inline bool fe_ge_p(const uint64_t v[4]) {
  return v[3] == ~0ULL && v[2] == ~0ULL && v[1] == ~0ULL && v[0] >= P0;
}

inline Fe fe_from_be(const uint8_t b[32]) {
  Fe r;
  for (int i = 0; i < 4; i++) {
    uint64_t w = 0;
    for (int j = 0; j < 8; j++) w = (w << 8) | b[8 * (3 - i) + j];
    r.v[i] = w;
  }
  return r;
}

inline void fe_to_be(const Fe& a, uint8_t b[32]) {
  for (int i = 0; i < 4; i++)
    for (int j = 0; j < 8; j++) b[8 * (3 - i) + j] = static_cast<uint8_t>(a.v[i] >> (8 * (7 - j)));
}

// r = v + c * 2^256 (mod p) for c < 2^64 and then fully reduced: 2^256 = PC
inline Fe fe_fold(uint64_t v[4], uint64_t c) {
  while (c) {
    u128 t = static_cast<u128>(c) * PC;  // < 2^97
    for (int i = 0; i < 4; i++) {
      t += v[i];
      v[i] = static_cast<uint64_t>(t);
      t >>= 64;
    }
    c = static_cast<uint64_t>(t);  // 0 or 1
  }
  Fe r;
  if (fe_ge_p(v)) {  // v - p = v + PC - 2^256, and v < 2^256 <= p + PC: one subtraction does
    u128 t = static_cast<u128>(v[0]) + PC;
    r.v[0] = static_cast<uint64_t>(t);
    for (int i = 1; i < 4; i++) {
      t = (t >> 64) + v[i];
      r.v[i] = static_cast<uint64_t>(t);
    }
  } else {
    memcpy(r.v, v, sizeof(r.v));
  }
  return r;
}

inline Fe fe_mul(const Fe& a, const Fe& b) {
  uint64_t w[8] = {0};
  for (int i = 0; i < 4; i++) {
    u128 carry = 0;
    for (int j = 0; j < 4; j++) {
      carry += static_cast<u128>(a.v[i]) * b.v[j] + w[i + j];
      w[i + j] = static_cast<uint64_t>(carry);
      carry >>= 64;
    }
    w[i + 4] = static_cast<uint64_t>(carry);
  }
  // low + high * PC: four limbs and a carry of under 2^34
  uint64_t v[4];
  u128 t = 0;
  for (int i = 0; i < 4; i++) {
    t += static_cast<u128>(w[i + 4]) * PC + w[i];
    v[i] = static_cast<uint64_t>(t);
    t >>= 64;
  }
  return fe_fold(v, static_cast<uint64_t>(t));
}

inline Fe fe_sqr_n(Fe a, int n) {
  for (int i = 0; i < n; i++) a = fe_mul(a, a);
  return a;
}

inline Fe fe_add_small(const Fe& a, uint64_t k) {
  uint64_t v[4];
  u128 t = k;
  for (int i = 0; i < 4; i++) {
    t += a.v[i];
    v[i] = static_cast<uint64_t>(t);
    t >>= 64;
  }
  return fe_fold(v, static_cast<uint64_t>(t));
}

inline bool fe_eq(const Fe& a, const Fe& b) { return memcmp(a.v, b.v, sizeof(a.v)) == 0; }

inline Fe fe_neg(const Fe& a) {  // p - a, and 0 for 0
  Fe r = a;
  if ((a.v[0] | a.v[1] | a.v[2] | a.v[3]) == 0) return r;
  u128 borrow = 0;
  const uint64_t p[4] = {P0, ~0ULL, ~0ULL, ~0ULL};
  for (int i = 0; i < 4; i++) {
    u128 d = static_cast<u128>(p[i]) - a.v[i] - static_cast<uint64_t>(borrow);
    r.v[i] = static_cast<uint64_t>(d);
    borrow = (d >> 64) & 1;
  }
  return r;
}

// a^((p+1)/4): p = 3 (mod 4), so this is a square root of a where a has one.
// (p+1)/4 = 2^254 - 2^30 - 244 has runs of 223, 22 and 2 ones in binary:
// the chain builds a^(2^k - 1) for k = 2, 3, 6, 9, 11, 22, 44, 88, 176, 220, 223
// (253 squarings and 13 multiplications, as libsecp256k1's secp256k1_fe_sqrt)
inline Fe fe_sqrt_candidate(const Fe& a) {
  Fe x2 = fe_mul(fe_sqr_n(a, 1), a);
  Fe x3 = fe_mul(fe_sqr_n(x2, 1), a);
  Fe x6 = fe_mul(fe_sqr_n(x3, 3), x3);
  Fe x9 = fe_mul(fe_sqr_n(x6, 3), x3);
  Fe x11 = fe_mul(fe_sqr_n(x9, 2), x2);
  Fe x22 = fe_mul(fe_sqr_n(x11, 11), x11);
  Fe x44 = fe_mul(fe_sqr_n(x22, 22), x22);
  Fe x88 = fe_mul(fe_sqr_n(x44, 44), x44);
  Fe x176 = fe_mul(fe_sqr_n(x88, 88), x88);
  Fe x220 = fe_mul(fe_sqr_n(x176, 44), x44);
  Fe x223 = fe_mul(fe_sqr_n(x220, 3), x3);
  Fe t = fe_mul(fe_sqr_n(x223, 23), x22);
  t = fe_mul(fe_sqr_n(t, 6), x2);
  return fe_sqr_n(t, 2);
}

}  // namespace

extern "C" {

// keys: n x 32 bytes (little-endian words); out: n x out_len bytes
void chacha20_keystream_batch(const uint8_t* keys, uint64_t n, uint8_t* out, uint64_t out_len) {
  uint64_t blocks = (out_len + 63) / 64;
  uint8_t buf[64];
  for (uint64_t i = 0; i < n; i++) {
    uint32_t key[8];
    memcpy(key, keys + i * 32, 32);
    uint8_t* dst = out + i * out_len;
    for (uint64_t b = 0; b < blocks; b++) {
      chacha_block(key, b, buf);
      uint64_t off = b * 64;
      uint64_t take = out_len - off < 64 ? out_len - off : 64;
      memcpy(dst + off, buf, take);
    }
  }
}

// xs: n x 32 bytes, big-endian x-coordinates; odd: n bytes, nonzero where the
// odd root is wanted (a 0x03 compressed key), or NULL for the even root
// everywhere (BIP340 lift_x); ys: n x 32 bytes out, big-endian; ok: n bytes
// out, 1 where x < p and x^3 + 7 is a square (ys[i] is zeroed where not)
void secp_lift_x_batch(const uint8_t* xs, uint64_t n, const uint8_t* odd, uint8_t* ys, uint8_t* ok) {
  for (uint64_t i = 0; i < n; i++) {
    Fe x = fe_from_be(xs + 32 * i);
    uint8_t* y_out = ys + 32 * i;
    ok[i] = 0;
    memset(y_out, 0, 32);
    if (fe_ge_p(x.v)) continue;
    Fe y_sq = fe_add_small(fe_mul(fe_mul(x, x), x), 7);
    Fe y = fe_sqrt_candidate(y_sq);
    if (!fe_eq(fe_mul(y, y), y_sq)) continue;
    bool want_odd = odd != nullptr && odd[i] != 0;
    if (((y.v[0] & 1) != 0) != want_odd) y = fe_neg(y);
    fe_to_be(y, y_out);
    ok[i] = 1;
  }
}

}  // extern "C"
