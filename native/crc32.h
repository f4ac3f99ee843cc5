// crc32.h — the library's one CRC-32, shared by the plan executor
// (planexec.cc: every message of a native fire, both sides) and the
// shm ring's per-message calls (btl_shm.cc: shmring_read_msg verifies
// inside the copy out of the ring); planexec_crc32 exports it to
// Python for the nativewire sender and the socket leg.
#ifndef OMPITPU_CRC32_H_
#define OMPITPU_CRC32_H_

#include <cstddef>
#include <cstdint>
#include <cstring>
#include <mutex>

#if defined(__x86_64__)
#include <immintrin.h>
#endif

namespace ompitpu {

// zlib-compatible IEEE CRC-32 (polynomial 0xEDB88320), chained like
// zlib.crc32(data, prior) so scatter-gather payloads CRC segment by
// segment without a join. Every message is checked over every byte
// on both sides, so this loop is on the critical path of each message:
// the bulk goes through carry-less-multiply folding where the CPU
// has PCLMULQDQ (asked at run time), through slicing-by-8 tables
// elsewhere; heads, tails and short inputs take the tables. Both
// work on the raw (inverted) register, so they chain mid-message.
// Little-endian hosts only, like planexec.cc's blob parser.
inline uint32_t crc_table[8][256];
inline std::once_flag crc_once;

inline void crc_init() {
  for (uint32_t i = 0; i < 256; ++i) {
    uint32_t c = i;
    for (int k = 0; k < 8; ++k)
      c = (c & 1) ? 0xEDB88320u ^ (c >> 1) : c >> 1;
    crc_table[0][i] = c;
  }
  for (uint32_t i = 0; i < 256; ++i)
    for (int k = 1; k < 8; ++k)
      crc_table[k][i] = crc_table[0][crc_table[k - 1][i] & 0xFF] ^
                        (crc_table[k - 1][i] >> 8);
}

inline uint32_t crc_tables(uint32_t raw, const uint8_t* p, size_t n) {
  std::call_once(crc_once, crc_init);
  const auto& t = crc_table;
  for (; n && (reinterpret_cast<uintptr_t>(p) & 7); --n)
    raw = (raw >> 8) ^ t[0][(raw ^ *p++) & 0xFF];
  for (; n >= 8; p += 8, n -= 8) {
    uint64_t v;
    std::memcpy(&v, p, 8);
    v ^= raw;
    raw = t[7][v & 0xFF] ^ t[6][(v >> 8) & 0xFF] ^
          t[5][(v >> 16) & 0xFF] ^ t[4][(v >> 24) & 0xFF] ^
          t[3][(v >> 32) & 0xFF] ^ t[2][(v >> 40) & 0xFF] ^
          t[1][(v >> 48) & 0xFF] ^ t[0][v >> 56];
  }
  for (; n; --n) raw = (raw >> 8) ^ t[0][(raw ^ *p++) & 0xFF];
  return raw;
}

#if defined(__x86_64__)
#define OMPITPU_CLMUL __attribute__((target("pclmul,sse4.1")))

OMPITPU_CLMUL inline __m128i clmul_load(const uint8_t* p) {
  return _mm_loadu_si128(reinterpret_cast<const __m128i*>(p));
}

// a * x^k folded onto the next block: (a.lo * k.lo) ^ (a.hi * k.hi) ^ in
OMPITPU_CLMUL inline __m128i clmul_fold(__m128i a, __m128i k,
                                        __m128i in) {
  return _mm_xor_si128(
      _mm_xor_si128(_mm_clmulepi64_si128(a, k, 0x00),
                    _mm_clmulepi64_si128(a, k, 0x11)), in);
}

// Folding after Gopal et al., "Fast CRC Computation for Generic
// Polynomials Using PCLMULQDQ" (Intel, 2009), bit-reflected form.
// Needs n >= 64 and n % 16 == 0. The constants are x^(512+32),
// x^(512-32), x^(128+32), x^(128-32) and x^64 mod P, then P and
// floor(x^64 / P) for the Barrett step.
OMPITPU_CLMUL inline uint32_t crc_clmul(uint32_t raw, const uint8_t* p,
                                        size_t n) {
  const __m128i k512 = _mm_set_epi64x(0x01c6e41596, 0x0154442bd4);
  const __m128i k128 = _mm_set_epi64x(0x00ccaa009e, 0x01751997d0);
  const __m128i k64 = _mm_set_epi64x(0, 0x0163cd6124);
  const __m128i poly = _mm_set_epi64x(0x01f7011641, 0x01db710641);
  const __m128i low32 = _mm_setr_epi32(~0, 0, ~0, 0);
  __m128i a0 = _mm_xor_si128(clmul_load(p),
                             _mm_cvtsi32_si128(static_cast<int>(raw)));
  __m128i a1 = clmul_load(p + 16);
  __m128i a2 = clmul_load(p + 32);
  __m128i a3 = clmul_load(p + 48);
  for (p += 64, n -= 64; n >= 64; p += 64, n -= 64) {
    a0 = clmul_fold(a0, k512, clmul_load(p));
    a1 = clmul_fold(a1, k512, clmul_load(p + 16));
    a2 = clmul_fold(a2, k512, clmul_load(p + 32));
    a3 = clmul_fold(a3, k512, clmul_load(p + 48));
  }
  a0 = clmul_fold(a0, k128, a1);
  a0 = clmul_fold(a0, k128, a2);
  a0 = clmul_fold(a0, k128, a3);
  for (; n >= 16; p += 16, n -= 16)
    a0 = clmul_fold(a0, k128, clmul_load(p));
  // 128 -> 64 -> 32 bits
  __m128i t = _mm_clmulepi64_si128(a0, k128, 0x10);
  a0 = _mm_xor_si128(_mm_srli_si128(a0, 8), t);
  t = _mm_srli_si128(a0, 4);
  a0 = _mm_xor_si128(
      _mm_clmulepi64_si128(_mm_and_si128(a0, low32), k64, 0x00), t);
  t = _mm_clmulepi64_si128(_mm_and_si128(a0, low32), poly, 0x10);
  t = _mm_clmulepi64_si128(_mm_and_si128(t, low32), poly, 0x00);
  return static_cast<uint32_t>(
      _mm_extract_epi32(_mm_xor_si128(a0, t), 1));
}

inline bool have_clmul() {
  static const bool yes = __builtin_cpu_supports("pclmul") &&
                          __builtin_cpu_supports("sse4.1");
  return yes;
}
#endif

inline uint32_t crc32_update(uint32_t crc, const uint8_t* p, size_t n,
                             bool tables_only = false) {
  uint32_t raw = ~crc;
#if defined(__x86_64__)
  if (n >= 64 && !tables_only && have_clmul()) {
    size_t bulk = n & ~static_cast<size_t>(15);
    raw = crc_clmul(raw, p, bulk);
    p += bulk;
    n -= bulk;
  }
#else
  (void)tables_only;
#endif
  return ~crc_tables(raw, p, n);
}

}  // namespace ompitpu

#endif  // OMPITPU_CRC32_H_
