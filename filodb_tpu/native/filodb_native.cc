// Native acceleration for host-side hot paths.
//
// The reference leans on native-backed JVM pieces for exactly these loops:
// xxHash for partKey/shard-key hashing (ref: memory/.../format/
// BinaryRegion.scala:14 hasher32 via lz4-java's native XXHash) and the
// NibblePack codec for histogram/timestamp wire compression (ref:
// memory/.../format/NibblePack.scala, spec doc/compression.md:33-90).
// These C implementations are bit-compatible with the pure-Python versions
// in utils/hashing.py and memory/nibblepack.py (enforced by
// tests/test_native.py parity tests) and are loaded via ctypes — no
// pybind11 dependency.
//
// Build: make -C filodb_tpu/native   (g++ -O3 -shared -fPIC)

#include <cstdint>
#include <cstddef>
#include <cstring>

extern "C" {

// ----------------------------------------------------------------- xxHash

static const uint32_t P32_1 = 0x9E3779B1u, P32_2 = 0x85EBCA77u,
                      P32_3 = 0xC2B2AE3Du, P32_4 = 0x27D4EB2Fu,
                      P32_5 = 0x165667B1u;
static const uint64_t P64_1 = 0x9E3779B185EBCA87ull,
                      P64_2 = 0xC2B2AE3D27D4EB4Full,
                      P64_3 = 0x165667B19E3779F9ull,
                      P64_4 = 0x85EBCA77C2B2AE63ull,
                      P64_5 = 0x27D4EB2F165667C5ull;

static inline uint32_t rotl32(uint32_t x, int r) {
  return (x << r) | (x >> (32 - r));
}
static inline uint64_t rotl64(uint64_t x, int r) {
  return (x << r) | (x >> (64 - r));
}
static inline uint32_t read32(const uint8_t* p) {
  uint32_t v;
  std::memcpy(&v, p, 4);
  return v;  // little-endian hosts only (x86_64 / aarch64)
}
static inline uint64_t read64(const uint8_t* p) {
  uint64_t v;
  std::memcpy(&v, p, 8);
  return v;
}
static inline uint32_t round32(uint32_t acc, uint32_t lane) {
  return rotl32(acc + lane * P32_2, 13) * P32_1;
}
static inline uint64_t round64(uint64_t acc, uint64_t lane) {
  return rotl64(acc + lane * P64_2, 31) * P64_1;
}
static inline uint64_t merge64(uint64_t acc, uint64_t val) {
  acc ^= round64(0, val);
  return acc * P64_1 + P64_4;
}

uint32_t filodb_xxhash32(const uint8_t* data, size_t n, uint32_t seed) {
  const uint8_t* p = data;
  const uint8_t* end = data + n;
  uint32_t h;
  if (n >= 16) {
    uint32_t v1 = seed + P32_1 + P32_2, v2 = seed + P32_2, v3 = seed,
             v4 = seed - P32_1;
    const uint8_t* limit = end - 16;
    do {
      v1 = round32(v1, read32(p)); p += 4;
      v2 = round32(v2, read32(p)); p += 4;
      v3 = round32(v3, read32(p)); p += 4;
      v4 = round32(v4, read32(p)); p += 4;
    } while (p <= limit);
    h = rotl32(v1, 1) + rotl32(v2, 7) + rotl32(v3, 12) + rotl32(v4, 18);
  } else {
    h = seed + P32_5;
  }
  h += (uint32_t)n;
  while (p + 4 <= end) {
    h = rotl32(h + read32(p) * P32_3, 17) * P32_4;
    p += 4;
  }
  while (p < end) {
    h = rotl32(h + (*p) * P32_5, 11) * P32_1;
    ++p;
  }
  h ^= h >> 15; h *= P32_2;
  h ^= h >> 13; h *= P32_3;
  h ^= h >> 16;
  return h;
}

uint64_t filodb_xxhash64(const uint8_t* data, size_t n, uint64_t seed) {
  const uint8_t* p = data;
  const uint8_t* end = data + n;
  uint64_t h;
  if (n >= 32) {
    uint64_t v1 = seed + P64_1 + P64_2, v2 = seed + P64_2, v3 = seed,
             v4 = seed - P64_1;
    const uint8_t* limit = end - 32;
    do {
      v1 = round64(v1, read64(p)); p += 8;
      v2 = round64(v2, read64(p)); p += 8;
      v3 = round64(v3, read64(p)); p += 8;
      v4 = round64(v4, read64(p)); p += 8;
    } while (p <= limit);
    h = rotl64(v1, 1) + rotl64(v2, 7) + rotl64(v3, 12) + rotl64(v4, 18);
    h = merge64(h, v1); h = merge64(h, v2);
    h = merge64(h, v3); h = merge64(h, v4);
  } else {
    h = seed + P64_5;
  }
  h += (uint64_t)n;
  while (p + 8 <= end) {
    h ^= round64(0, read64(p));
    h = rotl64(h, 27) * P64_1 + P64_4;
    p += 8;
  }
  if (p + 4 <= end) {
    h ^= (uint64_t)read32(p) * P64_1;
    h = rotl64(h, 23) * P64_2 + P64_3;
    p += 4;
  }
  while (p < end) {
    h ^= (*p) * P64_5;
    h = rotl64(h, 11) * P64_1;
    ++p;
  }
  h ^= h >> 33; h *= P64_2;
  h ^= h >> 29; h *= P64_3;
  h ^= h >> 32;
  return h;
}

// ------------------------------------------------------------- NibblePack
//
// Wire format per group of 8 u64s (spec doc/compression.md:33-90):
//   u8 bitmask (bit i => value i nonzero), then — unless bitmask==0 —
//   u8 header (low nibble: trailing zero nibbles; high: numNibbles-1),
//   then the packed LSB-first nibble stream of the nonzero values.

static inline int trailing_zero_nibbles(uint64_t x) {
  if (x == 0) return 16;
  int n = 0;
  while ((x & 0xF) == 0) { x >>= 4; ++n; }
  return n;
}
static inline int leading_zero_nibbles(uint64_t x) {
  if (x == 0) return 16;
  return __builtin_clzll(x) >> 2;
}

// Returns bytes written, or -1 if out_cap is too small.
// Worst case per group: 2 header bytes + 64 payload bytes.
long filodb_nibble_pack(const uint64_t* vals, size_t n, uint8_t* out,
                        size_t out_cap) {
  size_t pos = 0;
  size_t ngroups = (n + 7) / 8;
  for (size_t g = 0; g < ngroups; ++g) {
    uint64_t group[8] = {0, 0, 0, 0, 0, 0, 0, 0};
    size_t have = n - g * 8 < 8 ? n - g * 8 : 8;
    std::memcpy(group, vals + g * 8, have * sizeof(uint64_t));
    uint8_t bitmask = 0;
    for (int i = 0; i < 8; ++i)
      if (group[i] != 0) bitmask |= (uint8_t)(1u << i);
    if (pos + 66 > out_cap) return -1;
    out[pos++] = bitmask;
    if (bitmask == 0) continue;
    int trailing = 16, leading = 16;
    for (int i = 0; i < 8; ++i) {
      if (group[i] == 0) continue;
      int t = trailing_zero_nibbles(group[i]);
      int l = leading_zero_nibbles(group[i]);
      if (t < trailing) trailing = t;
      if (l < leading) leading = l;
    }
    int num_nibbles = 16 - leading - trailing;
    out[pos++] = (uint8_t)((trailing & 0xF) | ((num_nibbles - 1) << 4));
    // LSB-first nibble stream; a 128-bit accumulator sidesteps 64-bit
    // shift-width limits (vbits can be 64)
    int vbits = num_nibbles * 4;
    uint64_t vmask = vbits >= 64 ? ~0ull : ((1ull << vbits) - 1);
    unsigned __int128 acc = 0;
    int acc_bits = 0;
    for (int i = 0; i < 8; ++i) {
      if (group[i] == 0) continue;
      uint64_t v = (group[i] >> (trailing * 4)) & vmask;
      acc |= (unsigned __int128)v << acc_bits;
      acc_bits += vbits;
      while (acc_bits >= 8) {
        out[pos++] = (uint8_t)(acc & 0xFF);
        acc >>= 8;
        acc_bits -= 8;
      }
    }
    if (acc_bits > 0) out[pos++] = (uint8_t)(acc & 0xFF);
  }
  return (long)pos;
}

// filodb_nibble_pack of each of `rows` rows of `n` values (row-major), one
// after another into `out`; ends[r] is where row r's bytes end.  One call
// a flush block instead of one a series.  Returns bytes written, or -1.
long filodb_nibble_pack_rows(const uint64_t* vals, size_t rows, size_t n,
                             uint8_t* out, size_t out_cap, int64_t* ends) {
  size_t pos = 0;
  for (size_t r = 0; r < rows; ++r) {
    long w = filodb_nibble_pack(vals + r * n, n, out + pos, out_cap - pos);
    if (w < 0) return -1;
    pos += (size_t)w;
    ends[r] = (int64_t)pos;
  }
  return (long)pos;
}

// Returns bytes consumed, or -1 on truncated input.
long filodb_nibble_unpack(const uint8_t* data, size_t len, uint64_t* out,
                          size_t count) {
  size_t pos = 0, idx = 0;
  std::memset(out, 0, count * sizeof(uint64_t));
  while (idx < count) {
    if (pos >= len) return -1;
    uint8_t bitmask = data[pos++];
    if (bitmask == 0) { idx += 8; continue; }
    if (pos >= len) return -1;
    uint8_t hdr = data[pos++];
    int trailing = hdr & 0xF;
    int num_nibbles = (hdr >> 4) + 1;
    int vbits = num_nibbles * 4;
    uint64_t vmask = vbits >= 64 ? ~0ull : ((1ull << vbits) - 1);
    int nonzero = __builtin_popcount(bitmask);
    size_t total_bits = (size_t)vbits * nonzero;
    size_t nbytes = (total_bits + 7) / 8;
    if (pos + nbytes > len) return -1;
    unsigned __int128 acc = 0;
    int acc_bits = 0;
    size_t byte_i = 0;
    for (int i = 0; i < 8; ++i) {
      if (!(bitmask & (1u << i))) continue;
      while (acc_bits < vbits && byte_i < nbytes) {
        acc |= (unsigned __int128)data[pos + byte_i] << acc_bits;
        ++byte_i;
        acc_bits += 8;
      }
      uint64_t v = (uint64_t)acc & vmask;
      acc >>= vbits;
      acc_bits -= vbits;
      if (idx + i < count)
        out[idx + i] = v << (trailing * 4);
    }
    pos += nbytes;
    idx += 8;
  }
  return (long)pos;
}

}  // extern "C"
