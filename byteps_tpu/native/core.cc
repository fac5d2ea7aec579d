// byteps_tpu native runtime core — C ABI, loaded via ctypes.
//
// TPU-native counterpart of the reference's C++ core runtime
// (byteps/common/scheduled_queue.cc, operations.cc:140-180 PartitionTensor,
// global.cc:628-677 EncodeDefaultKey, cpu_reducer.cc).  The reference runs a
// 12-stage threaded pipeline because its stages span CUDA streams, shm and a
// network PS; on TPU the per-chunk pipeline collapses into one fused XLA
// program, so what remains native is the byte-crunching the host still does:
// the byte-bound partition arithmetic, key packing, a multithreaded host
// reducer for staging buffers (async-PS KV store, torch host tensors), the
// Elias-delta coder and CRC32C.  The priority/credit chunk queue is Python
// (common/scheduler.py): its cost is the interpreter's, not the heap's.
//
// No pybind11 in the image — plain extern "C" symbols only.

#include <algorithm>
#include <cstdint>
#include <cstring>
#include <thread>
#include <vector>

namespace {

// -------------------------------------------------------------- cpu reducer

template <typename T>
void add_range(T* dst, const T* src, int64_t begin, int64_t end) {
  for (int64_t i = begin; i < end; ++i) dst[i] += src[i];
}

template <typename T>
void scaled_range(T* dst, const T* src, T alpha, int64_t begin, int64_t end) {
  for (int64_t i = begin; i < end; ++i) dst[i] += alpha * src[i];
}

inline float bf16_to_f32(uint16_t v) {
  uint32_t u = static_cast<uint32_t>(v) << 16;
  float f;
  std::memcpy(&f, &u, 4);
  return f;
}

inline uint16_t f32_to_bf16(float f) {
  uint32_t u;
  std::memcpy(&u, &f, 4);
  // round-to-nearest-even on the truncated 16 bits
  uint32_t rounding = 0x7fff + ((u >> 16) & 1);
  return static_cast<uint16_t>((u + rounding) >> 16);
}

// Split [0, n) across up to nthreads workers; tiny inputs stay inline —
// thread spawn costs ~10us, worth it only for multi-MB buffers.
template <typename Fn>
void parallel_for(int64_t n, int nthreads, Fn fn) {
  const int64_t kMinPerThread = 1 << 18;  // 256k elements
  int workers = static_cast<int>(std::min<int64_t>(
      nthreads, (n + kMinPerThread - 1) / kMinPerThread));
  if (workers <= 1) {
    fn(0, n);
    return;
  }
  std::vector<std::thread> ts;
  ts.reserve(workers);
  int64_t per = (n + workers - 1) / workers;
  for (int w = 0; w < workers; ++w) {
    int64_t b = w * per, e = std::min<int64_t>(n, b + per);
    if (b >= e) break;
    ts.emplace_back([=] { fn(b, e); });
  }
  for (auto& t : ts) t.join();
}

}  // namespace

extern "C" {

// ------------------------------------------------------------ key encoding
// Reference key space: declared_key<<16 gives 2^16 tensors x 2^16 partitions
// (operations.cc:302-311).
uint64_t bps_make_key(uint64_t declared, uint64_t part) {
  return (declared << 16) | (part & 0xffff);
}
uint64_t bps_key_declared(uint64_t key) { return key >> 16; }
uint64_t bps_key_part(uint64_t key) { return key & 0xffff; }

// ------------------------------------------------------------- partitioner
// Byte-bounded chunk bounds with element alignment (reference
// operations.cc:140-180; ALIGN keeps boundaries on vreg-tile multiples).
// Returns the number of chunks written (<= cap), or the required count if
// out buffers are null.
int64_t bps_chunk_bounds(int64_t num_elems, int64_t itemsize,
                         int64_t partition_bytes, int64_t align_elems,
                         int64_t* out_off, int64_t* out_len, int64_t cap) {
  if (num_elems < 0 || itemsize <= 0 || partition_bytes <= 0) return -1;
  if (num_elems == 0) {
    if (out_off && cap >= 1) { out_off[0] = 0; out_len[0] = 0; }
    return 1;
  }
  int64_t max_elems = std::max<int64_t>(1, partition_bytes / itemsize);
  if (num_elems <= max_elems) {
    if (out_off && cap >= 1) { out_off[0] = 0; out_len[0] = num_elems; }
    return 1;
  }
  if (align_elems > 0 && max_elems > align_elems)
    max_elems -= max_elems % align_elems;
  int64_t n = 0, off = 0;
  while (off < num_elems) {
    int64_t ln = std::min(max_elems, num_elems - off);
    if (out_off) {
      if (n >= cap) return -2;  // caller's buffer too small
      out_off[n] = off;
      out_len[n] = ln;
    }
    ++n;
    off += ln;
  }
  return n;
}

// -------------------------------------------------------------- cpu reducer
// dst += src (reference CpuReducer::sum, cpu_reducer.cc — OpenMP there,
// std::thread fan-out here; numpy's single-threaded add is the Python
// fallback).

void bps_reduce_sum_f32(float* dst, const float* src, int64_t n,
                        int nthreads) {
  parallel_for(n, nthreads,
               [=](int64_t b, int64_t e) { add_range(dst, src, b, e); });
}

void bps_reduce_sum_f64(double* dst, const double* src, int64_t n,
                        int nthreads) {
  parallel_for(n, nthreads,
               [=](int64_t b, int64_t e) { add_range(dst, src, b, e); });
}

void bps_reduce_sum_i32(int32_t* dst, const int32_t* src, int64_t n,
                        int nthreads) {
  parallel_for(n, nthreads,
               [=](int64_t b, int64_t e) { add_range(dst, src, b, e); });
}

void bps_reduce_sum_i64(int64_t* dst, const int64_t* src, int64_t n,
                        int nthreads) {
  parallel_for(n, nthreads,
               [=](int64_t b, int64_t e) { add_range(dst, src, b, e); });
}

// dst += alpha * src (compressor decorators use the scaled form,
// cpu_reducer.h:67-180)
void bps_reduce_scaled_f32(float* dst, const float* src, float alpha,
                           int64_t n, int nthreads) {
  parallel_for(n, nthreads, [=](int64_t b, int64_t e) {
    scaled_range(dst, src, alpha, b, e);
  });
}

// bf16 sum in f32 precision with round-to-nearest-even writeback (the
// reference's software half_t serves the same purpose for its CUDA-less
// server, half.h).
void bps_reduce_sum_bf16(uint16_t* dst, const uint16_t* src, int64_t n,
                         int nthreads) {
  parallel_for(n, nthreads, [=](int64_t b, int64_t e) {
    for (int64_t i = b; i < e; ++i)
      dst[i] = f32_to_bf16(bf16_to_f32(dst[i]) + bf16_to_f32(src[i]));
  });
}

// ------------------------------------------------------- elias-delta coder
// Host-side entropy coding of sparse quantization codes: per nonzero
// element, gap-to-previous (Elias-delta), sign bit, |level| (Elias-delta).
// Same wire *semantics* as the reference's dithering output
// (compressor/impl/dithering.cc:51-110, BitWriter/EliasDelta in utils.h),
// re-derived with an LSB-first-in-word layout.  Sequential by nature, so it
// lives on the host (KV/async-PS paths) — the device-side layouts (dense
// int8, sparse index+code) stay static-shape for XLA.

namespace {

struct BitCursor {
  uint32_t* words;
  int64_t cap_bits;
  int64_t pos = 0;
  bool overflow = false;

  void put(uint32_t bit) {
    if (pos >= cap_bits) {
      overflow = true;
      return;
    }
    if (bit)
      words[pos >> 5] |= (1u << (pos & 31));
    pos++;
  }
};

struct BitReaderC {
  const uint32_t* words;
  int64_t nbits;
  int64_t pos = 0;
  bool fail = false;

  uint32_t get() {
    if (pos >= nbits) {
      fail = true;
      return 0;
    }
    uint32_t b = (words[pos >> 5] >> (pos & 31)) & 1u;
    pos++;
    return b;
  }
};

inline int bitlen_u64(uint64_t x) {
  int n = 0;
  while (x) {
    ++n;
    x >>= 1;
  }
  return n;
}

// x >= 1.  N = bitlen(x); L = bitlen(N): L-1 zeros, N's L bits (MSB
// first), then x's low N-1 bits (MSB first).
void elias_put(BitCursor& w, uint64_t x) {
  int n = bitlen_u64(x);
  int l = bitlen_u64(static_cast<uint64_t>(n));
  for (int i = 0; i < l - 1; ++i) w.put(0);
  for (int i = l - 1; i >= 0; --i) w.put((n >> i) & 1);
  for (int i = n - 2; i >= 0; --i) w.put((x >> i) & 1);
}

uint64_t elias_get(BitReaderC& r) {
  int zeros = 0;
  while (!r.fail && r.get() == 0) {
    // valid value bit-lengths are <= 64, so L = bitlen(N) <= 7 and at
    // most 6 leading zeros can occur; more is a forged/corrupt stream
    if (++zeros > 6) {
      r.fail = true;
      return 0;
    }
  }
  if (r.fail) return 0;
  uint64_t n = 1;
  for (int i = 0; i < zeros; ++i) n = (n << 1) | r.get();
  if (r.fail || n > 64) {  // bound BEFORE the value loop: a crafted
    r.fail = true;         // length must not run 2^63 iterations
    return 0;
  }
  uint64_t x = 1;
  for (uint64_t i = 1; i < n && !r.fail; ++i) x = (x << 1) | r.get();
  return r.fail ? 0 : x;
}

}  // namespace

// Encode signed int8 level codes.  Returns the bit count, or -2 when
// cap_words is too small (caller re-allocates).  out must be zeroed by the
// caller (bits are OR-ed in).
int64_t bps_elias_encode(const int8_t* codes, int64_t n, uint32_t* out,
                         int64_t cap_words) {
  BitCursor w{out, cap_words * 32};
  int64_t last = -1;
  for (int64_t i = 0; i < n; ++i) {
    if (codes[i] == 0) continue;
    elias_put(w, static_cast<uint64_t>(i - last));
    w.put(codes[i] < 0 ? 1u : 0u);
    int mag = codes[i] < 0 ? -static_cast<int>(codes[i])
                           : static_cast<int>(codes[i]);
    elias_put(w, static_cast<uint64_t>(mag));
    last = i;
  }
  return w.overflow ? -2 : w.pos;
}

// Decode into a zeroed int8 buffer of n elements.  Returns 0, or -1 on a
// malformed/truncated stream (out may be partially filled).
int64_t bps_elias_decode(const uint32_t* words, int64_t nbits,
                         int8_t* out, int64_t n) {
  BitReaderC r{words, nbits};
  int64_t pos = -1;
  while (r.pos < nbits) {
    uint64_t gap = elias_get(r);
    // bound-check in unsigned space BEFORE any cast: a forged gap
    // >= 2^63 would wrap negative as int64 and index before the buffer
    if (r.fail || gap == 0 ||
        gap > static_cast<uint64_t>(n - 1 - pos))
      return -1;
    uint32_t sign = r.get();
    uint64_t mag = elias_get(r);
    if (r.fail || mag == 0 || mag > 127) return -1;
    pos += static_cast<int64_t>(gap);
    out[pos] = static_cast<int8_t>(sign ? -static_cast<int>(mag)
                                        : static_cast<int>(mag));
  }
  return 0;
}

// ------------------------------------------------------------------ crc32c
//
// CRC32C (Castagnoli) for the integrity envelopes (common/integrity.py):
// every host-crossing payload — server pushes, async-PS deltas, membership
// bus frames, rejoin state blobs — is framed and verified with this
// checksum.  Slice-by-8 software implementation (~1 GB/s at -O3): fast
// enough that the envelope never becomes the wire bottleneck, with no ISA
// dependency (no SSE4.2 requirement).

namespace {

struct Crc32cTables {
  uint32_t t[8][256];
  Crc32cTables() {
    const uint32_t kPoly = 0x82f63b78u;  // reflected Castagnoli
    for (uint32_t i = 0; i < 256; ++i) {
      uint32_t c = i;
      for (int k = 0; k < 8; ++k) c = (c & 1) ? (kPoly ^ (c >> 1)) : (c >> 1);
      t[0][i] = c;
    }
    for (uint32_t i = 0; i < 256; ++i) {
      uint32_t c = t[0][i];
      for (int s = 1; s < 8; ++s) {
        c = t[0][c & 0xff] ^ (c >> 8);
        t[s][i] = c;
      }
    }
  }
};

const Crc32cTables kCrc;

inline uint32_t crc32c_byte(uint32_t crc, uint8_t b) {
  return kCrc.t[0][(crc ^ b) & 0xff] ^ (crc >> 8);
}

inline bool host_is_little_endian() {
  const uint16_t probe = 1;
  uint8_t low;
  std::memcpy(&low, &probe, 1);
  return low == 1;
}

}  // namespace

// Continue `crc` (0 to start) over n bytes; returns the finalized value.
uint32_t bps_crc32c(const uint8_t* p, int64_t n, uint32_t crc) {
  crc = ~crc;
  if (host_is_little_endian()) {
    while (n > 0 && (reinterpret_cast<uintptr_t>(p) & 7)) {
      crc = crc32c_byte(crc, *p++);
      --n;
    }
    while (n >= 8) {
      uint64_t v;
      std::memcpy(&v, p, 8);
      v ^= crc;
      crc = kCrc.t[7][v & 0xff] ^ kCrc.t[6][(v >> 8) & 0xff] ^
            kCrc.t[5][(v >> 16) & 0xff] ^ kCrc.t[4][(v >> 24) & 0xff] ^
            kCrc.t[3][(v >> 32) & 0xff] ^ kCrc.t[2][(v >> 40) & 0xff] ^
            kCrc.t[1][(v >> 48) & 0xff] ^ kCrc.t[0][(v >> 56) & 0xff];
      p += 8;
      n -= 8;
    }
  }
  while (n > 0) {
    crc = crc32c_byte(crc, *p++);
    --n;
  }
  return ~crc;
}

int bps_native_abi_version() { return 5; }

}  // extern "C"
