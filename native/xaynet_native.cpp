// Native host kernels for xaynet_tpu.
//
// The reference implements its entire hot path in native code (Rust); the
// TPU build keeps the *device* hot loops in XLA/Pallas and implements the
// host-side compute-heavy pieces here in C++:
//
//   - ChaCha20 keystream generation (the PET mask-expansion PRNG;
//     reference semantics: rust/xaynet-core/src/crypto/prng.rs:16-27),
//   - rejection sampling of uniform finite-group elements from that
//     keystream (byte-stream compatible with the Python/JAX samplers),
//   - fixed-width little-endian modular add/sub over element vectors (the
//     CPU fallback of the aggregation kernels).
//
// Built as a plain shared library; loaded via ctypes (no pybind11).

#include <atomic>
#include <chrono>
#include <cerrno>
#include <cstdint>
#include <cstdlib>
#include <cstring>
#include <ctime>
#include <memory>
#include <thread>
#include <vector>

#include <poll.h>
#include <sys/resource.h>
#include <sys/socket.h>

#ifdef __AVX2__
#include <immintrin.h>
#endif

#define XN_EXPORT extern "C" __attribute__((visibility("default")))

namespace {

inline uint32_t rotl(uint32_t x, int n) { return (x << n) | (x >> (32 - n)); }

inline void quarter(uint32_t s[16], int a, int b, int c, int d) {
  s[a] += s[b];
  s[d] = rotl(s[d] ^ s[a], 16);
  s[c] += s[d];
  s[b] = rotl(s[b] ^ s[c], 12);
  s[a] += s[b];
  s[d] = rotl(s[d] ^ s[a], 8);
  s[c] += s[d];
  s[b] = rotl(s[b] ^ s[c], 7);
}

// One 64-byte ChaCha20 block (djb variant: 64-bit counter, 64-bit zero nonce).
void chacha20_block(const uint32_t key[8], uint64_t counter, uint8_t out[64]) {
  uint32_t s[16] = {0x61707865u, 0x3320646eu, 0x79622d32u, 0x6b206574u,
                    key[0],      key[1],      key[2],      key[3],
                    key[4],      key[5],      key[6],      key[7],
                    (uint32_t)(counter & 0xffffffffu),
                    (uint32_t)(counter >> 32),
                    0u,          0u};
  uint32_t w[16];
  std::memcpy(w, s, sizeof(w));
  for (int i = 0; i < 10; i++) {
    quarter(w, 0, 4, 8, 12);
    quarter(w, 1, 5, 9, 13);
    quarter(w, 2, 6, 10, 14);
    quarter(w, 3, 7, 11, 15);
    quarter(w, 0, 5, 10, 15);
    quarter(w, 1, 6, 11, 12);
    quarter(w, 2, 7, 8, 13);
    quarter(w, 3, 4, 9, 14);
  }
  for (int i = 0; i < 16; i++) {
    uint32_t v = w[i] + s[i];
    out[i * 4 + 0] = (uint8_t)(v);
    out[i * 4 + 1] = (uint8_t)(v >> 8);
    out[i * 4 + 2] = (uint8_t)(v >> 16);
    out[i * 4 + 3] = (uint8_t)(v >> 24);
  }
}

#ifdef __AVX2__
namespace {

inline __m256i rotl8v(__m256i x, int n) {
  return _mm256_or_si256(_mm256_slli_epi32(x, n), _mm256_srli_epi32(x, 32 - n));
}

// rotations by 16 and 8 bits move whole bytes: one shuffle instead of two
// shifts and an or
#define XN_QUARTER8(a, b, c, d)                             \
  a = _mm256_add_epi32(a, b);                               \
  d = _mm256_shuffle_epi8(_mm256_xor_si256(d, a), rot16);   \
  c = _mm256_add_epi32(c, d);                               \
  b = rotl8v(_mm256_xor_si256(b, c), 12);                   \
  a = _mm256_add_epi32(a, b);                               \
  d = _mm256_shuffle_epi8(_mm256_xor_si256(d, a), rot8);    \
  c = _mm256_add_epi32(c, d);                               \
  b = rotl8v(_mm256_xor_si256(b, c), 7)

// Eight consecutive ChaCha20 blocks in parallel (one block per SIMD lane).
void chacha20_blocks8(const uint32_t key[8], uint64_t counter0, uint8_t out[512]) {
  const uint32_t consts[4] = {0x61707865u, 0x3320646eu, 0x79622d32u, 0x6b206574u};
  __m256i s[16];
  for (int i = 0; i < 4; i++) s[i] = _mm256_set1_epi32((int)consts[i]);
  for (int i = 0; i < 8; i++) s[4 + i] = _mm256_set1_epi32((int)key[i]);
  alignas(32) uint32_t ctr_lo[8], ctr_hi[8];
  for (int l = 0; l < 8; l++) {
    uint64_t c = counter0 + (uint64_t)l;
    ctr_lo[l] = (uint32_t)(c & 0xffffffffu);
    ctr_hi[l] = (uint32_t)(c >> 32);
  }
  s[12] = _mm256_load_si256((const __m256i*)ctr_lo);
  s[13] = _mm256_load_si256((const __m256i*)ctr_hi);
  s[14] = _mm256_setzero_si256();
  s[15] = _mm256_setzero_si256();

  const __m256i rot16 = _mm256_set_epi8(13, 12, 15, 14, 9, 8, 11, 10, 5, 4, 7, 6, 1, 0, 3, 2,
                                        13, 12, 15, 14, 9, 8, 11, 10, 5, 4, 7, 6, 1, 0, 3, 2);
  const __m256i rot8 = _mm256_set_epi8(14, 13, 12, 15, 10, 9, 8, 11, 6, 5, 4, 7, 2, 1, 0, 3,
                                       14, 13, 12, 15, 10, 9, 8, 11, 6, 5, 4, 7, 2, 1, 0, 3);
  __m256i w0 = s[0], w1 = s[1], w2 = s[2], w3 = s[3], w4 = s[4], w5 = s[5],
          w6 = s[6], w7 = s[7], w8 = s[8], w9 = s[9], w10 = s[10], w11 = s[11],
          w12 = s[12], w13 = s[13], w14 = s[14], w15 = s[15];
  for (int r = 0; r < 10; r++) {
    XN_QUARTER8(w0, w4, w8, w12);
    XN_QUARTER8(w1, w5, w9, w13);
    XN_QUARTER8(w2, w6, w10, w14);
    XN_QUARTER8(w3, w7, w11, w15);
    XN_QUARTER8(w0, w5, w10, w15);
    XN_QUARTER8(w1, w6, w11, w12);
    XN_QUARTER8(w2, w7, w8, w13);
    XN_QUARTER8(w3, w4, w9, w14);
  }
  __m256i v[16] = {w0, w1, w2, w3, w4, w5, w6, w7, w8, w9, w10, w11, w12, w13, w14, w15};
  for (int i = 0; i < 16; i++) v[i] = _mm256_add_epi32(v[i], s[i]);
  // transpose: block l = words 0..15, lane l. Two SIMD 8x8 32-bit
  // transposes (words 0-7 -> first 32B of each block, words 8-15 -> second
  // 32B) replace the 128 scalar stores the first version paid per 512B.
  for (int half = 0; half < 2; half++) {
    const __m256i* r = v + half * 8;
    __m256i t0 = _mm256_unpacklo_epi32(r[0], r[1]);
    __m256i t1 = _mm256_unpackhi_epi32(r[0], r[1]);
    __m256i t2 = _mm256_unpacklo_epi32(r[2], r[3]);
    __m256i t3 = _mm256_unpackhi_epi32(r[2], r[3]);
    __m256i t4 = _mm256_unpacklo_epi32(r[4], r[5]);
    __m256i t5 = _mm256_unpackhi_epi32(r[4], r[5]);
    __m256i t6 = _mm256_unpacklo_epi32(r[6], r[7]);
    __m256i t7 = _mm256_unpackhi_epi32(r[6], r[7]);
    __m256i u0 = _mm256_unpacklo_epi64(t0, t2);
    __m256i u1 = _mm256_unpackhi_epi64(t0, t2);
    __m256i u2 = _mm256_unpacklo_epi64(t1, t3);
    __m256i u3 = _mm256_unpackhi_epi64(t1, t3);
    __m256i u4 = _mm256_unpacklo_epi64(t4, t6);
    __m256i u5 = _mm256_unpackhi_epi64(t4, t6);
    __m256i u6 = _mm256_unpacklo_epi64(t5, t7);
    __m256i u7 = _mm256_unpackhi_epi64(t5, t7);
    uint8_t* o = out + half * 32;
    _mm256_storeu_si256((__m256i*)(o + 0 * 64), _mm256_permute2x128_si256(u0, u4, 0x20));
    _mm256_storeu_si256((__m256i*)(o + 1 * 64), _mm256_permute2x128_si256(u1, u5, 0x20));
    _mm256_storeu_si256((__m256i*)(o + 2 * 64), _mm256_permute2x128_si256(u2, u6, 0x20));
    _mm256_storeu_si256((__m256i*)(o + 3 * 64), _mm256_permute2x128_si256(u3, u7, 0x20));
    _mm256_storeu_si256((__m256i*)(o + 4 * 64), _mm256_permute2x128_si256(u0, u4, 0x31));
    _mm256_storeu_si256((__m256i*)(o + 5 * 64), _mm256_permute2x128_si256(u1, u5, 0x31));
    _mm256_storeu_si256((__m256i*)(o + 6 * 64), _mm256_permute2x128_si256(u2, u6, 0x31));
    _mm256_storeu_si256((__m256i*)(o + 7 * 64), _mm256_permute2x128_si256(u3, u7, 0x31));
  }
}

}  // namespace
#endif  // __AVX2__

namespace {

// Fill `nblocks` consecutive blocks starting at `counter0` into `out`,
// using the 8-way kernel where possible.
void chacha20_fill(const uint32_t key[8], uint64_t counter0, uint64_t nblocks,
                   uint8_t* out) {
  uint64_t b = 0;
#ifdef __AVX2__
  for (; b + 8 <= nblocks; b += 8) {
    chacha20_blocks8(key, counter0 + b, out + b * 64);
  }
#endif
  for (; b < nblocks; b++) chacha20_block(key, counter0 + b, out + b * 64);
}

}  // namespace

// value < order over fixed-width little-endian byte strings.
inline bool lt_le(const uint8_t* value, const uint8_t* order, uint32_t n) {
  for (int i = (int)n - 1; i >= 0; i--) {
    if (value[i] < order[i]) return true;
    if (value[i] > order[i]) return false;
  }
  return false;  // equal
}

inline unsigned __int128 load_le16(const uint8_t* p, uint32_t nbytes) {
  uint64_t lo, hi;
  std::memcpy(&lo, p, 8);
  if (nbytes <= 8) {
    if (nbytes == 8) return lo;
    return lo & ((1ull << (8 * nbytes)) - 1);
  }
  std::memcpy(&hi, p + 8, 8);
  unsigned __int128 v = ((unsigned __int128)hi << 64) | lo;
  if (nbytes == 16) return v;
  unsigned __int128 mask = ((unsigned __int128)1 << (8 * nbytes)) - 1;
  return v & mask;
}

}  // namespace

// Generate `nblocks` keystream blocks starting at `block_start` into `out`
// (64 bytes per block).
XN_EXPORT void xn_chacha20_blocks(const uint8_t key_bytes[32], uint64_t block_start,
                                  uint64_t nblocks, uint8_t* out) {
  uint32_t key[8];
  std::memcpy(key, key_bytes, 32);
  chacha20_fill(key, block_start, nblocks, out);
}

// Draw `count` uniform values below `order` (little-endian, `order_nbytes`
// wide — the byte length of the order itself) from the keystream of `key`,
// starting at absolute keystream byte `byte_offset`. Each rejection attempt
// consumes `order_nbytes` bytes, exactly like the sequential reference
// sampler. Accepted values are written fixed-width little-endian to `out`
// (count * order_nbytes bytes). Returns the new keystream byte offset.
XN_EXPORT uint64_t xn_sample_uniform(const uint8_t key_bytes[32], uint64_t byte_offset,
                                     uint64_t count, const uint8_t* order_le,
                                     uint32_t order_nbytes, uint8_t* out) {
  uint32_t key[8];
  std::memcpy(key, key_bytes, 32);
  unsigned __int128 order128 = 0;
  const bool small_order = order_nbytes <= 16;
  if (small_order) {
    for (int i = (int)order_nbytes - 1; i >= 0; i--)
      order128 = (order128 << 8) | order_le[i];
  }

  // Buffered keystream: generate CHUNK_BLOCKS blocks at a time and slice
  // candidates out of the flat buffer (carrying the partial tail between
  // refills), instead of reassembling byte-by-byte.
  constexpr uint64_t CHUNK_BLOCKS = 1024;  // 64 KiB of keystream per refill
  std::vector<uint8_t> buf(CHUNK_BLOCKS * 64 + 512);
  uint64_t avail = 0;  // valid bytes in buf

  uint64_t next_block = byte_offset / 64;
  uint64_t intra = byte_offset % 64;
  // prime the buffer with the partial first block
  if (intra) {
    uint8_t first[64];
    chacha20_block(key, next_block, first);
    next_block++;
    avail = 64 - intra;
    std::memcpy(buf.data(), first + intra, avail);
  }

  uint64_t offset = byte_offset;
  uint64_t pos = 0;  // read cursor within buf
  uint64_t got = 0;

  if (order_nbytes <= 8) {
    // u64 fast path (every <= 2-limb order): one unaligned 8-byte load +
    // mask + compare per candidate instead of the generic __int128
    // reassembly, and accepted values store as one masked u64 (the spill
    // byte is zero and the next accept overwrites it; only the LAST
    // element stores exactly its width). The candidate loop — not the
    // keystream — was ~80% of the sampler wall at bpn=7.
    const uint64_t order64 = (uint64_t)order128;
    const uint64_t vmask =
        order_nbytes == 8 ? ~0ull : ((1ull << (8 * order_nbytes)) - 1);
    const uint64_t out_bytes = count * order_nbytes;
    while (got < count) {
      if (avail - pos < order_nbytes + 8) {
        uint64_t tail = avail - pos;
        std::memmove(buf.data(), buf.data() + pos, tail);
        chacha20_fill(key, next_block, CHUNK_BLOCKS, buf.data() + tail);
        next_block += CHUNK_BLOCKS;
        avail = tail + CHUNK_BLOCKS * 64;
        pos = 0;
      }
      // candidates fully inside the buffer (8-byte loads stay in the +512
      // slack); stop at `count` accepts so the cursor lands exactly on the
      // byte after the count-th accepted attempt
      const uint64_t n_here = (avail - pos - 8) / order_nbytes;
      const uint8_t* p = buf.data() + pos;
      uint64_t consumed = 0;
      for (uint64_t i = 0; i < n_here; i++) {
        uint64_t v;
        std::memcpy(&v, p + i * order_nbytes, 8);
        v &= vmask;
        consumed += order_nbytes;
        if (v < order64) {
          if (got * order_nbytes + 8 <= out_bytes) {
            std::memcpy(out + got * order_nbytes, &v, 8);
          } else {
            std::memcpy(out + got * order_nbytes, &v, order_nbytes);
          }
          got++;
          if (got == count) break;
        }
      }
      pos += consumed;
      offset += consumed;
    }
    return offset;
  }

  for (; got < count;) {
    if (avail - pos < order_nbytes) {
      // move the tail to the front, refill through the 8-way AVX2 kernel
      uint64_t tail = avail - pos;
      std::memmove(buf.data(), buf.data() + pos, tail);
      chacha20_fill(key, next_block, CHUNK_BLOCKS, buf.data() + tail);
      next_block += CHUNK_BLOCKS;
      avail = tail + CHUNK_BLOCKS * 64;
      pos = 0;
    }
    const uint8_t* candidate = buf.data() + pos;
    pos += order_nbytes;
    offset += order_nbytes;
    const bool accept = small_order ? (load_le16(candidate, order_nbytes) < order128)
                                    : lt_le(candidate, order_le, order_nbytes);
    if (accept) {
      std::memcpy(out + got * order_nbytes, candidate, order_nbytes);
      got++;
    }
  }
  return offset;
}

// (a + b) mod order, elementwise over `n` values of `n_limbs` uint32 limbs
// (little-endian limb order, wire layout [n, L]); a, b < order.
// `order_limbs` may be all zero when order == 2^(32*L) (natural wraparound).
XN_EXPORT void xn_mod_add(const uint32_t* a, const uint32_t* b, uint32_t* out,
                          uint64_t n, uint32_t n_limbs, const uint32_t* order_limbs) {
  bool order_is_pow2_boundary = true;
  for (uint32_t j = 0; j < n_limbs; j++)
    if (order_limbs[j] != 0) order_is_pow2_boundary = false;

  for (uint64_t i = 0; i < n; i++) {
    const uint32_t* av = a + i * n_limbs;
    const uint32_t* bv = b + i * n_limbs;
    uint32_t* ov = out + i * n_limbs;
    uint64_t carry = 0;
    for (uint32_t j = 0; j < n_limbs; j++) {
      uint64_t s = (uint64_t)av[j] + bv[j] + carry;
      ov[j] = (uint32_t)s;
      carry = s >> 32;
    }
    if (order_is_pow2_boundary) continue;
    bool ge = carry != 0;
    if (!ge) {
      ge = !lt_le((const uint8_t*)ov, (const uint8_t*)order_limbs, n_limbs * 4);
    }
    if (ge) {
      uint64_t borrow = 0;
      for (uint32_t j = 0; j < n_limbs; j++) {
        uint64_t d = (uint64_t)ov[j] - order_limbs[j] - borrow;
        ov[j] = (uint32_t)d;
        borrow = (d >> 63) & 1;
      }
    }
  }
}

namespace {

// Worker-thread count for the batch folds: XAYNET_NATIVE_THREADS overrides
// (values < 1 mean single-threaded), otherwise 2x hardware_concurrency
// capped at 16. The folds are bandwidth-bound; the 2x oversubscription is
// deliberate — on the small shared-container CPU quotas the coordinator
// runs under, extra runnable threads hide per-thread DRAM stalls and
// scheduler preemption (measured ~15% over 1x at the 25M bench shape on a
// 2-CPU cgroup), while the cap keeps big hosts from spawning threads well
// past the memory channels.
unsigned fold_threads() {
  static const unsigned cached = [] {
    const char* env = std::getenv("XAYNET_NATIVE_THREADS");
    if (env && *env) {
      const long v = std::strtol(env, nullptr, 10);
      if (v < 1) return 1u;
      return (unsigned)(v > 64 ? 64 : v);
    }
    unsigned hc = std::thread::hardware_concurrency();
    if (hc == 0) hc = 1;
    const unsigned t = 2 * hc;
    return t > 16 ? 16u : t;
  }();
  return cached;
}

// What the worker threads started and joined by one thread have spent, in
// total since that thread began (ABI 15): user and system microseconds, minor
// and major page faults, voluntary and involuntary context switches. A worker
// is a fresh thread, so the kernel's count for it at its end is what it spent,
// its own stack's first touch included; the thread that joins it adds that to
// its own tally, which a caller that times a stage reads beside its own
// getrusage (telemetry/tracing.py: the calling thread's reading alone would
// say that a copy on sixteen workers cost nothing and faulted nothing in).
thread_local uint64_t tl_workers_spent[6] = {0, 0, 0, 0, 0, 0};

struct WorkerTally {
  std::atomic<uint64_t> v[6];
  WorkerTally() {
    for (auto& x : v) x.store(0, std::memory_order_relaxed);
  }
  // on a worker, at its end
  void add_this_thread() {
#ifdef RUSAGE_THREAD
    struct rusage ru;
    if (getrusage(RUSAGE_THREAD, &ru) != 0) return;
    const uint64_t now[6] = {
        (uint64_t)ru.ru_utime.tv_sec * 1000000ull + (uint64_t)ru.ru_utime.tv_usec,
        (uint64_t)ru.ru_stime.tv_sec * 1000000ull + (uint64_t)ru.ru_stime.tv_usec,
        (uint64_t)ru.ru_minflt, (uint64_t)ru.ru_majflt,
        (uint64_t)ru.ru_nvcsw,  (uint64_t)ru.ru_nivcsw};
    for (int i = 0; i < 6; i++) v[i].fetch_add(now[i], std::memory_order_relaxed);
#endif
  }
  // on the thread that started them, once they are joined
  void settle() {
    for (int i = 0; i < 6; i++) tl_workers_spent[i] += v[i].load(std::memory_order_relaxed);
  }
};

// Run fn(s0, s1) over contiguous slices of [0, n): the fold's element axis
// is embarrassingly parallel, so each thread owns a disjoint slice and no
// merge step exists. Slices align to `align` (the fold's BLOCK size) and a
// minimum slice keeps tiny folds single-threaded — thread spawn (~10us)
// must never dominate a sub-millisecond fold. `nt_override` > 0 pins the
// worker count for this call (the plane packs, which the producer thread
// runs once per shard slice); 0 keeps fold_threads().
template <typename F>
void run_sliced(uint64_t n, uint64_t align, F&& fn, unsigned nt_override = 0) {
  unsigned nt = nt_override ? (nt_override > 64 ? 64u : nt_override) : fold_threads();
  constexpr uint64_t MIN_SLICE = 1ull << 19;  // 512k elements (~4 MB of u64 sums)
  if (nt > 1) {
    const uint64_t cap = n / MIN_SLICE;
    if (cap < nt) nt = (unsigned)(cap ? cap : 1);
  }
  if (nt <= 1) {
    fn((uint64_t)0, n);
    return;
  }
  uint64_t chunk = (n + nt - 1) / nt;
  chunk = (chunk + align - 1) / align * align;
  std::vector<std::thread> threads;
  threads.reserve(nt);
  WorkerTally tally;
  for (unsigned t = 0; t < nt; t++) {
    const uint64_t s0 = (uint64_t)t * chunk;
    if (s0 >= n) break;
    const uint64_t s1 = s0 + chunk < n ? s0 + chunk : n;
    threads.emplace_back([&fn, &tally, s0, s1] {
      fn(s0, s1);
      tally.add_this_thread();
    });
  }
  for (auto& th : threads) th.join();
  tally.settle();
}

// The single-pass u64 batch fold over one element slice [s0, s1) of the
// wire layout uint32[n, L] (for L == 2 a wire row is one little-endian
// u64 — contiguous 8-byte loads). The arithmetic: double-reciprocal
// quotient with two rounding fixups, u64 wraparound on pow2-boundary
// orders (order == 0).
void fold_wire_u64_slice(const uint32_t* acc, const uint32_t* stack, uint32_t* out, uint64_t n,
                         uint32_t n_limbs, uint64_t k, uint64_t order, uint64_t s0,
                         uint64_t s1) {
  const bool pow2_boundary = order == 0;
  const bool two_limbs = n_limbs == 2;
  // quotient sum/order is tiny (< K+1): one double multiply approximates it
  // to +-1 and two fixups make it exact — far cheaper than a u64 divide
  const double inv_order = pow2_boundary ? 0.0 : 1.0 / (double)order;

  // i-blocked so every inner loop is a flat auto-vectorizable stream and
  // the u64 partial sums stay in L1/L2 while the K streams are read once
  constexpr uint64_t BLOCK = 4096;
  uint64_t sum[BLOCK];
  for (uint64_t s = s0; s < s1; s += BLOCK) {
    const uint64_t bn = (s1 - s) < BLOCK ? (s1 - s) : BLOCK;
    if (two_limbs) {
      for (uint64_t i = 0; i < bn; i++) {
        const uint32_t* row = acc + 2 * (s + i);
        sum[i] = (uint64_t)row[0] | ((uint64_t)row[1] << 32);
      }
      for (uint64_t kk = 0; kk < k; kk++) {
        const uint32_t* up = stack + kk * 2 * n + 2 * s;
        for (uint64_t i = 0; i < bn; i++)
          sum[i] += (uint64_t)up[2 * i] | ((uint64_t)up[2 * i + 1] << 32);
      }
    } else {
      for (uint64_t i = 0; i < bn; i++) sum[i] = acc[s + i];
      for (uint64_t kk = 0; kk < k; kk++) {
        const uint32_t* up = stack + kk * n + s;
        for (uint64_t i = 0; i < bn; i++) sum[i] += up[i];
      }
    }
    if (!pow2_boundary) {
      for (uint64_t i = 0; i < bn; i++) {
        const uint64_t q = (uint64_t)((double)sum[i] * inv_order);
        uint64_t r = sum[i] - q * order;
        // double rounding can land one order off in either direction
        r += (r >> 63) ? order : 0;     // q overshot (r went negative)
        r -= (r >= order) ? order : 0;  // q undershot
        sum[i] = r;
      }
    } else if (!two_limbs) {
      for (uint64_t i = 0; i < bn; i++) sum[i] &= 0xFFFFFFFFull;
    }  // order == 2^64: u64 arithmetic wraps naturally
    if (two_limbs) {
      for (uint64_t i = 0; i < bn; i++) {
        out[2 * (s + i)] = (uint32_t)sum[i];
        out[2 * (s + i) + 1] = (uint32_t)(sum[i] >> 32);
      }
    } else {
      for (uint64_t i = 0; i < bn; i++) out[s + i] = (uint32_t)sum[i];
    }
  }
}

}  // namespace

// ---------------------------------------------------------------------------
// Streaming derive-and-sum (ABI 11): the sum participant's masks, derived and
// summed in one pass, with no mask ever in memory (docs/DESIGN.md section 15).
//
// Every attempt of the rejection sampler consumes exactly `bpn` bytes,
// accepted or not, and ChaCha20 is addressable by block. So candidate j of a
// seed sits at keystream byte `byte_offset + j * bpn`, known in advance; only
// the OUTPUT index of an accepted candidate (how many were accepted before it)
// depends on the stream. A seed's candidates are therefore cut into segments
// of `seg_cand` candidates. Any thread samples any segment into a small
// compacted buffer and publishes the count it accepted; segments are placed in
// segment order (the seed's running count `pos` is a segment's position,
// `pos + cnt` the next one's) by whichever thread finds the due one sampled,
// and the owner then adds its values into `acc[pos : pos + cnt]`. A thread
// whose segment is not placed yet samples on (DS_RUN_AHEAD), so one thread
// taken off its core does not stop the others. The ranges of one seed are
// disjoint, so the threads of a group share one accumulator with no lock, and
// the serial part of a segment is a few loads and stores. The walk of a seed
// stops at the segment in which the n-th acceptance falls; the end cursor is
// the byte after that attempt, as in xn_sample_uniform.
//
// Threads are split into `n_groups` groups, each with an accumulator of its
// own (group 0 uses the caller's) and every n_groups-th seed: one group when
// an accumulator is large (all threads share each seed's segments), one
// thread a group when seeds are many and small (seed-grained work, nothing
// waits). The caller picks the split from n, k, the draw width and the
// acceptance rate; the result does not depend on it.
//
// Accumulation is lazy in a word of `acc_stride` bytes (8, 12 or 16) that k
// sums cannot overflow ((k + 1) * order < 2^(8 * acc_stride), the caller's
// choice), reduced once at the end; with `eager` set (a 16-byte order too
// close to 2^128 for any headroom) every add is a modular add instead.

namespace {

using u128 = unsigned __int128;

inline void spin_wait(unsigned& spins) {
  if (++spins < 64) {
#if defined(__x86_64__) || defined(__i386__)
    __builtin_ia32_pause();
#endif
  } else {
    std::this_thread::yield();
  }
}

// A sampled segment on its way into the sum: its owner publishes the count it
// accepted (`sampled`), whoever places it gives it the position its values go
// to (`placed`), and the owner takes that (`taken`) and adds them there. A
// slot serves ticket j, then j + ring, ...: the three words hold the ticket
// plus one, and a ticket is published only once the slot's last is taken.
struct DeriveSlot {
  std::atomic<uint64_t> sampled{0};
  std::atomic<uint64_t> placed{0};
  std::atomic<uint64_t> taken{0};
  uint64_t cnt = 0, pos = 0, take = 0;
};

struct alignas(64) DeriveSeed {
  std::atomic<uint64_t> next{0};   // next segment to sample (a ticket)
  std::atomic<uint64_t> turn{0};   // the segment whose place is due
  std::atomic<uint64_t> added{0};  // placed segments whose adds are complete
  std::atomic<bool> done{false};   // the segment holding the n-th accept is placed
  std::atomic<bool> placing{false};  // one thread at a time walks `turn` on
  std::atomic<uint64_t> pos{0};    // accepted so far; written by the placing thread
  uint64_t total = 0;              // placed segments; valid once `done`
  std::unique_ptr<DeriveSlot[]> ring;
};

// A thread whose segment cannot be placed yet samples on: up to this many
// segments held, sampled and not added. Positions are handed out in ticket
// order, so a thread that the scheduler (or, on a shared host, the
// hypervisor) takes off its core in the middle of a segment holds up the
// place of every later one; the others then lose that time after
// DS_RUN_AHEAD segments of further work each, and not at once. Buffers past
// the first are allocated and touched only where such a wait happened.
constexpr uint32_t DS_RUN_AHEAD = 4;
// ... once it has waited this long: placing is a handful of stores, so in
// step the wait is microseconds and a thread holds one segment.
constexpr auto DS_AHEAD_AFTER = std::chrono::microseconds(100);

// Compact the accepted candidates of `c` attempts at `p` into `vals`.
// Orders of up to 8 bytes: one masked 8-byte load an attempt, and a store
// that always happens with a count that moves only on accept, so a 28%
// acceptance rate costs no branch mispredictions.
inline uint64_t sample_segment(const uint8_t* p, uint64_t c, uint32_t bpn, u128 order,
                               uint64_t* vals) {
  const uint64_t order64 = (uint64_t)order;
  const uint64_t vmask = bpn == 8 ? ~0ull : ((1ull << (8 * bpn)) - 1);
  uint64_t cnt = 0;
  for (uint64_t i = 0; i < c; i++) {
    uint64_t v;
    std::memcpy(&v, p + i * bpn, 8);
    v &= vmask;
    vals[cnt] = v;
    cnt += v < order64;
  }
  return cnt;
}

// Orders of 9 to 16 bytes: the top eight bytes decide nearly every attempt
// (a 75-bit order in 10 bytes rejects 98% of them there), the whole value is
// loaded only for a candidate that survives them.
inline uint64_t sample_segment(const uint8_t* p, uint64_t c, uint32_t bpn, u128 order,
                               u128* vals) {
  const uint32_t top_at = bpn - 8;
  const uint64_t order_top = (uint64_t)(order >> (8 * top_at));
  uint64_t cnt = 0;
  for (uint64_t i = 0; i < c; i++) {
    const uint8_t* q = p + i * bpn;
    uint64_t top;
    std::memcpy(&top, q + top_at, 8);
    if (top > order_top) continue;
    const u128 v = load_le16(q, bpn);
    if (v < order) vals[cnt++] = v;
  }
  return cnt;
}

// A segment is sampled in chunks of this much keystream, so that the bytes
// are consumed from the cache they were generated into whatever the
// segment's length.
constexpr uint64_t DS_CHUNK_BYTES = 64 * 1024;

// Sample the `c` candidates that start at keystream byte `b0` of `key` into
// `vals` (room for `c`); returns how many were accepted. `ks` holds a chunk:
// whole blocks around its bytes and 16 bytes of slack for the last wide load.
template <typename V>
inline uint64_t sample_range(const uint32_t key[8], uint64_t b0, uint64_t c, uint32_t bpn,
                             u128 order, uint8_t* ks, V* vals) {
  const uint64_t step = DS_CHUNK_BYTES / bpn;
  uint64_t cnt = 0;
  for (uint64_t c0 = 0; c0 < c; c0 += step) {
    const uint64_t here = c - c0 < step ? c - c0 : step;
    const uint64_t b = b0 + c0 * bpn;
    chacha20_fill(key, b / 64, (b % 64 + here * bpn + 63) / 64, ks);
    cnt += sample_segment(ks + b % 64, here, bpn, order, vals + cnt);
  }
  return cnt;
}

// Index of the `nth` (1-based) accepted candidate of those from byte `b0`.
inline uint64_t nth_accept(const uint32_t key[8], uint64_t b0, uint32_t bpn, u128 order,
                           uint64_t nth, uint8_t* ks) {
  const uint64_t step = DS_CHUNK_BYTES / bpn;
  for (uint64_t c0 = 0;; c0 += step) {
    const uint64_t b = b0 + c0 * bpn;
    chacha20_fill(key, b / 64, (b % 64 + step * bpn + 63) / 64, ks);
    const uint8_t* p = ks + b % 64;
    for (uint64_t i = 0; i < step; i++) {
      if (load_le16(p + i * bpn, bpn) < order && --nth == 0) return c0 + i;
    }
  }
}

template <int S>
inline u128 acc_load(const uint8_t* a) {
  if (S == 8) {
    uint64_t v;
    std::memcpy(&v, a, 8);
    return v;
  }
  uint64_t lo;
  std::memcpy(&lo, a, 8);
  if (S == 12) {
    uint32_t hi;
    std::memcpy(&hi, a + 8, 4);
    return ((u128)hi << 64) | lo;
  }
  uint64_t hi;
  std::memcpy(&hi, a + 8, 8);
  return ((u128)hi << 64) | lo;
}

template <int S>
inline void acc_store(uint8_t* a, u128 v) {
  const uint64_t lo = (uint64_t)v;
  std::memcpy(a, &lo, 8);
  if (S == 12) {
    const uint32_t hi = (uint32_t)(v >> 64);
    std::memcpy(a + 8, &hi, 4);
  } else if (S == 16) {
    const uint64_t hi = (uint64_t)(v >> 64);
    std::memcpy(a + 8, &hi, 8);
  }
}

// (a + b) mod order for a, b < order <= 2^128 - 1: the true sum is under
// 2^129, so a lost carry or a sum at or over the order both mean one
// subtraction, and u128 wraparound makes it exact.
inline u128 mod_add128(u128 a, u128 b, u128 order) {
  const u128 s = a + b;
  return (s < a || s >= order) ? s - order : s;
}

template <typename V, int S>
inline void acc_add(uint8_t* acc, uint64_t pos, const V* vals, uint64_t cnt, bool eager,
                    u128 order) {
  uint8_t* a = acc + pos * S;
  if (eager) {
    for (uint64_t i = 0; i < cnt; i++)
      acc_store<S>(a + i * S, mod_add128(acc_load<S>(a + i * S), vals[i], order));
  } else {
    for (uint64_t i = 0; i < cnt; i++)
      acc_store<S>(a + i * S, acc_load<S>(a + i * S) + vals[i]);
  }
}

inline double to_double(u128 v) {
  return (double)(uint64_t)(v >> 64) * 18446744073709551616.0 + (double)(uint64_t)v;
}

// sum mod order for sum < 2^40 * order: the quotient is small, so one
// double multiply lands within one of it and the loops below run at most
// once each.
inline u128 reduce128(u128 sum, u128 order, double inv_order) {
  u128 below = (u128)(uint64_t)(to_double(sum) * inv_order) * order;
  while (below > sum) below -= order;
  u128 r = sum - below;
  while (r >= order) r -= order;
  return r;
}

struct DeriveSumArgs {
  const uint8_t* seeds;
  const uint64_t* offsets;
  uint64_t k, n;
  u128 order;
  uint32_t bpn;
  uint8_t* acc;
  bool eager;
  uint32_t n_limbs;
  uint32_t* out;
  uint64_t* ends;
  uint32_t n_threads, n_groups;
  uint64_t seg_cand;
};

template <typename V, int S>
int derive_sum_run(const DeriveSumArgs& a) {
  const uint32_t nt = a.n_threads;
  const uint32_t ng = a.n_groups;
  const uint64_t seg_bytes = a.seg_cand * a.bpn;

  // group 0 sums into the caller's accumulator, the others into their own
  std::vector<std::unique_ptr<uint8_t, decltype(&std::free)>> extra;
  std::vector<uint8_t*> accs(ng, a.acc);
  for (uint32_t g = 1; g < ng; g++) {
    extra.emplace_back((uint8_t*)std::calloc(a.n, S), &std::free);
    if (!extra.back()) return 2;
    accs[g] = extra.back().get();
  }
  std::unique_ptr<DeriveSeed[]> seeds(new DeriveSeed[a.k]);
  // a seed's tickets in flight: DS_RUN_AHEAD for each thread of its group
  const uint64_t ring = (uint64_t)((nt + ng - 1) / ng) * DS_RUN_AHEAD;
  for (uint64_t s = 0; s < a.k; s++) seeds[s].ring.reset(new DeriveSlot[ring]);

  // Give the sampled segments from `turn` on their positions, as far as they
  // are sampled; the one that holds the n-th accept ends the seed. Any thread
  // of the group does this for all of them, one at a time.
  auto place = [&](uint64_t s, DeriveSeed& st, const uint32_t key[8], uint8_t* ks) {
    if (st.placing.exchange(true, std::memory_order_acquire)) return;
    while (!st.done.load(std::memory_order_relaxed)) {
      const uint64_t j = st.turn.load(std::memory_order_relaxed);
      DeriveSlot& sl = st.ring[j % ring];
      if (sl.sampled.load(std::memory_order_acquire) != j + 1) break;
      const uint64_t pos = st.pos.load(std::memory_order_relaxed);
      sl.pos = pos;
      if (pos + sl.cnt >= a.n) {
        sl.take = a.n - pos;
        const uint64_t b0 = a.offsets[s] + j * seg_bytes;
        a.ends[s] = b0 + (nth_accept(key, b0, a.bpn, a.order, sl.take, ks) + 1) * a.bpn;
        st.total = j + 1;
        sl.placed.store(j + 1, std::memory_order_release);
        st.done.store(true, std::memory_order_release);
      } else {
        sl.take = sl.cnt;
        st.pos.store(pos + sl.cnt, std::memory_order_relaxed);
        sl.placed.store(j + 1, std::memory_order_release);
        st.turn.store(j + 1, std::memory_order_release);
      }
    }
    st.placing.store(false, std::memory_order_release);
  };

  auto worker = [&](uint32_t t) {
    const uint32_t g = t % ng;
    uint8_t* acc = accs[g];
    std::vector<uint8_t> ks(DS_CHUNK_BYTES + 64 + 64 + 16);
    // this thread's tickets, sampled and not added yet, oldest first; only
    // the newest can be waiting for its slot
    struct Held {
      uint64_t j, cnt;
      uint32_t buf;
    };
    Held held[DS_RUN_AHEAD];
    std::unique_ptr<V[]> bufs[DS_RUN_AHEAD];  // each touched as far as it fills
    for (uint64_t s = g; s < a.k; s += ng) {
      DeriveSeed& st = seeds[s];
      DeriveSeed* prev = s >= ng ? &seeds[s - ng] : nullptr;
      uint32_t key[8];
      std::memcpy(key, a.seeds + 32 * s, 32);
      uint32_t n_held = 0;
      uint32_t free_bufs = (1u << DS_RUN_AHEAD) - 1;
      bool unpublished = false;
      unsigned spins = 0;
      std::chrono::steady_clock::time_point waiting_since;
      for (;;) {
        if (unpublished) {
          const Held& h = held[n_held - 1];
          DeriveSlot& sl = st.ring[h.j % ring];
          if (h.j < ring || sl.taken.load(std::memory_order_acquire) == h.j - ring + 1) {
            sl.cnt = h.cnt;
            sl.sampled.store(h.j + 1, std::memory_order_release);
            unpublished = false;
            spins = 0;
            continue;
          }
        }
        if (n_held > (unpublished ? 1u : 0u)) {
          DeriveSlot& sl = st.ring[held[0].j % ring];
          if (sl.placed.load(std::memory_order_acquire) == held[0].j + 1) {
            const Held h = held[0];
            const uint64_t pos = sl.pos, take = sl.take;
            sl.taken.store(h.j + 1, std::memory_order_release);
            for (uint32_t i = 1; i < n_held; i++) held[i - 1] = held[i];
            n_held--;
            // this group's previous seed may still be adding into the same slots
            if (prev != nullptr) {
              spins = 0;
              while (prev->added.load(std::memory_order_acquire) != prev->total)
                spin_wait(spins);
            }
            acc_add<V, S>(acc, pos, bufs[h.buf].get(), take, a.eager, a.order);
            st.added.fetch_add(1, std::memory_order_release);
            free_bufs |= 1u << h.buf;
            spins = 0;
            continue;
          }
        }
        if (st.done.load(std::memory_order_acquire)) {
          // what is placed is placed by now: the rest are tickets past the end
          while (n_held && held[n_held - 1].j >= st.total) n_held--;
          unpublished = false;
          if (n_held == 0) break;
          continue;
        }
        const uint64_t due = st.turn.load(std::memory_order_acquire);
        if (st.ring[due % ring].sampled.load(std::memory_order_acquire) == due + 1) {
          place(s, st, key, ks.data());
          continue;
        }
        bool sample = n_held == 0;
        if (!sample && !unpublished && n_held < DS_RUN_AHEAD && spins >= 64) {
          // ahead only of a wait that lasts, and not past the seed's end as
          // far as this thread's own acceptance rate foretells it
          const uint64_t in_flight = st.next.load(std::memory_order_relaxed) - due;
          sample = std::chrono::steady_clock::now() - waiting_since >= DS_AHEAD_AFTER &&
                   in_flight * held[0].cnt < a.n - st.pos.load(std::memory_order_relaxed);
        }
        if (!sample) {
          if (spins == 0) waiting_since = std::chrono::steady_clock::now();
          spin_wait(spins);
          continue;
        }
        const uint32_t b = (uint32_t)__builtin_ctz(free_bufs);
        free_bufs &= ~(1u << b);
        if (!bufs[b]) bufs[b].reset(new V[a.seg_cand]);
        Held& h = held[n_held++];
        h.j = st.next.fetch_add(1, std::memory_order_relaxed);
        h.buf = b;
        h.cnt = sample_range(key, a.offsets[s] + h.j * seg_bytes, a.seg_cand, a.bpn, a.order,
                             ks.data(), bufs[b].get());
        unpublished = true;
        spins = 0;
      }
    }
  };

  if (nt <= 1) {
    worker(0);
  } else {
    std::vector<std::thread> threads;
    std::vector<uint32_t> unspawned;
    threads.reserve(nt - 1);
    WorkerTally tally;
    for (uint32_t t = 1; t < nt; t++) {
      try {
        threads.emplace_back([&worker, &tally, t] {
          worker(t);
          tally.add_this_thread();
        });
      } catch (...) {
        unspawned.push_back(t);  // a worker is complete alone: run it here
      }
    }
    worker(0);
    for (uint32_t t : unspawned) worker(t);
    for (auto& th : threads) th.join();
    tally.settle();
  }

  // merge the groups, reduce, write uint32[n, L]; `out` may be `acc` itself
  // when an accumulator word is an element's limbs (S == 4 * n_limbs)
  const double inv_order = 1.0 / to_double(a.order);
  const uint32_t L = a.n_limbs;
  run_sliced(
      a.n, 4096,
      [&](uint64_t s0, uint64_t s1) {
        for (uint64_t i = s0; i < s1; i++) {
          u128 v = acc_load<S>(a.acc + i * S);
          for (uint32_t g = 1; g < ng; g++) {
            const u128 w = acc_load<S>(accs[g] + i * S);
            v = a.eager ? mod_add128(v, w, a.order) : v + w;
          }
          if (!a.eager) v = reduce128(v, a.order, inv_order);
          uint32_t* o = a.out + i * L;
          for (uint32_t l = 0; l < L; l++) o[l] = (uint32_t)(v >> (32 * l));
        }
      },
      nt);
  return 0;
}

}  // namespace

// Derive the masks of `k` seeds (32 bytes each at `seeds`; the vector draws
// of seed s start at keystream byte `byte_offsets[s]`, after its unit draw)
// and write their elementwise sum mod `order` to `out` as uint32[n, n_limbs].
// `acc` is `n * acc_stride` zeroed bytes (it may be `out` when acc_stride ==
// 4 * n_limbs); `end_offsets[s]` receives seed s's end cursor. Returns 0, or
// 1 for arguments outside this entry (order over 16 bytes, a stride that
// cannot hold it), or 2 when a group's accumulator could not be allocated.
XN_EXPORT int xn_derive_sum(const uint8_t* seeds, const uint64_t* byte_offsets, uint64_t k,
                            uint64_t n, const uint8_t* order_le, uint32_t order_nbytes,
                            uint32_t acc_stride, uint8_t* acc, uint32_t eager,
                            uint32_t n_limbs, uint32_t* out, uint64_t* end_offsets,
                            uint32_t n_threads, uint32_t n_groups, uint64_t seg_cand) {
  if (order_nbytes == 0 || order_nbytes > 16 || n_limbs == 0 || n_limbs > 4 || k == 0 ||
      n == 0 || seg_cand == 0)
    return 1;
  u128 order = 0;
  for (int i = (int)order_nbytes - 1; i >= 0; i--) order = (order << 8) | order_le[i];
  if (order == 0 || (eager && acc_stride != 16)) return 1;
  uint32_t nt = n_threads < 1 ? 1 : (n_threads > 64 ? 64 : n_threads);
  uint32_t ng = n_groups < 1 ? 1 : n_groups;
  if (ng > nt) ng = nt;
  if (ng > k) ng = (uint32_t)k;
  const DeriveSumArgs a{seeds,   byte_offsets, k,   n,           order, order_nbytes, acc,
                        eager != 0, n_limbs,   out, end_offsets, nt,    ng,           seg_cand};
  const bool narrow = order_nbytes <= 8;
  if (acc_stride == 8) return narrow ? derive_sum_run<uint64_t, 8>(a) : 1;
  if (acc_stride == 12)
    return narrow ? derive_sum_run<uint64_t, 12>(a) : derive_sum_run<u128, 12>(a);
  if (acc_stride == 16)
    return narrow ? derive_sum_run<uint64_t, 16>(a) : derive_sum_run<u128, 16>(a);
  return 1;
}

// Pack wire-layout uint32 elements into byte-planar planes (ABI 8; the
// staging-ring pack of ops/limbs.py). `wire` points at n elements of
// n_limbs little-endian u32 limbs each (stride n_limbs — callers pass a
// pre-offset pointer to address a column slice of a larger batch); byte
// plane b of the output receives byte b of every element at
// out + b * out_plane_stride. Plane-major loops keep every write
// unit-stride; numpy's byte-granularity gather for the same copy measures
// ~3x a planar transpose, this kernel ~memcpy speed. `n_threads` > 0 pins
// the worker count (the producer thread packs 8 shard slices per batch).
XN_EXPORT void xn_pack_wire_planes(const uint32_t* wire, uint64_t n, uint32_t n_limbs,
                                   uint32_t bpn, uint8_t* out, uint64_t out_plane_stride,
                                   uint32_t n_threads) {
  run_sliced(
      n, 4096,
      [=](uint64_t s0, uint64_t s1) {
        // i-blocked like the fold kernels: the first byte-plane's pass
        // warms the element block into L1, the remaining bpn-1 passes hit
        // cache instead of re-streaming DRAM
        constexpr uint64_t BLOCK = 4096;
        for (uint64_t s = s0; s < s1; s += BLOCK) {
          const uint64_t bn = (s1 - s) < BLOCK ? (s1 - s) : BLOCK;
          for (uint32_t b = 0; b < bpn; b++) {
            const uint32_t* src = wire + s * n_limbs + (b / 4);
            const uint32_t sh = 8u * (b % 4);
            uint8_t* dst = out + (uint64_t)b * out_plane_stride + s;
            for (uint64_t i = 0; i < bn; i++)
              dst[i] = (uint8_t)(src[i * n_limbs] >> sh);
          }
        }
      },
      n_threads);
}

// Planar twin: pack planar uint32[L, n] limb planes (plane stride
// `in_plane_stride` elements) into byte planes — unit-stride reads AND
// writes (the host planar-row staging path).
XN_EXPORT void xn_pack_planar_planes(const uint32_t* planar, uint64_t n,
                                     uint64_t in_plane_stride, uint32_t bpn, uint8_t* out,
                                     uint64_t out_plane_stride, uint32_t n_threads) {
  run_sliced(
      n, 4096,
      [=](uint64_t s0, uint64_t s1) {
        constexpr uint64_t BLOCK = 4096;
        for (uint64_t s = s0; s < s1; s += BLOCK) {
          const uint64_t bn = (s1 - s) < BLOCK ? (s1 - s) : BLOCK;
          for (uint32_t b = 0; b < bpn; b++) {
            const uint32_t* src = planar + (uint64_t)(b / 4) * in_plane_stride + s;
            const uint32_t sh = 8u * (b % 4);
            uint8_t* dst = out + (uint64_t)b * out_plane_stride + s;
            for (uint64_t i = 0; i < bn; i++) dst[i] = (uint8_t)(src[i] >> sh);
          }
        }
      },
      n_threads);
}

// Single-pass batch fold for orders that fit in 64 bits (n_limbs <= 2):
// fold K wire-layout uint32[n, L] updates plus
// the accumulator in ONE read of the batch, sliced over the element axis
// across fold_threads() workers (the fold is elementwise — no merge step).
// The layout is the one the coordinator's host aggregation path
// (`Aggregation.aggregate_batch`) already holds, so nothing is transposed
// (reference hot loop analogue:
// rust/xaynet-core/src/mask/masking.rs:292-316).
//
// Layouts: acc/out uint32[n, L], stack uint32[K, n, L].
// Requirements: every input element < order; (K+1) * order < 2^64 for
// non-pow2 orders. order_limbs all zero means order == 2^(32*L): natural
// wraparound, valid for any K.
XN_EXPORT void xn_fold_wire_u64(const uint32_t* acc, const uint32_t* stack, uint32_t* out,
                                uint64_t n, uint32_t n_limbs, uint64_t k,
                                const uint32_t* order_limbs) {
  uint64_t order = 0;
  for (uint32_t j = 0; j < n_limbs; j++) order |= (uint64_t)order_limbs[j] << (32 * j);
  run_sliced(n, 4096, [=](uint64_t s0, uint64_t s1) {
    fold_wire_u64_slice(acc, stack, out, n, n_limbs, k, order, s0, s1);
  });
}

// (a - b) mod order, elementwise (same layout/conventions as xn_mod_add).
XN_EXPORT void xn_mod_sub(const uint32_t* a, const uint32_t* b, uint32_t* out,
                          uint64_t n, uint32_t n_limbs, const uint32_t* order_limbs) {
  for (uint64_t i = 0; i < n; i++) {
    const uint32_t* av = a + i * n_limbs;
    const uint32_t* bv = b + i * n_limbs;
    uint32_t* ov = out + i * n_limbs;
    uint64_t borrow = 0;
    for (uint32_t j = 0; j < n_limbs; j++) {
      uint64_t d = (uint64_t)av[j] - bv[j] - borrow;
      ov[j] = (uint32_t)d;
      borrow = (d >> 63) & 1;
    }
    if (borrow) {
      uint64_t carry = 0;
      for (uint32_t j = 0; j < n_limbs; j++) {
        uint64_t s = (uint64_t)ov[j] + order_limbs[j] + carry;
        ov[j] = (uint32_t)s;
        carry = s >> 32;
      }
    }
  }
}

// Generic n-limb single-pass fold (wire layout): covers every config the
// u64 fast path cannot — f64 families (3-6 limbs) through the 173-byte
// f64/Bmax worst case (44 limbs). One read of the batch: per-limb column
// sums accumulate in u64 (exact for K+1 <= 2^32 terms), then each element
// carry-propagates into an (L+1)-limb value and reduces modulo the order
// with ceil(log2(K+1)) conditional subtracts of order << b — the same
// reduction schedule as the device fold (ops/fold_jax.fold_planar_batch).
//
// Layouts: acc/out uint32[n, L] wire-order, stack uint32[K, n, L].
// Requirements: elements < order; K <= 65535; L <= 63. All-zero
// order_limbs means order == 2^(32L): natural wraparound. Returns 0 on
// success, 1 on a parameter violation.
// PRECONDITION (not checked here, cost would double the single pass):
// every acc/stack element must already be < order — the kbits reduction
// relies on the running value staying < (K+1)*order, so out-of-range
// input silently yields a result >= order. Python callers route inbound
// data through elements_lt_order/is_valid before folding.
XN_EXPORT int xn_fold_wire_nlimb(const uint32_t* acc, const uint32_t* stack, uint32_t* out,
                                 uint64_t n, uint32_t n_limbs, uint64_t k,
                                 const uint32_t* order_limbs) {
  if (n_limbs == 0 || n_limbs > 63 || k > 65535) return 1;
  const uint32_t L = n_limbs;
  int pow2_boundary = 1;
  for (uint32_t l = 0; l < L; l++) pow2_boundary &= (order_limbs[l] == 0);

  // how many conditional-subtract rounds the reduction needs: value < (K+1)*order
  uint32_t kbits = 0;
  while ((1ull << kbits) < k + 1) kbits++;

  // precompute order << b for every reduction round (kbits <= 16, so the
  // shift never crosses a limb boundary by more than one limb)
  std::vector<uint32_t> shifted((kbits + 1) * (L + 1));
  for (uint32_t b = 0; b <= kbits; b++) {
    uint32_t* so = shifted.data() + b * (L + 1);
    const uint32_t limb_off = b >> 5;
    const uint32_t bit_off = b & 31;
    for (uint32_t l = 0; l <= L; l++) {
      uint64_t ol = 0;
      const int src_hi = (int)l - (int)limb_off;
      if (src_hi >= 0 && src_hi < (int)L) ol = ((uint64_t)order_limbs[src_hi] << bit_off) & 0xFFFFFFFFull;
      if (bit_off && src_hi - 1 >= 0 && src_hi - 1 < (int)L)
        ol |= order_limbs[src_hi - 1] >> (32 - bit_off);
      so[l] = (uint32_t)ol;
    }
  }

  // block over elements so each batch row is read as one contiguous
  // stretch (element-at-a-time order would reload every cache line
  // ~elements-per-line times); block sized to keep the u64 column
  // accumulator ~16 KB regardless of L. Element slices are independent, so
  // the blocks fan out over fold_threads() workers (shifted is shared
  // read-only; colbuf/w are per-slice).
  uint64_t block = 2048 / L;
  if (block == 0) block = 1;
  const uint32_t* shifted_ro = shifted.data();
  run_sliced(n, block, [=](uint64_t e0, uint64_t e1) {
    std::vector<uint64_t> colbuf(block * L);
    uint32_t w[64];  // carry-propagated (L+1)-limb value, one element
    for (uint64_t i0 = e0; i0 < e1; i0 += block) {
      const uint64_t bn = (i0 + block <= e1) ? block : e1 - i0;
      uint64_t* col = colbuf.data();
      for (uint64_t j = 0; j < bn * L; j++) col[j] = acc[i0 * L + j];
      for (uint64_t kk = 0; kk < k; kk++) {
        const uint32_t* row = stack + (kk * n + i0) * L;
        for (uint64_t j = 0; j < bn * L; j++) col[j] += row[j];
      }
      for (uint64_t bi = 0; bi < bn; bi++) {
        const uint64_t i = i0 + bi;
        uint64_t carry = 0;
        for (uint32_t l = 0; l < L; l++) {
          const uint64_t t = col[bi * L + l] + carry;
          w[l] = (uint32_t)t;
          carry = t >> 32;
        }
        w[L] = (uint32_t)carry;  // < K+1 <= 2^16
        if (pow2_boundary) {
          for (uint32_t l = 0; l < L; l++) out[i * L + l] = w[l];
          continue;
        }
        // reduce: repeated conditional subtract of the precomputed order << b
        for (int b = (int)kbits; b >= 0; b--) {
          const uint32_t* so = shifted_ro + (uint32_t)b * (L + 1);
          int ge = 1;  // lexicographic w >= (order << b), from the top limb down
          for (int l = (int)L; l >= 0; l--) {
            if (w[l] > so[l]) { ge = 1; break; }
            if (w[l] < so[l]) { ge = 0; break; }
          }
          if (!ge) continue;
          uint64_t borrow = 0;
          for (uint32_t l = 0; l <= L; l++) {
            const uint64_t d = (uint64_t)w[l] - so[l] - borrow;
            w[l] = (uint32_t)d;
            borrow = (d >> 63) & 1;
          }
        }
        for (uint32_t l = 0; l < L; l++) out[i * L + l] = w[l];
      }
    }
  });
  return 0;
}

// --- wire <-> limb codecs --------------------------------------------------
//
// The coordinator ingests every masked update as `count` fixed-width
// little-endian group elements (`bytes_per_number` wide, reference wire
// shape: rust/xaynet-core/src/mask/object/serialization.rs) and the
// participant serializes the masked model back out the same way. The numpy
// strided pad/slice path measures ~370 MB/s parse / ~120 MB/s serialize on
// one core; these single-pass codecs run at memory bandwidth, which matters
// because at 25M params one update is a 150 MB wire payload and parse is on
// the coordinator's per-update critical path.

XN_EXPORT void xn_wire_to_limbs(const uint8_t* buf, uint64_t count, uint32_t bpn,
                                uint32_t n_limbs, uint32_t* out) {
  if (count == 0 || bpn == 0 || n_limbs == 0) return;
  // enough trailing elements decoded bytewise that the fast path's 8-byte
  // load at its last element, (n_fast-1)*bpn + 8, stays inside the
  // count*bpn buffer: n_fast = count + 1 - ceil(8/bpn)
  const uint64_t tail = (8 + bpn - 1) / bpn - 1;
  const uint64_t n_fast = (bpn <= 8 && n_limbs <= 2 && count > tail) ? count - tail : 0;
  if (n_fast) {
    const uint64_t mask = bpn == 8 ? ~0ull : ((1ull << (8 * bpn)) - 1);
    if (n_limbs == 2) {
      for (uint64_t i = 0; i < n_fast; i++) {
        uint64_t v;
        std::memcpy(&v, buf + i * bpn, 8);
        v &= mask;
        out[i * 2] = (uint32_t)v;
        out[i * 2 + 1] = (uint32_t)(v >> 32);
      }
    } else {
      for (uint64_t i = 0; i < n_fast; i++) {
        uint64_t v;
        std::memcpy(&v, buf + i * bpn, 8);
        out[i] = (uint32_t)(v & mask);
      }
    }
  }
  const uint64_t start = n_fast;
  for (uint64_t i = start; i < count; i++) {
    const uint8_t* p = buf + i * bpn;
    for (uint32_t l = 0; l < n_limbs; l++) {
      uint32_t v = 0;
      for (uint32_t b = 0; b < 4; b++) {
        const uint32_t idx = l * 4 + b;
        if (idx < bpn) v |= (uint32_t)p[idx] << (8 * b);
      }
      out[i * n_limbs + l] = v;
    }
  }
}

XN_EXPORT void xn_limbs_to_wire(const uint32_t* limbs, uint64_t count, uint32_t bpn,
                                uint32_t n_limbs, uint8_t* out) {
  if (count == 0 || bpn == 0 || n_limbs == 0) return;
  // write 8 bytes per element: the overhang clobbers the next element's
  // leading bytes, which the next iteration immediately rewrites; the last
  // ceil(8/bpn)-1 elements are written bytewise so the final 8-byte store,
  // (n_fast-1)*bpn + 8, never lands past the count*bpn buffer
  const uint64_t tail = (8 + bpn - 1) / bpn - 1;
  const uint64_t n_fast = (bpn <= 8 && n_limbs <= 2 && count > tail) ? count - tail : 0;
  for (uint64_t i = 0; i < n_fast; i++) {
    uint64_t v = limbs[i * n_limbs];
    if (n_limbs == 2) v |= (uint64_t)limbs[i * 2 + 1] << 32;
    std::memcpy(out + i * bpn, &v, 8);
  }
  const uint64_t start = n_fast;
  for (uint64_t i = start; i < count; i++) {
    uint8_t* p = out + i * bpn;
    for (uint32_t idx = 0; idx < bpn; idx++) {
      p[idx] = (uint8_t)(limbs[i * n_limbs + idx / 4] >> (8 * (idx % 4)));
    }
  }
}

// Count of elements >= order (0 == every element is a valid group member).
// Callers handle the 2^(32L) boundary (all-zero order_limbs) themselves —
// that order admits every representable element.
XN_EXPORT uint64_t xn_count_ge(const uint32_t* limbs, uint64_t count, uint32_t n_limbs,
                               const uint32_t* order_limbs) {
  uint64_t bad = 0;
  for (uint64_t i = 0; i < count; i++) {
    const uint32_t* v = limbs + i * n_limbs;
    int ge = 1;  // equal-so-far counts as >=
    for (int l = (int)n_limbs - 1; l >= 0; l--) {
      if (v[l] > order_limbs[l]) { ge = 1; break; }
      if (v[l] < order_limbs[l]) { ge = 0; break; }
    }
    bad += (uint64_t)ge;
  }
  return bad;
}

// The same count over a byte-planar block (ABI 13; a wire v2 vector as it
// arrives, ops/limbs.py::planes_lt_order): plane b, at planes + b *
// plane_stride, holds byte b of each of the n elements, and `order_le` is
// the order in bpn little-endian bytes (callers handle an order of
// 2^(8*bpn), which admits every element the planes can hold). An element is
// compared from its top byte down and decided at the first byte that
// differs, so all but the elements whose top byte ties the order's are
// decided by a read of the top plane alone. The element axis runs on
// fold_threads() threads.
XN_EXPORT uint64_t xn_count_ge_planes(const uint8_t* planes, uint64_t n, uint64_t plane_stride,
                                      uint32_t bpn, const uint8_t* order_le) {
  if (n == 0 || bpn == 0) return 0;
  std::atomic<uint64_t> bad{0};
  const uint8_t* top = planes + (uint64_t)(bpn - 1) * plane_stride;
  const uint8_t top_order = order_le[bpn - 1];
  run_sliced(
      n, 4096,
      [&, top, top_order](uint64_t s0, uint64_t s1) {
        uint64_t mine = 0;
        for (uint64_t i = s0; i < s1; i++) {
          if (top[i] < top_order) continue;
          int ge = 1;  // equal down to the last byte counts as >=
          for (int b = (int)bpn - 1; b >= 0; b--) {
            const uint8_t v = planes[(uint64_t)b * plane_stride + i];
            if (v > order_le[b]) { ge = 1; break; }
            if (v < order_le[b]) { ge = 0; break; }
          }
          mine += (uint64_t)ge;
        }
        if (mine) bad.fetch_add(mine, std::memory_order_relaxed);
      });
  return bad.load();
}

// Copy `width` bytes of each of `bpn` planes, src plane b at src + b *
// src_plane_stride into dst + b * dst_plane_stride (ABI 13): a column range
// of a wire v2 body into a shard's staging slot, whose planes are as wide as
// the shard's padded range (ops/limbs.py::copy_planes). The column axis runs
// on fold_threads() threads, each copying its slice of every plane.
XN_EXPORT void xn_copy_planes(const uint8_t* src, uint64_t src_plane_stride, uint8_t* dst,
                              uint64_t dst_plane_stride, uint32_t bpn, uint64_t width) {
  run_sliced(width, 4096, [=](uint64_t s0, uint64_t s1) {
    for (uint32_t b = 0; b < bpn; b++)
      std::memcpy(dst + (uint64_t)b * dst_plane_stride + s0,
                  src + (uint64_t)b * src_plane_stride + s0, (size_t)(s1 - s0));
  });
}

namespace {

// 1 if the bpn-byte little-endian element at p is >= the order, compared
// from the top byte down (equal down to the last byte counts as >=).
inline uint64_t element_ge(const uint8_t* p, uint32_t bpn, const uint8_t* order_le) {
  for (int b = (int)bpn - 1; b >= 0; b--) {
    if (p[b] > order_le[b]) return 1;
    if (p[b] < order_le[b]) return 0;
  }
  return 1;
}

// Elements [s, e) of the interleaved block into their plane columns and
// their count >= order, a byte at a time: the tail of a slice, and every
// element where the library was built without AVX2.
inline uint64_t wire_to_planes_bytewise(const uint8_t* wire, uint32_t bpn, uint8_t* planes,
                                        uint64_t plane_stride, const uint8_t* order_le,
                                        uint64_t s, uint64_t e) {
  constexpr uint64_t BLOCK = 4096;  // as the plane packs: a block stays in L1 for its bpn passes
  uint64_t bad = 0;
  for (; s < e; s += BLOCK) {
    const uint64_t bn = (e - s) < BLOCK ? (e - s) : BLOCK;
    const uint8_t* src = wire + s * bpn;
    for (uint32_t b = 0; b < bpn; b++) {
      uint8_t* dst = planes + (uint64_t)b * plane_stride + s;
      for (uint64_t i = 0; i < bn; i++) dst[i] = src[i * bpn + b];
    }
    if (order_le) {
      const uint8_t* top = planes + (uint64_t)(bpn - 1) * plane_stride + s;
      for (uint64_t i = 0; i < bn; i++)
        if (top[i] >= order_le[bpn - 1]) bad += element_ge(src + i * bpn, bpn, order_le);
    }
  }
  return bad;
}

#ifdef __AVX2__
// Eight registers of byte pairs (16-bit lane k of a[i], in each 128-bit
// half: byte k of elements 2i and 2i+1 of that half's sixteen) -> eight
// registers of whole plane runs: p[k] holds byte k of the sixteen elements
// of each half, in element order.
inline void byte_pairs_to_planes(const __m256i a[8], __m256i p[8]) {
  __m256i q[8];
  for (int j = 0; j < 4; j++) {
    q[j] = _mm256_unpacklo_epi16(a[2 * j], a[2 * j + 1]);      // bytes 0..3 of four elements
    q[4 + j] = _mm256_unpackhi_epi16(a[2 * j], a[2 * j + 1]);  // bytes 4..7
  }
  for (int h = 0; h < 2; h++) {
    const __m256i* b = q + 4 * h;
    const __m256i c0 = _mm256_unpacklo_epi32(b[0], b[1]), c1 = _mm256_unpacklo_epi32(b[2], b[3]);
    const __m256i c2 = _mm256_unpackhi_epi32(b[0], b[1]), c3 = _mm256_unpackhi_epi32(b[2], b[3]);
    p[4 * h + 0] = _mm256_unpacklo_epi64(c0, c1);
    p[4 * h + 1] = _mm256_unpackhi_epi64(c0, c1);
    p[4 * h + 2] = _mm256_unpacklo_epi64(c2, c3);
    p[4 * h + 3] = _mm256_unpackhi_epi64(c2, c3);
  }
}

// One slice of xn_wire_to_planes at a width the compiler knows: 32 elements
// a turn, each loaded whole (8 or 16 bytes from its first, the excess
// belonging to its successors), transposed in registers by the unpack
// network and stored as a 32-byte run of each of the BPN planes; the run of
// the top plane is compared with the order's top byte before it leaves its
// register. The planes beyond BPN are never computed: BPN is a constant.
template <int BPN>
uint64_t wire_to_planes_slice(const uint8_t* wire, uint64_t count, uint8_t* planes,
                              uint64_t plane_stride, const uint8_t* order_le, uint64_t s0,
                              uint64_t s1) {
  constexpr int LOAD = BPN <= 8 ? 8 : 16;
  // the last load of a turn, at element s + 31, ends inside the block
  const uint64_t total = count * BPN;
  const __m256i top_order = _mm256_set1_epi8(order_le ? (char)order_le[BPN - 1] : 0);
  uint64_t bad = 0, s = s0;
  for (; s + 32 <= s1 && (s + 31) * BPN + LOAD <= total; s += 32) {
    const uint8_t* src = wire + s * BPN;
    __m256i r[16], a[8], p[16];
    for (int i = 0; i < 16; i++) {
      const uint8_t *lo = src + i * BPN, *hi = src + (16 + i) * BPN;
      if (LOAD == 8)
        r[i] = _mm256_set_m128i(_mm_loadl_epi64((const __m128i*)hi),
                                _mm_loadl_epi64((const __m128i*)lo));
      else
        r[i] = _mm256_loadu2_m128i((const __m128i*)hi, (const __m128i*)lo);
    }
    for (int i = 0; i < 8; i++) a[i] = _mm256_unpacklo_epi8(r[2 * i], r[2 * i + 1]);
    byte_pairs_to_planes(a, p);
    if (BPN > 8) {
      for (int i = 0; i < 8; i++) a[i] = _mm256_unpackhi_epi8(r[2 * i], r[2 * i + 1]);
      byte_pairs_to_planes(a, p + 8);
    }
    for (int b = 0; b < BPN; b++)
      _mm256_storeu_si256((__m256i*)(planes + (uint64_t)b * plane_stride + s), p[b]);
    if (order_le) {
      const __m256i top = p[BPN - 1];
      uint32_t tied = (uint32_t)_mm256_movemask_epi8(
          _mm256_cmpeq_epi8(_mm256_max_epu8(top, top_order), top));  // top byte >= the order's
      for (; tied; tied &= tied - 1)
        bad += element_ge(src + (uint64_t)__builtin_ctz(tied) * BPN, BPN, order_le);
    }
  }
  return bad + wire_to_planes_bytewise(wire, BPN, planes, plane_stride, order_le, s, s1);
}
#endif  // __AVX2__

typedef uint64_t (*WireToPlanesSlice)(const uint8_t*, uint64_t, uint8_t*, uint64_t,
                                      const uint8_t*, uint64_t, uint64_t);

}  // namespace

// Interleaved wire bytes -> checked byte planes, in one pass (ABI 14; the
// eager parse of a v1 Update vector on a coordinator whose staging slots are
// byte planes, ops/limbs.py::wire_to_planes): `count` elements of `bpn`
// little-endian bytes each at `wire`; byte b of element i goes to planes + b
// * plane_stride + i, the layout of a wire v2 body and of a staging slot
// (xn_pack_wire_planes writes the same bytes from limb rows). Returns the
// number of elements >= the order, `order_le` being its bpn little-endian
// bytes, compared from the top byte down as xn_count_ge_planes compares; a
// null `order_le` (an order of 2^(8*bpn)) admits all and compares nothing.
// One read of the wire bytes, one write of the planes, the comparison on
// bytes still in registers. Any bpn from 1 to 16 by the same loop (wider
// elements, and a build without AVX2, a byte at a time). The element axis
// runs on `n_threads` threads (0 = fold_threads()).
XN_EXPORT uint64_t xn_wire_to_planes(const uint8_t* wire, uint64_t count, uint32_t bpn,
                                     uint8_t* planes, uint64_t plane_stride,
                                     const uint8_t* order_le, uint32_t n_threads) {
  if (count == 0 || bpn == 0) return 0;
  std::atomic<uint64_t> bad{0};
#ifdef __AVX2__
  static const WireToPlanesSlice by_width[16] = {
      wire_to_planes_slice<1>,  wire_to_planes_slice<2>,  wire_to_planes_slice<3>,
      wire_to_planes_slice<4>,  wire_to_planes_slice<5>,  wire_to_planes_slice<6>,
      wire_to_planes_slice<7>,  wire_to_planes_slice<8>,  wire_to_planes_slice<9>,
      wire_to_planes_slice<10>, wire_to_planes_slice<11>, wire_to_planes_slice<12>,
      wire_to_planes_slice<13>, wire_to_planes_slice<14>, wire_to_planes_slice<15>,
      wire_to_planes_slice<16>};
  const WireToPlanesSlice slice = bpn <= 16 ? by_width[bpn - 1] : nullptr;
#else
  const WireToPlanesSlice slice = nullptr;
#endif
  run_sliced(
      count, 4096,
      [&, slice](uint64_t s0, uint64_t s1) {
        const uint64_t mine =
            slice ? slice(wire, count, planes, plane_stride, order_le, s0, s1)
                  : wire_to_planes_bytewise(wire, bpn, planes, plane_stride, order_le, s0, s1);
        if (mine) bad.fetch_add(mine, std::memory_order_relaxed);
      },
      n_threads);
  return bad.load();
}

XN_EXPORT uint32_t xn_abi_version(void) { return 15; }

// The calling thread's tally of what the workers it started and joined have
// spent so far (ABI 15; `tl_workers_spent` above): out[0..6) = user us,
// system us, minor faults, major faults, voluntary and involuntary switches.
XN_EXPORT void xn_workers_spent(uint64_t* out) {
  for (int i = 0; i < 6; i++) out[i] = tl_workers_spent[i];
}

// Fill buf[start, len) from the non-blocking stream socket `fd` within
// `timeout_s` seconds and return how far buf is filled (ABI 9; the REST
// server's read of a large request body, server/rest.py). Short when the
// peer closed or reset, when the time ran out, or when the socket was shut
// down under the call to abort it. Never reads past `len`. ctypes releases
// the interpreter lock once for the whole body: a 179 MB upload is about a
// thousand recv() calls, none of which waits for the lock.
XN_EXPORT uint64_t xn_recv_exactly(int fd, uint8_t* buf, uint64_t start, uint64_t len,
                                   double timeout_s) {
  auto now = [] {
    struct timespec t;
    clock_gettime(CLOCK_MONOTONIC, &t);
    return (double)t.tv_sec + 1e-9 * (double)t.tv_nsec;
  };
  const double deadline = now() + timeout_s;
  struct pollfd p = {fd, POLLIN, 0};
  uint64_t got = start;
  while (got < len) {
    ssize_t n = recv(fd, buf + got, (size_t)(len - got), 0);
    if (n > 0) { got += (uint64_t)n; continue; }
    if (n == 0) break;
    if (errno == EINTR) continue;
    if (errno != EAGAIN && errno != EWOULDBLOCK) break;
    double left = deadline - now();
    if (left <= 0) break;
    if (left > 3600.0) left = 3600.0;
    if (poll(&p, 1, (int)(left * 1000.0) + 1) < 0 && errno != EINTR) break;
  }
  return got;
}

// Copy n bytes on fold_threads() threads (ABI 12). A vector-sized
// serialisation into a fresh buffer is bound by the first touch of the
// destination's pages, not by the copy: every thread touches its own slice
// (the decoded model's one serialisation, utils/native.py::tobytes).
XN_EXPORT void xn_copy_bytes(const uint8_t* src, uint8_t* dst, uint64_t n) {
  run_sliced(n, 4096, [=](uint64_t s0, uint64_t s1) {
    std::memcpy(dst + s0, src + s0, (size_t)(s1 - s0));
  });
}

// Fixed-point decode: out[i] = ((value_i - C) ) * inv, computed in
// double-double, where value_i is the unmasked group element (uint32 limbs,
// n_limbs <= 4 so values fit __int128), C = nb_models * add_shift *
// exp_shift (integer, little-endian bytes), and (inv_hi, inv_lo) is the
// double-double reciprocal of exp_shift * scalar_sum. Element i's limb j is
// read at limbs[j * plane_stride + i]: the planar layout the device arms
// fetch, plane_stride being the padded length; plane_stride == 0 reads the
// wire layout, limbs[i * n_limbs + j]. The element axis runs through
// run_sliced on fold_threads() threads, each writing its own slice of
// `out` (whose fresh pages are first touched there). The arithmetic per
// element is the same on every thread count and in both layouts.
// This is the unmask decode hot loop (python fallback: double-double
// numpy in xaynet_tpu/core/mask/encode.py).
XN_EXPORT int xn_decode_f64(const uint32_t* limbs, uint64_t n, uint32_t n_limbs,
                            uint64_t plane_stride, const uint8_t* c_le,
                            uint32_t c_len, double inv_hi, double inv_lo,
                            double* out) {
  if (n_limbs == 0 || n_limbs > 4 || c_len > 15) return 1;
  if (plane_stride != 0 && plane_stride < n) return 1;
  __int128 c = 0;
  for (int i = (int)c_len - 1; i >= 0; i--) c = (c << 8) | c_le[i];
  const uint64_t elem_step = plane_stride ? 1 : n_limbs;
  const uint64_t limb_step = plane_stride ? plane_stride : 1;

  run_sliced(n, 4096, [=](uint64_t s0, uint64_t s1) {
    for (uint64_t i = s0; i < s1; i++) {
      const uint32_t* v = limbs + i * elem_step;
      unsigned __int128 val = 0;
      for (int j = (int)n_limbs - 1; j >= 0; j--) val = (val << 32) | v[j * limb_step];
      __int128 diff = (__int128)val - c;
      // exact double-double of diff (|diff| < 2^127)
      double d_hi = (double)diff;
      double d_lo = (double)(diff - (__int128)d_hi);
      // dd multiply (d_hi, d_lo) * (inv_hi, inv_lo), Dekker two_prod
      double p = d_hi * inv_hi;
      const double split = 134217729.0;  // 2^27 + 1
      double ah = split * d_hi, bh = split * inv_hi;
      ah = ah - (ah - d_hi);
      bh = bh - (bh - inv_hi);
      double al = d_hi - ah, bl = inv_hi - bh;
      double err = ((ah * bh - p) + ah * bl + al * bh) + al * bl;
      err += d_hi * inv_lo + d_lo * inv_hi;
      out[i] = p + err;
    }
  });
  return 0;
}

namespace {

// Dekker double-double helpers (same sequences as xaynet_tpu/ops/dd.py,
// so results are bit-identical to the numpy fast path).
inline void two_sum(double x, double y, double& s, double& err) {
  s = x + y;
  double bb = s - x;
  err = (x - (s - bb)) + (y - bb);
}
inline void quick_two_sum(double x, double y, double& s, double& err) {
  s = x + y;
  err = y - (s - x);
}
inline void two_prod(double x, double y, double& p, double& err) {
  p = x * y;
  const double split = 134217729.0;
  double xh = split * x, yh = split * y;
  xh = xh - (xh - x);
  yh = yh - (yh - y);
  double xl = x - xh, yl = y - yh;
  err = ((xh * yh - p) + xh * yl + xl * yh) + xl * yl;
}

}  // namespace

// Exact-path unmask decode for ANY config family (arbitrary limb width,
// including i64/f64/Bmax where C = nb_models * add_shift * exp_shift can be
// hundreds of bits): out[i] = (value_i - C) * inv. The subtraction is exact
// multi-limb integer arithmetic; the difference (which has no cancellation
// left) is then truncated to its top three 32-bit limbs and multiplied by
// the double-double *normalized mantissa* (inv_hi, inv_lo) of the
// reciprocal of exp_shift * scalar_sum, whose binary exponent `inv_exp` is
// applied by one final ldexp — so reciprocals far outside float64 range
// (BMAX exp_shifts) stay exact. Worst-case relative error ~2^-64 (small
// leading limb), far below the 1/exp_shift protocol tolerance and the f64
// output rounding (reference: rust/xaynet-core/src/mask/masking.rs:190-231).
// Returns nonzero on unsupported widths.
XN_EXPORT int xn_decode_exact(const uint32_t* limbs, uint64_t n, uint32_t n_limbs,
                              const uint32_t* c_limbs, uint32_t c_nlimbs,
                              double inv_hi, double inv_lo, int32_t inv_exp,
                              double* out) {
  constexpr uint32_t MAX_LIMBS = 96;  // catalogue orders cap at 2143 bits = 67 limbs
  if (n_limbs == 0 || n_limbs > MAX_LIMBS || c_nlimbs > MAX_LIMBS) return 1;
  const uint32_t L = (n_limbs > c_nlimbs ? n_limbs : c_nlimbs);
  uint32_t c_ext[MAX_LIMBS];
  for (uint32_t j = 0; j < L; j++) c_ext[j] = (j < c_nlimbs) ? c_limbs[j] : 0;

  // embarrassingly parallel over elements: split across hardware threads for
  // large inputs (the 25M x 67-limb worst case is ~6.6 GB of limb reads)
  auto decode_range = [&](uint64_t i_lo, uint64_t i_hi) {
    for (uint64_t i = i_lo; i < i_hi; i++) {
    const uint32_t* v = limbs + i * n_limbs;
    uint32_t d[MAX_LIMBS];
    uint64_t borrow = 0;
    for (uint32_t j = 0; j < L; j++) {
      uint64_t vj = (j < n_limbs) ? v[j] : 0;
      uint64_t s = vj - c_ext[j] - borrow;
      d[j] = (uint32_t)s;
      borrow = (s >> 63) & 1;
    }
    double sign = 1.0;
    if (borrow) {  // negative: two's-complement negate to the magnitude
      sign = -1.0;
      uint64_t carry = 1;
      for (uint32_t j = 0; j < L; j++) {
        uint64_t s = (uint64_t)(uint32_t)~d[j] + carry;
        d[j] = (uint32_t)s;
        carry = s >> 32;
      }
    }
    // top three limbs -> <= 96-bit chunk, exactly scaled by 2^(32*low)
    int t = (int)L - 1;
    while (t > 0 && d[t] == 0) t--;
    unsigned __int128 chunk = d[t];
    int low = t;
    if (t >= 1) { chunk = (chunk << 32) | d[t - 1]; low = t - 1; }
    if (t >= 2) { chunk = (chunk << 32) | d[t - 2]; low = t - 2; }
    double d_hi = (double)chunk;  // <= 2^96: cast back below cannot overflow
    double d_lo = (double)(__int128)(chunk - (unsigned __int128)d_hi);
    // dd multiply (d_hi, d_lo) * (inv_hi, inv_lo); scale once at the end so
    // neither the limb value nor the reciprocal needs to fit float64 range
    double p, err;
    two_prod(d_hi, inv_hi, p, err);
    err += d_hi * inv_lo + d_lo * inv_hi;
    out[i] = __builtin_ldexp(sign * (p + err), 32 * low + inv_exp);
    }
  };

  const uint64_t work = n * (uint64_t)L;
  unsigned nthreads = std::thread::hardware_concurrency();
  if (nthreads > 16) nthreads = 16;
  if (nthreads < 2 || work < (1u << 22)) {
    decode_range(0, n);
    return 0;
  }
  std::vector<std::thread> pool;
  WorkerTally tally;
  uint64_t per = (n + nthreads - 1) / nthreads;
  for (unsigned ti = 0; ti < nthreads; ti++) {
    uint64_t lo = ti * per, hi = lo + per < n ? lo + per : n;
    if (lo >= hi) break;
    pool.emplace_back([&decode_range, &tally, lo, hi] {
      decode_range(lo, hi);
      tally.add_this_thread();
    });
  }
  for (auto& th : pool) th.join();
  tally.settle();
  return 0;
}

// Fused participant masking for bounded-f32 configs with orders <= 128 bits:
// per element, draw the next uniform mask value from the seed's keystream
// (rejection sampling, byte-stream compatible with the other samplers),
// fixed-point-encode the weight in double-double (bit-identical to the
// numpy fast path, and to encode_vect_exact for every bounded-f32 config
// B0-B6 when the scalar is dyadic), add modulo the order, and emit the
// wire-layout element.
// Returns the new keystream byte offset, or 0 on unsupported parameters.
XN_EXPORT uint64_t xn_mask_f32(const uint8_t key_bytes[32], uint64_t byte_offset,
                               const float* weights, uint64_t n,
                               const uint8_t* order_le, uint32_t draw_nbytes,
                               uint32_t elem_nbytes, double a, double e,
                               double s_hi, double s_lo, uint8_t* out) {
  if (draw_nbytes == 0 || draw_nbytes > 16 || elem_nbytes > 16 ||
      elem_nbytes > draw_nbytes)
    return 0;
  uint32_t key[8];
  std::memcpy(key, key_bytes, 32);
  unsigned __int128 order = 0;
  for (int i = (int)draw_nbytes - 1; i >= 0; i--) order = (order << 8) | order_le[i];

  constexpr uint64_t CHUNK_BLOCKS = 1024;
  std::vector<uint8_t> buf(CHUNK_BLOCKS * 64 + 64);
  uint64_t avail = 0, pos = 0;
  uint64_t next_block = byte_offset / 64;
  uint64_t intra = byte_offset % 64;
  if (intra) {
    uint8_t first[64];
    chacha20_block(key, next_block, first);
    next_block++;
    avail = 64 - intra;
    std::memcpy(buf.data(), first + intra, avail);
  }
  uint64_t offset = byte_offset;

  for (uint64_t i = 0; i < n; i++) {
    // 1. next accepted uniform draw below the order
    unsigned __int128 rnd;
    for (;;) {
      if (avail - pos < draw_nbytes) {
        uint64_t tail = avail - pos;
        std::memmove(buf.data(), buf.data() + pos, tail);
        chacha20_fill(key, next_block, CHUNK_BLOCKS, buf.data() + tail);
        next_block += CHUNK_BLOCKS;
        avail = tail + CHUNK_BLOCKS * 64;
        pos = 0;
      }
      const uint8_t* cand = buf.data() + pos;
      pos += draw_nbytes;
      offset += draw_nbytes;
      rnd = load_le16(cand, draw_nbytes);
      if (rnd < order) break;
    }

    // 2. double-double fixed-point encode of the weight
    double w = (double)weights[i];
    double hi, lo;
    two_prod(w, s_hi, hi, lo);
    lo += w * s_lo;
    quick_two_sum(hi, lo, hi, lo);
    if (hi > a || (hi == a && lo > 0)) {
      hi = a;
      lo = 0;
    } else if (hi < -a || (hi == -a && lo < 0)) {
      hi = -a;
      lo = 0;
    }
    double t, terr;
    two_sum(hi, a, t, terr);
    terr += lo;
    quick_two_sum(t, terr, hi, lo);
    double p, perr;
    two_prod(hi, e, p, perr);
    perr += lo * e;
    quick_two_sum(p, perr, hi, lo);
    // floor of (hi, lo) in integers: at B6 the value reaches 2e16 > 2^53,
    // where hi is an integer already and lo carries the units one double
    // cannot hold (dd.floor_i64; tests/test_encode_exact.py holds both
    // routes to encode_vect_exact)
    double f = __builtin_floor(hi);
    long long shifted = (long long)f + (long long)__builtin_floor((hi - f) + lo);
    if (shifted < 0) shifted = 0;

    // 3. modular add + wire emit (little-endian fixed width)
    unsigned __int128 masked = rnd + (unsigned __int128)shifted;
    if (masked >= order) masked -= order;
    uint8_t* dst = out + i * elem_nbytes;
    for (uint32_t j = 0; j < elem_nbytes; j++) {
      dst[j] = (uint8_t)(masked & 0xff);
      masked >>= 8;
    }
  }
  return offset;
}
