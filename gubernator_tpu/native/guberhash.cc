// Batch 64-bit key hashing for the serving hot path.
//
// The serving tier hashes every request key (name + "_" + unique_key) to a
// 64-bit slot hash before shipping the batch to the device. In Python this
// costs ~1us/key (hashlib call overhead); at millions of decisions per
// second host hashing would dominate, so the batch loop lives here. The
// Python side passes one concatenated byte buffer plus an offsets array and
// receives a uint64 array — one FFI call per batch, no per-key overhead.
//
// Hash: XXH64 (Yann Collet's public-domain algorithm, implemented from the
// spec). 64-bit avalanche quality is what the slot store needs: row
// indices and the fingerprint tag are all derived from this one value
// (gubernator_tpu/core/store.py slot_indices/fingerprints).
//
// Build: make -C gubernator_tpu/native   (or scripts in repo Makefile)

#include <algorithm>
#include <cstdint>
#include <cstring>
#include <string>
#include <vector>

namespace {

constexpr uint64_t P1 = 0x9E3779B185EBCA87ULL;
constexpr uint64_t P2 = 0xC2B2AE3D27D4EB4FULL;
constexpr uint64_t P3 = 0x165667B19E3779F9ULL;
constexpr uint64_t P4 = 0x85EBCA77C2B2AE63ULL;
constexpr uint64_t P5 = 0x27D4EB2F165667C5ULL;

inline uint64_t rotl(uint64_t x, int r) {
  return (x << r) | (x >> (64 - r));
}

inline uint64_t read64(const uint8_t* p) {
  uint64_t v;
  std::memcpy(&v, p, 8);
  return v;  // little-endian hosts only (x86-64 / arm64)
}

inline uint32_t read32(const uint8_t* p) {
  uint32_t v;
  std::memcpy(&v, p, 4);
  return v;
}

inline uint64_t round1(uint64_t acc, uint64_t lane) {
  acc += lane * P2;
  acc = rotl(acc, 31);
  return acc * P1;
}

inline uint64_t merge_round(uint64_t acc, uint64_t val) {
  acc ^= round1(0, val);
  return acc * P1 + P4;
}

uint64_t xxh64(const uint8_t* data, size_t len, uint64_t seed) {
  const uint8_t* p = data;
  const uint8_t* const end = data + len;
  uint64_t h;

  if (len >= 32) {
    uint64_t v1 = seed + P1 + P2;
    uint64_t v2 = seed + P2;
    uint64_t v3 = seed;
    uint64_t v4 = seed - P1;
    const uint8_t* const limit = end - 32;
    do {
      v1 = round1(v1, read64(p));
      v2 = round1(v2, read64(p + 8));
      v3 = round1(v3, read64(p + 16));
      v4 = round1(v4, read64(p + 24));
      p += 32;
    } while (p <= limit);
    h = rotl(v1, 1) + rotl(v2, 7) + rotl(v3, 12) + rotl(v4, 18);
    h = merge_round(h, v1);
    h = merge_round(h, v2);
    h = merge_round(h, v3);
    h = merge_round(h, v4);
  } else {
    h = seed + P5;
  }

  h += static_cast<uint64_t>(len);

  while (p + 8 <= end) {
    h ^= round1(0, read64(p));
    h = rotl(h, 27) * P1 + P4;
    p += 8;
  }
  if (p + 4 <= end) {
    h ^= static_cast<uint64_t>(read32(p)) * P1;
    h = rotl(h, 23) * P2 + P3;
    p += 4;
  }
  while (p < end) {
    h ^= static_cast<uint64_t>(*p) * P5;
    h = rotl(h, 11) * P1;
    ++p;
  }

  h ^= h >> 33;
  h *= P2;
  h ^= h >> 29;
  h *= P3;
  h ^= h >> 32;
  return h;
}

}  // namespace

extern "C" {

// Hash n byte-slices of one concatenated buffer. offsets has n+1 entries;
// slice i is buf[offsets[i] : offsets[i+1]].
void guber_hash_batch(const uint8_t* buf, const int64_t* offsets, int64_t n,
                      uint64_t seed, uint64_t* out) {
  for (int64_t i = 0; i < n; ++i) {
    out[i] =
        xxh64(buf + offsets[i],
              static_cast<size_t>(offsets[i + 1] - offsets[i]), seed);
  }
}

// crc32 (IEEE, reflected) batch — ring points for peer ownership, matching
// the reference picker's hash function (reference hash.go:40-42).
static uint32_t crc_table[256];
static bool crc_init_done = false;

static void crc_init() {
  for (uint32_t i = 0; i < 256; ++i) {
    uint32_t c = i;
    for (int k = 0; k < 8; ++k) {
      c = (c & 1) ? 0xEDB88320u ^ (c >> 1) : c >> 1;
    }
    crc_table[i] = c;
  }
  crc_init_done = true;
}

void guber_crc32_batch(const uint8_t* buf, const int64_t* offsets, int64_t n,
                       uint32_t* out) {
  if (!crc_init_done) crc_init();
  for (int64_t i = 0; i < n; ++i) {
    uint32_t c = 0xFFFFFFFFu;
    for (int64_t j = offsets[i]; j < offsets[i + 1]; ++j) {
      c = crc_table[(c ^ buf[j]) & 0xFF] ^ (c >> 8);
    }
    out[i] = c ^ 0xFFFFFFFFu;
  }
}

}  // extern "C"

// ---------------------------------------------------------------------------
// Batch presort: argsort by (bucket(key_hash), fingerprint(key_hash)) — the
// order decide_presorted requires (core/kernels.py). numpy's comparison
// argsort measured ~1.8ms for 16k keys, slower than the device batch it
// feeds; this LSD radix sort runs ~15x faster and keeps the host side of
// the pipeline off the critical path. Must stay bit-identical to
// core/store.py group_sort_key / bucket_index / fingerprints.

namespace {

constexpr uint64_t BUCKET_SALT = 0x9E3779B97F4A7C15ULL;
// must match gubernator_tpu/parallel/sharded.py _SHARD_SALT
constexpr uint64_t SHARD_SALT = 0xA24BAED4963EE407ULL;

inline uint64_t splitmix64(uint64_t x) {
  x = (x ^ (x >> 30)) * 0xBF58476D1CE4E5B9ULL;
  x = (x ^ (x >> 27)) * 0x94D049BB133111EBULL;
  return x ^ (x >> 31);
}

// Stable LSD radix argsort of `keys` (low `total_bits` bits meaningful);
// writes the permutation into order_out.
void radix_argsort(std::vector<uint64_t>& keys, int64_t n, int total_bits,
                   int32_t* order_out) {
  std::vector<int32_t> idx(n), idx2(n);
  for (int64_t i = 0; i < n; ++i) idx[i] = static_cast<int32_t>(i);
  std::vector<uint64_t> keys2(n);

  const int passes = (total_bits + 15) / 16;
  static thread_local std::vector<uint32_t> count(1 << 16);
  for (int pass = 0; pass < passes; ++pass) {
    const int shift = pass * 16;
    std::memset(count.data(), 0, count.size() * sizeof(uint32_t));
    for (int64_t i = 0; i < n; ++i) {
      ++count[(keys[i] >> shift) & 0xFFFF];
    }
    uint32_t sum = 0;
    for (uint32_t d = 0; d < (1u << 16); ++d) {
      uint32_t c = count[d];
      count[d] = sum;
      sum += c;
    }
    for (int64_t i = 0; i < n; ++i) {
      uint32_t pos = count[(keys[i] >> shift) & 0xFFFF]++;
      keys2[pos] = keys[i];
      idx2[pos] = idx[i];
    }
    keys.swap(keys2);
    idx.swap(idx2);
  }
  std::memcpy(order_out, idx.data(), n * sizeof(int32_t));
}

// Run-major counting argsort for composite run keys (shard/bucket bits)
// that fit a direct histogram: ONE stable counting pass on the run key,
// then a per-run stable fingerprint sort for the rare multi-key runs
// (store load factors keep mean keys/bucket around 1, and duplicate
// rows of ONE key share a fingerprint, so most runs are fp-uniform and
// skip the sort entirely). Output is bit-identical to the LSD radix on
// (run_key<<32 | fp) — fp ascending within a run, ties in input order —
// at ~3x less memory traffic for B=32k. skey/fp are per INPUT row;
// ends_out receives each run's END offset in the sorted order.
void counting_argsort_fp(const uint32_t* skey, const uint32_t* fp,
                         int64_t n, uint64_t space, int32_t* order_out,
                         std::vector<uint32_t>& ends_out) {
  ends_out.assign(space, 0);
  for (int64_t i = 0; i < n; ++i) ++ends_out[skey[i]];
  uint32_t sum = 0;
  for (uint64_t b = 0; b < space; ++b) {  // counts -> start offsets
    uint32_t c = ends_out[b];
    ends_out[b] = sum;
    sum += c;
  }
  for (int64_t i = 0; i < n; ++i) {  // stable scatter; starts -> ends
    order_out[ends_out[skey[i]]++] = static_cast<int32_t>(i);
  }
  int64_t s = 0;
  for (uint64_t b = 0; b < space; ++b) {
    const int64_t e = ends_out[b];
    if (e - s > 1) {
      const uint32_t f0 = fp[order_out[s]];
      bool uniform = true;
      for (int64_t i = s + 1; i < e; ++i) {
        if (fp[order_out[i]] != f0) {
          uniform = false;
          break;
        }
      }
      if (!uniform) {
        std::stable_sort(
            order_out + s, order_out + e,
            [&](int32_t a, int32_t c) { return fp[a] < fp[c]; });
      }
    }
    s = e;
  }
}

// Histograms above this are slower to zero than the radix passes save.
constexpr uint64_t COUNTING_SPACE_MAX = 1ULL << 16;
// The sharded composite (owner|bucket) key gets a larger cap: the bigger
// memset trades against skipping 3-4 radix passes instead of 2-3.
constexpr uint64_t SHARDED_COUNTING_SPACE_MAX = 1ULL << 18;

// Build the sharded run keys (owner << bucket_bits | bucket), per-row
// fingerprints, and per-shard row counts in one pass.
void build_sharded_keys(const uint64_t* key_hash, int64_t n, uint64_t bmask,
                        int bucket_bits, uint64_t n_shards,
                        int64_t* counts_out, std::vector<uint32_t>& sk,
                        std::vector<uint32_t>& fp) {
  sk.resize(n);
  fp.resize(n);
  for (int64_t i = 0; i < n; ++i) {
    uint64_t kh = key_hash[i];
    uint64_t owner = splitmix64(kh ^ SHARD_SALT) % n_shards;
    ++counts_out[owner];
    uint64_t bkt = splitmix64(kh ^ BUCKET_SALT) & bmask;
    sk[i] = static_cast<uint32_t>((owner << bucket_bits) | bkt);
    uint32_t f = static_cast<uint32_t>(kh >> 32);
    if (f == 0) f = 1;
    fp[i] = f;
  }
}

// Walk the sorted runs emitting duplicate-key groups (fp-runs within a
// run key). When group_counts_out is non-null, each group also counts
// toward its owning shard (owner = run_key >> bucket_bits). Returns the
// group count.
int64_t emit_groups(const std::vector<uint32_t>& ends, uint64_t space,
                    const std::vector<uint32_t>& fp, const int32_t* order,
                    int32_t* group_id_out, int32_t* leader_pos_out,
                    int64_t* group_counts_out, int bucket_bits) {
  int64_t g = 0;
  int64_t s = 0;
  for (uint64_t r = 0; r < space; ++r) {
    const int64_t e = ends[r];
    int64_t i = s;
    while (i < e) {
      const uint32_t f = fp[order[i]];
      leader_pos_out[g] = static_cast<int32_t>(i);
      if (group_counts_out) ++group_counts_out[r >> bucket_bits];
      while (i < e && fp[order[i]] == f) {
        group_id_out[i] = static_cast<int32_t>(g);
        ++i;
      }
      ++g;
    }
    s = e;
  }
  return g;
}

// Single-device composite (bucket | fp) fast path; false -> radix.
bool counting_presort(const uint64_t* key_hash, int64_t n, uint64_t buckets,
                      int32_t* order_out, std::vector<uint32_t>& fp_out,
                      std::vector<uint32_t>& ends_out) {
  if (buckets > COUNTING_SPACE_MAX) return false;
  const uint64_t bmask = buckets - 1;
  fp_out.resize(n);
  static thread_local std::vector<uint32_t> bk;
  bk.resize(n);
  for (int64_t i = 0; i < n; ++i) {
    uint64_t kh = key_hash[i];
    bk[i] = static_cast<uint32_t>(splitmix64(kh ^ BUCKET_SALT) & bmask);
    uint32_t f = static_cast<uint32_t>(kh >> 32);
    if (f == 0) f = 1;
    fp_out[i] = f;
  }
  counting_argsort_fp(bk.data(), fp_out.data(), n, buckets, order_out,
                      ends_out);
  return true;
}

}  // namespace

extern "C" {

// order_out[i] = index of the i-th row in (bucket, fingerprint) order.
// buckets must be a power of two. Stable (equal keys keep input order).
void guber_presort(const uint64_t* key_hash, int64_t n, uint64_t buckets,
                   int32_t* order_out) {
  {
    static thread_local std::vector<uint32_t> fp, ends;
    if (counting_presort(key_hash, n, buckets, order_out, fp, ends)) return;
  }
  const uint64_t bmask = buckets - 1;
  int bucket_bits = 0;
  while ((1ULL << bucket_bits) < buckets) ++bucket_bits;

  // sort key: (bucket << 32) | fingerprint  — 32 + bucket_bits bits
  std::vector<uint64_t> keys(n);
  for (int64_t i = 0; i < n; ++i) {
    uint64_t kh = key_hash[i];
    uint64_t bkt = splitmix64(kh ^ BUCKET_SALT) & bmask;
    uint64_t fp = kh >> 32;
    if (fp == 0) fp = 1;
    keys[i] = (bkt << 32) | fp;
  }
  radix_argsort(keys, n, 32 + bucket_bits, order_out);
}

// Batch marshalling: gather-with-permutation + pad in one C pass. The
// serving hot path must build the device request arrays (sorted by the
// presort permutation, clipped to the int32 envelope, padded by
// repeating the last sorted row) and unpermute the responses for every
// batch; the numpy version costs ~40ns/element across six fields
// (~630us/16k batch), this runs in one cache-friendly pass.

void guber_gather_pad_i64_clip(const int64_t* src, const int32_t* order,
                               int64_t n, int64_t b, int64_t lo, int64_t hi,
                               int32_t* out) {
  for (int64_t i = 0; i < n; ++i) {
    int64_t v = src[order[i]];
    v = v < lo ? lo : (v > hi ? hi : v);
    out[i] = static_cast<int32_t>(v);
  }
  const int32_t fill = n ? out[n - 1] : 0;
  for (int64_t i = n; i < b; ++i) out[i] = fill;
}

void guber_gather_pad_i32(const int32_t* src, const int32_t* order,
                          int64_t n, int64_t b, int32_t* out) {
  for (int64_t i = 0; i < n; ++i) out[i] = src[order[i]];
  const int32_t fill = n ? out[n - 1] : 0;
  for (int64_t i = n; i < b; ++i) out[i] = fill;
}

void guber_gather_pad_u64(const uint64_t* src, const int32_t* order,
                          int64_t n, int64_t b, uint64_t* out) {
  for (int64_t i = 0; i < n; ++i) out[i] = src[order[i]];
  const uint64_t fill = n ? out[n - 1] : 0;
  for (int64_t i = n; i < b; ++i) out[i] = fill;
}

void guber_gather_pad_u8(const uint8_t* src, const int32_t* order,
                         int64_t n, int64_t b, uint8_t* out) {
  for (int64_t i = 0; i < n; ++i) out[i] = src[order[i]];
  const uint8_t fill = n ? out[n - 1] : 0;
  for (int64_t i = n; i < b; ++i) out[i] = fill;
}

// out[order[i]] = sorted[i] for the first n positions of each of `k`
// response arrays laid out back to back ([k, b] row-major), writing into
// k output arrays of length b back to back.
void guber_unpermute_i32(const int32_t* sorted, const int32_t* order,
                         int64_t n, int64_t b, int64_t k, int32_t* out) {
  for (int64_t a = 0; a < k; ++a) {
    const int32_t* s = sorted + a * b;
    int32_t* o = out + a * b;
    for (int64_t i = 0; i < n; ++i) o[order[i]] = s[i];
  }
}

// guber_presort + group structure from the sorted key stream: the runs
// of equal (bucket, fingerprint) ARE the duplicate-key groups whose
// store I/O the kernel compacts (core/kernels.py BatchGroups), and they
// fall out of the sort for one extra O(n) pass. group_id_out[i] = group
// slot of sorted row i; leader_pos_out[g] = first sorted row of group g
// (only the first *n_groups_out entries are written).
void guber_presort_grouped(const uint64_t* key_hash, int64_t n,
                           uint64_t buckets, int32_t* order_out,
                           int32_t* group_id_out, int32_t* leader_pos_out,
                           int64_t* n_groups_out) {
  {
    static thread_local std::vector<uint32_t> fp, ends;
    if (counting_presort(key_hash, n, buckets, order_out, fp, ends)) {
      // groups are runs of equal fp within a bucket run (two distinct
      // key hashes sharing (bucket, fp) merge into one group — exactly
      // the composite-key behavior of the radix path, and of the store,
      // whose tag IS the fp)
      *n_groups_out = emit_groups(ends, buckets, fp, order_out,
                                  group_id_out, leader_pos_out, nullptr, 0);
      return;
    }
  }
  const uint64_t bmask = buckets - 1;
  int bucket_bits = 0;
  while ((1ULL << bucket_bits) < buckets) ++bucket_bits;

  std::vector<uint64_t> keys(n);
  for (int64_t i = 0; i < n; ++i) {
    uint64_t kh = key_hash[i];
    uint64_t bkt = splitmix64(kh ^ BUCKET_SALT) & bmask;
    uint64_t fp = kh >> 32;
    if (fp == 0) fp = 1;
    keys[i] = (bkt << 32) | fp;
  }
  std::vector<uint64_t> sorted(keys);  // radix_argsort leaves keys sorted,
  // but the buffer identity depends on pass parity — copy for clarity
  radix_argsort(sorted, n, 32 + bucket_bits, order_out);

  int64_t g = 0;
  uint64_t prev = 0;
  for (int64_t i = 0; i < n; ++i) {
    const uint64_t k = keys[order_out[i]];
    if (i == 0 || k != prev) {
      leader_pos_out[g] = static_cast<int32_t>(i);
      ++g;
      prev = k;
    }
    group_id_out[i] = static_cast<int32_t>(g - 1);
  }
  *n_groups_out = g;
}

// Mesh-sharded presort: argsort by (owner_shard, bucket, fingerprint) and
// per-shard row counts. owner = splitmix64(kh ^ SHARD_SALT) % n_shards —
// must stay bit-identical to parallel/sharded.py owner_of / owner_of_np.
// Rows of one shard come out contiguous, internally in the (bucket, fp)
// order decide_presorted requires, so the host can slice per-shard
// sub-batches straight out of the permutation (batch-axis sharding over
// the mesh: each chip gets only the rows it owns).
void guber_presort_sharded(const uint64_t* key_hash, int64_t n,
                           uint64_t buckets, uint64_t n_shards,
                           int32_t* order_out, int64_t* counts_out) {
  const uint64_t bmask = buckets - 1;
  int bucket_bits = 0;
  while ((1ULL << bucket_bits) < buckets) ++bucket_bits;
  int shard_bits = 1;
  while ((1ULL << shard_bits) < n_shards) ++shard_bits;

  for (uint64_t s = 0; s < n_shards; ++s) counts_out[s] = 0;

  if ((n_shards << bucket_bits) <= SHARDED_COUNTING_SPACE_MAX) {
    static thread_local std::vector<uint32_t> sk, fp, ends;
    build_sharded_keys(key_hash, n, bmask, bucket_bits, n_shards,
                       counts_out, sk, fp);
    counting_argsort_fp(sk.data(), fp.data(), n, n_shards << bucket_bits,
                        order_out, ends);
    return;
  }

  std::vector<uint64_t> keys(n);
  for (int64_t i = 0; i < n; ++i) {
    uint64_t kh = key_hash[i];
    uint64_t owner = splitmix64(kh ^ SHARD_SALT) % n_shards;
    ++counts_out[owner];
    uint64_t bkt = splitmix64(kh ^ BUCKET_SALT) & bmask;
    uint64_t fp = kh >> 32;
    if (fp == 0) fp = 1;
    keys[i] = (owner << (32 + bucket_bits)) | (bkt << 32) | fp;
  }
  radix_argsort(keys, n, 32 + bucket_bits + shard_bits, order_out);
}

// guber_presort_sharded + per-shard group structure: groups are runs of
// equal (owner, bucket, fp) composite keys in the sorted stream (shard
// boundaries break groups automatically — the owner rides the top sort
// bits). group_id_out[i] = GLOBAL group index of sorted row i;
// leader_pos_out[g] = first sorted row of global group g;
// group_counts_out[s] = number of groups owned by shard s.
void guber_presort_sharded_grouped(
    const uint64_t* key_hash, int64_t n, uint64_t buckets,
    uint64_t n_shards, int32_t* order_out, int64_t* counts_out,
    int32_t* group_id_out, int32_t* leader_pos_out,
    int64_t* group_counts_out) {
  const uint64_t bmask = buckets - 1;
  int bucket_bits = 0;
  while ((1ULL << bucket_bits) < buckets) ++bucket_bits;
  int shard_bits = 1;
  while ((1ULL << shard_bits) < n_shards) ++shard_bits;

  for (uint64_t s = 0; s < n_shards; ++s) {
    counts_out[s] = 0;
    group_counts_out[s] = 0;
  }

  if ((n_shards << bucket_bits) <= SHARDED_COUNTING_SPACE_MAX) {
    static thread_local std::vector<uint32_t> sk, fp, ends;
    build_sharded_keys(key_hash, n, bmask, bucket_bits, n_shards,
                       counts_out, sk, fp);
    const uint64_t space = n_shards << bucket_bits;
    counting_argsort_fp(sk.data(), fp.data(), n, space, order_out, ends);
    emit_groups(ends, space, fp, order_out, group_id_out, leader_pos_out,
                group_counts_out, bucket_bits);
    return;
  }

  std::vector<uint64_t> keys(n);
  for (int64_t i = 0; i < n; ++i) {
    uint64_t kh = key_hash[i];
    uint64_t owner = splitmix64(kh ^ SHARD_SALT) % n_shards;
    ++counts_out[owner];
    uint64_t bkt = splitmix64(kh ^ BUCKET_SALT) & bmask;
    uint64_t fp = kh >> 32;
    if (fp == 0) fp = 1;
    keys[i] = (owner << (32 + bucket_bits)) | (bkt << 32) | fp;
  }
  std::vector<uint64_t> sorted(keys);
  radix_argsort(sorted, n, 32 + bucket_bits + shard_bits, order_out);

  int64_t g = 0;
  uint64_t prev = 0;
  for (int64_t i = 0; i < n; ++i) {
    const uint64_t k = keys[order_out[i]];
    if (i == 0 || k != prev) {
      leader_pos_out[g] = static_cast<int32_t>(i);
      ++group_counts_out[k >> (32 + bucket_bits)];
      ++g;
      prev = k;
    }
    group_id_out[i] = static_cast<int32_t>(g - 1);
  }
}

}  // extern "C"

// ---------------------------------------------------------------------------
// One-call sharded batch prep: presort + duplicate-key groups + device-array
// marshal, optionally thread-parallel.
//
// The r2 mesh host path was numpy: a native sharded presort followed by
// per-field fancy-indexed gathers and a per-shard Python build_groups loop —
// measured ~4.3ms per 32k batch on one core, ~10x the presort itself, which
// capped a served mesh at a fraction of one chip's throughput (the r2
// verdict's "single-threaded host prep" ceiling). This entry point absorbs
// the whole pipeline into one pass:
//
//   phase A (parallel over row ranges): owner/bucket/fingerprint per row +
//           per-thread shard histograms
//   phase B (serial, O(threads*shards)): stable scatter offsets
//   phase C (parallel over row ranges): partition rows by owning shard
//   phase D (parallel over shards): per-shard stable LSD radix argsort by
//           (bucket, fingerprint) — 8-bit digits, skip-uniform passes —
//           then ONE fused walk emits the sorted permutation, the
//           duplicate-key group structure (engine.build_groups
//           conventions), all six clipped+padded device fields, and
//           take_idx.
//
// Thread count: GUBER_PREP_THREADS env, default hardware_concurrency
// (capped 32); 1 runs everything inline with zero pool overhead. Output is
// bit-identical to the numpy twin (parallel/sharded.py fallbacks) at every
// thread count: phases A/C preserve input order per shard (contiguous
// thread ranges, thread-minor offsets) and the per-shard radix is stable.

#include <atomic>
#include <chrono>
#include <condition_variable>
#include <functional>
#include <mutex>
#include <thread>
#include <pthread.h>

namespace {

// Forked children inherit lanes_ > 1 but ZERO worker threads (threads
// don't survive fork) — without this flag a child's first prep call
// would park in done_cv_.wait() forever. The atfork child handler flips
// it so children run every phase inline.
std::atomic<bool> g_pool_forked{false};

class PrepPool {
 public:
  static PrepPool& inst() {
    static PrepPool* p = new PrepPool();  // leaked: workers live for the
    // process; a static destructor would race threads parked in wait()
    return *p;
  }
  int lanes() const {
    return g_pool_forked.load(std::memory_order_relaxed) ? 1 : lanes_;
  }

  // Run fn(tid, lanes) on every lane; the caller runs lane 0.
  // Concurrent callers (K prep-worker threads, serve/batcher.py) are
  // serialized on caller_m_: each caller's pooled section runs alone —
  // worker-thread parallelism and in-call pool parallelism compose by
  // time-slicing rather than deadlocking. lanes==1 touches no shared
  // state and skips the lock entirely.
  void run(const std::function<void(int, int)>& fn) {
    if (lanes() == 1) {
      fn(0, 1);
      return;
    }
    std::lock_guard<std::mutex> caller_lock(caller_m_);
    {
      std::unique_lock<std::mutex> lk(m_);
      fn_ = &fn;
      pending_ = lanes_ - 1;
      ++gen_;
    }
    cv_.notify_all();
    fn(0, lanes_);
    std::unique_lock<std::mutex> lk(m_);
    done_cv_.wait(lk, [&] { return pending_ == 0; });
    fn_ = nullptr;
  }

 private:
  PrepPool() {
    long t = 0;
    if (const char* e = getenv("GUBER_PREP_THREADS")) t = atol(e);
    if (t <= 0) t = (long)std::thread::hardware_concurrency();
    if (t < 1) t = 1;
    if (t > 32) t = 32;
    lanes_ = (int)t;
    if (lanes_ > 1) {
      pthread_atfork(nullptr, nullptr, [] {
        g_pool_forked.store(true, std::memory_order_relaxed);
      });
    }
    for (int i = 1; i < lanes_; ++i) {
      std::thread th([this, i] { worker(i); });
      th.detach();
    }
  }
  void worker(int tid) {
    uint64_t seen = 0;
    for (;;) {
      const std::function<void(int, int)>* fn;
      {
        std::unique_lock<std::mutex> lk(m_);
        cv_.wait(lk, [&] { return gen_ != seen; });
        seen = gen_;
        fn = fn_;
      }
      (*fn)(tid, lanes_);
      {
        std::unique_lock<std::mutex> lk(m_);
        if (--pending_ == 0) done_cv_.notify_one();
      }
    }
  }

  std::mutex m_, caller_m_;
  std::condition_variable cv_, done_cv_;
  const std::function<void(int, int)>* fn_ = nullptr;
  uint64_t gen_ = 0;
  int pending_ = 0;
  int lanes_ = 1;
};

// group_rungs twin (core/engine.py group_rungs): {15b/64, b/4, 3b/8, b}
// floored, min 64, deduped ascending. Returns count; writes into out[4].
// MUST stay in lockstep with the Python ladder — the native prep picks
// its G rung here and the bit-identity tests compare against Python.
inline int group_rungs_c(int64_t b, int64_t out[4]) {
  auto rung = [b](int64_t num, int64_t den) {
    int64_t r = b < 64 ? b : ((num * b) / den < 64 ? 64 : (num * b) / den);
    return r > b ? b : r;
  };
  int64_t v[4] = {rung(15, 64), rung(1, 4), rung(3, 8), b};
  // insertion sort + dedup (4 elements)
  for (int i = 1; i < 4; ++i)
    for (int j = i; j > 0 && v[j] < v[j - 1]; --j) std::swap(v[j], v[j - 1]);
  int k = 0;
  for (int i = 0; i < 4; ++i)
    if (k == 0 || v[i] != out[k - 1]) out[k++] = v[i];
  return k;
}

inline int64_t pick_rung(const int64_t* rungs, int64_t n_rungs,
                         int64_t need) {
  for (int64_t i = 0; i < n_rungs; ++i)
    if (rungs[i] >= need) return rungs[i];
  return -1;
}

inline int32_t clip_i64(int64_t v, int64_t lo, int64_t hi) {
  return (int32_t)(v < lo ? lo : (v > hi ? hi : v));
}

// One mesh device batch as the engine stacks it: n_shards rows of B
// request cells (six fields, valid, group_id) and G group cells, rows
// written compactly with stride B / G. Both producers fill it —
// guber_prep_sharded (flush-time presort) and guber_merge_runs_sharded
// (merge of arrival-time runs) — so the padding conventions below have
// one native home, as engine.build_groups is their one Python home.
struct ShardStack {
  int64_t B, G;
  uint64_t* kh;
  int32_t* hits;
  int32_t* limit;
  int32_t* dur;
  int32_t* algo;
  uint8_t* gnp;
  uint8_t* valid;
  int32_t* gid;
  uint64_t* gkh;
  int32_t* glead;
  int32_t* gend;
  uint8_t* gvalid;
};

// Shard s once its first cnt (> 0) request rows stand in the six field
// columns: the padding tail repeats the last real row with valid=0, and
// the group cells come from the gc shard-local leader positions `ls`
// with build_groups' conventions — the final real group owns the
// request padding tail, padded request rows point at it, padded group
// slots carry leader=B / end=B-1 / valid=0 / the key of row B-1.
inline void finish_shard(const ShardStack& o, int64_t s, int64_t cnt,
                         const int32_t* ls, int64_t gc) {
  const int64_t B = o.B, G = o.G;
  uint64_t* kh_o = o.kh + s * B;
  int32_t* hi_o = o.hits + s * B;
  int32_t* li_o = o.limit + s * B;
  int32_t* du_o = o.dur + s * B;
  int32_t* al_o = o.algo + s * B;
  uint8_t* gn_o = o.gnp + s * B;
  uint8_t* va_o = o.valid + s * B;
  int32_t* gi_o = o.gid + s * B;
  uint64_t* gk_o = o.gkh + s * G;
  int32_t* gl_o = o.glead + s * G;
  int32_t* ge_o = o.gend + s * G;
  uint8_t* gv_o = o.gvalid + s * G;
  std::fill(kh_o + cnt, kh_o + B, kh_o[cnt - 1]);
  std::fill(hi_o + cnt, hi_o + B, hi_o[cnt - 1]);
  std::fill(li_o + cnt, li_o + B, li_o[cnt - 1]);
  std::fill(du_o + cnt, du_o + B, du_o[cnt - 1]);
  std::fill(al_o + cnt, al_o + B, al_o[cnt - 1]);
  std::fill(gn_o + cnt, gn_o + B, gn_o[cnt - 1]);
  std::memset(va_o, 1, cnt);
  std::memset(va_o + cnt, 0, B - cnt);
  for (int64_t g = 0; g < gc; ++g) {
    const int64_t lead = ls[g];
    const int64_t next = (g + 1 < gc) ? ls[g + 1] : cnt;
    gl_o[g] = (int32_t)lead;
    ge_o[g] = (int32_t)((g + 1 < gc) ? next - 1 : B - 1);
    gk_o[g] = kh_o[lead];
    gv_o[g] = 1;
    for (int64_t j = lead; j < next; ++j) gi_o[j] = (int32_t)g;
  }
  std::fill(gi_o + cnt, gi_o + B, (int32_t)(gc - 1));
  std::fill(gl_o + gc, gl_o + G, (int32_t)B);
  std::fill(ge_o + gc, ge_o + G, (int32_t)(B - 1));
  std::memset(gv_o + gc, 0, G - gc);
  std::fill(gk_o + gc, gk_o + G, kh_o[B - 1]);
}

// The empty shards, once every other shard stands (their fill row is
// another shard's): numpy twin semantics — every request cell
// replicates the sorted row clip(starts[s], 0, n-1), the next shard's
// first row or the batch's last, found in the stack through take_idx
// (an empty batch: zeros); valid=0, group ids 0, no real group, group
// keys that same row's.
inline void fill_empty_shards(const ShardStack& o, const int64_t* counts,
                              const int64_t* starts, int64_t n_shards,
                              int64_t n, const int64_t* take_idx) {
  const int64_t B = o.B, G = o.G;
  for (int64_t s = 0; s < n_shards; ++s) {
    if (counts[s] != 0) continue;
    const int64_t src =
        n > 0 ? take_idx[starts[s] < n ? starts[s] : n - 1] : -1;
    const uint64_t kf = src >= 0 ? o.kh[src] : 0;
    std::fill_n(o.kh + s * B, B, kf);
    std::fill_n(o.hits + s * B, B, src >= 0 ? o.hits[src] : 0);
    std::fill_n(o.limit + s * B, B, src >= 0 ? o.limit[src] : 0);
    std::fill_n(o.dur + s * B, B, src >= 0 ? o.dur[src] : 0);
    std::fill_n(o.algo + s * B, B, src >= 0 ? o.algo[src] : 0);
    std::fill_n(o.gnp + s * B, B, src >= 0 ? o.gnp[src] : (uint8_t)0);
    std::fill_n(o.valid + s * B, B, (uint8_t)0);
    std::fill_n(o.gid + s * B, B, 0);
    std::fill_n(o.gkh + s * G, G, kf);
    std::fill_n(o.glead + s * G, G, (int32_t)B);
    std::fill_n(o.gend + s * G, G, (int32_t)(B - 1));
    std::fill_n(o.gvalid + s * G, G, (uint8_t)0);
  }
}

// Stable k-way merge of k pre-sorted key runs (ns[r] keys each, n in
// all): row(i, r, j, key) once per merged position i, in order, for
// run r's j-th key. A binary min-heap of run heads ordered by (key,
// run index): the run tie-break keeps equal keys in caller order (runs
// are caller-ordered), matching a stable sort of the concatenation.
// Shared by the flat and the sharded merge entry points below.
template <class Row>
inline void merge_sorted_runs(const uint64_t* const* skeys,
                              const int64_t* ns, int64_t k, int64_t n,
                              Row&& row) {
  struct Head {
    uint64_t key;
    int64_t run;
  };
  std::vector<Head> heap;
  heap.reserve((size_t)k);
  std::vector<int64_t> pos((size_t)k, 0);
  auto lt = [](const Head& a, const Head& b) {
    return a.key < b.key || (a.key == b.key && a.run < b.run);
  };
  auto sift_down = [&](size_t i) {
    const size_t sz = heap.size();
    for (;;) {
      size_t s = i, l = 2 * i + 1, r2 = 2 * i + 2;
      if (l < sz && lt(heap[l], heap[s])) s = l;
      if (r2 < sz && lt(heap[r2], heap[s])) s = r2;
      if (s == i) return;
      std::swap(heap[i], heap[s]);
      i = s;
    }
  };
  for (int64_t r = 0; r < k; ++r)
    if (ns[r] > 0) heap.push_back({skeys[r][0], r});
  for (size_t i = heap.size(); i-- > 0;) sift_down(i);
  for (int64_t i = 0; i < n; ++i) {
    const int64_t r = heap[0].run;
    const int64_t j = pos[(size_t)r]++;
    row(i, r, j, heap[0].key);
    if (j + 1 < ns[r]) {
      heap[0].key = skeys[r][j + 1];
    } else {
      heap[0] = heap.back();
      heap.pop_back();
    }
    if (!heap.empty()) sift_down(0);
  }
}

}  // namespace

extern "C" {

int64_t guber_prep_threads() { return PrepPool::inst().lanes(); }

namespace {
// GUBER_PREP_DEBUG=1: print per-phase microseconds to stderr
inline bool prep_debug() {
  static const bool on = [] {
    const char* e = getenv("GUBER_PREP_DEBUG");
    return e && *e && *e != '0';
  }();
  return on;
}
inline int64_t now_us() {
  return std::chrono::duration_cast<std::chrono::microseconds>(
             std::chrono::steady_clock::now().time_since_epoch())
      .count();
}
}  // namespace

// Returns 0 on success; -1 if a shard's row count exceeds the rung
// ladder top; -2 if g_override is given but smaller than a shard's group
// count. picked_out: {B_sub, G_sub}. Output buffers are caller-allocated
// for n_shards rows of the ladder-top rung; rows are written compactly
// with stride B_sub (fields / gid), G_sub (group arrays).
int64_t guber_prep_sharded(
    const uint64_t* key_hash, const int64_t* hits, const int64_t* limit,
    const int64_t* duration, const int32_t* algo, const uint8_t* gnp,
    int64_t n, uint64_t buckets, int64_t n_shards, const int64_t* rungs,
    int64_t n_rungs, int64_t g_override, int64_t lo, int64_t hi,
    int64_t dlo, int64_t dhi,
    // outputs
    int32_t* order_out, int64_t* counts_out, int64_t* picked_out,
    uint64_t* kh_out, int32_t* hits_out, int32_t* limit_out,
    int32_t* dur_out, int32_t* algo_out, uint8_t* gnp_out,
    uint8_t* valid_out, uint64_t* gkh_out, int32_t* glead_out,
    int32_t* gend_out, uint8_t* gvalid_out, int32_t* gid_out,
    int64_t* take_idx_out) {
  const uint64_t bmask = buckets - 1;
  int bucket_bits = 0;
  while ((1ULL << bucket_bits) < buckets) ++bucket_bits;

  PrepPool& pool = PrepPool::inst();
  const int T = pool.lanes();
  const bool dbg = prep_debug();
  int64_t t0 = dbg ? now_us() : 0, t1 = 0, t2 = 0, t3 = 0, t4 = 0;

  // phase A: per-row composite keys + per-thread shard histograms.
  // NOTE: scratch vectors are main-thread-owned; worker lambdas must
  // capture raw POINTERS — a `thread_local` referenced inside the
  // lambda body would resolve to each worker's own (empty) instance.
  static thread_local std::vector<uint64_t> key_arr_tl;
  static thread_local std::vector<int32_t> owner_arr_tl;
  key_arr_tl.resize(n);
  std::vector<std::vector<int64_t>> hist(T);
  const bool multi = n_shards > 1;
  if (multi) owner_arr_tl.resize(n);
  uint64_t* const key_arr = key_arr_tl.data();
  int32_t* const owner_arr = multi ? owner_arr_tl.data() : nullptr;
  // power-of-two shard counts (every real mesh) take a mask instead of
  // the ~30-90-cycle 64-bit modulo; both match owner_of / owner_of_np
  const bool ns_pow2 = (n_shards & (n_shards - 1)) == 0;
  const uint64_t ns_mask = (uint64_t)n_shards - 1;
  pool.run([&](int tid, int lanes) {
    hist[tid].assign(n_shards, 0);
    int64_t* const h = hist[tid].data();
    const int64_t s0 = n * tid / lanes, s1 = n * (tid + 1) / lanes;
    for (int64_t i = s0; i < s1; ++i) {
      const uint64_t kh = key_hash[i];
      const uint64_t bkt = splitmix64(kh ^ BUCKET_SALT) & bmask;
      uint64_t fp = kh >> 32;
      if (fp == 0) fp = 1;
      key_arr[i] = (bkt << 32) | fp;
      if (multi) {
        const uint64_t mix = splitmix64(kh ^ SHARD_SALT);
        const int32_t o = (int32_t)(ns_pow2 ? (mix & ns_mask)
                                            : (mix % (uint64_t)n_shards));
        owner_arr[i] = o;
        ++h[o];
      } else {
        ++h[0];
      }
    }
  });

  // phase B: starts per shard + per-(shard, thread) scatter offsets
  std::vector<int64_t> starts(n_shards + 1, 0);
  std::vector<std::vector<int64_t>> off(T, std::vector<int64_t>(n_shards));
  {
    int64_t sum = 0;
    for (int64_t s = 0; s < n_shards; ++s) {
      starts[s] = sum;
      int64_t c = 0;
      for (int t = 0; t < T; ++t) {
        off[t][s] = sum + c;
        c += hist[t][s];
      }
      counts_out[s] = c;
      sum += c;
    }
    starts[n_shards] = sum;
  }

  if (dbg) t1 = now_us();
  int64_t maxc = 1;
  for (int64_t s = 0; s < n_shards; ++s)
    if (counts_out[s] > maxc) maxc = counts_out[s];
  const int64_t B = pick_rung(rungs, n_rungs, maxc);
  if (B < 0) return -1;

  // phase C: stable partition of row indices by owning shard
  static thread_local std::vector<int32_t> part_tl;
  part_tl.resize(n);
  int32_t* const part = part_tl.data();
  if (multi) {
    pool.run([&](int tid, int lanes) {
      const int64_t s0 = n * tid / lanes, s1 = n * (tid + 1) / lanes;
      int64_t* const o = off[tid].data();
      for (int64_t i = s0; i < s1; ++i) part[o[owner_arr[i]]++] = (int32_t)i;
    });
  } else {
    pool.run([&](int tid, int lanes) {
      const int64_t s0 = n * tid / lanes, s1 = n * (tid + 1) / lanes;
      for (int64_t i = s0; i < s1; ++i) part[i] = (int32_t)i;
    });
  }

  if (dbg) t2 = now_us();
  // phase D part 1: per-shard stable radix argsort (order_out) + group
  // counts (gcounts). G rung selection needs every shard's group count,
  // so the fused output walk is a second parallel phase.
  std::vector<int64_t> gcounts(n_shards, 0), gstarts(n_shards + 1, 0);
  // leader positions (shard-local j) found by the sort pass, consumed by
  // the marshal pass: at most one leader per row
  static thread_local std::vector<int32_t> lead_tl;
  lead_tl.resize(n);
  int32_t* const lead_scratch = lead_tl.data();
  // 12-bit digits over the BUCKET bits only (fp handled by per-run
  // fixups): ceil(15/12) = 2 passes at the default 32k-bucket store,
  // histogram small enough that the per-pass memset (32 KiB) is noise
  constexpr int DIGIT = 12;
  constexpr int64_t DMASK = (1 << DIGIT) - 1;
  const int passes = (bucket_bits + DIGIT - 1) / DIGIT;
  std::atomic<int64_t> next_shard{0};
  pool.run([&](int, int) {
    // (key, idx) pair radix: keys stream sequentially each pass and the
    // scatter partitions stay cache-resident (vs random key_arr[a[j]]
    // loads every pass in an index-only sort)
    static thread_local std::vector<uint64_t> ka, kb;
    static thread_local std::vector<int32_t> ia, ib;
    static thread_local std::vector<int64_t> h(1 << DIGIT);
    for (;;) {
      const int64_t s = next_shard.fetch_add(1);
      if (s >= n_shards) break;
      const int64_t cnt = counts_out[s], st = starts[s];
      if (cnt == 0) continue;
      ka.resize(cnt);
      kb.resize(cnt);
      ia.resize(cnt);
      ib.resize(cnt);
      for (int64_t j = 0; j < cnt; ++j) {
        const int32_t row = part[st + j];
        ia[j] = row;
        ka[j] = key_arr[row];
      }
      // radix ONLY the bucket bits (>= 32): full-fp passes would be
      // wasted work — fingerprint order matters only WITHIN a bucket
      // run, and at serving load factors (~1 key/bucket) almost every
      // run is a singleton or a single hot key's duplicates. The rare
      // multi-fp run gets a stable_sort fixup below. Halves the passes
      // at the default 15-bucket-bit store (2 vs 4).
      if (passes > 1 && cnt >= (int64_t)(buckets >> 2) &&
          bucket_bits <= 18) {
        // dense slice (single-device path: cnt == n vs 32k buckets):
        // ONE counting pass over the whole bucket space beats two
        // 12-bit passes — the histogram walk amortizes over enough rows
        static thread_local std::vector<int64_t> hb;
        hb.assign((size_t)buckets, 0);
        for (int64_t j = 0; j < cnt; ++j) ++hb[ka[j] >> 32];
        int64_t sum = 0;
        for (uint64_t d = 0; d < buckets; ++d) {
          const int64_t c = hb[d];
          hb[d] = sum;
          sum += c;
        }
        for (int64_t j = 0; j < cnt; ++j) {
          const int64_t pos = hb[ka[j] >> 32]++;
          kb[pos] = ka[j];
          ib[pos] = ia[j];
        }
        ka.swap(kb);
        ia.swap(ib);
      } else {
        for (int p = 0; p < passes; ++p) {
          const int shift = 32 + p * DIGIT;
          std::memset(h.data(), 0, h.size() * sizeof(int64_t));
          const uint32_t first = (ka[0] >> shift) & DMASK;
          bool uniform = true;
          for (int64_t j = 0; j < cnt; ++j) {
            const uint32_t d = (ka[j] >> shift) & DMASK;
            ++h[d];
            uniform &= (d == first);
          }
          if (uniform) continue;  // pass is a no-op permutation
          int64_t sum = 0;
          for (int64_t d = 0; d <= DMASK; ++d) {
            const int64_t c = h[d];
            h[d] = sum;
            sum += c;
          }
          for (int64_t j = 0; j < cnt; ++j) {
            const int64_t pos = h[(ka[j] >> shift) & DMASK]++;
            kb[pos] = ka[j];
            ib[pos] = ia[j];
          }
          ka.swap(kb);
          ia.swap(ib);
        }
      }
      // fixups + leaders in one walk: for each bucket run, if the fps
      // are not already non-decreasing, stable_sort the (key, idx)
      // pairs by full key (fp in the low bits; stability keeps input
      // order on ties). Leaders are full-key change positions.
      int32_t* ls = lead_scratch + st;
      int64_t g = 0;
      int64_t rs = 0;  // bucket-run start
      for (int64_t j = 0; j <= cnt; ++j) {
        const bool run_end =
            (j == cnt) || ((ka[j] >> 32) != (ka[rs] >> 32));
        if (!run_end) continue;
        if (j - rs > 1) {
          bool sorted = true;
          for (int64_t q = rs + 1; q < j; ++q)
            if (ka[q] < ka[q - 1]) {
              sorted = false;
              break;
            }
          if (!sorted) {
            // sort pairs by key, input-stable: indices ride along
            static thread_local std::vector<std::pair<uint64_t, int32_t>>
                tmp;
            tmp.resize(j - rs);
            for (int64_t q = rs; q < j; ++q)
              tmp[q - rs] = {ka[q], ia[q]};
            std::stable_sort(
                tmp.begin(), tmp.end(),
                [](const auto& x, const auto& y) {
                  return x.first < y.first;
                });
            for (int64_t q = rs; q < j; ++q) {
              ka[q] = tmp[q - rs].first;
              ia[q] = tmp[q - rs].second;
            }
          }
        }
        for (int64_t q = rs; q < j; ++q)
          if (q == rs || ka[q] != ka[q - 1]) ls[g++] = (int32_t)q;
        if (j < cnt) rs = j;
      }
      gcounts[s] = g;
      std::memcpy(order_out + st, ia.data(), cnt * sizeof(int32_t));
    }
  });

  if (dbg) t3 = now_us();
  int64_t maxg = 1;
  for (int64_t s = 0; s < n_shards; ++s) {
    gstarts[s + 1] = gstarts[s] + gcounts[s];
    if (gcounts[s] > maxg) maxg = gcounts[s];
  }
  int64_t G;
  if (g_override > 0) {
    if (g_override < maxg) return -2;
    G = g_override;
  } else {
    int64_t gr[4];
    const int ng = group_rungs_c(B, gr);
    G = pick_rung(gr, ng, maxg);
    if (G < 0) return -1;  // unreachable: top rung is B >= maxc >= maxg
  }
  picked_out[0] = B;
  picked_out[1] = G;

  // phase D part 2: per-shard marshal — one streaming loop PER FIELD
  // (interleaved 8-array writes per row defeat vectorization; per-field
  // loops make the padding tail a vectorized constant fill and the real
  // rows a single gather+store stream), then groups from the sort
  // pass's leader scratch with build_groups' padding conventions.
  const ShardStack stack{B,        G,        kh_out,    hits_out, limit_out,
                         dur_out,  algo_out, gnp_out,   valid_out, gid_out,
                         gkh_out,  glead_out, gend_out, gvalid_out};
  std::atomic<int64_t> next_shard2{0};
  pool.run([&](int, int) {
    for (;;) {
      const int64_t s = next_shard2.fetch_add(1);
      if (s >= n_shards) break;
      const int64_t cnt = counts_out[s], st = starts[s];
      if (cnt == 0) continue;  // filled by the serial fixup below —
      // the fill row belongs to another shard whose order may not be
      // written yet
      const int32_t* ord = order_out + st;
      uint64_t* kh_o = kh_out + s * B;
      int32_t* hi_o = hits_out + s * B;
      int32_t* li_o = limit_out + s * B;
      int32_t* du_o = dur_out + s * B;
      int32_t* al_o = algo_out + s * B;
      uint8_t* gn_o = gnp_out + s * B;
      for (int64_t j = 0; j < cnt; ++j) kh_o[j] = key_hash[ord[j]];
      for (int64_t j = 0; j < cnt; ++j)
        hi_o[j] = clip_i64(hits[ord[j]], lo, hi);
      for (int64_t j = 0; j < cnt; ++j)
        li_o[j] = clip_i64(limit[ord[j]], lo, hi);
      for (int64_t j = 0; j < cnt; ++j)
        du_o[j] = clip_i64(duration[ord[j]], dlo, dhi);
      for (int64_t j = 0; j < cnt; ++j) al_o[j] = algo[ord[j]];
      for (int64_t j = 0; j < cnt; ++j) gn_o[j] = gnp[ord[j]];
      int64_t* tk = take_idx_out + st;
      const int64_t base = s * B;
      for (int64_t j = 0; j < cnt; ++j) tk[j] = base + j;
      // padding tail + groups: leaders from the sort pass
      finish_shard(stack, s, cnt, lead_scratch + st, gcounts[s]);
    }
  });

  if (dbg) t4 = now_us();
  fill_empty_shards(stack, counts_out, starts.data(), n_shards, n,
                    take_idx_out);
  if (dbg) {
    const int64_t t5 = now_us();
    fprintf(stderr,
            "prep phases us: A+B=%ld C=%ld sort=%ld marshal=%ld fixup=%ld "
            "total=%ld (T=%d)\n",
            (long)(t1 - t0), (long)(t2 - t1), (long)(t3 - t2),
            (long)(t4 - t3), (long)(t5 - t4), (long)(t5 - t0), T);
  }
  return 0;
}

// Mesh response unflatten: out[c][order[st+j]] = packed[s][c*B_sub + j]
// for the n real rows — the native twin of MeshEngine.decide_arrays's
// per-column `out[order] = flat[take_idx]`, all four response columns in
// one pass. packed rows have `stride` int32s (4*B_sub + stats tail).
void guber_unflatten_resp(const int32_t* packed, const int32_t* order,
                          const int64_t* counts, int64_t n,
                          int64_t n_shards, int64_t b_sub, int64_t stride,
                          int32_t* out) {
  int64_t st = 0;
  for (int64_t s = 0; s < n_shards; ++s) {
    const int64_t cnt = counts[s];
    const int32_t* row = packed + s * stride;
    for (int64_t c = 0; c < 4; ++c) {
      const int32_t* col = row + c * b_sub;
      int32_t* o = out + c * n;
      for (int64_t j = 0; j < cnt; ++j) o[order[st + j]] = col[j];
    }
    st += cnt;
  }
}

// Sorted-run merge combine (r9): stable k-way merge of per-group
// PRE-SORTED runs (serve/batcher.py arrival-time prep), fused with the
// field materialization + request padding the flush path needs — one
// GIL-free pass replacing the flattened batch's concat + full radix
// sort + marshal. Stability contract (pinned python-side): equal sort
// keys resolve in run order, and runs arrive in caller order, so the
// merged permutation equals np.argsort(concat, kind="stable").
//
// Inputs are k parallel pointer tables (one entry per run) of the
// sorted skey / device-dtype fields / within-run caller order, plus
// per-run lengths ns[k] and flattened-batch base offsets bases[k].
// Outputs: merged skey[n] (group derivation), order_out[B] (global
// caller index; tail = identity, the engine's padding convention), the
// six padded field arrays [B] (tail repeats the last merged row,
// valid=0 — pad_request_sorted's convention), and the duplicate-key
// group stream (group_id[n], leader_pos[n], g_real). Pass B == n to
// skip padding (the numpy twins' flat merge, serve/prep.py).
// When n_rungs > 0, the group stream is additionally PADDED to the
// smallest rung G >= max(g_real, 1) of g_rungs (engine.group_rungs'
// ladder, engine.build_groups' conventions): gkh/glead/gend/gvalid
// sized G (caller allocates g_rungs[n_rungs-1]), group_id_out sized B
// with the padding tail pointing at the last real group, and the
// picked G returned through g_pick_out — so the whole merge + pad +
// group build is one GIL-free call. n_rungs == 0 skips the padding.
// The mesh's stacked layout is guber_merge_runs_sharded's, below.
int64_t guber_merge_runs(
    const uint64_t* const* skeys, const uint64_t* const* khs,
    const int32_t* const* hits, const int32_t* const* limits,
    const int32_t* const* durs, const int32_t* const* algos,
    const uint8_t* const* gnps, const int32_t* const* orders,
    const int64_t* ns, const int64_t* bases, int64_t k, int64_t B,
    const int64_t* g_rungs, int64_t n_rungs, uint64_t* skey_out,
    int32_t* order_out, uint64_t* kh_out, int32_t* hits_out,
    int32_t* limit_out, int32_t* dur_out, int32_t* algo_out,
    uint8_t* gnp_out, uint8_t* valid_out, int32_t* group_id_out,
    int32_t* leader_pos_out, uint64_t* gkh_out, int32_t* gend_out,
    uint8_t* gvalid_out, int64_t* g_real_out, int64_t* g_pick_out) {
  int64_t n = 0;
  for (int64_t r = 0; r < k; ++r) n += ns[r];
  if (n > B) return -1;
  int64_t g = -1;
  uint64_t prev_key = 0;
  merge_sorted_runs(
      skeys, ns, k, n, [&](int64_t i, int64_t r, int64_t j, uint64_t key) {
        skey_out[i] = key;
        order_out[i] = (int32_t)(orders[r][j] + bases[r]);
        kh_out[i] = khs[r][j];
        hits_out[i] = hits[r][j];
        limit_out[i] = limits[r][j];
        dur_out[i] = durs[r][j];
        algo_out[i] = algos[r][j];
        gnp_out[i] = gnps[r][j];
        valid_out[i] = 1;
        if (i == 0 || key != prev_key) {
          leader_pos_out[++g] = (int32_t)i;
          prev_key = key;
        }
        group_id_out[i] = (int32_t)g;
      });
  const int64_t g_real = g + 1;
  *g_real_out = g_real;
  // padding tail: repeat the last merged row with valid=0; order maps
  // padding rows to themselves (engine padding conventions)
  // (see guber_prep_run below for the arrival-side producer)
  for (int64_t i = n; i < B; ++i) {
    order_out[i] = (int32_t)i;
    kh_out[i] = n ? kh_out[n - 1] : 0;
    hits_out[i] = n ? hits_out[n - 1] : 0;
    limit_out[i] = n ? limit_out[n - 1] : 0;
    dur_out[i] = n ? dur_out[n - 1] : 0;
    algo_out[i] = n ? algo_out[n - 1] : 0;
    gnp_out[i] = n ? gnp_out[n - 1] : 0;
    valid_out[i] = 0;
  }
  if (n_rungs > 0) {
    // padded group build, engine.build_groups conventions: pick the
    // smallest rung holding the real groups, real slots get
    // leader/end/key, the final real group owns the request padding
    // tail, padded slots carry leader=B / end=B-1 / valid=0, and
    // padded request rows point at the last real group
    const int64_t g_need = g_real > 1 ? g_real : 1;
    int64_t G = 0;
    for (int64_t r = 0; r < n_rungs; ++r) {
      if (g_rungs[r] >= g_need) {
        G = g_rungs[r];
        break;
      }
    }
    if (G == 0) return -3;  // ladder cannot hold the group count
    *g_pick_out = G;
    for (int64_t q = 0; q < g_real; ++q) {
      const int32_t lead = leader_pos_out[q];
      gkh_out[q] = kh_out[lead];
      gend_out[q] =
          q + 1 < g_real ? leader_pos_out[q + 1] - 1 : (int32_t)(B - 1);
      gvalid_out[q] = 1;
    }
    const uint64_t k_pad = B ? kh_out[B - 1] : 0;
    for (int64_t q = g_real; q < G; ++q) {
      leader_pos_out[q] = (int32_t)B;
      gkh_out[q] = k_pad;
      gend_out[q] = (int32_t)(B - 1);
      gvalid_out[q] = 0;
    }
    const int32_t gid_pad = (int32_t)(g_real > 0 ? g_real - 1 : 0);
    for (int64_t i = n; i < B; ++i) group_id_out[i] = gid_pad;
  }
  return 0;
}

// The same merge laid out for the mesh: ONE GIL-free call gives what
// serve/prep.py merge_runs + sharded.py build_presorted_sharded +
// stack_shard_groups build in numpy (the twin, and the oracle of
// tests/test_prep_pipeline.py: byte-identical in every output). The
// composite sort key carries the owner shard above bit owner_shift
// (32 + the store's bucket bits: guber_prep_run), so the merged stream
// is the shards' contiguous slices in shard order and every row's cell
// is known as it is merged: shard s's rows land at [s, 0..count) of
// the stacked [n_shards, B_sub] columns, B_sub the smallest of
// sub_rungs that holds the fullest shard. Groups break at shard
// boundaries (the owner bits differ), their leaders are shard-local,
// and G_sub is the smallest of group_rungs(B_sub) that holds the
// fullest shard's groups; padding as finish_shard / fill_empty_shards
// say. Output buffers are caller-allocated for n_shards rows of a rung
// >= B_sub and written compactly (stride B_sub / G_sub, picked_out =
// {B_sub, G_sub}); order_out[n] and take_idx_out[n] (merged position
// -> flat cell s * B_sub + j) are per merged row; counts_out[n_shards]
// the rows a shard. Returns 0; 1 = DECLINED, nothing written: the
// fullest shard exceeds the ladder's top, whose extension (and the
// one-time warning) is the Python caller's; < 0 on runs that break the
// contract (an owner >= n_shards, keys out of order).
int64_t guber_merge_runs_sharded(
    const uint64_t* const* skeys, const uint64_t* const* khs,
    const int32_t* const* hits, const int32_t* const* limits,
    const int32_t* const* durs, const int32_t* const* algos,
    const uint8_t* const* gnps, const int32_t* const* orders,
    const int64_t* ns, const int64_t* bases, int64_t k, int64_t n_shards,
    int64_t owner_shift, const int64_t* sub_rungs, int64_t n_sub_rungs,
    int32_t* order_out, int64_t* take_idx_out, int64_t* counts_out,
    int64_t* picked_out, uint64_t* kh_out, int32_t* hits_out,
    int32_t* limit_out, int32_t* dur_out, int32_t* algo_out,
    uint8_t* gnp_out, uint8_t* valid_out, int32_t* gid_out,
    uint64_t* gkh_out, int32_t* glead_out, int32_t* gend_out,
    uint8_t* gvalid_out) {
  int64_t n = 0;
  std::fill_n(counts_out, n_shards, 0);
  for (int64_t r = 0; r < k; ++r) {
    n += ns[r];
    for (int64_t j = 0; j < ns[r]; ++j) {
      const uint64_t owner = skeys[r][j] >> owner_shift;
      if (owner >= (uint64_t)n_shards) return -1;
      ++counts_out[owner];
    }
  }
  std::vector<int64_t> starts((size_t)n_shards + 1, 0);
  std::vector<int64_t> gcounts((size_t)n_shards, 0);
  int64_t maxc = 1;
  for (int64_t s = 0; s < n_shards; ++s) {
    starts[s + 1] = starts[s] + counts_out[s];
    if (counts_out[s] > maxc) maxc = counts_out[s];
  }
  const int64_t B = pick_rung(sub_rungs, n_sub_rungs, maxc);
  if (B < 0) return 1;
  // shard-local leader positions, shard s's from lead[starts[s]]: at
  // most one a row
  std::vector<int32_t> lead((size_t)n);
  uint64_t prev_key = 0;
  bool sorted = true;
  merge_sorted_runs(
      skeys, ns, k, n, [&](int64_t i, int64_t r, int64_t j, uint64_t key) {
        const int64_t s = (int64_t)(key >> owner_shift);
        const int64_t local = i - starts[s];
        if ((uint64_t)local >= (uint64_t)counts_out[s]) {
          sorted = false;  // an owner out of shard order: write nothing
          return;
        }
        const int64_t dst = s * B + local;
        order_out[i] = (int32_t)(orders[r][j] + bases[r]);
        take_idx_out[i] = dst;
        kh_out[dst] = khs[r][j];
        hits_out[dst] = hits[r][j];
        limit_out[dst] = limits[r][j];
        dur_out[dst] = durs[r][j];
        algo_out[dst] = algos[r][j];
        gnp_out[dst] = gnps[r][j];
        if (local == 0 || key != prev_key) {
          lead[(size_t)(starts[s] + gcounts[s]++)] = (int32_t)local;
          prev_key = key;
        }
      });
  if (!sorted) return -2;
  int64_t maxg = 1;
  for (int64_t s = 0; s < n_shards; ++s)
    if (gcounts[s] > maxg) maxg = gcounts[s];
  int64_t gr[4];
  const int64_t G = pick_rung(gr, group_rungs_c(B, gr), maxg);
  if (G < 0) return -3;  // unreachable: the top rung is B >= maxc >= maxg
  picked_out[0] = B;
  picked_out[1] = G;
  const ShardStack stack{B,        G,        kh_out,    hits_out, limit_out,
                         dur_out,  algo_out, gnp_out,   valid_out, gid_out,
                         gkh_out,  glead_out, gend_out, gvalid_out};
  for (int64_t s = 0; s < n_shards; ++s)
    if (counts_out[s])
      finish_shard(stack, s, counts_out[s], lead.data() + starts[s],
                   gcounts[s]);
  fill_empty_shards(stack, counts_out, starts.data(), n_shards, n,
                    take_idx_out);
  return 0;
}

// Arrival-time per-group prep (r9): ONE call fusing the sharded
// presort (guber_presort_sharded), the device-dtype clip+gather of all
// six request fields, and the composite sort-key stream the merge
// orders by — the producer side of guber_merge_runs. One GIL-free
// call per enqueued group keeps the prep pool's threads off the
// interpreter while the serving loop is hot. n_shards == 1 degrades
// to the single-device (bucket, fingerprint) order: the owner bits
// are zero, so the composite key equals group_sort_key_np's.
int64_t guber_prep_run(const uint64_t* key_hash, const int64_t* hits,
                       const int64_t* limits, const int64_t* durs,
                       const int32_t* algos, const uint8_t* gnps,
                       int64_t n, uint64_t buckets, int64_t n_shards,
                       int64_t lo, int64_t hi, int64_t dlo, int64_t dhi,
                       int32_t* order_out, int64_t* counts_out,
                       uint64_t* skey_out, uint64_t* kh_out,
                       int32_t* hits_out, int32_t* limit_out,
                       int32_t* dur_out, int32_t* algo_out,
                       uint8_t* gnp_out) {
  guber_presort_sharded(key_hash, n, buckets, (uint64_t)n_shards,
                        order_out, counts_out);
  int bucket_bits = 0;
  while ((1ULL << bucket_bits) < buckets) ++bucket_bits;
  if (bucket_bits < 1) bucket_bits = 1;  // python max(bit_length-1, 1)
  const uint64_t bmask = buckets - 1;
  for (int64_t i = 0; i < n; ++i) {
    const int64_t j = order_out[i];
    const uint64_t kh = key_hash[j];
    kh_out[i] = kh;
    uint64_t owner =
        n_shards > 1 ? splitmix64(kh ^ SHARD_SALT) % (uint64_t)n_shards
                     : 0;
    const uint64_t bkt = splitmix64(kh ^ BUCKET_SALT) & bmask;
    uint64_t fp = kh >> 32;
    if (fp == 0) fp = 1;
    skey_out[i] = (owner << (32 + bucket_bits)) | (bkt << 32) | fp;
    int64_t h = hits[j];
    hits_out[i] = (int32_t)(h < lo ? lo : (h > hi ? hi : h));
    int64_t l = limits[j];
    limit_out[i] = (int32_t)(l < lo ? lo : (l > hi ? hi : l));
    int64_t d = durs[j];
    dur_out[i] = (int32_t)(d < dlo ? dlo : (d > dhi ? dhi : d));
    algo_out[i] = algos[j];
    gnp_out[i] = gnps[j];
  }
  return 0;
}

}  // extern "C"

// ---------------------------------------------------------------------------
// The PeersV1 door's wire fold (serve/server.py GetPeerRateLimits): one
// forwarded batch, serialised GetPeerRateLimitsReq bytes -> request
// columns, and answer columns -> serialised GetPeerRateLimitsResp bytes,
// each in ONE GIL-free call, so a 1000-item batch makes no Python object
// per item. The parser accepts exactly the messages whose meaning it
// shares with the protobuf runtime and DECLINES everything else (a
// negative code); the caller then parses the same bytes with the runtime
// and serves them through the object path, so declining is always safe.
// ---------------------------------------------------------------------------

namespace {

// decline codes of guber_parse_peer_batch (hashlib_native.PEER_DECLINE);
// guber_parse_string_frame shares -4 .. -6 and adds its own
// (hashlib_native.STRING_DECLINE)
constexpr int64_t PEER_CHAIN = -1;      // a quota chain (field 8)
constexpr int64_t PEER_UNKNOWN = -2;    // unknown field, or a known one
                                        // under another wire type
constexpr int64_t PEER_ENUM = -3;       // algorithm / behavior not named
constexpr int64_t PEER_UTF8 = -4;       // name or key not valid UTF-8
constexpr int64_t PEER_TRUNCATED = -5;  // a length or varint runs past
                                        // its message
constexpr int64_t PEER_TOO_MANY = -6;   // more items than max_items
// -7 is unused
constexpr int64_t FRAME_EMPTY = -8;     // an empty name or unique_key
constexpr int64_t FRAME_TRAILING = -9;  // bytes left after the last item
constexpr int64_t FRAME_NUL = -10;      // a NUL byte in a name or key:
                                        // the joined keys could not be
                                        // split again

// One varint of at most 10 bytes; false where it runs past `end` or
// carries more than 64 bits.
inline bool read_varint(const uint8_t*& p, const uint8_t* end,
                        uint64_t& out) {
  uint64_t v = 0;
  for (int shift = 0; shift < 64; shift += 7) {
    if (p >= end) return false;
    const uint8_t b = *p++;
    if (shift == 63 && b > 1) return false;
    v |= static_cast<uint64_t>(b & 0x7F) << shift;
    if (b < 0x80) {
      out = v;
      return true;
    }
  }
  return false;
}

// Strict UTF-8 (RFC 3629: no overlong forms, no surrogates, nothing past
// U+10FFFF) — what the protobuf runtime demands of a proto3 string and
// what Python's str needs to decode it.
inline bool valid_utf8(const uint8_t* p, size_t len) {
  const uint8_t* const end = p + len;
  while (p < end) {
    const uint8_t c = *p;
    if (c < 0x80) {
      ++p;
    } else if (c >= 0xC2 && c <= 0xDF) {
      if (end - p < 2 || (p[1] & 0xC0) != 0x80) return false;
      p += 2;
    } else if (c >= 0xE0 && c <= 0xEF) {
      if (end - p < 3 || (p[1] & 0xC0) != 0x80 || (p[2] & 0xC0) != 0x80)
        return false;
      if (c == 0xE0 && p[1] < 0xA0) return false;  // overlong
      if (c == 0xED && p[1] > 0x9F) return false;  // surrogate
      p += 3;
    } else if (c >= 0xF0 && c <= 0xF4) {
      if (end - p < 4 || (p[1] & 0xC0) != 0x80 || (p[2] & 0xC0) != 0x80 ||
          (p[3] & 0xC0) != 0x80)
        return false;
      if (c == 0xF0 && p[1] < 0x90) return false;  // overlong
      if (c == 0xF4 && p[1] > 0x8F) return false;  // past U+10FFFF
      p += 4;
    } else {
      return false;
    }
  }
  return true;
}

inline uint8_t* put_varint(uint8_t* p, uint64_t v) {
  while (v >= 0x80) {
    *p++ = static_cast<uint8_t>(v) | 0x80;
    v >>= 7;
  }
  *p++ = static_cast<uint8_t>(v);
  return p;
}

}  // namespace

extern "C" {

// Parse a serialised GetPeerRateLimitsReq (repeated RateLimitReq
// requests = 1) into columns. Returns the number of items, or a decline
// code < 0 (the columns are then garbage). Per item: key_hash =
// xxh64(name + "_" + unique_key, seed) hashed off the wire bytes — the
// value core/hashing.slot_hash_batch gives the same key — hits / limit /
// duration as int64, algorithm and behavior as their enum numbers, and
// where name and unique_key lie in `buf` (for the few items whose key
// strings the caller needs). An absent field reads its proto3 default;
// of a field sent twice the last wins, as in the runtime. A tag is
// accepted only in its one-byte canonical form.
int64_t guber_parse_peer_batch(const uint8_t* buf, int64_t len,
                               int64_t max_items, uint64_t seed,
                               uint64_t* key_hash, int64_t* hits,
                               int64_t* limit, int64_t* duration,
                               int32_t* algo, uint8_t* behavior,
                               int32_t* name_off, int32_t* name_len,
                               int32_t* key_off, int32_t* key_len) {
  if (len > INT32_MAX) return PEER_TRUNCATED;
  const uint8_t* p = buf;
  const uint8_t* const end = buf + len;
  std::vector<uint8_t> scratch;
  int64_t n = 0;
  while (p < end) {
    if (*p++ != 0x0A) return PEER_UNKNOWN;  // requests = 1, LEN
    uint64_t mlen;
    if (!read_varint(p, end, mlen) ||
        mlen > static_cast<uint64_t>(end - p))
      return PEER_TRUNCATED;
    if (n >= max_items) return PEER_TOO_MANY;
    const uint8_t* q = p;
    const uint8_t* const qend = p + mlen;
    p = qend;
    const uint8_t* name = q;
    const uint8_t* key = q;
    uint64_t nlen = 0, klen = 0;
    uint64_t f_hits = 0, f_limit = 0, f_dur = 0, f_algo = 0, f_beh = 0;
    while (q < qend) {
      const uint8_t tag = *q++;
      if (tag == 0x0A || tag == 0x12) {  // name = 1 / unique_key = 2
        uint64_t slen;
        if (!read_varint(q, qend, slen) ||
            slen > static_cast<uint64_t>(qend - q))
          return PEER_TRUNCATED;
        if (!valid_utf8(q, slen)) return PEER_UTF8;
        if (tag == 0x0A) {
          name = q;
          nlen = slen;
        } else {
          key = q;
          klen = slen;
        }
        q += slen;
      } else if (tag == 0x18 || tag == 0x20 || tag == 0x28 ||
                 tag == 0x30 || tag == 0x38) {
        uint64_t v;
        if (!read_varint(q, qend, v)) return PEER_TRUNCATED;
        switch (tag) {
          case 0x18: f_hits = v; break;   // hits = 3
          case 0x20: f_limit = v; break;  // limit = 4
          case 0x28: f_dur = v; break;    // duration = 5
          case 0x30: f_algo = v; break;   // algorithm = 6
          default: f_beh = v; break;      // behavior = 7
        }
      } else if (tag == 0x42) {  // chain = 8
        return PEER_CHAIN;
      } else {
        return PEER_UNKNOWN;
      }
    }
    // the enums' named values: Algorithm 0..3, Behavior 0..2
    if (f_algo > 3 || f_beh > 2) return PEER_ENUM;
    scratch.resize(nlen + 1 + klen);
    if (nlen) std::memcpy(scratch.data(), name, nlen);
    scratch[nlen] = '_';
    if (klen) std::memcpy(scratch.data() + nlen + 1, key, klen);
    key_hash[n] = xxh64(scratch.data(), scratch.size(), seed);
    hits[n] = static_cast<int64_t>(f_hits);
    limit[n] = static_cast<int64_t>(f_limit);
    duration[n] = static_cast<int64_t>(f_dur);
    algo[n] = static_cast<int32_t>(f_algo);
    behavior[n] = static_cast<uint8_t>(f_beh);
    name_off[n] = static_cast<int32_t>(name - buf);
    name_len[n] = static_cast<int32_t>(nlen);
    key_off[n] = static_cast<int32_t>(key - buf);
    key_len[n] = static_cast<int32_t>(klen);
    ++n;
  }
  return n;
}

// Parse the payload of one GEB string frame (serve/edge_bridge.py: the
// header's item count `n`, then per item <H name_len, name, <H key_len,
// unique_key, <qqqBB hits, limit, duration, algorithm, behavior, all
// little-endian) into columns: the native twin of the lean parse in
// EdgeBridge._fold_string_frame, which stays as its fallback and its
// oracle. Returns n, or a decline code < 0 (the columns are then
// garbage) exactly where that loop returns None: n over len / 30 (an
// item is at least 30 bytes), a truncated item, an empty name or key,
// invalid UTF-8, bytes left over - and for a NUL byte in a name or key,
// which the loop serves. key_hash is xxh64(name + "_" + unique_key,
// seed), core/hashing.slot_hash_batch's value; an algorithm byte over 3
// reads 0, as the loop clamps it. `keys_out` (len bytes: an item's hash
// key and its separator are shorter than the item) receives the n hash
// keys joined by NUL, *keys_len their bytes: one decode and one split
// make the frame's key strings, no Python step per item.
int64_t guber_parse_string_frame(const uint8_t* buf, int64_t len,
                                 int64_t n, uint64_t seed,
                                 uint64_t* key_hash, int64_t* hits,
                                 int64_t* limit, int64_t* duration,
                                 int32_t* algo, uint8_t* behavior,
                                 int32_t* name_off, int32_t* name_len,
                                 int32_t* key_off, int32_t* key_len,
                                 uint8_t* keys_out, int64_t* keys_len) {
  constexpr int64_t FIX = 26;  // <qqqBB
  if (len > INT32_MAX) return PEER_TRUNCATED;
  if (n < 0 || n > len / 30) return PEER_TOO_MANY;
  const uint8_t* p = buf;
  const uint8_t* const end = buf + len;
  uint8_t* w = keys_out;
  for (int64_t i = 0; i < n; ++i) {
    const uint8_t* span[2];
    int64_t slen[2];
    for (int f = 0; f < 2; ++f) {  // name, then unique_key
      if (end - p < 2) return PEER_TRUNCATED;
      slen[f] = p[0] | (static_cast<int64_t>(p[1]) << 8);
      p += 2;
      if (end - p < slen[f]) return PEER_TRUNCATED;
      if (slen[f] == 0) return FRAME_EMPTY;
      if (std::memchr(p, 0, slen[f]) != nullptr) return FRAME_NUL;
      if (!valid_utf8(p, slen[f])) return PEER_UTF8;
      span[f] = p;
      p += slen[f];
    }
    if (end - p < FIX) return PEER_TRUNCATED;
    if (i) *w++ = 0;
    uint8_t* const k0 = w;
    std::memcpy(w, span[0], slen[0]);
    w += slen[0];
    *w++ = '_';
    std::memcpy(w, span[1], slen[1]);
    w += slen[1];
    key_hash[i] = xxh64(k0, static_cast<size_t>(w - k0), seed);
    // a little-endian host, as the numpy views of the fast frames assume
    std::memcpy(&hits[i], p, 8);
    std::memcpy(&limit[i], p + 8, 8);
    std::memcpy(&duration[i], p + 16, 8);
    algo[i] = p[24] <= 3 ? p[24] : 0;
    behavior[i] = p[25];
    p += FIX;
    name_off[i] = static_cast<int32_t>(span[0] - buf);
    name_len[i] = static_cast<int32_t>(slen[0]);
    key_off[i] = static_cast<int32_t>(span[1] - buf);
    key_len[i] = static_cast<int32_t>(slen[1]);
  }
  if (p != end) return FRAME_TRAILING;
  *keys_len = w - keys_out;
  return n;
}

// An item of guber_encode_peer_answers is at most this long: the
// item's tag and one length byte, then four fields of a tag and a
// 10-byte varint each.
int64_t guber_peer_answer_max_bytes() { return 2 + 4 * 11; }

// Serialise answer columns as a GetPeerRateLimitsResp (repeated
// RateLimitResp rate_limits = 1: status = 1, limit = 2, remaining = 3,
// reset_time = 4; no error, no metadata — what an owner's reply to its
// peer holds). proto3: a zero field is left out. `out` holds
// n * guber_peer_answer_max_bytes(); returns the bytes written.
int64_t guber_encode_peer_answers(const int64_t* status,
                                  const int64_t* limit,
                                  const int64_t* remaining,
                                  const int64_t* reset_time, int64_t n,
                                  uint8_t* out) {
  uint8_t* p = out;
  for (int64_t i = 0; i < n; ++i) {
    *p++ = 0x0A;
    uint8_t* const len_at = p++;
    const int64_t vals[4] = {status[i], limit[i], remaining[i],
                             reset_time[i]};
    for (int f = 0; f < 4; ++f) {
      if (vals[f] == 0) continue;
      *p++ = static_cast<uint8_t>((f + 1) << 3);
      p = put_varint(p, static_cast<uint64_t>(vals[f]));
    }
    *len_at = static_cast<uint8_t>(p - len_at - 1);  // <= 44
  }
  return p - out;
}

// ---------------------------------------------------------------------------
// The GEB door's split of a string frame by owner (serve/edge_bridge.py
// _plan_split) and the forwarder's column RPC (serve/peers.py
// PeerClient.forward_columns): the four calls below are the converses of
// the three above. Which ring member owns each key of a frame; the rows
// a peer owns as the bytes of a GetPeerRateLimitsReq; a peer's reply as
// answer columns; and a frame's answer columns, with an owner tag and an
// error text a row, as the response frame's items.
// ---------------------------------------------------------------------------

// owner[i] = the ring position whose point is the successor of key i's
// crc32 (IEEE) on `ring` (m ascending points, a position past the last
// wraps to 0): serve/peers.py ConsistentHashPicker.get, key for key.
// `keys` are the n hash keys joined by NUL, as guber_parse_string_frame
// leaves them. Returns 0, or -1 where the buffer does not hold n keys.
int64_t guber_ring_owners(const uint8_t* keys, int64_t keys_len, int64_t n,
                          const uint32_t* ring, int64_t m, int32_t* owner) {
  if (!crc_init_done) crc_init();
  if (n <= 0) return (n == 0 && keys_len == 0) ? 0 : -1;
  if (m <= 0) return -1;
  const uint8_t* p = keys;
  const uint8_t* const end = keys + keys_len;
  for (int64_t i = 0; i < n; ++i) {
    uint32_t c = 0xFFFFFFFFu;
    while (p < end && *p != 0) {
      c = crc_table[(c ^ *p++) & 0xFF] ^ (c >> 8);
    }
    c ^= 0xFFFFFFFFu;
    const uint32_t* at = std::lower_bound(ring, ring + m, c);
    owner[i] = at == ring + m ? 0 : static_cast<int32_t>(at - ring);
    if (i + 1 < n) {
      if (p >= end) return -1;  // fewer keys than n
      ++p;                      // the separator
    }
  }
  return p == end ? 0 : -1;
}

// Serialise `n_rows` items of a parsed string frame (the columns of
// guber_parse_string_frame over the payload `buf`; rows[j] names the
// item, or with rows null the items 0 .. n_rows - 1) as a
// GetPeerRateLimitsReq: repeated RateLimitReq requests = 1 with name =
// 1, unique_key = 2, hits = 3, limit = 4, duration = 5, algorithm = 6,
// behavior = 7 in field order, a zero or empty field left out — byte
// for byte what the protobuf runtime writes for api/convert.py
// req_to_pb's message, and what guber_parse_peer_batch takes without a
// decline. A behavior byte over 2 reads 0, as decode_request_frame
// clamps it. Returns the bytes written, or -1 where `cap` is too small.
int64_t guber_encode_peer_batch(const uint8_t* buf, const int32_t* name_off,
                                const int32_t* name_len,
                                const int32_t* key_off,
                                const int32_t* key_len, const int64_t* hits,
                                const int64_t* limit,
                                const int64_t* duration, const int32_t* algo,
                                const uint8_t* behavior, const int32_t* rows,
                                int64_t n_rows, uint8_t* out, int64_t cap) {
  uint8_t* p = out;
  const uint8_t* const end = out + cap;
  for (int64_t j = 0; j < n_rows; ++j) {
    const int64_t i = rows ? rows[j] : j;
    const int64_t nl = name_len[i], kl = key_len[i];
    // tag + length (<= 5) of the item; two strings of tag + length
    // (<= 3: a u16 length) + bytes; five fields of tag + varint
    if (end - p < 6 + 4 + nl + 4 + kl + 5 * 11) return -1;
    const uint64_t vals[5] = {
        static_cast<uint64_t>(hits[i]), static_cast<uint64_t>(limit[i]),
        static_cast<uint64_t>(duration[i]), static_cast<uint64_t>(algo[i]),
        behavior[i] <= 2 ? behavior[i] : 0u};
    uint8_t body[5 * 11];
    uint8_t* b = body;
    for (int f = 0; f < 5; ++f) {
      if (vals[f] == 0) continue;
      *b++ = static_cast<uint8_t>((f + 3) << 3);
      b = put_varint(b, vals[f]);
    }
    uint8_t lenbuf[2][3];
    const size_t nlv = nl ? put_varint(lenbuf[0], nl) - lenbuf[0] : 0;
    const size_t klv = kl ? put_varint(lenbuf[1], kl) - lenbuf[1] : 0;
    const uint64_t item = (nl ? 1 + nlv + nl : 0) + (kl ? 1 + klv + kl : 0) +
                          static_cast<uint64_t>(b - body);
    *p++ = 0x0A;
    p = put_varint(p, item);
    if (nl) {
      *p++ = 0x0A;
      std::memcpy(p, lenbuf[0], nlv);
      p += nlv;
      std::memcpy(p, buf + name_off[i], nl);
      p += nl;
    }
    if (kl) {
      *p++ = 0x12;
      std::memcpy(p, lenbuf[1], klv);
      p += klv;
      std::memcpy(p, buf + key_off[i], kl);
      p += kl;
    }
    std::memcpy(p, body, b - body);
    p += b - body;
  }
  return p - out;
}

// what guber_parse_peer_answers declines beside the shared codes: an
// item that carries an error text or metadata (the caller parses those
// replies with the protobuf runtime: hashlib_native.ANSWER_DECLINE)
constexpr int64_t ANSWER_TEXT = -11;

// Parse a serialised GetPeerRateLimitsResp (repeated RateLimitResp
// rate_limits = 1: status = 1, limit = 2, remaining = 3, reset_time = 4)
// into four int64 columns: the converse of guber_encode_peer_answers,
// and what the protobuf runtime reads from the same bytes wherever this
// does not decline. Returns the number of items, or a decline code < 0
// (the columns are then garbage): an item with an error (5) or metadata
// (6), an unknown field or wire type, a status without a name, a length
// or varint past its message, more than max_items items. An absent
// field reads 0; of a field sent twice the last wins.
int64_t guber_parse_peer_answers(const uint8_t* buf, int64_t len,
                                 int64_t max_items, int64_t* status,
                                 int64_t* limit, int64_t* remaining,
                                 int64_t* reset_time) {
  const uint8_t* p = buf;
  const uint8_t* const end = buf + len;
  int64_t n = 0;
  while (p < end) {
    if (*p++ != 0x0A) return PEER_UNKNOWN;  // rate_limits = 1, LEN
    uint64_t mlen;
    if (!read_varint(p, end, mlen) ||
        mlen > static_cast<uint64_t>(end - p))
      return PEER_TRUNCATED;
    if (n >= max_items) return PEER_TOO_MANY;
    const uint8_t* q = p;
    const uint8_t* const qend = p + mlen;
    p = qend;
    uint64_t vals[4] = {0, 0, 0, 0};
    while (q < qend) {
      const uint8_t tag = *q++;
      if (tag == 0x08 || tag == 0x10 || tag == 0x18 || tag == 0x20) {
        if (!read_varint(q, qend, vals[(tag >> 3) - 1]))
          return PEER_TRUNCATED;
      } else if (tag == 0x2A || tag == 0x32) {
        return ANSWER_TEXT;
      } else {
        return PEER_UNKNOWN;
      }
    }
    if (vals[0] > 1) return PEER_ENUM;  // Status 0..1
    status[n] = static_cast<int64_t>(vals[0]);
    limit[n] = static_cast<int64_t>(vals[1]);
    remaining[n] = static_cast<int64_t>(vals[2]);
    reset_time[n] = static_cast<int64_t>(vals[3]);
    ++n;
  }
  return n;
}

// The items of a GEB string response frame (serve/edge_bridge.py: per
// item <B status, <qqq limit, remaining, reset_time, <H error_len,
// error, <H owner_len, owner) from four answer columns and, a row, an
// error text and an owner tag: err[i] and owner[i] index the `m` byte
// strings strs[str_off[k] : str_off[k + 1]], -1 for none. Byte for
// byte encode_response_frame's items for the same answers. Returns the
// bytes written, or -1 where `cap` is too small, an index is out of
// range or a string is longer than a u16 says.
int64_t guber_encode_string_answers(const int64_t* status,
                                    const int64_t* limit,
                                    const int64_t* remaining,
                                    const int64_t* reset_time,
                                    const int32_t* err, const int32_t* owner,
                                    const uint8_t* strs,
                                    const int64_t* str_off, int64_t m,
                                    int64_t n, uint8_t* out, int64_t cap) {
  uint8_t* p = out;
  const uint8_t* const end = out + cap;
  for (int64_t i = 0; i < n; ++i) {
    if (end - p < 25) return -1;
    *p++ = static_cast<uint8_t>(status[i]);
    std::memcpy(p, &limit[i], 8);
    std::memcpy(p + 8, &remaining[i], 8);
    std::memcpy(p + 16, &reset_time[i], 8);
    p += 24;
    const int32_t idx[2] = {err[i], owner[i]};
    for (int f = 0; f < 2; ++f) {
      int64_t slen = 0;
      const uint8_t* s = nullptr;
      if (idx[f] >= 0) {
        if (idx[f] >= m) return -1;
        s = strs + str_off[idx[f]];
        slen = str_off[idx[f] + 1] - str_off[idx[f]];
        if (slen < 0 || slen > 0xFFFF) return -1;
      }
      if (end - p < 2 + slen) return -1;
      *p++ = static_cast<uint8_t>(slen & 0xFF);
      *p++ = static_cast<uint8_t>(slen >> 8);
      if (slen) std::memcpy(p, s, slen);
      p += slen;
    }
  }
  return p - out;
}

}  // extern "C"

// ---------------------------------------------------------------------------
// The traffic observers' per-batch fold (core/sketches.py TrafficStats):
// the hot-key summary (Space-Saving) and the HyperLogLog registers take a
// whole batch - the key hashes the door already holds and the keys' bytes
// - in ONE GIL-free call: no Python dictionary pass and no heap cascade
// over 1000 names a frame on the serving loop. core/sketches.py
// SpaceSaving.observe + observe_weighted and HyperLogLog.add_hashes stay
// as the fallback where this symbol is absent, and as the oracle: after
// the same batches the summary here tracks the same keys with the same
// count and err, and the registers are byte-identical
// (tests/test_traffic_native.py).
// ---------------------------------------------------------------------------

namespace {

struct HotSlot {
  uint64_t hash;
  int64_t count;
  int64_t err;
  int32_t next;  // the next slot of its bucket, -1 at the end
  std::string key;
};

// One key of a batch after pre-aggregation.
struct BatchKey {
  uint64_t hash;
  const uint8_t* key;
  size_t len;
  int64_t weight;
};

struct HotKeys {
  explicit HotKeys(int64_t capacity) : cap(capacity) {
    size_t b = 16;
    while (b < static_cast<size_t>(2 * capacity)) b <<= 1;
    heads.assign(b, -1);
    slots.reserve(capacity);
  }

  int64_t cap;
  int64_t total = 0;
  std::vector<HotSlot> slots;  // at most cap, never shrinks but by reset
  std::vector<int32_t> heads;  // bucket -> first slot, by the key's hash
  // scratch of one fold, kept for its allocations
  std::vector<BatchKey> agg;
  std::vector<int32_t> seen;
  std::vector<int32_t> fresh;
  std::vector<int32_t> heap;

  size_t bucket(uint64_t h) const { return h & (heads.size() - 1); }

  int32_t find(uint64_t h, const uint8_t* key, size_t len) const {
    for (int32_t s = heads[bucket(h)]; s >= 0; s = slots[s].next) {
      const HotSlot& t = slots[s];
      if (t.hash == h && t.key.size() == len &&
          std::memcmp(t.key.data(), key, len) == 0)
        return s;
    }
    return -1;
  }

  void link(int32_t s) {
    int32_t& head = heads[bucket(slots[s].hash)];
    slots[s].next = head;
    head = s;
  }

  void unlink(int32_t s) {
    int32_t* at = &heads[bucket(slots[s].hash)];
    while (*at != s) at = &slots[*at].next;
    *at = slots[s].next;
  }

  void put(int32_t s, const BatchKey& k, int64_t floor) {
    HotSlot& t = slots[s];
    t.hash = k.hash;
    t.count = floor + k.weight;
    t.err = floor;
    t.key.assign(reinterpret_cast<const char*>(k.key), k.len);
    link(s);
  }

  // (count, key) as Python orders the summary's heap of tuples: a str
  // compares by code point, which is the byte order of its UTF-8
  bool below(int32_t a, int32_t b) const {
    const HotSlot& x = slots[a];
    const HotSlot& y = slots[b];
    if (x.count != y.count) return x.count < y.count;
    const size_t common = std::min(x.key.size(), y.key.size());
    const int c = std::memcmp(x.key.data(), y.key.data(), common);
    return c != 0 ? c < 0 : x.key.size() < y.key.size();
  }

  // heap[i] down to its place in the min-heap under it
  void sink(size_t i) {
    const size_t n = heap.size();
    const int32_t v = heap[i];
    for (size_t c; (c = 2 * i + 1) < n; i = c) {
      if (c + 1 < n && below(heap[c + 1], heap[c])) ++c;
      if (!below(heap[c], v)) break;
      heap[i] = heap[c];
    }
    heap[i] = v;
  }
};

// rho of one hash into its register: HyperLogLog.add_hashes, an item
inline void hll_add(uint8_t* reg, int p, uint64_t h) {
  const uint64_t rem = h << p;
  const uint8_t rho = static_cast<uint8_t>(
      rem ? __builtin_clzll(rem) + 1 : 64 - p + 1);
  uint8_t& r = reg[h >> (64 - p)];
  if (rho > r) r = rho;
}

}  // namespace

extern "C" {

void* guber_hotkeys_new(int64_t capacity) {
  return capacity >= 1 ? new HotKeys(capacity) : nullptr;
}

void guber_hotkeys_free(void* hot) { delete static_cast<HotKeys*>(hot); }

void guber_hotkeys_reset(void* hot) {
  HotKeys* s = static_cast<HotKeys*>(hot);
  s->slots.clear();
  std::fill(s->heads.begin(), s->heads.end(), -1);
  s->total = 0;
}

// Fold one batch of n items into the hot-key summary `hot` and the HLL
// registers `reg` (uint8[1 << p]); either may be null and is then left
// out (a pre-hashed frame has no names: registers only). hashes[i] is
// the slot hash OF key i, whose bytes are keys[offsets[i]:offsets[i+1]]
// or, where `offsets` is null, the i-th of n NUL-joined keys
// (guber_parse_string_frame's keys_out as it comes). The summary does
// what SpaceSaving.observe + observe_weighted do, in their order: the
// batch pre-aggregated in order of first occurrence, tracked keys add
// their weight, free slots fill, then each new key replaces the CURRENT
// minimum by (count, key) and inherits its count as err. Returns 0, or
// -1 (nothing folded) where the joined keys are not n.
int64_t guber_traffic_fold(void* hot, const uint64_t* hashes, int64_t n,
                           const uint8_t* keys, int64_t keys_len,
                           const int64_t* offsets, uint8_t* reg,
                           int64_t p) {
  HotKeys* s = static_cast<HotKeys*>(hot);
  if (s != nullptr && keys != nullptr && n > 0) {
    // pre-aggregate: open addressing over the batch, first come first
    size_t cells = 16;
    while (cells < static_cast<size_t>(2 * n)) cells <<= 1;
    s->seen.assign(cells, -1);
    s->agg.clear();
    int64_t at = 0;  // the next joined key starts here
    for (int64_t i = 0; i < n; ++i) {
      const uint8_t* key;
      size_t len;
      if (offsets != nullptr) {
        key = keys + offsets[i];
        len = static_cast<size_t>(offsets[i + 1] - offsets[i]);
      } else {
        if (at > keys_len) return -1;  // fewer keys than items
        const void* nul = std::memchr(keys + at, 0, keys_len - at);
        const int64_t stop =
            nul ? static_cast<const uint8_t*>(nul) - keys : keys_len;
        key = keys + at;
        len = static_cast<size_t>(stop - at);
        at = stop + 1;
      }
      const uint64_t h = hashes[i];
      size_t c = h & (cells - 1);
      for (;; c = (c + 1) & (cells - 1)) {
        const int32_t a = s->seen[c];
        if (a < 0) {
          s->seen[c] = static_cast<int32_t>(s->agg.size());
          s->agg.push_back({h, key, len, 1});
          break;
        }
        BatchKey& k = s->agg[a];
        if (k.hash == h && k.len == len &&
            std::memcmp(k.key, key, len) == 0) {
          ++k.weight;
          break;
        }
      }
    }
    if (offsets == nullptr && at != keys_len + 1) return -1;  // more keys
    s->total += n;
    s->fresh.clear();
    for (size_t a = 0; a < s->agg.size(); ++a) {
      const BatchKey& k = s->agg[a];
      const int32_t slot = s->find(k.hash, k.key, k.len);
      if (slot >= 0)
        s->slots[slot].count += k.weight;
      else
        s->fresh.push_back(static_cast<int32_t>(a));
    }
    size_t f = 0;
    for (; f < s->fresh.size() &&
           static_cast<int64_t>(s->slots.size()) < s->cap; ++f) {
      s->slots.emplace_back();
      s->put(static_cast<int32_t>(s->slots.size() - 1),
             s->agg[s->fresh[f]], 0);
    }
    if (f < s->fresh.size()) {
      // the cascade: a min-heap of the slots by (count, key), whose
      // root is the victim; its slot takes the new key and sinks
      s->heap.resize(s->slots.size());
      for (size_t i = 0; i < s->heap.size(); ++i)
        s->heap[i] = static_cast<int32_t>(i);
      for (size_t i = s->heap.size() / 2; i-- > 0;) s->sink(i);
      for (; f < s->fresh.size(); ++f) {
        const int32_t victim = s->heap[0];
        s->unlink(victim);
        s->put(victim, s->agg[s->fresh[f]], s->slots[victim].count);
        s->sink(0);
      }
    }
  }
  if (reg != nullptr)
    for (int64_t i = 0; i < n; ++i)
      hll_add(reg, static_cast<int>(p), hashes[i]);
  return 0;
}

// The summary's size: tracked keys, and through the pointers the items
// observed so far and the bytes of the tracked keys together.
int64_t guber_hotkeys_size(const void* hot, int64_t* total,
                           int64_t* key_bytes) {
  const HotKeys* s = static_cast<const HotKeys*>(hot);
  int64_t bytes = 0;
  for (const HotSlot& t : s->slots) bytes += t.key.size();
  *total = s->total;
  *key_bytes = bytes;
  return static_cast<int64_t>(s->slots.size());
}

// Every tracked key, in no order: count and err by slot, the keys' bytes
// end to end in `keys` with offsets[slot] .. offsets[slot + 1] (the
// arrays sized from guber_hotkeys_size under the caller's lock).
// Returns the number of slots written.
int64_t guber_hotkeys_export(const void* hot, int64_t* counts,
                             int64_t* errs, int64_t* offsets,
                             uint8_t* keys) {
  const HotKeys* s = static_cast<const HotKeys*>(hot);
  int64_t at = 0;
  int64_t i = 0;
  for (const HotSlot& t : s->slots) {
    counts[i] = t.count;
    errs[i] = t.err;
    offsets[i++] = at;
    std::memcpy(keys + at, t.key.data(), t.key.size());
    at += t.key.size();
  }
  offsets[i] = at;
  return i;
}

}  // extern "C"

// ---------------------------------------------------------------------------
// The shed cache's array consult and population (serve/shedcache.py
// ShedCache.screen_fields / observe_fields): what the serving loop does
// to a thousand-row frame on each side of its wait for the device, as ONE
// call each with the GIL released. The numpy bodies there (_screen_numpy,
// _observe_numpy) are some 45 array calls a frame, each long enough to
// give the GIL up, and the loop then waits for the submit, fetch and prep
// threads to hand it back; they stay as the form a host without this
// library runs and as the oracle (tests/test_shed_cache.py holds the two
// to each other row for row). The cache's dictionary, its LRU order and
// every write to a slot stay in Python: nothing here writes to the cache.
// ---------------------------------------------------------------------------

namespace {

// The cache's lookup structure as ShedCache._find reads it: the sorted
// index of the fingerprints live at the last sort with their slots, the
// sorted overlay of those bound since, and the slots' own fingerprint
// column.
struct ShedIndex {
  const uint64_t* ix_fp;
  const int64_t* ix_slot;
  int64_t m;
  const uint64_t* ov_fp;
  const int64_t* ov_slot;
  int64_t k;
  const uint64_t* fp;

  // How many fingerprints find() takes at once.
  static constexpr int64_t BLOCK = 16;

  // For each of `count` (at most BLOCK) fingerprints the slot that
  // holds it, or -1: the index row at or after it (clamped to the
  // last), overridden by the overlay's LAST binding of the fingerprint
  // (stable sort, so the upper bound less one), proven by the slot's
  // own fingerprint - a stale index row is refused here. The slot may
  // be a dropped one (reset_time 0). The searches of a block descend
  // together, a level at a time and without a branch on the keys, so
  // their cache misses overlap: one search alone in a 30,000-row index
  // is ~150 ns of mispredicted branches and waits, a thousand of them
  // what the numpy searchsorted took.
  void find(const uint64_t* kh, int64_t count, int64_t* slot) const {
    int64_t at[BLOCK];
    for (int64_t b = 0; b < count; ++b) slot[b] = -1;
    if (m > 0) {
      // lower bound: the first row whose fingerprint is >= kh
      for (int64_t b = 0; b < count; ++b) at[b] = 0;
      int64_t len = m;
      for (; len > 1; len -= len / 2) {
        const int64_t half = len / 2;
        for (int64_t b = 0; b < count; ++b)
          at[b] += ix_fp[at[b] + half - 1] < kh[b] ? half : 0;
      }
      for (int64_t b = 0; b < count; ++b) {
        const int64_t pos = at[b] + (ix_fp[at[b]] < kh[b]);
        slot[b] = ix_slot[pos < m ? pos : m - 1];
      }
    }
    if (k > 0) {
      // upper bound: the first row whose fingerprint is > kh
      for (int64_t b = 0; b < count; ++b) at[b] = 0;
      int64_t len = k;
      for (; len > 1; len -= len / 2) {
        const int64_t half = len / 2;
        for (int64_t b = 0; b < count; ++b)
          at[b] += ov_fp[at[b] + half - 1] <= kh[b] ? half : 0;
      }
      for (int64_t b = 0; b < count; ++b) {
        const int64_t j = at[b] + (ov_fp[at[b]] <= kh[b]) - 1;
        if (j >= 0 && ov_fp[j] == kh[b]) slot[b] = ov_slot[j];
      }
    }
    for (int64_t b = 0; b < count; ++b)
      if (slot[b] >= 0 && fp[slot[b]] != kh[b]) slot[b] = -1;
  }
};

// One result column as the batcher hands it on: int32 from the device's
// packed answers, int64 from a peer's reply.
struct ResultColumn {
  const void* at;
  int64_t width;  // bytes an element: 4 or 8
  int64_t operator[](int64_t i) const {
    return width == 4 ? static_cast<const int32_t*>(at)[i]
                      : static_cast<const int64_t*>(at)[i];
  }
};

constexpr int32_t SHED_ALGO_TOKEN = 0;   // api/types.py Algorithm
constexpr int64_t SHED_OVER_LIMIT = 1;   // api/types.py Status

}  // namespace

extern "C" {

// screen_fields over one frame of n rows. A row is shed when its
// fingerprint holds a live slot (find), it is a token-bucket request
// that carries hits and is no replica read (`gnp`, which may be null),
// its limit and duration are the slot's, and now < the slot's
// reset_time. Writes mask[n] (1 = shed) and into `out`, nine int64
// rows of n: the answers status / limit / remaining / reset_time with
// the shed rows filled and the others zero, then for the rows NOT shed,
// in frame order, their indices and their key_hash (the uint64's bits)
// / hits / limit / duration; their algo and gnp go to r_algo and r_gnp.
// Returns the rows shed, and through `eligible` the rows that were
// token-bucket, hit-carrying and no replica read.
int64_t guber_shed_screen(
    const uint64_t* kh, const int64_t* hits, const int64_t* limit,
    const int64_t* duration, const int32_t* algo, const uint8_t* gnp,
    int64_t n, int64_t now,
    const uint64_t* ix_fp, const int64_t* ix_slot, int64_t m,
    const uint64_t* ov_fp, const int64_t* ov_slot, int64_t k,
    const uint64_t* fp, const int64_t* lim, const int64_t* dur,
    const int64_t* reset,
    uint8_t* mask, int64_t* out, int32_t* r_algo, uint8_t* r_gnp,
    int64_t* eligible) {
  const ShedIndex index{ix_fp, ix_slot, m, ov_fp, ov_slot, k, fp};
  int64_t* const status = out;
  int64_t* const limit_o = out + n;
  int64_t* const remaining = out + 2 * n;
  int64_t* const reset_o = out + 3 * n;
  int64_t* const keep = out + 4 * n;
  int64_t* const r_kh = out + 5 * n;
  int64_t* const r_hits = out + 6 * n;
  int64_t* const r_limit = out + 7 * n;
  int64_t* const r_duration = out + 8 * n;
  int64_t shed = 0, asked = 0, r = 0;
  int64_t slots[ShedIndex::BLOCK];
  for (int64_t i = 0; i < n; ++i) {
    const int64_t b = i % ShedIndex::BLOCK;
    if (b == 0)
      index.find(kh + i, std::min(n - i, ShedIndex::BLOCK), slots);
    const int64_t slot = slots[b];
    const bool can = algo[i] == SHED_ALGO_TOKEN && hits[i] > 0 &&
                     !(gnp != nullptr && gnp[i]);
    asked += can;
    const bool hit = can && slot >= 0 && lim[slot] == limit[i] &&
                     dur[slot] == duration[i] && now < reset[slot];
    mask[i] = hit;
    remaining[i] = 0;
    if (hit) {
      ++shed;
      status[i] = SHED_OVER_LIMIT;
      limit_o[i] = limit[i];
      reset_o[i] = reset[slot];
      continue;
    }
    status[i] = limit_o[i] = reset_o[i] = 0;
    keep[r] = i;
    std::memcpy(&r_kh[r], &kh[i], 8);
    r_hits[r] = hits[i];
    r_limit[r] = limit[i];
    r_duration[r] = duration[i];
    r_algo[r] = algo[i];
    if (gnp != nullptr) r_gnp[r] = gnp[i];
    ++r;
  }
  *eligible = asked;
  return shed;
}

// observe_fields over the n rows the batcher resolved: which of them the
// Python walk (_observe_one) has to visit. Every row whose fingerprint
// holds a LIVE slot (find, and a reset_time that is not 0) - the
// correctness rows: confirm, drop, leaky pop - and, after them, the
// frozen verdicts (OVER_LIMIT with nothing remaining) of fingerprints
// that hold none, `cap` of them at most; row order within each. Of
// those, a row that is no token-bucket request (`algo`, which may be
// null: all token) can only drop an entry, so it is walked only where
// the call also holds a frozen token-bucket row of its fingerprint,
// which the walk may have stored by then: a hot leaky key that is over
// limit is a fifth of a frame's rows and none of the cache's business. The count is returned and the
// indices written to walk[n]. With m and k both 0 (an empty cache)
// nothing is cached. Where `keep` is not null the four result columns
// are also stitched into the frame's answer columns of n_full rows:
// full_c[keep[i]] = result_c[i]; an index outside them returns -1 with
// nothing written.
int64_t guber_shed_observe(
    const uint64_t* kh, const int32_t* algo, int64_t n,
    const void* r_status, int64_t w_status,
    const void* r_limit, int64_t w_limit,
    const void* r_remaining, int64_t w_remaining,
    const void* r_reset, int64_t w_reset,
    const uint64_t* ix_fp, const int64_t* ix_slot, int64_t m,
    const uint64_t* ov_fp, const int64_t* ov_slot, int64_t k,
    const uint64_t* fp, const int64_t* reset, int64_t cap,
    int64_t* walk,
    const int64_t* keep, int64_t n_full, int64_t* full_status,
    int64_t* full_limit, int64_t* full_remaining, int64_t* full_reset) {
  if (keep != nullptr)
    for (int64_t i = 0; i < n; ++i)
      if (keep[i] < 0 || keep[i] >= n_full) return -1;
  const ShedIndex index{ix_fp, ix_slot, m, ov_fp, ov_slot, k, fp};
  const ResultColumn status{r_status, w_status};
  const ResultColumn limit{r_limit, w_limit};
  const ResultColumn remaining{r_remaining, w_remaining};
  const ResultColumn reset_r{r_reset, w_reset};
  // the cached rows go to walk as they are met; the frozen rows of
  // uncached fingerprints wait in `fresh`, the token-bucket ones'
  // fingerprints in `stored`, for the rule above
  int64_t count = 0;
  std::vector<int64_t> fresh;
  std::vector<uint64_t> stored;
  int64_t slots[ShedIndex::BLOCK];
  for (int64_t i = 0; i < n; ++i) {
    const int64_t b = i % ShedIndex::BLOCK;
    if (b == 0) {
      const int64_t rows = std::min(n - i, ShedIndex::BLOCK);
      if (m + k > 0)
        index.find(kh + i, rows, slots);
      else
        std::fill(slots, slots + rows, -1);
    }
    if (slots[b] >= 0 && reset[slots[b]] != 0) {
      walk[count++] = i;
    } else if (status[i] == SHED_OVER_LIMIT && remaining[i] == 0) {
      fresh.push_back(i);
      if (algo == nullptr || algo[i] == SHED_ALGO_TOKEN)
        stored.push_back(kh[i]);
    }
  }
  std::sort(stored.begin(), stored.end());
  int64_t ins = 0;
  for (const int64_t i : fresh) {
    if (ins == cap) break;
    if (algo == nullptr || algo[i] == SHED_ALGO_TOKEN ||
        std::binary_search(stored.begin(), stored.end(), kh[i])) {
      walk[count++] = i;
      ++ins;
    }
  }
  if (keep != nullptr)
    for (int64_t i = 0; i < n; ++i) {
      const int64_t at = keep[i];
      full_status[at] = status[i];
      full_limit[at] = limit[i];
      full_remaining[at] = remaining[i];
      full_reset[at] = reset_r[i];
    }
  return count;
}

}  // extern "C"
