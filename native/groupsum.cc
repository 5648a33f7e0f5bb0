// Native GROUP BY ... SUM for the materialized views: the insert path's
// per-block grouping and the read path's exact re-group.
//
// Plays the role of ClickHouse's SummingMergeTree: the per-insert-block
// aggregation (the three MVs at build/charts/theia/provisioning/
// datasources/create_table.sh:92-351: group an insert block by 9-20
// integer key columns and sum 6-8 metric columns) and the collapse of
// equal keys across parts that a read sees. The numpy path needs a
// 15-20-key lexsort plus several full-matrix gathers; both entry points
// here are one hash-grouping pass that compares the FULL key on every
// hash match (a collision can neither join nor split a group) — no sort
// at all. Groups come in order of first appearance: a view's row order
// is unspecified (a SELECT without ORDER BY), deterministic for given
// parts in a given order.
//
// C API (ctypes; same .so as flowblock/seriesbuild):
//   gs_build(key_cols, key_widths, n, k, val_cols, val_widths, m)
//       the insert path. key_cols/val_cols: arrays of column pointers
//       (column-major input, no row-major staging copy in Python);
//       widths are the per-column element sizes in bytes (4 = int32,
//       8 = int64). Returns a handle.
//   gs_build_rows(key_parts, val_parts, part_rows, parts, k, m)
//       the read path: `parts` view parts as they lie, each a row-major
//       [part_rows[p], k] int64 key matrix and a row-major
//       [part_rows[p], m] int64 value matrix, grouped as one table with
//       no concatenation; fewer than 2^31 rows in all. The handle keeps
//       the pointers: the parts stay alive until gs_free.
//   gs_dims(h, &g)            number of groups
//   gs_fill(h, out_keys, out_values)
//       out_keys [g,k] int64 row-major, out_values [g,m] int64 (sums
//       wrap as numpy's do).
//   gs_free(h)

#include <cstdint>
#include <cstring>
#include <vector>

namespace {

struct GroupSum {
  int64_t g = 0;
  int32_t k = 0, m = 0;
  std::vector<int64_t> keys;   // g*k, group-representative keys
  std::vector<int64_t> sums;   // g*m
  // gs_build_rows only. It stages nothing: gs_fill writes each output
  // cell once, straight from the caller's parts.
  bool rows = false;
  std::vector<const int64_t*> reps;       // g: a group's first row's keys
  std::vector<uint32_t> gid;              // a group id a row, parts in order
  std::vector<const int64_t*> val_parts;
  std::vector<int64_t> part_rows;
};

inline int64_t read_cell(const void* col, int32_t width, int64_t r) {
  if (width == 8)
    return static_cast<const int64_t*>(col)[r];
  return static_cast<const int32_t*>(col)[r];  // width == 4
}

inline uint64_t mix(uint64_t x) {
  x *= 0xff51afd7ed558ccdull;
  x ^= x >> 33;
  return x;
}

// One row-major key row to 64 bits: a multiply-xor chain over its
// cells (one sequential read of the matrix), then a finalizer that
// folds the high bits down, since the table indexes by the low ones.
inline uint64_t row_hash(const int64_t* row, int32_t k) {
  uint64_t h = 0x9e3779b97f4a7c15ull;
  for (int32_t i = 0; i < k; ++i)
    h = (h ^ static_cast<uint64_t>(row[i])) * 0xff51afd7ed558ccdull;
  h ^= h >> 32;
  h *= 0xc4ceb9fe1a85ec53ull;
  h ^= h >> 29;
  return h;
}

}  // namespace

extern "C" {

void* gs_build(const void** key_cols, const int32_t* key_widths,
               int64_t n, int32_t k,
               const void** val_cols, const int32_t* val_widths,
               int32_t m) {
  auto* gs = new GroupSum();
  gs->k = k;
  gs->m = m;
  if (n == 0) return gs;

  // Pass 1: per-row key hash, computed COLUMNWISE — sequential reads
  // of each key column and sequential writes of hash[n]. (The previous
  // version staged keys row-major first: for k≈20 the c-strided writes
  // touched a fresh cache line per cell, and that staging dominated
  // the whole group-by.) Column order is applied identically for every
  // row, so the hash equals the row-major FNV of the same cells.
  std::vector<uint64_t> hash(n, 1469598103934665603ull);
  for (int32_t c = 0; c < k; ++c) {
    const int32_t w = key_widths[c];
    uint64_t* hp = hash.data();
    if (w == 8) {
      const int64_t* src = static_cast<const int64_t*>(key_cols[c]);
      for (int64_t r = 0; r < n; ++r)
        hp[r] = (hp[r] ^ mix(static_cast<uint64_t>(src[r])))
                * 1099511628211ull;
    } else {
      const int32_t* src = static_cast<const int32_t*>(key_cols[c]);
      for (int64_t r = 0; r < n; ++r)
        hp[r] = (hp[r] ^ mix(static_cast<uint64_t>(src[r])))
                * 1099511628211ull;
    }
  }

  size_t cap = 1;
  while (cap < static_cast<size_t>(n) * 2) cap <<= 1;
  std::vector<int64_t> slot_row(cap, -1);   // representative row
  std::vector<int64_t> slot_gid(cap, -1);
  std::vector<uint64_t> slot_hash(cap, 0);

  // Pass 2: probe to a group id per row. Equality first checks the
  // full 64-bit hash, then compares cells straight from the original
  // columns (k scattered reads only on genuine hash match — nearly
  // always a real group hit).
  std::vector<int64_t> gid(n);
  // Worst case every row is its own group (true for the flows views,
  // whose keys include per-row timestamps) — preallocate so the
  // new-group path is a straight write, then shrink once at the end.
  gs->keys.resize(static_cast<size_t>(n) * k);
  for (int64_t r = 0; r < n; ++r) {
    const uint64_t hv = hash[r];
    size_t h = hv & (cap - 1);
    for (;;) {
      if (slot_row[h] < 0) {
        slot_row[h] = r;
        slot_gid[h] = gs->g;
        slot_hash[h] = hv;
        int64_t* dst = gs->keys.data() +
                       static_cast<size_t>(gs->g) * k;
        for (int32_t i = 0; i < k; ++i)
          dst[i] = read_cell(key_cols[i], key_widths[i], r);
        gid[r] = gs->g++;
        break;
      }
      if (slot_hash[h] == hv) {
        const int64_t rep = slot_row[h];
        bool eq = true;
        for (int32_t i = 0; i < k; ++i) {
          if (read_cell(key_cols[i], key_widths[i], r) !=
              read_cell(key_cols[i], key_widths[i], rep)) {
            eq = false;
            break;
          }
        }
        if (eq) {
          gid[r] = slot_gid[h];
          break;
        }
      }
      h = (h + 1) & (cap - 1);
    }
  }

  gs->keys.resize(static_cast<size_t>(gs->g) * k);

  // Pass 3: accumulate sums COLUMNWISE — each value column is read
  // sequentially; the accumulator rows are few and stay cache-hot.
  gs->sums.assign(static_cast<size_t>(gs->g) * m, 0);
  for (int32_t j = 0; j < m; ++j) {
    const int32_t w = val_widths[j];
    int64_t* sums = gs->sums.data() + j;
    if (w == 8) {
      const int64_t* src = static_cast<const int64_t*>(val_cols[j]);
      for (int64_t r = 0; r < n; ++r)
        sums[static_cast<size_t>(gid[r]) * m] += src[r];
    } else {
      const int32_t* src = static_cast<const int32_t*>(val_cols[j]);
      for (int64_t r = 0; r < n; ++r)
        sums[static_cast<size_t>(gid[r]) * m] += src[r];
    }
  }
  return gs;
}

void* gs_build_rows(const int64_t* const* key_parts,
                    const int64_t* const* val_parts,
                    const int64_t* part_rows, int32_t parts,
                    int32_t k, int32_t m) {
  auto* gs = new GroupSum();
  gs->k = k;
  gs->m = m;
  gs->rows = true;
  gs->val_parts.assign(val_parts, val_parts + parts);
  gs->part_rows.assign(part_rows, part_rows + parts);
  int64_t n = 0;
  for (int32_t p = 0; p < parts; ++p) n += part_rows[p];
  if (n == 0) return gs;

  // Open addressing over one word a slot: the hash's high half as a
  // tag beside the group id + 1 (0 = empty), so a probe touches one
  // cache line and reads a key only where 32 + log2(cap) bits agree.
  size_t cap = 1;
  while (cap < static_cast<size_t>(n) * 2) cap <<= 1;
  const size_t mask = cap - 1;
  const uint64_t kTag = 0xffffffff00000000ull;
  std::vector<uint64_t> table(cap, 0);
  gs->gid.resize(n);
  // Worst case every row is its own group (the flows views' keys hold
  // per-row timestamps): reserve so a new group is a straight write.
  gs->reps.reserve(n);
  const size_t key_bytes = static_cast<size_t>(k) * sizeof(int64_t);

  // The table is far larger than the caches, so the rows go in blocks:
  // hash a block and prefetch its slots, then probe it.
  constexpr int64_t kBlock = 16;
  uint64_t hv[kBlock];
  uint32_t* gid = gs->gid.data();
  uint32_t g = 0;
  for (int32_t p = 0; p < parts; ++p) {
    const int64_t* keys = key_parts[p];
    const int64_t rows = part_rows[p];
    for (int64_t r0 = 0; r0 < rows; r0 += kBlock) {
      const int64_t b = rows - r0 < kBlock ? rows - r0 : kBlock;
      for (int64_t i = 0; i < b; ++i) {
        hv[i] = row_hash(keys + (r0 + i) * k, k);
        __builtin_prefetch(&table[hv[i] & mask]);
      }
      for (int64_t i = 0; i < b; ++i) {
        const int64_t* row = keys + (r0 + i) * k;
        const uint64_t tag = hv[i] & kTag;
        size_t h = hv[i] & mask;
        for (;;) {
          const uint64_t slot = table[h];
          if (slot == 0) {
            table[h] = tag | (g + 1);
            gs->reps.push_back(row);
            *gid++ = g++;
            break;
          }
          if ((slot & kTag) == tag) {
            const uint32_t cand = static_cast<uint32_t>(slot) - 1;
            if (memcmp(gs->reps[cand], row, key_bytes) == 0) {
              *gid++ = cand;
              break;
            }
          }
          h = (h + 1) & mask;
        }
      }
    }
  }
  gs->g = g;
  return gs;
}

void gs_dims(void* h, int64_t* g) {
  *g = static_cast<GroupSum*>(h)->g;
}

void gs_fill(void* h, int64_t* out_keys, int64_t* out_values) {
  auto* gs = static_cast<GroupSum*>(h);
  if (gs->rows) {
    const int32_t k = gs->k, m = gs->m;
    // Keys: representatives that lie one after another (every row of
    // a part that shares no key with an earlier row) go as one copy.
    const size_t g = static_cast<size_t>(gs->g);
    for (size_t i = 0; i < g;) {
      size_t j = i + 1;
      while (j < g && gs->reps[j] == gs->reps[j - 1] + k) ++j;
      memcpy(out_keys + i * k, gs->reps[i],
             (j - i) * k * sizeof(int64_t));
      i = j;
    }
    // Sums: group ids were handed out in order of first appearance, so
    // a row opens its group exactly when its id is the next unseen one
    // — it is copied, and the output needs no zeroing. Unsigned adds:
    // numpy's wrap-around without signed overflow.
    const uint32_t* gid = gs->gid.data();
    uint32_t seen = 0;
    for (size_t p = 0; p < gs->val_parts.size(); ++p) {
      const int64_t* src = gs->val_parts[p];
      for (int64_t r = 0; r < gs->part_rows[p]; ++r, src += m) {
        const uint32_t id = *gid++;
        int64_t* dst = out_values + static_cast<size_t>(id) * m;
        if (id == seen) {
          ++seen;
          for (int32_t j = 0; j < m; ++j) dst[j] = src[j];
        } else {
          for (int32_t j = 0; j < m; ++j)
            dst[j] = static_cast<int64_t>(static_cast<uint64_t>(dst[j]) +
                                          static_cast<uint64_t>(src[j]));
        }
      }
    }
    return;
  }
  memcpy(out_keys, gs->keys.data(),
         gs->keys.size() * sizeof(int64_t));
  memcpy(out_values, gs->sums.data(),
         gs->sums.size() * sizeof(int64_t));
}

void gs_free(void* h) { delete static_cast<GroupSum*>(h); }

}  // extern "C"
