// Native series builder: group flow rows by an integer key tuple into
// padded per-series time arrays — the host tensorize step of the TAD
// job (theia_tpu/analytics/series.py). Replaces two numpy lexsorts
// (group_reduce + _pack_and_pad) with one hash-group pass over the
// columns where they lie and one pass that writes each point straight
// into the padded tensors; semantics are bit-identical to the numpy
// path:
//
//   * duplicate (key, time) rows reduce with op (0 = max, 1 = sum) —
//     the reference job's max(throughput)/sum(throughput) stage
//     (plugins/anomaly-detection/anomaly_detection.py:507-614);
//   * series are emitted in lexicographic key order (keys compared as
//     int64 values), points in time order, padded to the longest
//     series with a validity mask.
//
// How a series is written follows from how its times arrived, series
// by series, with no argument that chooses:
//   cursor  times never stepped back (a connection's rows): each row
//           is written as sb_fill meets it, a repeated time reduces
//           into the cell before it;
//   cells   times stepped back (a pod's rows: one connection's seconds
//           after another's) and the series' span of whole seconds
//           [lo, hi] costs no more memory than a copy of its rows
//           would: every row reduces into cell t - lo of hi - lo + 1
//           cells in one pass, and the cells are read out in order.
//           Nothing is gathered, sorted or merged;
//   sorted  times stepped back over a span far wider than the rows
//           (sparse samples, a record years off): the rows are
//           gathered, sorted and merged.
//
// C API (ctypes; same .so as flowblock/groupsum):
//   sb_new(k, op)              handle for series keyed by k columns
//   sb_add(h, cols, widths, strides, mask, n)
//       n rows of k + 2 columns: the k key columns, the time column,
//       the value column. A column is a pointer, its element size in
//       bytes (4 = int32, 8 = int64) and its byte stride (0 = one
//       constant cell); no row-major staging, no widened or masked
//       copy. mask: n bytes, 0 = row filtered out; null = every row.
//       The columns must stay alive until sb_fill. May be called more
//       than once (the pod mode's two sides).
//   sb_finish(h, &S, &T, ways)
//       number of series, longest series, and ways[3]: the series
//       written by the cursor, from cells, sorted
//   sb_fill(h, out_keys, out_values, value_width, out_times, out_mask)
//       out_keys [S,k] int64; out_values [S,T] float32 (4) or float64
//       (8); out_times [S,T] int64; out_mask [S,T] bytes. Caller-
//       allocated, need not be zeroed.
//   sb_free(h)

#include <algorithm>
#include <cstdint>
#include <cstring>
#include <utility>
#include <vector>

namespace {

struct Column {
  const char* base;
  int64_t stride;
  int32_t width;
};

inline int64_t cell(const Column& c, int64_t r) {
  const char* p = c.base + r * c.stride;
  if (c.width == 8) {
    int64_t x;
    memcpy(&x, p, sizeof x);
    return x;
  }
  int32_t x;  // width == 4
  memcpy(&x, p, sizeof x);
  return x;
}

inline uint64_t mix(uint64_t x) {
  x *= 0xff51afd7ed558ccdull;  // splitmix-style scramble per word
  x ^= x >> 33;
  return x;
}

inline int64_t reduce(int32_t op, int64_t a, int64_t b) {
  if (op == 0) return std::max(a, b);
  // wraps like numpy's int64 sum
  return static_cast<int64_t>(static_cast<uint64_t>(a) +
                              static_cast<uint64_t>(b));
}

using Point = std::pair<int64_t, int64_t>;  // (time, value)

// How a group's times have arrived so far.
enum Arrival : uint8_t { kIncreasing = 0, kTies = 1, kUnsorted = 2 };

// How a group is written (sb_finish decides; the header has the rule).
enum Way : uint8_t { kCursor = 0, kCells = 1, kSorted = 2 };

struct Group {
  int64_t rows = 0;    // rows of the group
  int64_t points = 0;  // distinct times, exact unless kUnsorted
  int64_t last = 0;    // newest time seen
  int64_t lo = 0;      // smallest and
  int64_t hi = 0;      // largest time seen
  int64_t at = 0;      // kCells: first cell; kSorted: index into loose
  uint8_t arrival = kIncreasing;
  uint8_t way = kCursor;
};

// A kUnsorted group takes cells when they cost no more memory than
// the gather they replace: 9 B a cell (an int64 sum and a seen byte)
// against 16 B a row (a (time, value) pair). Dense seconds pass at any
// length (a pod's 864 or 43,200 seconds under several connections'
// rows); a span far over its rows keeps the sort.
inline bool takes_cells(const Group& g) {
  const uint64_t steps =  // hi - lo, exact however far apart
      static_cast<uint64_t>(g.hi) - static_cast<uint64_t>(g.lo);
  return steps < static_cast<uint64_t>(g.rows) * 16 / 9;
}

// One sb_add: where its rows' points lie and each row's group.
struct Part {
  Column times, values;
  std::vector<int32_t> gid;  // -1 = filtered out
};

struct Builder {
  int32_t k = 0, op = 0;
  std::vector<int64_t> keys;     // a group's key: k words, first row's
  std::vector<uint64_t> hashes;  // and its hash
  std::vector<Group> groups;
  std::vector<int32_t> slots;  // open addressing: group id or -1
  std::vector<Part> parts;
  // sb_finish
  int64_t T = 0;
  std::vector<int32_t> order;            // output row -> group
  std::vector<int64_t> begin;            // group -> output row * T
  std::vector<std::vector<Point>> loose;  // kSorted: sorted, merged
  std::vector<int64_t> cells;             // kCells: a cell's reduction
  std::vector<uint8_t> seen;              // and whether a row met it
  int64_t ways[3] = {0, 0, 0};            // groups by Way

  int32_t group_of(const int64_t* row, uint64_t hv) {
    size_t s = hv & (slots.size() - 1);
    for (;;) {
      const int32_t g = slots[s];
      if (g < 0) break;
      if (hashes[g] == hv &&
          !memcmp(keys.data() + static_cast<size_t>(g) * k, row,
                  static_cast<size_t>(k) * sizeof(int64_t)))
        return g;
      s = (s + 1) & (slots.size() - 1);
    }
    const int32_t g = static_cast<int32_t>(groups.size());
    slots[s] = g;
    keys.insert(keys.end(), row, row + k);
    hashes.push_back(hv);
    groups.emplace_back();
    if (groups.size() * 2 > slots.size()) grow();
    return g;
  }

  // The table follows the number of groups, not of rows: 80 series of
  // 43,200 points probe 1,024 slots, not 8 M.
  void grow() {
    slots.assign(slots.size() * 2, -1);
    for (size_t g = 0; g < hashes.size(); ++g) {
      size_t s = hashes[g] & (slots.size() - 1);
      while (slots[s] >= 0) s = (s + 1) & (slots.size() - 1);
      slots[s] = static_cast<int32_t>(g);
    }
  }
};

template <typename V>
void fill(const Builder& b, V* out_values, int64_t* out_times,
          uint8_t* out_mask) {
  const int64_t T = b.T;
  // A group whose times arrived non-decreasing is written as its rows
  // are met: a new time takes the next cell, a repeated one reduces
  // into the cell before it (in int64, converted after).
  struct Cursor {
    int64_t first, next, last, acc;
  };
  std::vector<Cursor> cur(b.groups.size());
  for (size_t g = 0; g < cur.size(); ++g) {
    cur[g].first = b.begin[g];
    cur[g].next = b.groups[g].way == kCursor ? b.begin[g] : -1;
  }
  // the rows are walked only if some group is written from them
  if (b.ways[kCursor]) {
    for (const Part& p : b.parts) {
      const int64_t n = static_cast<int64_t>(p.gid.size());
      for (int64_t r = 0; r < n; ++r) {
        const int32_t g = p.gid[r];
        if (g < 0) continue;
        Cursor& c = cur[g];
        if (c.next < 0) continue;
        const int64_t t = cell(p.times, r), v = cell(p.values, r);
        if (c.next > c.first && c.last == t) {
          c.acc = reduce(b.op, c.acc, v);
          out_values[c.next - 1] =
              static_cast<V>(static_cast<double>(c.acc));
        } else {
          c.last = t;
          c.acc = v;
          out_times[c.next] = t;
          out_values[c.next] = static_cast<V>(static_cast<double>(v));
          out_mask[c.next] = 1;
          ++c.next;
        }
      }
    }
  }
  for (size_t g = 0; g < cur.size(); ++g) {
    const Group& grp = b.groups[g];
    int64_t at = cur[g].next;
    if (grp.way == kCells) {
      // the cells in order are the points in time order
      at = cur[g].first;
      const int64_t* acc = b.cells.data() + grp.at;
      const uint8_t* seen = b.seen.data() + grp.at;
      const int64_t span = grp.hi - grp.lo + 1;  // takes_cells bounds it
      for (int64_t i = 0; i < span; ++i) {
        if (!seen[i]) continue;
        out_times[at] = grp.lo + i;
        out_values[at] = static_cast<V>(static_cast<double>(acc[i]));
        out_mask[at] = 1;
        ++at;
      }
    } else if (grp.way == kSorted) {
      at = cur[g].first;
      for (const Point& pt : b.loose[grp.at]) {
        out_times[at] = pt.first;
        out_values[at] = static_cast<V>(static_cast<double>(pt.second));
        out_mask[at] = 1;
        ++at;
      }
    }
    // the padding: only what a series leaves of its row
    const size_t pad = static_cast<size_t>(cur[g].first + T - at);
    std::fill_n(out_values + at, pad, V(0));
    std::fill_n(out_times + at, pad, int64_t(0));
    memset(out_mask + at, 0, pad);
  }
}

}  // namespace

extern "C" {

void* sb_new(int32_t k, int32_t op) {
  auto* b = new Builder();
  b->k = k;
  b->op = op;
  b->slots.assign(1024, -1);
  return b;
}

void sb_add(void* h, const void** cols, const int32_t* widths,
            const int64_t* strides, const uint8_t* mask, int64_t n) {
  auto* b = static_cast<Builder*>(h);
  const int32_t k = b->k;
  std::vector<Column> key(k + 2);
  for (int32_t i = 0; i < k + 2; ++i)
    key[i] = {static_cast<const char*>(cols[i]), strides[i], widths[i]};
  b->parts.emplace_back();
  Part& part = b->parts.back();
  part.times = key[k];
  part.values = key[k + 1];
  part.gid.resize(n);
  std::vector<int64_t> row(k);
  for (int64_t r = 0; r < n; ++r) {
    if (mask && !mask[r]) {
      part.gid[r] = -1;
      continue;
    }
    uint64_t hv = 1469598103934665603ull;  // FNV offset basis
    for (int32_t i = 0; i < k; ++i) {
      row[i] = cell(key[i], r);
      hv = (hv ^ mix(static_cast<uint64_t>(row[i]))) * 1099511628211ull;
    }
    const int32_t g = b->group_of(row.data(), hv);
    part.gid[r] = g;
    Group& grp = b->groups[g];
    const int64_t t = cell(part.times, r);
    if (!grp.rows) {
      grp.lo = grp.hi = t;
      ++grp.points;
    } else if (t > grp.last) {
      // only a later time can pass hi, only an earlier one lo
      grp.hi = std::max(grp.hi, t);
      ++grp.points;
    } else {
      grp.lo = std::min(grp.lo, t);
      grp.arrival = std::max<uint8_t>(grp.arrival,
                                      t == grp.last ? kTies : kUnsorted);
    }
    grp.last = t;
    ++grp.rows;
  }
}

void sb_finish(void* h, int64_t* S, int64_t* T, int64_t* ways) {
  auto* b = static_cast<Builder*>(h);
  const int32_t k = b->k;
  const size_t G = b->groups.size();

  // A group whose times arrived out of order is summed into cells, or
  // gathered, sorted and merged (its rows are counted, so its vector
  // never regrows): takes_cells has the rule.
  int64_t n_cells = 0;
  for (Group& grp : b->groups) {
    if (grp.arrival == kUnsorted && takes_cells(grp)) {
      grp.way = kCells;
      grp.at = n_cells;
      n_cells += grp.hi - grp.lo + 1;
    } else if (grp.arrival == kUnsorted) {
      grp.way = kSorted;
      grp.at = static_cast<int64_t>(b->loose.size());
      b->loose.emplace_back();
      b->loose.back().reserve(grp.rows);
    }
    ++b->ways[grp.way];
  }
  if (b->ways[kCells] || b->ways[kSorted]) {
    // A cell starts as the reduction's identity, so every touch is a
    // reduce: max and the wrapping sum are order-free, and the result
    // is the sort's bit for bit.
    b->cells.assign(n_cells, b->op == 0 ? INT64_MIN : 0);
    b->seen.assign(n_cells, 0);
    for (const Part& p : b->parts) {
      const int64_t n = static_cast<int64_t>(p.gid.size());
      for (int64_t r = 0; r < n; ++r) {
        const int32_t g = p.gid[r];
        if (g < 0 || b->groups[g].way == kCursor) continue;
        const Group& grp = b->groups[g];
        const int64_t t = cell(p.times, r), v = cell(p.values, r);
        if (grp.way == kCells) {
          // t - lo is exact however far from 0 the span lies
          const uint64_t c = static_cast<uint64_t>(grp.at) +
                             (static_cast<uint64_t>(t) -
                              static_cast<uint64_t>(grp.lo));
          b->cells[c] = reduce(b->op, b->cells[c], v);
          b->seen[c] = 1;
        } else {
          b->loose[grp.at].emplace_back(t, v);
        }
      }
    }
    for (Group& grp : b->groups) {
      if (grp.way == kCells) {
        const auto s = b->seen.begin() + grp.at;
        grp.points = std::count(s, s + (grp.hi - grp.lo + 1), 1);
      } else if (grp.way == kSorted) {
        auto& pts = b->loose[grp.at];
        std::sort(pts.begin(), pts.end(),
                  [](const Point& x, const Point& y) {
                    return x.first < y.first;
                  });
        size_t w = 0;
        for (size_t i = 0; i < pts.size(); ++i) {
          if (w && pts[w - 1].first == pts[i].first)
            pts[w - 1].second =
                reduce(b->op, pts[w - 1].second, pts[i].second);
          else
            pts[w++] = pts[i];
        }
        pts.resize(w);
        grp.points = static_cast<int64_t>(w);
      }
    }
  }

  // Emit groups in lexicographic key order (np.lexsort parity).
  b->order.resize(G);
  for (size_t g = 0; g < G; ++g) b->order[g] = static_cast<int32_t>(g);
  const int64_t* keys = b->keys.data();
  std::sort(b->order.begin(), b->order.end(), [&](int32_t a, int32_t c) {
    const int64_t* ra = keys + static_cast<size_t>(a) * k;
    const int64_t* rc = keys + static_cast<size_t>(c) * k;
    for (int32_t i = 0; i < k; ++i)
      if (ra[i] != rc[i]) return ra[i] < rc[i];
    return false;
  });

  b->T = 0;
  for (const Group& grp : b->groups) b->T = std::max(b->T, grp.points);
  b->begin.resize(G);
  for (size_t s = 0; s < G; ++s)
    b->begin[b->order[s]] = static_cast<int64_t>(s) * b->T;
  *S = static_cast<int64_t>(G);
  *T = b->T;
  std::copy(b->ways, b->ways + 3, ways);
}

void sb_fill(void* h, int64_t* out_keys, void* out_values,
             int32_t value_width, int64_t* out_times,
             uint8_t* out_mask) {
  const auto* b = static_cast<const Builder*>(h);
  const int32_t k = b->k;
  for (size_t s = 0; s < b->order.size(); ++s)
    memcpy(out_keys + s * k,
           b->keys.data() + static_cast<size_t>(b->order[s]) * k,
           static_cast<size_t>(k) * sizeof(int64_t));
  if (value_width == 4)
    fill(*b, static_cast<float*>(out_values), out_times, out_mask);
  else
    fill(*b, static_cast<double*>(out_values), out_times, out_mask);
}

void sb_free(void* h) { delete static_cast<Builder*>(h); }

}  // extern "C"
