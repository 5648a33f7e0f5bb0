// flowblock — native columnar ingest for theia_tpu.
//
// Plays the role of the reference's native ingest tier (ClickHouse's C++
// TabSeparated/native-protocol parsers receiving FlowAggregator inserts;
// schema contract build/charts/theia/provisioning/datasources/
// create_table.sh:31-84): decode TSV flow records straight into
// fixed-width columnar buffers with per-column dictionary encoding, so
// Python never touches row objects and the arrays are ready for
// jax.device_put.
//
// C API (ctypes-friendly, no C++ types across the boundary):
//   fb_new(n_cols, kinds)        kinds[i]: 0 = int64, 1 = float64,
//                                2 = dictionary-encoded string
//   fb_seed(h, col, s, len)      append an existing dictionary entry
//                                (call in code order to mirror Python)
//   fb_decode(h, buf, nbytes, max_rows, out_ints, out_codes)
//                                parse rows; column-major outputs:
//                                out_ints [n_numeric][max_rows],
//                                out_codes [n_string][max_rows];
//                                returns rows decoded, or -1-row_index
//                                on a malformed row
//   fb_decode_block2(h, buf, nbytes, max_rows, widths, out_cols)
//                                decode one binary columnar block (the
//                                "TFB2" format below — the analogue of
//                                ClickHouse's column-major native
//                                protocol): header, per-string-column
//                                dictionary delta, then raw column
//                                planes bulk-copied into the outputs.
//                                Returns rows, or a negative error code
//                                (-1 malformed, -2 dictionary desync,
//                                -3 outputs too small)
//   fb_dict_size(h, col)         current dictionary size
//   fb_dict_get(h, col, idx, &len) read one dictionary entry (for
//                                syncing codes minted here back into
//                                the Python StringDictionary)
//   fb_free(h)
//
// Build: g++ -O3 -shared -fPIC (driven by theia_tpu/utils/native.py).

#include <cstdint>
#include <cstdlib>
#include <cstring>
#include <deque>
#include <string>
#include <unordered_map>
#include <vector>

namespace {

enum Kind : int32_t { kInt = 0, kFloat = 1, kString = 2 };

struct Dict {
  // Stored strings own the bytes; the map's string_views point into
  // them. std::deque never reallocates existing elements.
  std::deque<std::string> strings;
  std::unordered_map<std::string_view, int32_t> to_code;

  Dict() { add("", 0); }

  void add(std::string_view s, int32_t code) {
    strings.emplace_back(s);
    to_code.emplace(std::string_view(strings.back()), code);
  }

  int32_t encode(std::string_view s) {
    auto it = to_code.find(s);
    if (it != to_code.end()) return it->second;
    int32_t code = static_cast<int32_t>(strings.size());
    add(s, code);
    return code;
  }
};

struct Decoder {
  std::vector<int32_t> kinds;
  // per-column slot within its kind group (numeric vs string)
  std::vector<int32_t> slot;
  int32_t n_numeric = 0;
  int32_t n_string = 0;
  std::vector<Dict> dicts;  // indexed by string slot
};

inline bool parse_int(const char* b, const char* e, int64_t* out) {
  if (b == e) { *out = 0; return true; }
  bool neg = false;
  if (*b == '-') { neg = true; ++b; }
  int64_t v = 0;
  for (; b != e; ++b) {
    if (*b < '0' || *b > '9') return false;
    v = v * 10 + (*b - '0');
  }
  *out = neg ? -v : v;
  return true;
}

// Walk + validate the dictionary-delta section shared by both block
// formats; advances *pp past the deltas without mutating any
// dictionary. Delta entries must be novel (not already in the
// dictionary, and not repeated within the delta) — a duplicate would
// grow `strings` without a matching to_code entry and desync the code
// sequence for good. Fills new_sizes (indexed by string slot) with the
// post-delta dictionary sizes. Returns 0, or -1 malformed / -2 desync /
// -5 duplicate entry.
int32_t validate_deltas(const Decoder* d, const char** pp,
                        const char* end,
                        std::vector<int32_t>* new_sizes) {
  const char* p = *pp;
  auto need = [&](int64_t n) { return end - p >= n; };
  const int32_t n_cols = static_cast<int32_t>(d->kinds.size());
  for (int32_t c = 0; c < n_cols; ++c) {
    if (d->kinds[c] != kString) continue;
    const Dict& dict = d->dicts[d->slot[c]];
    int32_t base, count;
    if (!need(8)) return -1;
    memcpy(&base, p, 4); p += 4;
    memcpy(&count, p, 4); p += 4;
    if (count < 0) return -1;
    if (base != static_cast<int32_t>(dict.strings.size())) return -2;
    std::unordered_map<std::string_view, int32_t> fresh;
    for (int32_t i = 0; i < count; ++i) {
      int32_t len;
      if (!need(4)) return -1;
      memcpy(&len, p, 4); p += 4;
      if (len < 0 || !need(len)) return -1;
      std::string_view sv(p, static_cast<size_t>(len));
      if (dict.to_code.find(sv) != dict.to_code.end()) return -5;
      if (!fresh.emplace(sv, i).second) return -5;
      p += len;
    }
    (*new_sizes)[d->slot[c]] = base + count;
  }
  *pp = p;
  return 0;
}

// Append the delta entries (assumes validate_deltas passed over the
// same bytes); advances *pp past the deltas.
void commit_deltas(Decoder* d, const char** pp) {
  const char* p = *pp;
  const int32_t n_cols = static_cast<int32_t>(d->kinds.size());
  for (int32_t c = 0; c < n_cols; ++c) {
    if (d->kinds[c] != kString) continue;
    Dict& dict = d->dicts[d->slot[c]];
    int32_t base, count;
    memcpy(&base, p, 4); p += 4;
    memcpy(&count, p, 4); p += 4;
    for (int32_t i = 0; i < count; ++i) {
      int32_t len;
      memcpy(&len, p, 4); p += 4;
      dict.add(std::string_view(p, static_cast<size_t>(len)),
               base + i);
      p += len;
    }
  }
  *pp = p;
}

}  // namespace

extern "C" {

void* fb_new(int32_t n_cols, const int32_t* kinds) {
  auto* d = new Decoder();
  d->kinds.assign(kinds, kinds + n_cols);
  d->slot.resize(n_cols);
  for (int32_t i = 0; i < n_cols; ++i) {
    if (kinds[i] == kString) {
      d->slot[i] = d->n_string++;
      d->dicts.emplace_back();
    } else {
      d->slot[i] = d->n_numeric++;
    }
  }
  return d;
}

void fb_seed(void* h, int32_t col, const char* s, int64_t len) {
  auto* d = static_cast<Decoder*>(h);
  Dict& dict = d->dicts[d->slot[col]];
  std::string_view sv(s, static_cast<size_t>(len));
  if (dict.to_code.find(sv) == dict.to_code.end()) {
    dict.add(sv, static_cast<int32_t>(dict.strings.size()));
  }
}

int64_t fb_decode(void* h, const char* buf, int64_t nbytes,
                  int64_t max_rows, int64_t* out_ints,
                  int32_t* out_codes) {
  auto* d = static_cast<Decoder*>(h);
  const int32_t n_cols = static_cast<int32_t>(d->kinds.size());
  const char* p = buf;
  const char* end = buf + nbytes;
  int64_t row = 0;

  while (p < end && row < max_rows) {
    const char* line_end =
        static_cast<const char*>(memchr(p, '\n', end - p));
    if (line_end == nullptr) line_end = end;
    if (line_end == p) { ++p; continue; }  // skip blank lines

    const char* f = p;
    for (int32_t c = 0; c < n_cols; ++c) {
      const char* f_end = static_cast<const char*>(
          memchr(f, '\t', line_end - f));
      if (f_end == nullptr) f_end = line_end;
      if (c == n_cols - 1) f_end = line_end;

      const int32_t slot = d->slot[c];
      switch (d->kinds[c]) {
        case kInt: {
          int64_t v;
          if (!parse_int(f, f_end, &v)) return -1 - row;
          out_ints[slot * max_rows + row] = v;
          break;
        }
        case kFloat: {
          // stored through the int64 plane; Python reinterprets
          char tmp[64];
          size_t n = static_cast<size_t>(f_end - f);
          if (n >= sizeof(tmp)) return -1 - row;
          memcpy(tmp, f, n);
          tmp[n] = 0;
          double v = (n == 0) ? 0.0 : strtod(tmp, nullptr);
          memcpy(&out_ints[slot * max_rows + row], &v, sizeof(double));
          break;
        }
        case kString: {
          std::string_view sv(f, static_cast<size_t>(f_end - f));
          out_codes[slot * max_rows + row] =
              d->dicts[slot].encode(sv);
          break;
        }
      }
      f = (f_end < line_end) ? f_end + 1 : line_end;
    }
    ++row;
    p = (line_end < end) ? line_end + 1 : end;
  }
  return row;
}

// Binary columnar block ("TFB2", little-endian):
//   "TFB2" | n_rows:i64 | n_cols:i32
//   per string column (schema order): base:i32 | count:i32 |
//       count x (len:i32 | bytes)     -- dictionary delta; `base` must
//                                        equal the decoder's current
//                                        dictionary size (codes are a
//                                        shared, append-only sequence)
//   per column (schema order): raw plane at the column's NATIVE width
//       (widths[c] bytes per element: 1/2/4/8 for numerics, always 4
//       for string codes)
// Planes land directly in per-column output buffers (out_cols[c],
// allocated by the caller at the column's final dtype) — no widening
// on the wire and no re-narrowing pass after decode. String-code
// validation runs over the copied (aligned) output plane so the
// compiler can vectorize the min/max scan instead of per-row
// unaligned loads.
// Error codes: -1 malformed, -2 dictionary desync (delta base !=
// dictionary size), -3 outputs too small, -4 string code out of
// dictionary range, -5 delta repeats an existing or intra-delta entry.
// Dictionary state is only mutated after every check passes, so a bad
// block leaves the decoder exactly as it was (no poisoned state);
// output buffers may hold partial data on error (callers discard them
// on raise).
int64_t fb_decode_block2(void* h, const char* buf, int64_t nbytes,
                         int64_t max_rows, const int32_t* widths,
                         void** out_cols) {
  auto* d = static_cast<Decoder*>(h);
  const char* p = buf;
  const char* end = buf + nbytes;
  auto need = [&](int64_t n) { return end - p >= n; };

  if (!need(4) || memcmp(p, "TFB2", 4) != 0) return -1;
  p += 4;
  int64_t n_rows;
  int32_t n_cols;
  if (!need(12)) return -1;
  memcpy(&n_rows, p, 8); p += 8;
  memcpy(&n_cols, p, 4); p += 4;
  if (n_rows < 0 || n_cols != static_cast<int32_t>(d->kinds.size()))
    return -1;
  if (n_rows > max_rows) return -3;

  // -- dictionary-delta validation pass (no mutation).
  const char* delta_start = p;
  std::vector<int32_t> new_sizes(d->dicts.size());
  if (int32_t err = validate_deltas(d, &p, end, &new_sizes)) return err;

  // -- plane copy + code validation (dicts still untouched).
  for (int32_t c = 0; c < n_cols; ++c) {
    const int64_t plane = n_rows * widths[c];
    if (widths[c] <= 0 || !need(plane)) return -1;
    if (d->kinds[c] == kString && widths[c] != 4) return -1;
    memcpy(out_cols[c], p, static_cast<size_t>(plane));
    if (d->kinds[c] == kString) {
      const int32_t* codes = static_cast<const int32_t*>(out_cols[c]);
      int32_t lo = 0, hi = -1;
      if (n_rows > 0) { lo = codes[0]; hi = codes[0]; }
      for (int64_t r = 1; r < n_rows; ++r) {
        const int32_t v = codes[r];
        lo = v < lo ? v : lo;
        hi = v > hi ? v : hi;
      }
      if (n_rows > 0 &&
          (lo < 0 || hi >= new_sizes[d->slot[c]])) return -4;
    }
    p += plane;
  }

  // -- commit: append dictionary deltas.
  p = delta_start;
  commit_deltas(d, &p);
  return n_rows;
}

int64_t fb_dict_size(void* h, int32_t col) {
  auto* d = static_cast<Decoder*>(h);
  return static_cast<int64_t>(d->dicts[d->slot[col]].strings.size());
}

const char* fb_dict_get(void* h, int32_t col, int64_t idx,
                        int64_t* len) {
  auto* d = static_cast<Decoder*>(h);
  const std::string& s = d->dicts[d->slot[col]].strings[
      static_cast<size_t>(idx)];
  *len = static_cast<int64_t>(s.size());
  return s.data();
}

void fb_free(void* h) { delete static_cast<Decoder*>(h); }

}  // extern "C"
