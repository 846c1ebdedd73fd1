// Fast delimited-text parser — the native data-loader component.
//
// Re-design of the reference's C++ parsing stack
// (/root/reference/src/io/parser.cpp CSVParser/TSVParser +
// include/LightGBM/utils/text_reader.h + the vendored
// fast_double_parser): one OpenMP pass over an mmap-style buffer,
// line ranges split per thread, std::from_chars for float decoding.
// Exposed through plain C symbols consumed via ctypes
// (lightgbm_tpu/utils/native.py) — no pybind11 dependency.
//
// Layout contract: the caller allocates out[n_rows * n_cols] float64;
// unparseable / empty cells become NaN (the reference's missing-value
// convention for dense text loads).

#include <algorithm>
#include <cctype>
#include <charconv>
#include <cmath>
#include <cstdint>
#include <cstring>
#include <cstdlib>
#include <limits>
#include <vector>

#if defined(_OPENMP)
#include <omp.h>
#endif

extern "C" {

// Count data rows and detect the column count + delimiter.
// Returns 0 on success. delim_out: ',', '\t' or ' '.
int ltpu_sniff(const char* buf, int64_t len, int skip_header,
               int64_t* rows_out, int64_t* cols_out, char* delim_out) {
  int64_t pos = 0;
  if (skip_header) {
    while (pos < len && buf[pos] != '\n') pos++;
    if (pos < len) pos++;
  }
  // find first non-empty line for delimiter + column sniffing
  int64_t line_start = pos;
  while (line_start < len) {
    int64_t line_end = line_start;
    while (line_end < len && buf[line_end] != '\n') line_end++;
    if (line_end > line_start + 1) break;
    line_start = line_end + 1;
  }
  if (line_start >= len) return 1;
  int64_t line_end = line_start;
  char delim = ' ';
  while (line_end < len && buf[line_end] != '\n') {
    if (buf[line_end] == '\t') delim = '\t';
    else if (buf[line_end] == ',' && delim != '\t') delim = ',';
    line_end++;
  }
  int64_t cols = 1;
  for (int64_t i = line_start; i < line_end; ++i) {
    if (delim == ' ' ? (buf[i] == ' ' || buf[i] == '\t')
                     : buf[i] == delim) {
      cols++;
      if (delim == ' ')  // collapse runs of whitespace
        while (i + 1 < line_end &&
               (buf[i + 1] == ' ' || buf[i + 1] == '\t')) i++;
    }
  }
  int64_t rows = 0;
  for (int64_t i = pos; i < len; ++i)
    if (buf[i] == '\n' && i > pos && buf[i - 1] != '\n') rows++;
  if (len > pos && buf[len - 1] != '\n') rows++;  // unterminated last line
  *rows_out = rows;
  *cols_out = cols;
  *delim_out = delim;
  return 0;
}

static inline double parse_cell(const char* s, const char* e) {
  while (s < e && (*s == ' ' || *s == '\t')) s++;
  while (e > s && (*(e - 1) == ' ' || *(e - 1) == '\r')) e--;
  if (s >= e) return std::numeric_limits<double>::quiet_NaN();
  double v;
  auto res = std::from_chars(s, e, v);
  if (res.ec != std::errc()) {
    // from_chars rejects leading '+' and inf/nan spellings; fall back
    if ((e - s) >= 3 && (s[0] == 'n' || s[0] == 'N'))
      return std::numeric_limits<double>::quiet_NaN();
    char tmp[64];
    size_t m = static_cast<size_t>(e - s);
    if (m >= sizeof(tmp)) m = sizeof(tmp) - 1;
    std::memcpy(tmp, s, m);
    tmp[m] = 0;
    char* endp = nullptr;
    v = std::strtod(tmp, &endp);
    if (endp == tmp) return std::numeric_limits<double>::quiet_NaN();
  }
  return v;
}

// Parse the whole buffer into out[rows * cols] (row-major). Rows with
// fewer cells get NaN tails; extra cells are ignored.
// Returns the number of parsed rows.
int64_t ltpu_parse_dense(const char* buf, int64_t len, int skip_header,
                         char delim, int64_t rows, int64_t cols,
                         double* out) {
  int64_t pos = 0;
  if (skip_header) {
    while (pos < len && buf[pos] != '\n') pos++;
    if (pos < len) pos++;
  }
  // collect line offsets (serial, cheap) then parse cells in parallel
  std::vector<int64_t> starts;
  starts.reserve(static_cast<size_t>(rows) + 1);
  int64_t i = pos;
  while (i < len && static_cast<int64_t>(starts.size()) < rows) {
    int64_t le = i;
    while (le < len && buf[le] != '\n') le++;
    if (le > i) starts.push_back(i);
    i = le + 1;
  }
  const int64_t n = static_cast<int64_t>(starts.size());
  const bool ws = (delim == ' ');
#if defined(_OPENMP)
#pragma omp parallel for schedule(static)
#endif
  for (int64_t r = 0; r < n; ++r) {
    int64_t s = starts[static_cast<size_t>(r)];
    int64_t e = s;
    while (e < len && buf[e] != '\n') e++;
    double* row = out + r * cols;
    int64_t c = 0;
    int64_t cs = s;
    for (int64_t k = s; k <= e && c < cols; ++k) {
      bool is_delim = (k == e) ||
          (ws ? (buf[k] == ' ' || buf[k] == '\t') : buf[k] == delim);
      if (!is_delim) continue;
      row[c++] = parse_cell(buf + cs, buf + k);
      if (ws)  // collapse whitespace runs
        while (k + 1 <= e && k + 1 < len &&
               (buf[k + 1] == ' ' || buf[k + 1] == '\t')) k++;
      cs = k + 1;
    }
    for (; c < cols; ++c)
      row[c] = std::numeric_limits<double>::quiet_NaN();
  }
  return n;
}

// Bin numerical columns of a row-major [n, F] matrix — the native
// BinMapper::ValueToBin loop (the reference bins with compiled C++ in
// dataset_loader.cpp ConstructBinMappers + bin.h ValueToBin; the numpy
// path pays ~100-160 ns/value in per-call dispatch, measured round 5,
// which at Allstate width (4228 columns) made Dataset.construct the
// wall-clock bottleneck).
//
//   X        row-major values, float32 (is_f64=0) or float64 (=1)
//   cols     [C] source column indices into X
//   bounds   concatenated per-column upper bounds (float64, ascending)
//   bnd_off  [C+1] offsets into bounds
//   nan_to   [C] bin NaN maps to (num_bins-1 for MissingType::NAN,
//            else the precomputed bin of 0.0 — identical to the numpy
//            path's where(nan -> 0.0) + searchsorted)
//   out      row-major [n, C], uint8 (out_is_u16=0) or uint16 (=1)
//   nan_cells  [C] int64 or null: the NaN cells seen in each column are
//            ADDED to it (counted where the value is tested anyway)
//
// searchsorted(side="left") == std::lower_bound; the result is clamped
// to the last bound like the numpy path.
void ltpu_bin_columns(const void* X, int is_f64, int64_t n, int64_t F,
                      const int32_t* cols, int64_t C,
                      const double* bounds, const int64_t* bnd_off,
                      const int32_t* nan_to,
                      void* out, int out_is_u16, int64_t* nan_cells) {
  const float* xf = static_cast<const float*>(X);
  const double* xd = static_cast<const double*>(X);
  uint8_t* o8 = static_cast<uint8_t*>(out);
  uint16_t* o16 = static_cast<uint16_t*>(out);
  // column blocks keep the active bounds L2-resident; row tiles keep
  // reads row-major-contiguous and give threads false-sharing-free
  // output segments
  const int64_t CB = 64, RB = 4096;
  for (int64_t c0 = 0; c0 < C; c0 += CB) {
    const int64_t c1 = (c0 + CB < C) ? c0 + CB : C;
#if defined(_OPENMP)
#pragma omp parallel for schedule(static)
#endif
    for (int64_t r0 = 0; r0 < n; r0 += RB) {
      const int64_t r1 = (r0 + RB < n) ? r0 + RB : n;
      int64_t seen_nan[CB] = {0};  // this tile's NaN cells a column
      for (int64_t r = r0; r < r1; ++r) {
        for (int64_t c = c0; c < c1; ++c) {
          const int64_t src = r * F + cols[c];
          const double v = is_f64 ? xd[src]
                                  : static_cast<double>(xf[src]);
          const double* lo = bounds + bnd_off[c];
          const int64_t nb = bnd_off[c + 1] - bnd_off[c];
          int64_t b;
          if (std::isnan(v)) {
            b = nan_to[c];
            ++seen_nan[c - c0];
          } else {
            b = std::lower_bound(lo, lo + nb, v) - lo;
            if (b >= nb) b = nb - 1;
          }
          if (out_is_u16)
            o16[r * C + c] = static_cast<uint16_t>(b);
          else
            o8[r * C + c] = static_cast<uint8_t>(b);
        }
      }
      if (nan_cells)
        for (int64_t c = c0; c < c1; ++c)
          if (seen_nan[c - c0]) {
#if defined(_OPENMP)
#pragma omp atomic
#endif
            nan_cells[c] += seen_nan[c - c0];
          }
    }
  }
}

}  // extern "C"
