// jt_ingest — native train-request parser: raw msgpack bytes -> hashed
// sparse batch, bypassing Python object churn on the ingest hot path.
//
// The reference's hot loop is C++ end to end (per-datum fv convert +
// driver update, classifier_serv.cpp:127-146); round 1's measurement put
// the TPU port's serving ceiling at the Python host path (msgpack decode
// -> Datum -> fv convert under the GIL), an order of magnitude under the
// device kernel. This parser walks the train request's msgpack
// ([name, [[label, datum], ...]]) in place, applies the converter's
// num/string rules, hashes feature names with the zlib-identical CRC-32,
// and emits padded [B, K] index/value arrays plus label byte spans — the
// exact input of ops/classifier.train_batch. Python's remaining work per
// request is label-vocab lookup and one device_put.
//
// Supported converter subset (service.py checks eligibility and falls
// back to the Python converter otherwise): num rules {num, log, str},
// num filters, string rules with {str, space, ngram} splitters,
// sample_weight {bin, tf, log_tf}, global_weight {bin, idf}, and
// combination rules (mul/add; not combinable with idf; the pairs' hashes
// come from the base features' CRC states, see CrcShift, and the rows
// before the cross product are handed back too); no string
// filters, no "weight" global weight, no plugins.
// Semantics mirror core/fv/converter.py: feature names
//   "<key>@<type>"                      (num/log)
//   "<key>$<fmt(value)>@<type>"         (num str)
//   "<key>$<term>@<type>#<sw>/<gw>"     (string rules)
// accumulate by name, then by hashed index (crc32 & mask, 0 -> 1), per
// example sorted by index — bit-identical to FeatureHasher + convert().
//
// ABI (ctypes, see jubatus_tpu/native/__init__.py):
//   void* jt_ingest_create(const char* spec)   rules, one per line:
//       "num\t<kind>\t<pattern>"
//       "str\t<splitter>\t<sample_weight>\t<global_weight>\t<type>\t<pattern>"
//       "nf\t<kind>\t<a>\t<b>\t<pattern>\t<suffix>"
//       "combo\t<mul|add>\t<key_left>\t<key_right>"
//   int jt_ingest_parse(handle, buf, len, mask, JtIngestOut*)  0 = ok
//   void jt_ingest_free_out(JtIngestOut*)       frees the arrays
//   void jt_ingest_destroy(handle)
//
// Thread-safe: parse allocates per-call buffers; handles are immutable
// after create.

#include <algorithm>
#include <charconv>
#include <chrono>
#include <cmath>
#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <locale>
#include <sstream>
#include <cstring>
#include <deque>
#include <unordered_map>
#include <string>
#include <vector>

namespace {

// ---- zlib-compatible CRC-32 (same table algorithm as jt_native.cpp) ----
struct CrcTable {
  uint32_t t[256];
  CrcTable() {
    for (uint32_t i = 0; i < 256; ++i) {
      uint32_t c = i;
      for (int k = 0; k < 8; ++k)
        c = (c & 1) ? 0xEDB88320u ^ (c >> 1) : c >> 1;
      t[i] = c;
    }
  }
};
const CrcTable kCrc;

// Locale-independent f64 parse for spec literals (create-time only).
// from_chars where the toolchain has it (GCC 11+); classic-locale
// istringstream otherwise — never plain strtod, which honors LC_NUMERIC.
inline double parse_spec_f64(const std::string& s) {
#if defined(__cpp_lib_to_chars) && __cpp_lib_to_chars >= 201611L
  double v = 0.0;
  std::from_chars(s.data(), s.data() + s.size(), v);
  return v;
#else
  std::istringstream is(s);
  is.imbue(std::locale::classic());
  double v = 0.0;
  is >> v;
  return v;
#endif
}

inline uint32_t crc32_update(uint32_t c, const uint8_t* p, size_t n) {
  for (size_t i = 0; i < n; ++i) c = kCrc.t[(c ^ p[i]) & 0xFF] ^ (c >> 8);
  return c;
}

// The CRC register is linear over GF(2): running n bytes B from register s
// gives shift_n(s) ^ run(0, B), where shift_n runs n zero bytes. So the
// hash of "<a>&<b>" follows from the register after "<a>&" and what is
// known of <b> alone (its own register and its length) with no string
// built: four table reads per pair, whatever the names' length.
struct CrcShift {
  size_t len = 0;
  uint32_t t[4][256];
  uint32_t of_init = 0;  // shift_len(0xFFFFFFFF)

  explicit CrcShift(size_t n) : len(n) {
    uint32_t col[32];
    for (int k = 0; k < 32; ++k) {
      uint32_t c = 1u << k;
      for (size_t i = 0; i < n; ++i) c = kCrc.t[c & 0xFF] ^ (c >> 8);
      col[k] = c;
    }
    for (int j = 0; j < 4; ++j) {
      t[j][0] = 0;
      for (uint32_t v = 1; v < 256; ++v) {
        int low = __builtin_ctz(v);
        t[j][v] = t[j][v & (v - 1)] ^ col[8 * j + low];
      }
    }
    of_init = (*this)(0xFFFFFFFFu);
  }

  uint32_t operator()(uint32_t x) const {
    return t[0][x & 0xFF] ^ t[1][(x >> 8) & 0xFF] ^ t[2][(x >> 16) & 0xFF] ^
           t[3][x >> 24];
  }
};

// ---- key matchers: "*", "prefix*", "*suffix", exact --------------------
struct Matcher {
  enum Kind { ALL, PREFIX, SUFFIX, EXACT } kind = ALL;
  std::string pat;

  static Matcher make(const std::string& p) {
    Matcher m;
    if (p == "*") {
      m.kind = ALL;
    } else if (!p.empty() && p.back() == '*') {
      m.kind = PREFIX;
      m.pat = p.substr(0, p.size() - 1);
    } else if (!p.empty() && p.front() == '*') {
      m.kind = SUFFIX;
      m.pat = p.substr(1);
    } else {
      m.kind = EXACT;
      m.pat = p;
    }
    return m;
  }

  bool match(const uint8_t* s, size_t n) const {
    switch (kind) {
      case ALL:
        return true;
      case PREFIX:
        return n >= pat.size() && 0 == memcmp(s, pat.data(), pat.size());
      case SUFFIX:
        return n >= pat.size() &&
               0 == memcmp(s + n - pat.size(), pat.data(), pat.size());
      case EXACT:
        return n == pat.size() && 0 == memcmp(s, pat.data(), n);
    }
    return false;
  }
};

struct NumRule {
  enum Kind { NUM, LOG, STR } kind = NUM;
  Matcher m;
  std::string at_type;  // "@num" / "@log" / "@str" (rule's type name)
};

struct StrRule {
  enum Split { WHOLE, SPACE, NGRAM } split = WHOLE;
  enum Sw { BIN, TF, LOG_TF } sw = BIN;
  bool idf = false;  // global_weight idf: value *= log(ndocs/df) at parse
  int ngram_n = 0;   // code points per ngram token (split == NGRAM)
  Matcher m;
  std::string suffix;  // "@<type>#<sw>/<gw>"
};

struct NumFilter {
  // ≙ converter.py _build_num_filter: pure f64 math, so parity with the
  // Python lambdas is exact (same libm)
  enum Kind { ADD, LINEAR, GAUSS, SIGMOID } kind = ADD;
  double a = 0.0, b = 0.0;  // add: (value, -) linear: (lo, hi)
                            // gauss: (mean, std) sigmoid: (gain, bias)
  Matcher m;
  std::string suffix;  // appended key = key + suffix

  // *ok = false only where the PYTHON path would raise instead of
  // producing a value: math.exp raises OverflowError on +inf (CPython
  // checks isinf of the libm result), so a sigmoid whose exp overflows
  // must abort the fast path and let the converter raise the same error
  // — silently emitting 0.0 here would make the two paths disagree.
  double apply(double x, bool* ok) const {
    switch (kind) {
      case ADD:
        return x + a;
      case LINEAR:
        return (std::min(std::max(x, a), b) - a) / (b - a);
      case GAUSS:
        return (x - a) / b;
      case SIGMOID: {
        double e = std::exp(-a * (x - b));
        if (e == HUGE_VAL) {
          *ok = false;
          return 0.0;
        }
        return 1.0 / (1.0 + e);
      }
    }
    return x;
  }
};

struct ComboRule {
  // ≙ converter.py combination rules: the cross product of the example's
  // NAMED features (pre-hash), each unordered pair once in canonical
  // name order, value mul/add, name "<a>&<b>"
  enum Op { MUL, ADD } op = MUL;
  Matcher left, right;
};

struct Parser {
  std::vector<NumFilter> num_filters;
  std::vector<NumRule> num_rules;
  std::vector<StrRule> str_rules;
  std::vector<ComboRule> combos;
};

// ---- minimal msgpack reader (modern + legacy raw families) -------------
struct Reader {
  const uint8_t* p;
  const uint8_t* end;
  bool fail = false;

  uint8_t peek() {
    if (p >= end) {
      fail = true;
      return 0xC1;
    }
    return *p;
  }
  uint8_t take() {
    if (p >= end) {
      fail = true;
      return 0xC1;
    }
    return *p++;
  }
  bool need(size_t n) {
    if (size_t(end - p) < n) {
      fail = true;
      return false;
    }
    return true;
  }
  uint64_t be(int n) {
    if (!need(n)) return 0;
    uint64_t v = 0;
    for (int i = 0; i < n; ++i) v = (v << 8) | *p++;
    return v;
  }

  // array header; -1 on mismatch
  int64_t array_len() {
    uint8_t t = take();
    if ((t & 0xF0) == 0x90) return t & 0x0F;
    if (t == 0xDC) return int64_t(be(2));
    if (t == 0xDD) return int64_t(be(4));
    fail = true;
    return -1;
  }

  // raw/str/bin span (legacy fixraw/raw16/raw32 + modern str8/bin*)
  bool raw(const uint8_t** out, size_t* n) {
    uint8_t t = take();
    size_t len;
    if ((t & 0xE0) == 0xA0) {
      len = t & 0x1F;
    } else if (t == 0xD9 || t == 0xC4) {
      len = size_t(be(1));
    } else if (t == 0xDA || t == 0xC5) {
      len = size_t(be(2));
    } else if (t == 0xDB || t == 0xC6) {
      len = size_t(be(4));
    } else {
      fail = true;
      return false;
    }
    if (!need(len)) return false;
    *out = p;
    *n = len;
    p += len;
    return true;
  }

  // any int/float as double
  bool number(double* out) {
    uint8_t t = take();
    if (t <= 0x7F) {
      *out = t;
      return true;
    }
    if (t >= 0xE0) {
      *out = int8_t(t);
      return true;
    }
    switch (t) {
      case 0xCA: {
        uint32_t u = uint32_t(be(4));
        float f;
        memcpy(&f, &u, 4);
        *out = f;
        return true;
      }
      case 0xCB: {
        uint64_t u = be(8);
        double d;
        memcpy(&d, &u, 8);
        *out = d;
        return true;
      }
      case 0xCC:
        *out = double(be(1));
        return true;
      case 0xCD:
        *out = double(be(2));
        return true;
      case 0xCE:
        *out = double(be(4));
        return true;
      case 0xCF:
        *out = double(be(8));
        return true;
      case 0xD0:
        *out = double(int8_t(be(1)));
        return true;
      case 0xD1:
        *out = double(int16_t(be(2)));
        return true;
      case 0xD2:
        *out = double(int32_t(be(4)));
        return true;
      case 0xD3:
        *out = double(int64_t(be(8)));
        return true;
      default:
        fail = true;
        return false;
    }
  }

  // skip any object (for the binary_values slot)
  void skip() {
    uint8_t t = take();
    if (t <= 0x7F || t >= 0xE0 || t == 0xC0 || t == 0xC2 || t == 0xC3) return;
    if ((t & 0xE0) == 0xA0) {
      size_t n = t & 0x1F;
      if (need(n)) p += n;
      return;
    }
    if ((t & 0xF0) == 0x90) {
      for (int i = t & 0x0F; i > 0 && !fail; --i) skip();
      return;
    }
    if ((t & 0xF0) == 0x80) {
      for (int i = (t & 0x0F) * 2; i > 0 && !fail; --i) skip();
      return;
    }
    switch (t) {
      case 0xCC:
      case 0xD0:
        p += need(1) ? 1 : 0;
        return;
      case 0xCD:
      case 0xD1:
        p += need(2) ? 2 : 0;
        return;
      case 0xCA:
      case 0xCE:
      case 0xD2:
        p += need(4) ? 4 : 0;
        return;
      case 0xCB:
      case 0xCF:
      case 0xD3:
        p += need(8) ? 8 : 0;
        return;
      case 0xD9:
      case 0xC4: {
        size_t n = size_t(be(1));
        if (need(n)) p += n;
        return;
      }
      case 0xDA:
      case 0xC5: {
        size_t n = size_t(be(2));
        if (need(n)) p += n;
        return;
      }
      case 0xDB:
      case 0xC6: {
        size_t n = size_t(be(4));
        if (need(n)) p += n;
        return;
      }
      case 0xDC: {
        int64_t n = int64_t(be(2));
        for (int64_t i = 0; i < n && !fail; ++i) skip();
        return;
      }
      case 0xDD: {
        int64_t n = int64_t(be(4));
        for (int64_t i = 0; i < n && !fail; ++i) skip();
        return;
      }
      case 0xDE: {
        int64_t n = int64_t(be(2)) * 2;
        for (int64_t i = 0; i < n && !fail; ++i) skip();
        return;
      }
      case 0xDF: {
        int64_t n = int64_t(be(4)) * 2;
        for (int64_t i = 0; i < n && !fail; ++i) skip();
        return;
      }
      default:
        fail = true;  // ext or reserved: not part of this wire
    }
  }
};

// Decode one code point at txt[i] exactly like CPython's UTF-8 decoder
// under surrogateescape: *adv = bytes consumed. A sequence is one code
// point ONLY if it is shortest-form UTF-8 encoding a scalar value
// (no overlongs — lead 0xC0/0xC1, 0xE0 with 2nd byte < 0xA0, 0xF0 with
// 2nd byte < 0x90; no surrogates — 0xED with 2nd byte > 0x9F; nothing
// past U+10FFFF — leads 0xF5+, 0xF4 with 2nd byte > 0x8F); any invalid,
// truncated, or malformed byte decodes as ONE surrogate (adv 1, cp 0).
// Both splitters slide in these units, or they diverge from the Python
// converter on hostile bytes.
inline bool utf8_decode(const uint8_t* txt, size_t n, size_t i,
                        uint32_t* cp_out, size_t* adv) {
  uint8_t b = txt[i];
  *cp_out = 0;
  *adv = 1;
  if (b < 0x80) {
    *cp_out = b;
    return true;
  }
  size_t len;
  uint32_t cp;
  uint8_t lo = 0x80, hi = 0xBF;  // valid range of the SECOND byte
  if (b >= 0xC2 && b <= 0xDF) {
    len = 2;
    cp = b & 0x1F;
  } else if (b >= 0xE0 && b <= 0xEF) {
    len = 3;
    cp = b & 0x0F;
    if (b == 0xE0) lo = 0xA0;        // overlong
    if (b == 0xED) hi = 0x9F;        // surrogate range
  } else if (b >= 0xF0 && b <= 0xF4) {
    len = 4;
    cp = b & 0x07;
    if (b == 0xF0) lo = 0x90;        // overlong
    if (b == 0xF4) hi = 0x8F;        // > U+10FFFF
  } else {
    return false;  // stray continuation, 0xC0/0xC1 overlong, 0xF5+ lead
  }
  if (i + len > n) return false;  // truncated
  if (txt[i + 1] < lo || txt[i + 1] > hi) return false;
  cp = (cp << 6) | (txt[i + 1] & 0x3F);
  for (size_t k = 2; k < len; ++k) {
    if ((txt[i + k] & 0xC0) != 0x80) return false;
    cp = (cp << 6) | (txt[i + k] & 0x3F);
  }
  *adv = len;
  *cp_out = cp;
  return true;
}

// Python str.split() splits on Unicode whitespace (str.isspace): ASCII
// 0x09-0x0d, 0x1c-0x1f, 0x20, plus NEL/NBSP and the Unicode space
// separators. Invalid sequences decode as non-space surrogates.
inline bool is_py_space(const uint8_t* txt, size_t n, size_t i,
                        size_t* adv) {
  uint32_t cp;
  if (!utf8_decode(txt, n, i, &cp, adv)) return false;
  if (cp < 0x80)
    return (cp >= 0x09 && cp <= 0x0D) || (cp >= 0x1C && cp <= 0x1F) ||
           cp == 0x20;
  return cp == 0x85 || cp == 0xA0 || cp == 0x1680 ||
         (cp >= 0x2000 && cp <= 0x200A) || cp == 0x2028 || cp == 0x2029 ||
         cp == 0x202F || cp == 0x205F || cp == 0x3000;
}

// Byte length of the code point at txt[i] under Python's surrogateescape
// view of the bytes (utf8_decode rules): the ngram splitter slides in
// exactly these units to match converter.py's text[i:i+n].
inline size_t utf8_adv(const uint8_t* txt, size_t n, size_t i) {
  uint32_t cp;
  size_t adv;
  utf8_decode(txt, n, i, &cp, &adv);
  return adv;
}

// Python _format_num (converter.py:485-486): str(int(v)) when integral,
// else repr(v). repr = shortest round-trip digits, FIXED notation when
// the decimal exponent is in [-4, 16), scientific otherwise with a
// >=2-digit exponent — std::to_chars' default "shortest overall" picks
// scientific earlier (e.g. -1e-04 vs Python's -0.0001), so the rendering
// is reassembled here from the scientific digits. Returns 0 on values
// the exact Python rendering can't be reproduced for (integral beyond
// long long) — caller aborts the fast path and Python converts.
size_t format_num(double v, char* buf) {
  if (v == std::floor(v) && std::fabs(v) < 9.2e18) {
    long long i = (long long)v;
    auto r = std::to_chars(buf, buf + 32, i);
    return size_t(r.ptr - buf);
  }
  if (v == std::floor(v) && std::isfinite(v)) return 0;  // huge integral
  if (!std::isfinite(v)) return 0;  // nan/inf: Python renders differently
  char sci[48];
#if defined(__cpp_lib_to_chars) && __cpp_lib_to_chars >= 201611L
  auto tcr = std::to_chars(sci, sci + 48, v, std::chars_format::scientific);
  char* sci_end = tcr.ptr;
#else
  // libstdc++ < 11 has no floating-point to_chars: produce the same
  // shortest-round-trip scientific digits by minimal-precision printf +
  // strtod round-trip check (both are correctly rounded, so the digit
  // string is identical for the shortest precision that round-trips)
  char* sci_end = sci;
  for (int prec = 0; prec <= 17; ++prec) {
    int n = snprintf(sci, sizeof sci, "%.*e", prec, v);
    if (n <= 0) return 0;
    if (std::strtod(sci, nullptr) == v) {
      sci_end = sci + n;
      break;
    }
  }
  if (sci_end == sci) return 0;
#endif
  // parse "[-]d[.ddd]e±EE"
  char* p = sci;
  char* out = buf;
  if (*p == '-') {
    *out++ = '-';
    ++p;
  }
  char digits[40];
  size_t nd = 0;
  digits[nd++] = *p++;
  if (*p == '.') {
    ++p;
    while (p < sci_end && *p != 'e') digits[nd++] = *p++;
  }
  int exp10 = 0;
  {
    bool neg = false;
    ++p;  // 'e'
    if (*p == '-') {
      neg = true;
      ++p;
    } else if (*p == '+') {
      ++p;
    }
    while (p < sci_end) exp10 = exp10 * 10 + (*p++ - '0');
    if (neg) exp10 = -exp10;
  }
  if (-4 <= exp10 && exp10 < 16) {  // fixed
    if (exp10 >= 0) {
      // non-integral guarantees nd > exp10 + 1
      for (int i = 0; i <= exp10; ++i) *out++ = digits[i];
      *out++ = '.';
      for (size_t i = size_t(exp10) + 1; i < nd; ++i) *out++ = digits[i];
    } else {
      *out++ = '0';
      *out++ = '.';
      for (int i = 0; i < -exp10 - 1; ++i) *out++ = '0';
      for (size_t i = 0; i < nd; ++i) *out++ = digits[i];
    }
  } else {  // scientific, Python style: d[.ddd]e±EE (exponent >= 2 digits)
    *out++ = digits[0];
    if (nd > 1) {
      *out++ = '.';
      for (size_t i = 1; i < nd; ++i) *out++ = digits[i];
    }
    *out++ = 'e';
    *out++ = exp10 < 0 ? '-' : '+';
    int ae = exp10 < 0 ? -exp10 : exp10;
    char eb[8];
    auto er = std::to_chars(eb, eb + 8, ae);
    if (er.ptr - eb < 2) *out++ = '0';
    for (char* q = eb; q < er.ptr; ++q) *out++ = *q;
  }
  return size_t(out - buf);
}

struct Feature {
  int32_t idx;
  double val;  // accumulate in double, cast to f32 once at pack time
               // (matches the Python converter's f64 sums -> f32 arrays)
  uint8_t idf;  // produced by an idf-weighted rule (scaled pre-merge)
};

}  // namespace

extern "C" {

struct JtIngestOut {
  int32_t batch;       // examples parsed
  int32_t width;       // padded nnz per row: a rung of the width ladder or
                       // a power of two (core/sparse.py _request_width),
                       // >= 8
  int32_t labels_numeric;  // 1: targets[] is set (regression), 0: labels
  int32_t* idx;        // [batch, width], 0-padded
  float* val;          // [batch, width], 0-padded
  uint8_t* labels;     // concatenated DISTINCT label bytes
  int32_t* label_off;  // uniq + 1 offsets into labels
  float* targets;      // [batch] numeric targets (regression train)
  int32_t uniq;        // distinct labels in labels/label_off
  int32_t* label_idx;  // [batch] row -> distinct-label index
  // combination specs only (null / 0 otherwise): the rows BEFORE the
  // cross product, packed like idx/val. The server no longer reads them
  // (the device expansion they fed went in PR 28); the benchmark's test
  // of the parser does, and they go with it (ROADMAP R-B1)
  int32_t base_width;  // a power of two, >= 8
  int32_t* base_idx;   // [batch, base_width]
  float* base_val;     // [batch, base_width]
  int64_t cross_slots;  // pair features emitted, before the merge by index
  int64_t cross_ns;     // nanoseconds spent in the cross product
  int64_t str_tokens;   // tokens the string rules cut (a whole value is one)
  int64_t str_terms;    // distinct terms of them that became entries
  int32_t pow2;         // 1: uneven rows, so width is a power of two
  int64_t str_codepoints;  // code points the ngram rules slid over
};

void* jt_ingest_create(const char* spec) {
  auto* ps = new Parser();
  std::string s(spec ? spec : "");
  size_t pos = 0;
  while (pos < s.size()) {
    size_t nl = s.find('\n', pos);
    if (nl == std::string::npos) nl = s.size();
    std::string line = s.substr(pos, nl - pos);
    pos = nl + 1;
    if (line.empty()) continue;
    std::vector<std::string> f;
    size_t start = 0;
    while (true) {
      size_t tab = line.find('\t', start);
      if (tab == std::string::npos) {
        f.push_back(line.substr(start));
        break;
      }
      f.push_back(line.substr(start, tab - start));
      start = tab + 1;
    }
    if (f[0] == "nf" && f.size() == 6) {
      // "nf\t<kind>\t<a>\t<b>\t<pattern>\t<suffix>"
      NumFilter nf;
      if (f[1] == "add")
        nf.kind = NumFilter::ADD;
      else if (f[1] == "linear")
        nf.kind = NumFilter::LINEAR;
      else if (f[1] == "gauss")
        nf.kind = NumFilter::GAUSS;
      else if (f[1] == "sigmoid")
        nf.kind = NumFilter::SIGMOID;
      else {
        delete ps;
        return nullptr;
      }
      // from_chars: locale-INDEPENDENT ("5.5" must not parse as 5.0
      // under an LC_NUMERIC with a comma separator smuggled in by some
      // other module in the host process)
      nf.a = parse_spec_f64(f[2]);
      nf.b = parse_spec_f64(f[3]);
      nf.m = Matcher::make(f[4]);
      nf.suffix = f[5];
      ps->num_filters.push_back(std::move(nf));
    } else if (f[0] == "num" && f.size() == 3) {
      NumRule r;
      if (f[1] == "num")
        r.kind = NumRule::NUM;
      else if (f[1] == "log")
        r.kind = NumRule::LOG;
      else if (f[1] == "str")
        r.kind = NumRule::STR;
      else {
        delete ps;
        return nullptr;
      }
      r.at_type = "@" + f[1];
      r.m = Matcher::make(f[2]);
      ps->num_rules.push_back(std::move(r));
    } else if (f[0] == "str" && f.size() == 6) {
      StrRule r;
      if (f[1] == "str")
        r.split = StrRule::WHOLE;
      else if (f[1] == "space")
        r.split = StrRule::SPACE;
      else if (f[1].rfind("ngram:", 0) == 0) {
        r.split = StrRule::NGRAM;
        r.ngram_n = atoi(f[1].c_str() + 6);
        if (r.ngram_n < 1) {
          delete ps;
          return nullptr;
        }
      } else {
        delete ps;
        return nullptr;
      }
      if (f[2] == "bin")
        r.sw = StrRule::BIN;
      else if (f[2] == "tf")
        r.sw = StrRule::TF;
      else if (f[2] == "log_tf")
        r.sw = StrRule::LOG_TF;
      else {
        delete ps;
        return nullptr;
      }
      if (f[3] == "idf") {
        // idf needs the WeightManager's df table: the caller passes it
        // into jt_ingest_parse_w; the unweighted entry points refuse
        // specs carrying idf rules
        r.idf = true;
      } else if (f[3] != "bin") {  // "weight" needs the user-weight map
        delete ps;
        return nullptr;
      }
      r.suffix = "@" + f[4] + "#" + f[2] + "/" + f[3];
      r.m = Matcher::make(f[5]);
      ps->str_rules.push_back(std::move(r));
    } else if (f[0] == "combo" && f.size() == 4) {
      // "combo\t<mul|add>\t<key_left>\t<key_right>"
      ComboRule cr;
      if (f[1] == "mul")
        cr.op = ComboRule::MUL;
      else if (f[1] == "add")
        cr.op = ComboRule::ADD;
      else {
        delete ps;
        return nullptr;
      }
      cr.left = Matcher::make(f[2]);
      cr.right = Matcher::make(f[3]);
      ps->combos.push_back(std::move(cr));
    } else {
      delete ps;
      return nullptr;
    }
  }
  // combos iterate the pre-hash NAMED features; the idf path weights
  // hashed indices pre-merge — composing them here would need the full
  // name->weight pipeline, so such specs stay on the Python converter
  if (!ps->combos.empty())
    for (const StrRule& r : ps->str_rules)
      if (r.idf) {
        delete ps;
        return nullptr;
      }
  return ps;
}

void jt_ingest_destroy(void* h) { delete static_cast<Parser*>(h); }

void jt_ingest_free_out(JtIngestOut* out) {
  free(out->idx);
  free(out->val);
  free(out->labels);
  free(out->label_off);
  free(out->targets);
  free(out->label_idx);
  free(out->base_idx);
  free(out->base_val);
  out->base_idx = nullptr;
  out->base_val = nullptr;
  out->idx = nullptr;
  out->val = nullptr;
  out->labels = nullptr;
  out->label_off = nullptr;
  out->targets = nullptr;
  out->label_idx = nullptr;
}

//: idf weighting context, or null dfm for the unweighted path. Mirrors
//: converter.convert's order EXACTLY: per document, observe the distinct
//: idf feature indices FIRST (df += 1 once per index, ndocs += 1), then
//: value *= log(ndocs/df) (<=0 guards -> 1.0), THEN merge by index.
//: ``observe`` is 0 on the query path (classify/estimate read idf
//: without recording the document).
struct IdfCtx {
  const float* dfm = nullptr;  // df master (read)
  float* dfd = nullptr;        // df diff (incremented on train)
  double ndocs_m = 0.0;
  double* ndocs_d = nullptr;   // incremented on train
  int observe = 0;
};

static int parse_impl(void* h, const uint8_t* buf, int64_t len,
                      uint32_t mask, int with_labels, const IdfCtx* idf,
                      JtIngestOut* out) {
  const Parser& ps = *static_cast<Parser*>(h);
  Reader rd{buf, buf + len};
  bool has_idf_rule = false;
  for (const StrRule& r : ps.str_rules) has_idf_rule |= r.idf;
  if (has_idf_rule && (idf == nullptr || idf->dfm == nullptr))
    return 5;  // spec needs weight state the caller did not supply

  int64_t top = rd.array_len();  // [name, data]
  if (rd.fail || top != 2) return 1;
  rd.skip();  // cluster name
  int64_t n = rd.array_len();
  if (rd.fail || n < 0) return 1;

  std::vector<Feature> feats;       // all examples, concatenated
  std::vector<int64_t> offsets(1, 0);
  std::vector<uint8_t> labels;      // distinct label bytes, concatenated
  std::vector<int32_t> label_off(1, 0);  // uniq + 1 offsets
  std::vector<int32_t> label_idx;   // row -> distinct-label index
  std::vector<std::pair<size_t, size_t>> uniq_spans;  // (off, len) in labels
  std::vector<float> targets;       // regression: numeric first slot
  int labels_numeric = -1;          // unknown until the first example
  std::string name;                 // scratch feature-name buffer
  std::vector<std::pair<const uint8_t*, size_t>> terms;  // scratch
  std::vector<int32_t> idf_scratch;  // distinct idf indices per example
  // filter-appended keys (per-example scratch; the schema cache owns
  // copies of any key bytes it keeps)
  std::deque<std::string> key_arena;
  // string-rule scratch: hash-count slots, per-occurrence counts,
  // first-seen order, and the per-request (rule, key, term)->idx memo
  std::vector<int32_t> tslot;
  std::vector<int32_t> tcnt;
  std::vector<size_t> distinct;
  std::string lookup_key;
  std::vector<std::unordered_map<std::string, int32_t>> term_memo{
      ps.str_rules.size()};
  char numbuf[40];

  // Schema cache for num rules: real ingest streams repeat one key schema
  // (f0..fK in the same order every datum), so the (rule, position)->
  // hashed-index outcome from the previous datum usually holds — one
  // memcmp replaces name assembly + CRC-32 per feature. state: -1 unset,
  // 0 no-match, 1 emit idx with v, 2 emit idx with log(max(1,v)),
  // 3 value-dependent name (num "str" rule) — recompute.
  // entries OWN their key bytes (copied on miss): filter-appended keys
  // live in a per-example arena, so a borrowed pointer would dangle into
  // the previous example's scratch
  struct PosEntry {
    std::string key;
    int8_t state = -1;
    int32_t idx = 0;
  };
  std::vector<PosEntry> poscache;
  size_t pos_stride = 0;  // kv slots per rule; grows to max nnv seen

  // combo mode: the BASE features accumulate by NAME first (converter.py
  // _named_features dict) and the combination cross product runs over
  // that map. The term/pos memos are bypassed (they exist to skip name
  // assembly, which combos need). The cross product itself builds no name
  // (CrcShift) and keeps no map: two features of one name have one hash,
  // so the merge by index below adds them as the dict would.
  const bool combo_mode = !ps.combos.empty();
  std::vector<std::pair<std::string, double>> named;  // insertion order
  std::unordered_map<std::string, size_t> named_ix;
  std::vector<Feature> bfeats;        // the rows before the cross product
  std::vector<int64_t> boffsets(1, 0);
  std::deque<CrcShift> shifts;        // one per distinct name length
  struct Base {
    const std::string* name;
    double val;
    uint32_t after_amp;  // register after "<name>&"
    uint32_t from_zero;  // register after <name>, started from 0
    const CrcShift* shift;
  };
  std::vector<Base> base;  // one example's base features, in name order
  std::vector<uint8_t> lmatch, rmatch;
  int64_t cross_slots = 0, cross_ns = 0;
  int64_t str_tokens = 0, str_terms = 0, str_codepoints = 0;

  auto add_named = [&](const std::string& nm, double v) {
    auto it = named_ix.find(nm);
    if (it == named_ix.end()) {
      named_ix.emplace(nm, named.size());
      named.push_back({nm, v});
    } else {
      named[it->second].second += v;
    }
  };

  auto hash_push = [&](const std::string& nm, double v, bool idf) {
    uint32_t c = crc32_update(0xFFFFFFFFu,
                              reinterpret_cast<const uint8_t*>(nm.data()),
                              nm.size()) ^
                 0xFFFFFFFFu;
    uint32_t i = c & mask;
    if (i == 0) i = 1;  // padding slot is reserved
    feats.push_back({int32_t(i), v, uint8_t(idf)});
  };

  auto emit = [&](const std::string& nm, double v, bool idf = false) {
    if (combo_mode)
      add_named(nm, v);  // idf+combos declined at create
    else
      hash_push(nm, v, idf);
  };

  // sort one example's features by index and add those that share one
  auto merge_row = [](std::vector<Feature>& fs, std::vector<int64_t>& offs) {
    size_t start = size_t(offs.back());
    std::sort(fs.begin() + offs.back(), fs.end(),
              [](const Feature& a, const Feature& b) { return a.idx < b.idx; });
    size_t w = start;
    for (size_t rdi = start; rdi < fs.size(); ++rdi) {
      if (w > start && fs[rdi].idx == fs[w - 1].idx) {
        fs[w - 1].val += fs[rdi].val;
      } else {
        fs[w] = fs[rdi];
        ++w;
      }
    }
    fs.resize(w);
    offs.push_back(int64_t(fs.size()));
  };

  for (int64_t e = 0; e < n; ++e) {
    if (with_labels) {
      int64_t pair = rd.array_len();  // [label, datum] / [target, datum]
      if (rd.fail || pair != 2) return 1;
      uint8_t lt = rd.peek();
      bool is_raw = (lt & 0xE0) == 0xA0 || lt == 0xD9 || lt == 0xC4 ||
                    lt == 0xDA || lt == 0xC5 || lt == 0xDB || lt == 0xC6;
      if (labels_numeric == -1) labels_numeric = is_raw ? 0 : 1;
      if (is_raw != (labels_numeric == 0)) return 1;  // mixed: not this wire
      if (is_raw) {
        const uint8_t* lb;
        size_t lbn;
        if (!rd.raw(&lb, &lbn)) return 1;
        // dedup: linear scan over the distinct set (classification label
        // sets are small); past 256 distinct, stop scanning and append —
        // label_idx stays correct, rows just stop sharing entries
        int32_t li = -1;
        if (uniq_spans.size() <= 256) {
          for (size_t u = 0; u < uniq_spans.size(); ++u) {
            if (uniq_spans[u].second == lbn &&
                0 == memcmp(labels.data() + uniq_spans[u].first, lb, lbn)) {
              li = int32_t(u);
              break;
            }
          }
        }
        if (li < 0) {
          li = int32_t(uniq_spans.size());
          uniq_spans.push_back({labels.size(), lbn});
          labels.insert(labels.end(), lb, lb + lbn);
          label_off.push_back(int32_t(labels.size()));
        }
        label_idx.push_back(li);
      } else {
        double t;
        if (!rd.number(&t)) return 1;
        targets.push_back(float(t));
      }
    } else {
      labels_numeric = 0;  // classify/estimate: bare datum list, no labels
    }

    int64_t dlen = rd.array_len();  // [sv, nv, (bv)]
    if (rd.fail || dlen < 2 || dlen > 3) return 1;

    // string_values — bound claimed lengths by remaining bytes before any
    // allocation (a ~20-byte request claiming 2^32 pairs must produce an
    // error reply, not a bad_alloc/terminate)
    int64_t nsv = rd.array_len();
    if (rd.fail || nsv < 0 || nsv > rd.end - rd.p) return 1;
    // remember the sv spans (rules iterate over all kvs per rule)
    std::vector<std::pair<std::pair<const uint8_t*, size_t>,
                          std::pair<const uint8_t*, size_t>>>
        svs{size_t(nsv)};
    for (int64_t i = 0; i < nsv; ++i) {
      int64_t kv = rd.array_len();
      if (rd.fail || kv != 2) return 1;
      if (!rd.raw(&svs[i].first.first, &svs[i].first.second)) return 1;
      if (!rd.raw(&svs[i].second.first, &svs[i].second.second)) return 1;
    }
    // num_values
    int64_t nnv = rd.array_len();
    if (rd.fail || nnv < 0 || nnv > rd.end - rd.p) return 1;
    std::vector<std::pair<std::pair<const uint8_t*, size_t>, double>> nvs{
        size_t(nnv)};
    for (int64_t i = 0; i < nnv; ++i) {
      int64_t kv = rd.array_len();
      if (rd.fail || kv != 2) return 1;
      if (!rd.raw(&nvs[i].first.first, &nvs[i].first.second)) return 1;
      if (!rd.number(&nvs[i].second)) return 1;
    }
    if (dlen == 3) rd.skip();  // binary_values: no binary rules here

    // num filters (converter.py _apply_filters): each rule snapshots the
    // CURRENT list and appends (key+suffix, f(value)) — later filters see
    // earlier filters' output, exactly like the Python loop. Appended
    // keys live in a deque (stable addresses) for the whole parse call.
    key_arena.clear();  // per-example scratch (cache entries own copies)
    if (combo_mode) {
      named.clear();
      named_ix.clear();
    }
    for (const NumFilter& nf : ps.num_filters) {
      size_t cur = nvs.size();
      for (size_t fi = 0; fi < cur; ++fi) {
        auto kv = nvs[fi];  // by value: push_back below may reallocate
        if (!nf.m.match(kv.first.first, kv.first.second)) continue;
        key_arena.emplace_back();
        std::string& nk = key_arena.back();
        nk.assign(reinterpret_cast<const char*>(kv.first.first),
                  kv.first.second);
        nk += nf.suffix;
        bool ok = true;
        double fv = nf.apply(kv.second, &ok);
        if (!ok) return 3;  // Python path raises here: fall back to it
        nvs.push_back(
            {{reinterpret_cast<const uint8_t*>(nk.data()), nk.size()},
             fv});
      }
    }
    nnv = int64_t(nvs.size());

    // string rules (converter.py:346-366)
    for (const StrRule& r : ps.str_rules) {
      for (auto& kv : svs) {
        const uint8_t* key = kv.first.first;
        size_t keyn = kv.first.second;
        if (!r.m.match(key, keyn)) continue;
        const uint8_t* txt = kv.second.first;
        size_t txtn = kv.second.second;
        terms.clear();
        if (r.split == StrRule::WHOLE) {
          if (txtn) terms.push_back({txt, txtn});
        } else if (r.split == StrRule::SPACE) {
          // SPACE: Unicode whitespace runs (str.split())
          size_t i = 0;
          while (i < txtn) {
            size_t adv;
            while (i < txtn && is_py_space(txt, txtn, i, &adv)) i += adv;
            size_t s = i;
            while (i < txtn && !is_py_space(txt, txtn, i, &adv)) i += adv;
            if (i > s) terms.push_back({txt + s, i - s});
          }
        } else {  // NGRAM: sliding window of n CODE POINTS (converter.py
          // _make_ngram slides over a surrogateescape-decoded str)
          std::vector<size_t> cps;  // byte offset of each code point
          size_t i = 0;
          while (i < txtn) {
            cps.push_back(i);
            i += utf8_adv(txt, txtn, i);
          }
          cps.push_back(txtn);
          size_t n_cp = cps.size() - 1;
          str_codepoints += int64_t(n_cp);
          for (size_t a = 0; a + size_t(r.ngram_n) <= n_cp; ++a)
            terms.push_back(
                {txt + cps[a], cps[a + size_t(r.ngram_n)] - cps[a]});
        }
        // tf counts per distinct term: open-addressing hash count in
        // FIRST-SEEN order (the Python dict's insertion order) — the old
        // quadratic memcmp dedup was ~35% of text-parse time at 32
        // tokens/datum
        size_t T = terms.size();
        if (T == 0) continue;
        size_t cap = 4;
        while (cap < 2 * T) cap <<= 1;
        tslot.assign(cap, -1);
        tcnt.assign(T, 0);
        distinct.clear();
        for (size_t ti = 0; ti < T; ++ti) {
          const uint8_t* tp = terms[ti].first;
          size_t tn = terms[ti].second;
          uint64_t h = 1469598103934665603ull;  // FNV-1a
          for (size_t bi = 0; bi < tn; ++bi)
            h = (h ^ tp[bi]) * 1099511628211ull;
          size_t slot = size_t(h) & (cap - 1);
          while (true) {
            int32_t occ = tslot[slot];
            if (occ < 0) {
              tslot[slot] = int32_t(ti);
              tcnt[ti] = 1;
              distinct.push_back(ti);
              break;
            }
            if (terms[size_t(occ)].second == tn &&
                0 == memcmp(terms[size_t(occ)].first, tp, tn)) {
              ++tcnt[size_t(occ)];
              break;
            }
            slot = (slot + 1) & (cap - 1);
          }
        }
        str_tokens += int64_t(T);
        str_terms += int64_t(distinct.size());
        // (rule, key, term) -> hashed index memo across the request:
        // repeated vocabulary skips name assembly + CRC-32 entirely.
        // The key is LENGTH-PREFIXED (raw keys/terms may contain any
        // byte, so a separator could collide "a\0b"+"c" with "a"+"b\0c");
        // it is built once per kv and resized per term; the memo is
        // size-capped so high-cardinality text (unique ngrams) degrades
        // to plain misses instead of unbounded per-request allocation.
        auto& memo = term_memo[size_t(&r - ps.str_rules.data())];
        uint32_t klen32 = uint32_t(keyn);
        lookup_key.assign(reinterpret_cast<const char*>(&klen32), 4);
        lookup_key.append(reinterpret_cast<const char*>(key), keyn);
        size_t prefix_len = lookup_key.size();
        for (size_t di : distinct) {
          int tf = tcnt[di];
          double sw = r.sw == StrRule::BIN  ? 1.0
                      : r.sw == StrRule::TF ? double(tf)
                                            : std::log(1.0 + tf);
          if (!combo_mode) {
            lookup_key.resize(prefix_len);
            lookup_key.append(
                reinterpret_cast<const char*>(terms[di].first),
                terms[di].second);
            auto it = memo.find(lookup_key);
            if (it != memo.end()) {
              feats.push_back({it->second, sw, uint8_t(r.idf)});
              continue;
            }
          }
          name.assign(reinterpret_cast<const char*>(key), keyn);
          name += '$';
          name.append(reinterpret_cast<const char*>(terms[di].first),
                      terms[di].second);
          name += r.suffix;
          emit(name, sw, r.idf);
          if (!combo_mode && memo.size() < (1u << 16))
            memo.emplace(lookup_key, feats.back().idx);
        }
      }
    }
    // num rules (converter.py:369-388), schema-cached per (rule, position)
    if (size_t(nnv) > pos_stride) {
      // re-stride: invalidate (entries would alias across rules)
      pos_stride = size_t(nnv);
      poscache.assign(ps.num_rules.size() * pos_stride, PosEntry{});
    }
    for (size_t ri = 0; ri < ps.num_rules.size(); ++ri) {
      const NumRule& r = ps.num_rules[ri];
      PosEntry* row = poscache.data() + ri * pos_stride;
      for (int64_t ki = 0; ki < nnv; ++ki) {
        auto& kv = nvs[size_t(ki)];
        const uint8_t* key = kv.first.first;
        size_t keyn = kv.first.second;
        PosEntry& pe = row[ki];
        if (!combo_mode && pe.state >= 0 && pe.key.size() == keyn &&
            0 == memcmp(pe.key.data(), key, keyn)) {
          switch (pe.state) {
            case 0:
              continue;
            case 1:
              feats.push_back({pe.idx, kv.second});
              continue;
            case 2:
              feats.push_back({pe.idx, std::log(std::max(1.0, kv.second))});
              continue;
            default:
              break;  // state 3: value-dependent, fall through
          }
        } else {
          pe.key.assign(reinterpret_cast<const char*>(key), keyn);
          if (!r.m.match(key, keyn)) {
            pe.state = 0;
            continue;
          }
          pe.state = r.kind == NumRule::NUM   ? 1
                     : r.kind == NumRule::LOG ? 2
                                              : 3;
          if (pe.state != 3) {
            name.assign(reinterpret_cast<const char*>(key), keyn);
            name += r.at_type;
            emit(name, pe.state == 1 ? kv.second
                                     : std::log(std::max(1.0, kv.second)));
            if (!combo_mode)  // emit() owns the name->index rule
              pe.idx = feats.back().idx;
            continue;
          }
        }
        // NumRule::STR — the term is the formatted value; uncacheable
        size_t fn = format_num(kv.second, numbuf);
        if (fn == 0) return 3;  // unrepresentable: Python path converts
        name.assign(reinterpret_cast<const char*>(key), keyn);
        name += '$';
        name.append(numbuf, fn);
        name += r.at_type;
        emit(name, 1.0);
      }
    }

    // combinations (converter.py _apply_combos): every unordered pair of
    // the BASE named features once per rule, in canonical (bytewise ==
    // codepoint) name order, "<a>&<b>", mul/add of the base values
    if (combo_mode) {
      auto t_cross = std::chrono::steady_clock::now();
      size_t base_n = named.size();
      base.resize(base_n);
      for (size_t i2 = 0; i2 < base_n; ++i2) {
        const std::string& nm = named[i2].first;
        uint32_t reg = crc32_update(
            0xFFFFFFFFu, reinterpret_cast<const uint8_t*>(nm.data()),
            nm.size());
        uint32_t i = (reg ^ 0xFFFFFFFFu) & mask;
        feats.push_back({int32_t(i ? i : 1), named[i2].second, 0});
        const CrcShift* sh = nullptr;
        for (const CrcShift& c : shifts)
          if (c.len == nm.size()) sh = &c;
        if (sh == nullptr) {
          shifts.emplace_back(nm.size());
          sh = &shifts.back();
        }
        const uint8_t amp = '&';
        base[i2] = {&nm, named[i2].second, crc32_update(reg, &amp, 1),
                    reg ^ sh->of_init, sh};
      }
      bfeats.insert(bfeats.end(), feats.end() - base_n, feats.end());
      std::sort(base.begin(), base.end(), [](const Base& x, const Base& y) {
        return *x.name < *y.name;
      });
      for (const ComboRule& cr : ps.combos) {
        bool all = cr.left.kind == Matcher::ALL &&
                   cr.right.kind == Matcher::ALL;
        if (!all) {
          lmatch.resize(base_n);
          rmatch.resize(base_n);
          for (size_t oi = 0; oi < base_n; ++oi) {
            const std::string& nm = *base[oi].name;
            const uint8_t* p2 = reinterpret_cast<const uint8_t*>(nm.data());
            lmatch[oi] = cr.left.match(p2, nm.size());
            rmatch[oi] = cr.right.match(p2, nm.size());
          }
        }
        const bool mul = cr.op == ComboRule::MUL;
        for (size_t a = 0; a + 1 < base_n; ++a) {
          const uint32_t pre = base[a].after_amp;
          const double va = base[a].val;
          for (size_t b = a + 1; b < base_n; ++b) {
            // once per unordered pair per rule, whichever side matched
            // which matcher (the Python loop's seen-set)
            if (!all && !((lmatch[a] && rmatch[b]) ||
                          (lmatch[b] && rmatch[a])))
              continue;
            const Base& sb = base[b];
            uint32_t reg = (*sb.shift)(pre) ^ sb.from_zero;
            uint32_t i = (reg ^ 0xFFFFFFFFu) & mask;
            feats.push_back({int32_t(i ? i : 1),
                             mul ? va * sb.val : va + sb.val, 0});
            ++cross_slots;
          }
        }
      }
      cross_ns += std::chrono::duration_cast<std::chrono::nanoseconds>(
                      std::chrono::steady_clock::now() - t_cross)
                      .count();
    }

    // idf (converter.py convert(): observe distinct indices, then scale,
    // BEFORE the merge — a post-merge scale would mis-weight hash
    // collisions between idf and non-idf features)
    if (has_idf_rule) {
      size_t start = size_t(offsets.back());
      idf_scratch.clear();
      for (size_t fi = start; fi < feats.size(); ++fi)
        if (feats[fi].idf) idf_scratch.push_back(feats[fi].idx);
      if (!idf_scratch.empty()) {
        std::sort(idf_scratch.begin(), idf_scratch.end());
        idf_scratch.erase(
            std::unique(idf_scratch.begin(), idf_scratch.end()),
            idf_scratch.end());
        if (idf->observe) {
          for (int32_t ix : idf_scratch) idf->dfd[ix] += 1.0f;
          *idf->ndocs_d += 1.0;
        }
        double n = idf->ndocs_m + (idf->ndocs_d ? *idf->ndocs_d : 0.0);
        for (size_t fi = start; fi < feats.size(); ++fi) {
          if (!feats[fi].idf) continue;
          int32_t ix = feats[fi].idx;
          // f32 addition FIRST (then widen): WeightManager.idf does
          // float(master[i] + diff[i]) — a double-precision sum here
          // would diverge from the Python path once df saturates f32
          double df = double(idf->dfm[ix] +
                             (idf->dfd ? idf->dfd[ix] : 0.0f));
          double w = (n <= 0.0 || df <= 0.0) ? 1.0 : std::log(n / df);
          feats[fi].val *= w;
        }
      }
    }

    // per-example: sort by index, merge duplicates (convert() semantics)
    merge_row(feats, offsets);
    if (combo_mode) merge_row(bfeats, boffsets);
  }
  if (rd.fail) return 1;

  // pack to [batch, width] at the width of core/sparse.py _request_width,
  // the same arithmetic: the rung of the fullest row on _width_bucket's
  // ladder (eight rungs an octave, never finer than 8) where the rows fill
  // at least half of their entries there (rows that are alike: 39 of 40,
  // 780 of 832), else the power of two at or above the fullest row. Where
  // lengths are heavy-tailed (text) which rung a request lands on is
  // chance and every rung is a compiled program; on powers of two a
  // server's life sees one program a doubling. A stop-gap: nine entries in
  // ten of a 500-document call stay padding, and the rule goes when
  // requests leave here in a form without it.
  // The rows before the cross product (ladder false) keep the power of two:
  // they feed no gather or scatter of their own, and the benchmark's
  // tests/perfbench/test_cross.py holds their width at 64 for 39 features
  // (a benchmark PR's to move: ROADMAP R-B1).
  auto pack = [n](const std::vector<Feature>& fs,
                  const std::vector<int64_t>& offs, bool ladder,
                  int32_t* width, int32_t* pow2, int32_t** idx,
                  float** val) {
    int64_t max_nnz = 1;
    for (size_t e = 0; e + 1 < offs.size(); ++e)
      max_nnz = std::max(max_nnz, offs[e + 1] - offs[e]);
    // a step is a sixteenth of the power of two above the row (eight
    // rungs an octave), or that power of two itself
    const int64_t nw = std::max<int64_t>(max_nnz, 8);
    auto rung = [nw](int64_t steps) {
      int64_t step = 8;
      while (step * steps < nw) step *= 2;
      return int32_t((nw + step - 1) / step * step);
    };
    int32_t w = rung(ladder ? 16 : 1);
    *pow2 = ladder && 2 * (offs.back() - offs.front()) < n * int64_t(w);
    if (*pow2) w = rung(1);
    *width = w;
    *idx = static_cast<int32_t*>(calloc(size_t(n) * w, 4));
    *val = static_cast<float*>(calloc(size_t(n) * w, 4));
    if (!*idx || !*val) return false;
    for (int64_t e = 0; e < n; ++e) {
      int64_t s = offs[e], cnt = offs[e + 1] - offs[e];
      for (int64_t j = 0; j < cnt; ++j) {
        (*idx)[e * w + j] = fs[size_t(s + j)].idx;
        (*val)[e * w + j] = float(fs[size_t(s + j)].val);
      }
    }
    return true;
  };

  size_t uniq = uniq_spans.size();
  out->batch = int32_t(n);
  out->labels_numeric = labels_numeric == 1 ? 1 : 0;
  out->uniq = int32_t(uniq);
  out->cross_slots = cross_slots;
  out->cross_ns = cross_ns;
  out->str_tokens = str_tokens;
  out->str_terms = str_terms;
  out->str_codepoints = str_codepoints;
  int32_t base_pow2;
  bool packed = pack(feats, offsets, true, &out->width, &out->pow2, &out->idx,
                     &out->val);
  if (packed && combo_mode)
    packed = pack(bfeats, boffsets, false, &out->base_width, &base_pow2,
                  &out->base_idx, &out->base_val);
  out->labels = static_cast<uint8_t*>(malloc(labels.size() ? labels.size() : 1));
  out->label_off = static_cast<int32_t*>(malloc((uniq + 1) * 4));
  out->targets = static_cast<float*>(malloc((size_t(n) + 1) * 4));
  out->label_idx = static_cast<int32_t*>(malloc((size_t(n) + 1) * 4));
  if (!packed || !out->labels || !out->label_off || !out->targets ||
      !out->label_idx) {
    jt_ingest_free_out(out);
    return 2;
  }
  memcpy(out->labels, labels.data(), labels.size());
  if (labels_numeric == 1) {
    memcpy(out->targets, targets.data(), targets.size() * 4);
    out->label_off[0] = 0;
  } else {
    memcpy(out->label_off, label_off.data(), (uniq + 1) * 4);
    memcpy(out->label_idx, label_idx.data(), label_idx.size() * 4);
  }
  return 0;
}

int jt_ingest_parse(void* h, const uint8_t* buf, int64_t len, uint32_t mask,
                    JtIngestOut* out) {
  // no exception may cross the C ABI: an allocation failure (hostile
  // lengths, memory pressure) must surface as a parse error the caller
  // turns into an RPC error reply, never std::terminate
  try {
    return parse_impl(h, buf, len, mask, 1, nullptr, out);
  } catch (...) {
    return 4;
  }
}

// classify/estimate wire: [name, [datum, ...]] — no label slot; only the
// idx/val arrays of the result are meaningful
int jt_ingest_parse_datums(void* h, const uint8_t* buf, int64_t len,
                           uint32_t mask, JtIngestOut* out) {
  try {
    return parse_impl(h, buf, len, mask, 0, nullptr, out);
  } catch (...) {
    return 4;
  }
}

// idf-weighted variants: the caller supplies the WeightManager's dense
// df tables (master read-only, diff incremented per observed document)
// and ndocs counters. ``observe`` 1 = train path (record documents),
// 0 = query path (read-only idf lookup). The caller owns locking —
// these mutate dfd/ndocs_d in place.
int jt_ingest_parse_w(void* h, const uint8_t* buf, int64_t len,
                      uint32_t mask, const float* dfm, float* dfd,
                      double ndocs_m, double* ndocs_d, int observe,
                      JtIngestOut* out) {
  try {
    IdfCtx ctx{dfm, dfd, ndocs_m, ndocs_d, observe};
    return parse_impl(h, buf, len, mask, 1, &ctx, out);
  } catch (...) {
    return 4;
  }
}

int jt_ingest_parse_datums_w(void* h, const uint8_t* buf, int64_t len,
                             uint32_t mask, const float* dfm, float* dfd,
                             double ndocs_m, double* ndocs_d,
                             JtIngestOut* out) {
  try {
    IdfCtx ctx{dfm, dfd, ndocs_m, ndocs_d, 0};
    return parse_impl(h, buf, len, mask, 0, &ctx, out);
  } catch (...) {
    return 4;
  }
}

// ---- column-range routing of a padded flush -------------------------------
// parallel/sharded_model.py route_rows: a --shard-devices server hands each
// chip only the entries whose column it owns. idx/val are [b, k] row-major
// (column 0 is padding), shard s owns columns [s * d_local, (s+1) * d_local).
// Two calls, because the planes' width follows the count:
//   jt_route_count: lens [n_shards, b] = entries of row i that shard s owns;
//     returns the fullest such count, or -1 where a column lies outside
//     [0, n_shards * d_local).
//   jt_route_fill: ridx/rval [n_shards, ks, b] (every cell written; row
//     index minor-most: the device's own layout for such a plane, so the
//     upload transposes nothing): the entries of row i that shard s owns, as
//     local columns, in the order the row has them, column 0 / value 0
//     behind them; 0 = ok, 1 where a row holds more than ks entries of one
//     shard or a column is out of range.
// One pass over the rows each, a cursor a shard: any order of a row's
// entries is routed stably, sorted or not.

// a power-of-two d_local (every hash_max_size over 2^k chips) divides by
// a shift: 32 says it is none
static inline uint32_t route_shift(uint32_t d_local) {
  return (d_local & (d_local - 1)) == 0 ? __builtin_ctz(d_local) : 32;
}

static inline uint32_t route_owner(uint32_t c, uint32_t d_local,
                                   uint32_t shift) {
  return shift < 32 ? c >> shift : c / d_local;
}

int32_t jt_route_count(const int32_t* idx, int64_t b, int32_t k,
                       int32_t n_shards, int32_t d_local, int32_t* lens) {
  if (b < 0 || k < 0 || n_shards <= 0 || d_local <= 0) return -1;
  std::memset(lens, 0, sizeof(int32_t) * n_shards * b);
  const uint32_t d = d_local, n = n_shards, shift = route_shift(d);
  int32_t fullest = 0;
  for (int64_t i = 0; i < b; ++i) {
    const int32_t* row = idx + i * k;
    for (int32_t j = 0; j < k; ++j) {
      const uint32_t c = static_cast<uint32_t>(row[j]);
      if (c == 0) continue;
      const uint32_t s = route_owner(c, d, shift);
      if (s >= n) return -1;
      fullest = std::max(fullest, ++lens[s * b + i]);
    }
  }
  return fullest;
}

int jt_route_fill(const int32_t* idx, const float* val, int64_t b, int32_t k,
                  int32_t n_shards, int32_t d_local, int32_t ks,
                  int32_t* ridx, float* rval) {
  if (b < 0 || k < 0 || n_shards <= 0 || d_local <= 0 || ks < 0) return 1;
  const uint32_t d = d_local, n = n_shards, shift = route_shift(d);
  // A plane's lanes lie b rows apart, so a row's entries would land on
  // n * ks distant lines: route a block of rows into a buffer that the
  // cache holds, then copy each lane's stretch of the block out whole.
  constexpr int64_t kBlock = 128;
  const int64_t lanes = static_cast<int64_t>(n) * ks;
  std::vector<int32_t> bi(lanes * kBlock), at(n);
  std::vector<float> bv(lanes * kBlock);
  for (int64_t i0 = 0; i0 < b; i0 += kBlock) {
    const int64_t rows = std::min(kBlock, b - i0);
    std::fill(bi.begin(), bi.end(), 0);
    std::fill(bv.begin(), bv.end(), 0.0f);
    for (int64_t i = 0; i < rows; ++i) {
      const int32_t* row = idx + (i0 + i) * k;
      const float* vrow = val + (i0 + i) * k;
      std::fill(at.begin(), at.end(), 0);
      for (int32_t j = 0; j < k; ++j) {
        const uint32_t c = static_cast<uint32_t>(row[j]);
        if (c == 0) continue;
        const uint32_t s = route_owner(c, d, shift);
        if (s >= n || at[s] >= ks) return 1;
        const int64_t o = (static_cast<int64_t>(s) * ks + at[s]++) * kBlock + i;
        bi[o] = static_cast<int32_t>(c - s * d);
        bv[o] = vrow[j];
      }
    }
    for (int64_t q = 0; q < lanes; ++q) {
      std::memcpy(ridx + q * b + i0, &bi[q * kBlock], rows * sizeof(int32_t));
      std::memcpy(rval + q * b + i0, &bv[q * kBlock], rows * sizeof(float));
    }
  }
  return 0;
}

}  // extern "C"
