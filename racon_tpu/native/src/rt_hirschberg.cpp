#include "rt_hirschberg.hpp"

#include <algorithm>
#include <cstring>

namespace rt {

namespace {

constexpr int32_t kInf = 1 << 28;  // align_pallas.INF
constexpr int32_t kPadCode = 255;  // outside a staged target window

}  // namespace

int64_t hirschberg_pack(const int64_t* pairs, const int32_t* tasks,
                        uint64_t n_slots, int32_t rcap, int32_t K,
                        bool backward, uint32_t q_words, int32_t* scal,
                        int32_t* qs, int32_t* ts) {
  const int64_t tcap = static_cast<int64_t>(rcap) + K;
  for (uint64_t b = 0; b < n_slots; ++b) {
    const int32_t* task = tasks + b * kHirschbergTaskCols;
    int32_t* sc = scal + b * 4;
    int32_t* qrow = qs + b * q_words;
    int32_t* trow = ts + b * tcap;
    std::memset(sc, 0, 4 * sizeof(int32_t));
    std::memset(qrow, 0, q_words * sizeof(int32_t));
    std::fill(trow, trow + tcap, kPadCode);
    if (task[0] < 0) {
      continue;
    }
    const int64_t* pair = pairs + static_cast<int64_t>(task[0]) *
                                      kHirschbergPairCols;
    const int32_t* q = reinterpret_cast<const int32_t*>(pair[0]);
    const int32_t* t = reinterpret_cast<const int32_t*>(pair[1]);
    const int64_t n = pair[2], m = pair[3], gdmin = pair[4];
    const int64_t ia = task[1], ib = task[2], ja = task[3], jb = task[4];
    const int64_t R = ib - ia;
    const int64_t j_lo = backward ? std::max(ja, ia + gdmin) : ja;
    const int64_t j_hi = backward ? jb : std::min(jb, ib + gdmin + K);
    const int64_t S = j_hi - j_lo;
    if (ia < 0 || R < 0 || ib > n || R > rcap || ja < 0 || jb > m || S < 0 ||
        S > tcap || (R + 3) / 4 > static_cast<int64_t>(q_words)) {
      return static_cast<int64_t>(b);
    }
    const int64_t dmin = gdmin + ia - j_lo;
    sc[0] = static_cast<int32_t>(R);
    sc[1] = static_cast<int32_t>(S);
    sc[2] = static_cast<int32_t>(dmin);
    // query codes, one byte each, four to a word (encoding.pack_bases);
    // a backward launch reads q[R - 1 - k] at index k
    for (int64_t k = 0; k < R; ++k) {
      const uint32_t code = static_cast<uint32_t>(
          q[backward ? ib - 1 - k : ia + k]) & 0xFFu;
      qrow[k >> 2] = static_cast<int32_t>(
          static_cast<uint32_t>(qrow[k >> 2]) | (code << (8 * (k & 3))));
    }
    // forward ts[x] = t[j_lo + x + dmin]; backward ts[z] = t[j_lo + z -
    // rcap + R - 1 + dmin]
    const int64_t shift = dmin + (backward ? R - 1 - rcap : 0);
    const int64_t lo = std::max<int64_t>(0, -shift);
    const int64_t hi = std::min(tcap, S - shift);
    if (hi > lo) {
      std::memcpy(trow + lo, t + j_lo + lo + shift,
                  static_cast<size_t>(hi - lo) * sizeof(int32_t));
    }
  }
  return -1;
}

void hirschberg_select(const int32_t* F, const int32_t* Bv, uint32_t K,
                       const int32_t* rows, const int32_t* lo,
                       const int32_t* hi, uint64_t n, int32_t* lane,
                       int32_t* tot) {
  for (uint64_t i = 0; i < n; ++i) {
    const int32_t* f = F + static_cast<int64_t>(rows[i]) * K;
    const int32_t* b = Bv + static_cast<int64_t>(rows[i]) * K;
    const int32_t first = std::max(lo[i], 0);
    const int32_t last = std::min<int64_t>(hi[i], static_cast<int64_t>(K) - 1);
    int32_t best = 2 * kInf, at = -1;
    for (int32_t o = first; o <= last; ++o) {
      const int32_t v = f[o] + b[o];
      if (v < best) {
        best = v;
        at = o;
      }
    }
    lane[i] = at;
    tot[i] = best;
  }
}

void hirschberg_gather(const int64_t* src, const int32_t* cnt, uint64_t n,
                       bool reverse, int32_t* out) {
  for (uint64_t s = 0; s < n; ++s) {
    const int32_t* from = reinterpret_cast<const int32_t*>(src[s]);
    out = reverse ? std::reverse_copy(from, from + cnt[s], out)
                  : std::copy(from, from + cnt[s], out);
  }
}

int64_t ops_to_cigars(const int32_t* ops, const uint64_t* off, uint64_t n,
                      char* out, uint64_t* out_off) {
  char* w = out;
  out_off[0] = 0;
  for (uint64_t p = 0; p < n; ++p) {
    uint64_t s = off[p];
    const uint64_t end = off[p + 1];
    while (s < end) {
      const int32_t op = ops[s];
      if (op < 0 || op > 2) {
        return -1;
      }
      uint64_t e = s + 1;
      while (e < end && ops[e] == op) {
        ++e;
      }
      char digits[20];
      int nd = 0;
      for (uint64_t len = e - s; len > 0; len /= 10) {
        digits[nd++] = static_cast<char>('0' + len % 10);
      }
      while (nd > 0) {
        *w++ = digits[--nd];
      }
      *w++ = "MID"[op];
      s = e;
    }
    out_off[p + 1] = static_cast<uint64_t>(w - out);
  }
  return static_cast<int64_t>(w - out);
}

}  // namespace rt
