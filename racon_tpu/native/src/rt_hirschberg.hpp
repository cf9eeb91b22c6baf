// Host bookkeeping of the device's Hirschberg aligner
// (racon_tpu/ops/align_pallas.py), one call per kernel launch.
//
// The Python driver keeps a round's tasks as a table of int32 rows
// (pair, ia, ib, ja, jb: query rows [ia, ib) against target columns
// [ja, jb] of pair `pair`; pair < 0 is a pad slot) and a table of int64
// rows per pair (address of its int32 query codes, address of its target
// codes, n, m, gdmin).  What it did per task in Python — staging a
// launch's arrays, picking each task's crossing column, copying traced-back
// op codes out, run-length encoding them — is done here per launch.
// Nothing here allocates or throws; every buffer is the caller's.
#pragma once

#include <cstdint>

namespace rt {

constexpr int kHirschbergPairCols = 5;
constexpr int kHirschbergTaskCols = 5;

// Stage one launch of `n_slots` tasks for the edge kernels (forward or
// backward) or the base kernel (forward): scal [n_slots, 4] = (R, S, dmin,
// 0), qs [n_slots, q_words] the query rows packed 4 codes to a word
// (reversed for a backward launch), ts [n_slots, rcap + K] the target
// window clipped to the half's band-reachable columns and pre-shifted by
// what differs per task, 255 outside it.  A pad slot is all zero (R = 0)
// with a target row of 255.  Returns -1, or the first slot whose task does
// not fit its pair or the launch's geometry (nothing is written for it).
int64_t hirschberg_pack(const int64_t* pairs, const int32_t* tasks,
                        uint64_t n_slots, int32_t rcap, int32_t K,
                        bool backward, uint32_t q_words, int32_t* scal,
                        int32_t* qs, int32_t* ts);

// The crossing lane of `n` tasks from the edge kernels' last rows F and Bv
// ([*, K], row `rows[i]` is task i's): the first lane in [lo[i], hi[i]]
// (clipped to [0, K)) with the least F + Bv.  lane[i] = -1 and tot[i] =
// 2 * INF where the range is empty.
void hirschberg_select(const int32_t* F, const int32_t* Bv, uint32_t K,
                       const int32_t* rows, const int32_t* lo,
                       const int32_t* hi, uint64_t n, int32_t* lane,
                       int32_t* tot);

// Lay `n` segments of int32 codes back to back in `out` (sum of cnt codes):
// segment s is cnt[s] codes at address src[s], back to front if `reverse`.
void hirschberg_gather(const int64_t* src, const int32_t* cnt, uint64_t n,
                       bool reverse, int32_t* out);

// Run-length encode the forward op codes (0 = M, 1 = I, 2 = D) of `n` pairs,
// pair p = ops[off[p], off[p + 1]), as CIGAR strings written back to back
// into `out` (the caller gives 2 * off[n] bytes: a run is never longer than
// twice its ops); out_off[p], out_off[p + 1] bound pair p's string.
// Returns the bytes written, or -1 at a code above 2.
int64_t ops_to_cigars(const int32_t* ops, const uint64_t* off, uint64_t n,
                      char* out, uint64_t* out_off);

}  // namespace rt
