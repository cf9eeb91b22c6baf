"""Isolate the per-node cost of the fused POA kernel's DP loop on the
current backend (meant for the real TPU).

Builds stripped-down Pallas kernels that run a rank-ordered DP loop,
adding back one cost component per mode, and times each.  Modes 0-8 are
the one-window-per-program row layout (the tier removed in PR 31; kept
as the baseline that shows why the lockstep layout exists), modes 9-12
and 17-18 the lockstep layout of poa_pallas_ls.py, 13-16 the aligner's:

  mode 0: H-row math only (shift + cummax + write), node index = loop rank
  mode 1: + dynamic node index via the masked `order` load
  mode 2: + base/in_cnt masked loads
  mode 3: + a 2-edge predecessor scan (edge-row load, key check, H row
            reads, running max)
  mode 4: + the has_out masked RMW per edge
  mode 5: mode 0 with the cross-sublane roll steps REMOVED (wrong result,
          right shape) — isolates the cost of pltpu.roll(axis=0)
  mode 6: mode 0 on a flat (1, 8*JW) row layout (lane rolls only, 8x the
          vregs per op) — the v1-style row to compare against
  mode 7: mode 0 with radix-4 lane / radix-8 sublane scans — same work,
          ~half the dependency-chain depth (tests the latency-bound
          hypothesis)
  mode 8: mode 0 on PAIRED rows (2, 8, JW): two independent DP chains per
          iteration in double-width ops — tests pipeline ILP from wider
          vregs (per_node accounts for the 2x rows)
  mode 9: the v3 LANE-LOCKSTEP row shape (poa_pallas_ls.py): (JC, 8, 128)
          rows — window g in sublane g — with lane-radix-4 + chunk-prefix
          cummax and a 128-row VMEM ring write; 8 windows per iteration
          (per_node accounts for the 8x)
  mode 10: mode 9 + a depth-4 delta scan (4 ring-row loads, masked max)
          and 12 exr-style (1,8,128) graph-row loads per rank — the
          ls dp_body's per-rank load traffic
  mode 12: mode 9 under the ls RANK-PAIR loop (poa_pallas_ls.py
          pair_body): two unconditional dp steps per iteration (there
          is no mode 11: it timed the removed tier's column pairing)
  mode 13: the aligner band-loop baseline — a (1, 128) band row carried
          in registers, one scalar query-code load (masked loadn) and
          one shift+select recurrence per DP row
  mode 14: mode 13 PACKED (align_pallas.py pack path): one packed-word
          loadn per iteration, 4 byte-extracted rows scored per step —
          the serial trip count drops to ceil(R / 4)
  mode 15: banded-aligner FLAT baseline — the mode-13 recurrence on a
          full 1024-lane (8, 128) band row; the counter output returns
          IN-LOOP CELLS (lanes scored per DP row), not iterations
  mode 16: mode 15 on the banded 128-lane rung (ops/band.py ladder
          floor), band offset advancing along the diagonal per row —
          8x fewer in-loop cells
  mode 17: banded-POA FLAT baseline — an ls-shape rank row of 13 lane
          chunks (1664 columns) with chunk-prefix cummax and a VMEM
          ring write; counter returns in-loop cells per rank
  mode 18: mode 17 BANDED: only a 4-chunk window around the rank's
          backbone column is read/scored/written (`pl.ds(cb0, CB)`
          windowed ring access) — 13/4 = 3.25x fewer in-loop cells

mode 4 approximates a full one-window dp_body; mode 10 approximates the
ls dp_body. The deltas between modes say which component to attack next;
per-node microseconds are printed for each.

Every kernel also returns a MEASURED in-loop count via a second SMEM
output — serial loop iterations for modes 0-14, scored DP cells for
the banded modes 15-18 — and `--gate` compares the compressed modes
against their baselines on those measured counts, exiting nonzero
unless the ratios clear the floors (12 vs 9: >= 1.5x steps; 14 vs
13: >= 2x steps; 16 vs 15 and 18 vs 17: >= 3x cells, the
RACON_TPU_BAND acceptance floor for BOTH hot kernels).
Interpret-mode safe: the gate measures counts, not wall time, so CI
runs it on CPU.

Usage: python racon_tpu/tools/dp_cost_probe.py [R] [B] [reps]
       python racon_tpu/tools/dp_cost_probe.py --gate
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import numpy as np

from racon_tpu.ops.kernel_cache import device_keyed_cache

NEG = -(1 << 28)


@device_keyed_cache(maxsize=32)
def build(mode: int, R: int, B: int, interpret: bool):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    JW = 128
    NW = 256
    E = 12
    G = -8
    JC = 4       # lane chunks per lockstep row (modes 9/10)
    RING = 128   # lockstep H ring rows (modes 9/10)
    GSLOTS = 16  # lockstep graph-row slots (mode 10 dynamic loads)
    JC2 = 13     # banded-POA flat row chunks, 1664 cols (modes 17/18)
    CB = 4       # banded-POA live window chunks (mode 18)
    RING2 = 8    # banded-POA H ring rows (modes 17/18)

    def kernel(seed_ref, out_ref, steps_ref, H, order, base, key, in_cnt,
               in_src, has_out, gls):
        jlane = jax.lax.broadcasted_iota(jnp.int32, (8, JW), 1)
        jsub = jax.lax.broadcasted_iota(jnp.int32, (8, JW), 0)
        jj = jsub * JW + jlane
        nlane = jax.lax.broadcasted_iota(jnp.int32, (8, NW), 1)
        nsub = jax.lax.broadcasted_iota(jnp.int32, (8, NW), 0)
        nn_i = nsub * NW + nlane
        gvec = jj * G

        def loadn(tile, idx):
            return jnp.sum(jnp.where(nn_i == idx, tile,
                                     jnp.zeros_like(tile)))

        def eload(ref, e, u):
            row = ref[pl.ds(e, 1)][0]
            return jnp.sum(jnp.where(nn_i == u, row, jnp.zeros_like(row)))

        def shift1(x, fill):
            ln = pltpu.roll(x, 1, 1)
            if mode == 5:
                y = ln
            else:
                carry = pltpu.roll(ln, 1, 0)
                y = jnp.where(jlane == 0, carry, ln)
            return jnp.where(jj == 0, fill, y)

        def tree_max(xs):
            while len(xs) > 1:
                nxt = [jnp.maximum(a, b) for a, b in zip(xs[::2], xs[1::2])]
                if len(xs) % 2:
                    nxt.append(xs[-1])
                xs = nxt
            return xs[0]

        def cummaxj(x):
            if mode == 7:
                # radix-4 lane prefix: rounds of 3 independent shifted
                # copies, tree-combined (shallower chain than 7 binary
                # rounds)
                w = 1
                while w < JW:
                    shs = [jnp.where(jlane >= k * w,
                                     pltpu.roll(x, k * w, 1), NEG)
                           for k in (1, 2, 3) if k * w < JW]
                    x = tree_max([x] + shs)
                    w *= 4
            else:
                k = 1
                while k < JW:
                    x = jnp.maximum(
                        x, jnp.where(jlane >= k, pltpu.roll(x, k, 1), NEG))
                    k *= 2
            if mode == 5:
                return x
            tot = jnp.max(x, axis=1, keepdims=True)
            p = jnp.broadcast_to(tot, x.shape)
            if mode == 7:
                # radix-8 sublane exclusive prefix: 7 independent shifted
                # copies, tree-combined (row 0 is NEG by the jsub masks)
                return jnp.maximum(x, tree_max(
                    [jnp.where(jsub >= k, pltpu.roll(p, k, 0), NEG)
                     for k in range(1, 8)]))
            k = 1
            while k < 8:
                p = jnp.maximum(
                    p, jnp.where(jsub >= k, pltpu.roll(p, k, 0), NEG))
                k *= 2
            excl = jnp.where(jsub >= 1, pltpu.roll(p, 1, 0), NEG)
            return jnp.maximum(x, excl)

        FW = 8 * JW

        def shift1_flat(x, fill):
            flane = jax.lax.broadcasted_iota(jnp.int32, (1, FW), 1)
            return jnp.where(flane == 0, fill, pltpu.roll(x, 1, 1))

        def cummax_flat(x):
            flane = jax.lax.broadcasted_iota(jnp.int32, (1, FW), 1)
            k = 1
            while k < FW:
                x = jnp.maximum(
                    x, jnp.where(flane >= k, pltpu.roll(x, k, 1), NEG))
                k *= 2
            return x

        if mode == 6:
            flane = jax.lax.broadcasted_iota(jnp.int32, (1, FW), 1)
            gflat = flane * G
            H[0:1] = (gflat + seed_ref[0, 0, 0]).reshape(1, 1, FW)

            def dp_flat(r, c):
                P = H[pl.ds(r, 1)][0]
                scvec = jnp.where(flane % 4 == 1, 5, -4)
                diag = shift1_flat(P, NEG) + scvec
                up = P + G
                V = jnp.where(diag >= up, diag, up)
                row = cummax_flat(V - gflat) + gflat
                H[pl.ds(r + 1, 1)] = row.reshape(1, 1, FW)
                return c + 1

            steps_ref[0, 0, 0] = jax.lax.fori_loop(0, R, dp_flat, 0)
            out_ref[0, 0, 0] = H[pl.ds(R, 1)][0][0, 0]
            return

        if mode == 8:
            psub = jax.lax.broadcasted_iota(jnp.int32, (2, 8, JW), 1)
            plane = jax.lax.broadcasted_iota(jnp.int32, (2, 8, JW), 2)
            jj2 = psub * JW + plane
            gp = jj2 * G
            H[0:1] = (gp + seed_ref[0, 0, 0]).reshape(1, 2, 8, JW)

            def shift1_pair(x, fill):
                ln = pltpu.roll(x, 1, 2)
                carry = pltpu.roll(ln, 1, 1)
                y = jnp.where(plane == 0, carry, ln)
                return jnp.where(jj2 == 0, fill, y)

            def cummax_pair(x):
                k = 1
                while k < JW:
                    x = jnp.maximum(
                        x, jnp.where(plane >= k, pltpu.roll(x, k, 2), NEG))
                    k *= 2
                tot = jnp.max(x, axis=2, keepdims=True)
                p = jnp.broadcast_to(tot, x.shape)
                k = 1
                while k < 8:
                    p = jnp.maximum(
                        p, jnp.where(psub >= k, pltpu.roll(p, k, 1), NEG))
                    k *= 2
                excl = jnp.where(psub >= 1, pltpu.roll(p, 1, 1), NEG)
                return jnp.maximum(x, excl)

            def dp_pair(r, c):
                P = H[pl.ds(r, 1)][0]                  # (2, 8, JW)
                scvec = jnp.where(jj2 % 4 == 1, 5, -4)
                diag = shift1_pair(P, NEG) + scvec
                up = P + G
                V = jnp.where(diag >= up, diag, up)
                row = cummax_pair(V - gp) + gp
                H[pl.ds(r + 1, 1)] = row.reshape(1, 2, 8, JW)
                return c + 1

            steps_ref[0, 0, 0] = jax.lax.fori_loop(0, R, dp_pair, 0)
            out_ref[0, 0, 0] = H[pl.ds(R, 1)][0][0, 0, 0]
            return

        if mode in (9, 10, 12):
            # v3 lane-lockstep row shape: (JC, 8, 128), window g in
            # sublane g; ring of RING H rows; lane-radix-4 + chunk-prefix
            # cummax (no cross-sublane carries — windows are independent)
            llane = jax.lax.broadcasted_iota(jnp.int32, (JC, 8, 128), 2)
            lchunk = jax.lax.broadcasted_iota(jnp.int32, (JC, 8, 128), 0)
            ljj = lchunk * 128 + llane
            lg = ljj * G
            # the delta scan reads ring rows before the DP has written
            # them (r < RING): every slot must hold defined, seed-derived
            # data, or uninitialized VMEM poisons the chain on real TPU
            # (interpret mode zero-fills and would hide it)
            ring_i = jax.lax.broadcasted_iota(
                jnp.int32, (RING, JC, 8, 128), 0)
            H[:] = lg[None] + seed_ref[0, 0, 0] - ring_i

            def shiftr_ls(x, fill):
                ln = pltpu.roll(x, 1, 2)
                carry = pltpu.roll(ln, 1, 0)
                y = jnp.where(llane == 0, carry, ln)
                return jnp.where(ljj == 0, fill, y)

            def cummax_ls(x):
                w = 1
                while w < 128:
                    shs = [jnp.where(llane >= k * w,
                                     pltpu.roll(x, k * w, 2), NEG)
                           for k in (1, 2, 3) if k * w < 128]
                    x = tree_max([x] + shs)
                    w *= 4
                tot = jnp.max(x, axis=2, keepdims=True)
                p = jnp.broadcast_to(tot, x.shape)
                acc = jnp.full(x.shape, NEG, jnp.int32)
                for k in range(1, JC):
                    acc = jnp.maximum(
                        acc, jnp.where(lchunk >= k, pltpu.roll(p, k, 0),
                                       NEG))
                return jnp.maximum(x, acc)

            # graph-row slots standing in for rk_base/rk_delta[e]/rk_dmax
            # — real (rank-derived) content so the loads cannot fold away
            gl_lane = jax.lax.broadcasted_iota(
                jnp.int32, (GSLOTS, 8, 128), 2)
            gl_slot = jax.lax.broadcasted_iota(
                jnp.int32, (GSLOTS, 8, 128), 0)
            gls[:] = (gl_lane + gl_slot) % 7

            def dp_ls(r):
                P = H[pl.ds(r % RING, 1)][0]           # (JC, 8, 128)
                if mode == 10:
                    # exr-style per-rank graph loads: a DYNAMIC-index
                    # (1,8,128) row slice + lane mask each, like
                    # dp_body's ref[pl.ds(r // 128, 1)] reads
                    lane1p = jax.lax.broadcasted_iota(
                        jnp.int32, (8, 128), 1)
                    acc = jnp.int32(0)
                    for e in range(E):
                        c = gls[pl.ds((r + e) % GSLOTS, 1)][0]
                        acc = acc + jnp.sum(
                            jnp.where(lane1p == (r % 128), c, 0))
                    # depth-4 delta scan: prior ring rows, masked max;
                    # acc (from the loads) feeds both the scan depth and
                    # the row below, so the loads are not eliminable
                    def dscan(d, Pm):
                        prow = H[pl.ds((r - d) % RING, 1)][0]
                        return jnp.where(d <= (acc % 4) + 1,
                                         jnp.maximum(Pm, prow), Pm)
                    P = jax.lax.fori_loop(1, 5, dscan, P)
                    P = P + (acc & 1)
                scvec = jnp.where(ljj % 4 == 1, 5, -4)
                diag = shiftr_ls(P, NEG) + scvec
                up = P + G
                V = jnp.where(diag >= up, diag, up)
                row = cummax_ls(V - lg) + lg
                H[pl.ds((r + 1) % RING, 1)] = row.reshape(1, JC, 8, 128)

            if mode == 12:
                # the ls pair loop: two unconditional ranks per serial
                # iteration (poa_pallas_ls.py pair_body), trailing rank
                # guarded for odd R
                def pair_ls(p, c):
                    r = 2 * p
                    dp_ls(r)

                    @pl.when(r + 1 < R)
                    def _():
                        dp_ls(r + 1)

                    return c + 1

                iters = jax.lax.fori_loop(0, (R + 1) // 2, pair_ls, 0)
            else:
                def one_ls(r, c):
                    dp_ls(r)
                    return c + 1

                iters = jax.lax.fori_loop(0, R, one_ls, 0)
            steps_ref[0, 0, 0] = iters
            hr = H[pl.ds(R % RING, 1)][0]
            out_ref[0, 0, 0] = hr[0, 0, 0] + hr[0, 0, 1]
            return

        if mode in (13, 14):
            # aligner band-loop shape: one (1, 128) band row carried in
            # registers, shift + select recurrence per DP row (the
            # Hirschberg edge kernel's serial chain without its DMA).
            # mode 13 loads one scalar query code per row; mode 14 loads
            # one packed word per iteration and scores 4 byte-extracted
            # rows (align_pallas.py pack path)
            alane = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1)
            row0 = alane * G + seed_ref[0, 0, 0]

            def astep(qc, row):
                scvec = jnp.where(alane % 5 == qc, 5, -4)
                dshift = jnp.where(alane == 0, NEG, pltpu.roll(row, 1, 1))
                diag = dshift + scvec
                up = row + G
                return jnp.where(diag >= up, diag, up)

            if mode == 13:
                base[:] = nn_i % 5         # query codes, one per slot

                def arow(i, c):
                    row, s = c
                    qc = loadn(base[:], i)
                    return (astep(qc, row), s + 1)

                row, iters = jax.lax.fori_loop(
                    0, R, arow, (row0, jnp.int32(0)))
            else:
                # slot w holds codes 4w..4w+3, one byte each (the
                # encoding.pack_bases layout)
                pw = jnp.zeros_like(nn_i)
                for p in range(4):
                    pw = pw + (((4 * nn_i + p) % 5) << (8 * p))
                base[:] = pw

                def arow4(it, c):
                    row, s = c
                    qword = loadn(base[:], it)
                    for p in range(4):
                        i = it * 4 + p
                        qc = (qword >> (8 * p)) & 0xFF
                        row = jnp.where(i < R, astep(qc, row), row)
                    return (row, s + 1)

                row, iters = jax.lax.fori_loop(
                    0, (R + 3) // 4, arow4, (row0, jnp.int32(0)))
            steps_ref[0, 0, 0] = iters
            out_ref[0, 0, 0] = row[0, 0] + row[0, 1]
            return

        if mode in (15, 16):
            # banded-aligner CELL gate (ops/band.py): mode 15 scores a
            # full 1024-lane (8, 128) band row per DP row; mode 16 keeps
            # the 128-lane banded rung, its lane->column mapping
            # advancing one diagonal per row (the Ukkonen band offset).
            # The counter output is IN-LOOP CELLS, not iterations — the
            # serial chain length is identical by construction (banding
            # narrows live lanes per row, it does not shorten the row
            # chain), which is exactly the claim the cost model makes.
            AS = 8 if mode == 15 else 1
            blane = jax.lax.broadcasted_iota(jnp.int32, (AS, 128), 1)
            bsub = jax.lax.broadcasted_iota(jnp.int32, (AS, 128), 0)
            bjj = bsub * 128 + blane
            row0 = bjj * G + seed_ref[0, 0, 0]
            base[:] = nn_i % 5             # query codes, one per slot

            def bstep(r, c):
                row, cells = c
                qc = loadn(base[:], r)
                # mode 16: lane j of the banded row is global column
                # j + r (band advances along the main diagonal)
                col = bjj + (r if mode == 16 else 0)
                scvec = jnp.where(col % 5 == qc, 5, -4)
                ln = pltpu.roll(row, 1, 1)
                if AS > 1:
                    carry = pltpu.roll(ln, 1, 0)
                    ln = jnp.where(blane == 0, carry, ln)
                dshift = jnp.where(bjj == 0, NEG, ln)
                diag = dshift + scvec
                up = row + G
                return (jnp.where(diag >= up, diag, up),
                        cells + AS * 128)

            row, cells = jax.lax.fori_loop(
                0, R, bstep, (row0, jnp.int32(0)))
            steps_ref[0, 0, 0] = cells
            out_ref[0, 0, 0] = row[0, 0] + row[0, 1]
            return

        if mode in (17, 18):
            # banded-POA CELL gate: ls-shape rank rows of JC2 lane
            # chunks (13 * 128 = 1664 columns, the production wl-class).
            # Mode 17 reads/scores/writes all 13 chunks per rank; mode
            # 18 touches only a CB-chunk window around the rank's
            # backbone column via `pl.ds(cb0, CB)` on a flattened
            # (RING2 * JC2, ...) ring — the windowed access pattern of
            # the banded POA kernels.  Counter output is in-loop cells.
            W = JC2 if mode == 17 else CB
            wlane = jax.lax.broadcasted_iota(jnp.int32, (W, 8, 128), 2)
            wchunk = jax.lax.broadcasted_iota(jnp.int32, (W, 8, 128), 0)
            wjj = wchunk * 128 + wlane
            wg = wjj * G
            # every ring slot holds defined, seed-derived data (mode 18
            # reads windows row r+1 never wrote; see modes 9/10 note)
            ring_i = jax.lax.broadcasted_iota(
                jnp.int32, (RING2 * JC2, 8, 128), 0)
            H[:] = ring_i % 97 + seed_ref[0, 0, 0]

            def wshift(x, fill):
                ln = pltpu.roll(x, 1, 2)
                carry = pltpu.roll(ln, 1, 0)
                y = jnp.where(wlane == 0, carry, ln)
                return jnp.where(wjj == 0, fill, y)

            def wcummax(x):
                w = 1
                while w < 128:
                    shs = [jnp.where(wlane >= k * w,
                                     pltpu.roll(x, k * w, 2), NEG)
                           for k in (1, 2, 3) if k * w < 128]
                    x = tree_max([x] + shs)
                    w *= 4
                tot = jnp.max(x, axis=2, keepdims=True)
                p = jnp.broadcast_to(tot, x.shape)
                acc = jnp.full(x.shape, NEG, jnp.int32)
                for k in range(1, W):
                    acc = jnp.maximum(
                        acc, jnp.where(wchunk >= k, pltpu.roll(p, k, 0),
                                       NEG))
                return jnp.maximum(x, acc)

            def wrow(r, cells):
                # window origin tracks the rank's backbone column
                cb0 = jnp.clip(r * JC2 // R - CB // 2, 0, JC2 - W)
                P = H[pl.ds((r % RING2) * JC2 + cb0, W)]
                scvec = jnp.where(wjj % 4 == 1, 5, -4)
                diag = wshift(P, NEG) + scvec
                up = P + G
                V = jnp.where(diag >= up, diag, up)
                row = wcummax(V - wg) + wg
                H[pl.ds(((r + 1) % RING2) * JC2 + cb0, W)] = row
                return cells + W * 128

            cells = jax.lax.fori_loop(0, R, wrow, jnp.int32(0))
            steps_ref[0, 0, 0] = cells
            hr = H[pl.ds((R % RING2) * JC2, 1)][0]
            out_ref[0, 0, 0] = hr[0, 0] + hr[0, 1]
            return

        # graph state init (content irrelevant; loads must be real)
        order[:] = nn_i
        base[:] = nn_i % 4
        key[:] = nn_i.astype(jnp.float32)
        in_cnt[:] = jnp.where(nn_i > 0, 2, 0)
        in_src[:] = jnp.zeros((E, 8, NW), jnp.int32)
        in_src[0:1] = jnp.maximum(nn_i - 1, 0).reshape(1, 8, NW)
        in_src[1:2] = jnp.maximum(nn_i - 2, 0).reshape(1, 8, NW)
        has_out[:] = jnp.zeros((8, NW), jnp.int32)
        # runtime seed keeps XLA from constant-folding the whole call
        H[0:1] = (gvec + seed_ref[0, 0, 0]).reshape(1, 8, JW)

        # modes 5 and 7 are row-math variants of mode 0: no graph-state
        # machinery, or their deltas vs mode 0 would be confounded
        level = 0 if mode in (5, 7) else mode

        def dp_work(r):
            if level >= 1:
                u = loadn(order[:], r)
            else:
                u = r
            if level >= 2:
                ub = loadn(base[:], u)
                cnt = loadn(in_cnt[:], u)
            else:
                ub = jnp.int32(1)
                cnt = jnp.int32(0)

            if level >= 3:
                def pred_scan(e, c):
                    P, any_valid = c
                    src = eload(in_src, e, u)
                    ok = loadn(key[:], jnp.maximum(src, 0)) >= 0.0
                    prow = H[pl.ds(jnp.maximum(src, 0) + 1, 1)][0]
                    better = ok & (prow > P)
                    P = jnp.where(better, prow, P)
                    if level >= 4:
                        @pl.when(ok)
                        def _():
                            has_out[:] = jnp.where(
                                nn_i == jnp.maximum(src, 0), 1, has_out[:])
                    return (P, any_valid | ok)

                P0 = jnp.full((8, JW), NEG, jnp.int32)
                P, any_valid = jax.lax.fori_loop(0, cnt, pred_scan,
                                                 (P0, jnp.bool_(False)))
                # virtual-row fallback, as in the real kernel — without it
                # zero-pred nodes saturate the whole chain to NEG
                P = jnp.where(any_valid, P, H[0:1][0])
            else:
                P = H[pl.ds(jnp.maximum(u, 0), 1)][0]

            scvec = jnp.where(jj % 4 == ub, 5, -4)
            Psh = shift1(P, NEG)
            diag = Psh + scvec
            up = P + G
            V = jnp.where(diag >= up, diag, up)
            row = cummaxj(V - gvec) + gvec
            H[pl.ds(u + 1, 1)] = row.reshape(1, 8, JW)

        def dp(r, c):
            dp_work(r)
            return c + 1

        steps_ref[0, 0, 0] = jax.lax.fori_loop(0, R, dp, 0)
        # tap two lanes: a single lane can legitimately saturate to NEG in
        # the stripped-down modes, which would false-positive the seed check
        hr = H[pl.ds(R, 1)][0]
        out_ref[0, 0, 0] = hr[0, 0] + hr[0, 1]

    call = pl.pallas_call(
        kernel,
        grid=(B,),
        in_specs=[pl.BlockSpec((1, 1, 1), lambda b: (b, 0, 0),
                               memory_space=pltpu.SMEM)],
        out_specs=[pl.BlockSpec((1, 1, 1), lambda b: (b, 0, 0),
                                memory_space=pltpu.SMEM),
                   pl.BlockSpec((1, 1, 1), lambda b: (b, 0, 0),
                                memory_space=pltpu.SMEM)],
        out_shape=[jax.ShapeDtypeStruct((B, 1, 1), jnp.int32),
                   jax.ShapeDtypeStruct((B, 1, 1), jnp.int32)],
        scratch_shapes=[
            pltpu.VMEM((R + 1, 1, 8 * JW) if mode == 6 else
                       (R + 1, 2, 8, JW) if mode == 8 else
                       (RING, JC, 8, 128) if mode in (9, 10, 12) else
                       # flattened ring: leading dim = ring row * JC2 +
                       # chunk, so the banded window is ONE pl.ds slice
                       (RING2 * JC2, 8, 128) if mode in (17, 18) else
                       (R + 1, 8, JW), jnp.int32),   # H (ring, 9/10/12)
            pltpu.VMEM((8, NW), jnp.int32),          # order
            pltpu.VMEM((8, NW), jnp.int32),          # base
            pltpu.VMEM((8, NW), jnp.float32),        # key
            pltpu.VMEM((8, NW), jnp.int32),          # in_cnt
            pltpu.VMEM((E, 8, NW), jnp.int32),       # in_src
            pltpu.VMEM((8, NW), jnp.int32),          # has_out
            pltpu.VMEM((GSLOTS, 8, 128), jnp.int32),  # gls (modes 9/10)
        ],
        interpret=interpret,
    )
    return jax.jit(lambda seed: call(seed))


def gate(R: int = 32, B: int = 1) -> bool:
    """The CI gate: measured in-loop counts of the compressed modes vs
    their baselines — serial trip counts for the step-compression pairs,
    scored DP cells for the banded pairs (the RACON_TPU_BAND acceptance
    floor: >= 3x fewer cells on BOTH hot kernels).  Runs in interpret
    mode off-TPU (counts, not wall time, are the measurement), prints
    every ratio, returns False if any floor is missed."""
    import jax

    interp = jax.devices()[0].platform != "tpu"
    seed = np.zeros((B, 1, 1), np.int32)

    def steps_of(mode):
        _, steps = build(mode, R, B, interp)(seed)
        jax.block_until_ready(steps)
        return int(np.asarray(steps)[0, 0, 0])

    checks = (("poa-ls rank-pair", 9, 12, 1.5, "serial steps"),
              ("align row-pack", 13, 14, 2.0, "serial steps"),
              ("align banded-band", 15, 16, 3.0, "in-loop cells"),
              ("poa banded-window", 17, 18, 3.0, "in-loop cells"))
    ok = True
    for name, base_m, new_m, floor, unit in checks:
        b, n = steps_of(base_m), steps_of(new_m)
        ratio = b / n if n else float("inf")
        good = ratio >= floor
        ok = ok and good
        print(f"{name}: baseline mode {base_m} = {b} {unit}, "
              f"compressed mode {new_m} = {n}, measured ratio "
              f"{ratio:.2f}x (floor {floor}x) "
              f"{'OK' if good else 'FAIL'}")
    return ok


def main():
    if "--gate" in sys.argv[1:]:
        sys.exit(0 if gate() else 1)
    R = int(sys.argv[1]) if len(sys.argv) > 1 else 800
    B = int(sys.argv[2]) if len(sys.argv) > 2 else 16
    reps = int(sys.argv[3]) if len(sys.argv) > 3 else 3
    # the masked-load modes index node state by rank: ranks beyond the
    # (8, NW) slot capacity silently resolve to node 0 and break the
    # seed-dependence check below
    assert R <= 8 * 256 - 1, f"R={R} exceeds the 2047 node-slot capacity"

    import jax

    platform = jax.devices()[0].platform
    interp = platform != "tpu"
    print(f"platform={platform} R={R} B={B}")
    prev = 0.0
    for mode in (m for m in range(19) if m != 11):   # there is no mode 11
        fn = build(mode, R, B, interp)
        seed = np.zeros((B, 1, 1), np.int32)
        t0 = time.time()
        out, steps = fn(seed)
        jax.block_until_ready(out)
        first = time.time() - t0
        # sanity: the result must move with the seed, else the kernel was
        # folded away and the timing is fiction
        o1 = int(np.asarray(out)[0, 0, 0])
        o2 = int(np.asarray(fn(seed + 7)[0])[0, 0, 0])
        st = int(np.asarray(steps)[0, 0, 0])
        best = None
        for i in range(reps):
            t0 = time.time()
            jax.block_until_ready(fn(seed + i + 1))
            dt = time.time() - t0
            best = dt if best is None else min(best, dt)
        rows = R * B * (2 if mode == 8 else
                        8 if mode in (9, 10, 12, 17, 18) else 1)
        per_node_us = best / rows * 1e6
        folded = " [FOLDED? output ignores seed — timing is fiction]" \
            if o1 == o2 else ""
        print(f"mode={mode} first={first:.2f}s warm={best:.4f}s "
              f"per_node={per_node_us:.3f}us delta={per_node_us - prev:+.3f}"
              f"us steps={st} out(seed0)={o1} out(seed7)={o2}{folded}")
        prev = per_node_us


if __name__ == "__main__":
    main()
