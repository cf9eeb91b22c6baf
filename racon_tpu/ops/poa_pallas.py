"""Fused Pallas TPU kernel for batched POA window consensus.

Same semantics as the reference JAX implementation in poa.py (which mirrors
the host oracle rt_poa.cpp), but the entire per-window program — graph init,
per-layer sequence-to-graph DP, traceback, graph update, heaviest-bundle
consensus — runs as ONE kernel program per window (grid over the batch), with
the DP matrix and all graph state resident in VMEM.

Data layout (the v2 rework, after the first on-hardware measurements showed
~115 ms/window): every logical 1-D row is stored **sublane-blocked** as an
(8, W) tile with element i at (i // W, i % W) — so each vector op engages
all 8 VPU sublanes instead of 1-of-8 as a (1, N) row would:

  * DP/sequence rows (j in [0, L]):   (8, JW) — exactly one vreg at w=500
  * node/rank state  (u in [0, N)):   (8, NW) — two vregs at w=500
  * in-edge tables:                   (E, 8, NW), one dynamically indexed
    (8, NW) sublane-row per slot (the v1 layout mask-reduced the whole
    (E, N) array for every scalar edge read)

Layer sequences/weights stay in HBM (memory_space=ANY); each layer is DMA'd
into a double-buffered VMEM scratch slot while the previous layer's DP runs,
so VMEM residency is independent of the depth bucket (the v1 layout's
depth-200 bucket no longer threatens the ~16 MB core budget) and the copy
rides under compute.

Other deliberate choices, none semantic:
  * topological order is maintained incrementally (an O(N) vector
    shift-insert per new node) instead of argsort per layer; the subgraph is
    then a contiguous rank range [count(key < lo), min(count(key <= hi), n))
    — the min() clamp matters for full-graph layers, whose hi sentinel
    equals the unused-slot key sentinel and would otherwise sweep every
    node slot.
  * end-node detection reuses the DP's predecessor enumeration (any
    in-subgraph edge marks its source as "has out-edge").
  * the linear-gap cummax runs as lane-prefix + cross-sublane-prefix
    shift-max steps.
  * the DP rank loop steps per COLUMN, not per node (colstep=True,
    RACON_TPU_POA_COLSTEP): equal-key nodes are adjacent in rank order
    with no edges among themselves, so a same-column sibling is processed
    in the same iteration and the serial trip count is n_column_steps
    <= n_nodes (ops/colstep.py holds the host-side reference mapping).

VMEM budget (w=500 config: N=1536 -> NW=256, L=768 -> JW=128):
H and MV (1537, 8, 128) i32 ~6.3 MB each, node/edge state <0.3 MB, staged
layers 2 slots x 2 arrays x 4 KB — ~13 MB total for every depth bucket.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..device import named
from .kernel_cache import device_keyed_cache
from .poa import PoaConfig

NEG = -(1 << 28)


def _round_up(x, m):
    return (x + m - 1) // m * m


def blocked_width(n: int) -> int:
    """Lane width of the (8, W) sublane-blocked tile covering n elements."""
    return _round_up((n + 7) // 8, 128)


@device_keyed_cache(maxsize=32)
def build_pallas_poa_kernel(cfg: PoaConfig, interpret: bool = False,
                            colstep: bool = True, band: bool = False):
    N = cfg.max_nodes
    L = cfg.max_len
    BB = cfg.max_backbone
    E = cfg.max_edges
    D = cfg.depth
    JW = blocked_width(L + 1)           # j-dimension lanes per sublane row
    NW = blocked_width(N)               # node/rank lanes per sublane row
    SJ = 8 * JW                         # padded j capacity
    SN = 8 * NW                         # padded node-slot capacity
    # plain Python scalars: captured jnp values would become kernel constants
    M = int(cfg.match)
    X = int(cfg.mismatch)
    G = int(cfg.gap)
    KEY_INF = 3.0e38

    VSLOT = 15  # pred-slot sentinel meaning "virtual start row"

    # The banded build (band=True, RACON_TPU_BAND) adds one SMEM input
    # (wband: the per-window half-band width) and one SMEM output
    # (band_hit: traceback touched the band boundary, or the terminal
    # score's deficit exceeded the gap-cost bound — ops/band.py owns the
    # verify-and-widen ladder that consumes it).  Every band operation
    # is gated on the Python-level `band` flag so the flat build traces
    # to an unchanged jaxpr, and on `wband > 0` at runtime so a widened-
    # to-flat window (wband == 0) runs exact flat semantics through the
    # same compiled kernel.
    def kernel(*refs):
        if band:
            (bb_len_ref, n_layers_ref, lens_ref, begins_ref, ends_ref,
             bb_ref, bbw_ref, seqs_hbm, ws_hbm, wband_ref,
             cons_base_ref, cons_cov_ref, cons_len_ref, failed_ref,
             n_nodes_ref, band_hit_ref,
             H, MV, base, key, cov, order, in_src, in_w, in_cnt,
             nkey, runrem, score, pred, revbuf, esc, rank_of,
             seq_scr, w_scr, dma_sem) = refs
            wb = wband_ref[0, 0, 0]
        else:
            (bb_len_ref, n_layers_ref, lens_ref, begins_ref, ends_ref,
             bb_ref, bbw_ref, seqs_hbm, ws_hbm,
             cons_base_ref, cons_cov_ref, cons_len_ref, failed_ref,
             n_nodes_ref,
             H, MV, base, key, cov, order, in_src, in_w, in_cnt,
             nkey, runrem, score, pred, revbuf, esc, rank_of,
             seq_scr, w_scr, dma_sem) = refs
        jlane = jax.lax.broadcasted_iota(jnp.int32, (8, JW), 1)
        jsub = jax.lax.broadcasted_iota(jnp.int32, (8, JW), 0)
        jj = jsub * JW + jlane                      # j index per element
        nlane = jax.lax.broadcasted_iota(jnp.int32, (8, NW), 1)
        nsub = jax.lax.broadcasted_iota(jnp.int32, (8, NW), 0)
        nn_i = nsub * NW + nlane                    # node/rank index
        gvec = jj * G

        # Mosaic cannot store scalars to VMEM; every scalar store becomes a
        # masked tile read-modify-write, and every dynamic-position scalar
        # load a masked reduction. On the blocked layout each costs 1-2
        # vregs of VPU work.
        def rmwj(ref, idx, val):
            ref[:] = jnp.where(jj == idx, val, ref[:])

        def rmwn(ref, idx, val):
            ref[:] = jnp.where(nn_i == idx, val, ref[:])

        def loadj(tile, idx):
            return jnp.sum(jnp.where(jj == idx, tile, jnp.zeros_like(tile)))

        def loadn(tile, idx):
            return jnp.sum(jnp.where(nn_i == idx, tile,
                                     jnp.zeros_like(tile)))

        # in-edge tables: one dynamically indexed sublane-row per slot
        def eload(ref, e, u):
            row = ref[pl.ds(e, 1)][0]
            return jnp.sum(jnp.where(nn_i == u, row, jnp.zeros_like(row)))

        def ermw(ref, e, u, val):
            row = ref[pl.ds(e, 1)][0]
            ref[pl.ds(e, 1)] = jnp.where(nn_i == u, val,
                                         row).reshape(1, 8, NW)

        # masked increments: no scalar read-back needed
        def rmwn_add(ref, idx, delta):
            ref[:] = jnp.where(nn_i == idx, ref[:] + delta, ref[:])

        def ermw_add(ref, e, u, delta):
            row = ref[pl.ds(e, 1)][0]
            ref[pl.ds(e, 1)] = jnp.where(nn_i == u, row + delta,
                                         row).reshape(1, 8, NW)

        def shift1(x, iota2, lane, fill):
            # blocked shift: new[i] = old[i-1]; new[0] = fill
            ln = pltpu.roll(x, 1, 1)
            carry = pltpu.roll(ln, 1, 0)            # sublane roll
            y = jnp.where(lane == 0, carry, ln)
            return jnp.where(iota2 == 0, fill, y)

        def tree_max(xs):
            # balanced pairwise reduction: log2 depth independent of any
            # compiler reassociation of integer max
            while len(xs) > 1:
                nxt = [jnp.maximum(a, b) for a, b in zip(xs[::2], xs[1::2])]
                if len(xs) % 2:
                    nxt.append(xs[-1])
                xs = nxt
            return xs[0]

        def cummaxj(x):
            # prefix max over the blocked j line: radix-4 lane prefix
            # within each sublane row, then a radix-8 exclusive
            # cross-sublane prefix of the row maxima. Radix-4/8 does the
            # same work as the binary scan in about half the
            # dependency-chain depth (the shifted copies within a round
            # are independent, and tree_max keeps the combine log-deep) —
            # this loop is latency-bound, not throughput-bound
            # (docs/benchmarks.md, dp_cost_probe).
            w = 1
            while w < JW:
                shs = [jnp.where(jlane >= k * w,
                                 pltpu.roll(x, k * w, 1), NEG)
                       for k in (1, 2, 3) if k * w < JW]
                x = tree_max([x] + shs)
                w *= 4
            tot = jnp.max(x, axis=1, keepdims=True)  # (8, 1) row maxima
            p = jnp.broadcast_to(tot, (8, JW))
            # row 0 ends up NEG by construction: every copy is masked by
            # jsub >= k with k >= 1
            excl = tree_max([jnp.where(jsub >= k, pltpu.roll(p, k, 0), NEG)
                             for k in range(1, 8)])
            return jnp.maximum(x, excl)

        bb_len = bb_len_ref[0, 0, 0]
        n_layers = n_layers_ref[0, 0, 0]
        b_prog = pl.program_id(0)

        def start_copy(li, slot):
            pltpu.make_async_copy(seqs_hbm.at[b_prog, li],
                                  seq_scr.at[slot],
                                  dma_sem.at[slot, 0]).start()
            pltpu.make_async_copy(ws_hbm.at[b_prog, li],
                                  w_scr.at[slot],
                                  dma_sem.at[slot, 1]).start()

        def wait_copy(li, slot):
            pltpu.make_async_copy(seqs_hbm.at[b_prog, li],
                                  seq_scr.at[slot],
                                  dma_sem.at[slot, 0]).wait()
            pltpu.make_async_copy(ws_hbm.at[b_prog, li],
                                  w_scr.at[slot],
                                  dma_sem.at[slot, 1]).wait()

        # ---- graph init from the backbone chain --------------------------
        bbblk = bb_ref[0]                           # (8, NW), node-blocked
        used0 = nn_i < bb_len
        base[:] = jnp.where(used0, bbblk, -1)
        key[:] = jnp.where(used0, nn_i.astype(jnp.float32), KEY_INF)
        cov[:] = jnp.where(used0, 1, 0)
        order[:] = nn_i
        bbw_blk = bbw_ref[0]
        chain = (nn_i > 0) & used0
        in_src[:] = jnp.full((E, 8, NW), -1, jnp.int32)
        in_src[0:1] = jnp.where(chain, nn_i - 1, -1).reshape(1, 8, NW)
        in_w[:] = jnp.zeros((E, 8, NW), jnp.int32)
        in_w[0:1] = jnp.where(
            chain, shift1(bbw_blk, nn_i, nlane, 0) + bbw_blk,
            0).reshape(1, 8, NW)
        # edge slots fill contiguously from 0, so in_cnt doubles as "first
        # empty slot" and bounds every per-node slot loop to the true degree
        in_cnt[:] = jnp.where(chain, 1, 0)
        H[0:1] = gvec.reshape(1, 8, JW)

        # ---- one layer ----------------------------------------------------
        def do_layer(li, slot, carry):
            if band:
                n, failed, hit = carry
            else:
                n, failed = carry
            Ln = lens_ref[0, 0, li]
            begin = begins_ref[0, 0, li]
            end = ends_ref[0, 0, li]

            # full-graph rule (reference: src/window.cpp:88-97)
            offset = (0.01 * bb_len.astype(jnp.float32)).astype(jnp.int32)
            full = (begin < offset) & (end > bb_len - offset)
            lo = jnp.where(full, jnp.float32(-3.0e38),
                           begin.astype(jnp.float32))
            hi = jnp.where(full, jnp.float32(3.0e38), end.astype(jnp.float32))

            seqv = seq_scr[pl.ds(slot, 1)][0]        # (8, JW)
            wv = w_scr[pl.ds(slot, 1)][0]

            keys = key[:]
            r_lo = jnp.sum(jnp.where(keys < lo, 1, 0)).astype(jnp.int32)
            # clamp to n: for full layers hi == the unused-slot sentinel
            r_hi = jnp.minimum(
                jnp.sum(jnp.where(keys <= hi, 1, 0)).astype(jnp.int32), n)

            seqm1 = shift1(seqv, jj, jlane, 255)
            virt_row = H[0:1][0]        # loop-invariant: hoist out of dp_body

            # End-node selection is fused into the DP sweep: each node's
            # score at column Ln lands in esc (indexed by RANK, so "first
            # max in rank order" is just "lowest index among maxima"), and
            # gaining an in-subgraph out-edge cancels the source's slot —
            # predecessors always precede successors in rank order, so the
            # cancel never races the write. rank_of maps node id -> rank
            # for the cancel. This removes the separate end_body sweep.
            esc[:] = jnp.full((8, NW), NEG, jnp.int32)

            # ---- DP over subgraph nodes in rank order ---------------------
            # Per-cell move records (2 bits move + pred slot, VSLOT =
            # virtual) land in MV so the traceback is one load per step.
            def dp_body(r, _):
                u = loadn(order[:], r)
                ub = loadn(base[:], u)
                rmwn(rank_of, u, r)

                def pred_scan(e, c):
                    P, Pslot, any_valid = c
                    src = eload(in_src, e, u)
                    ok = loadn(key[:], jnp.maximum(src, 0)) >= lo
                    prow = H[pl.ds(jnp.maximum(src, 0) + 1, 1)][0]
                    better = ok & (prow > P)  # strict: first max slot wins
                    P = jnp.where(better, prow, P)
                    Pslot = jnp.where(better, e, Pslot)

                    @pl.when(ok)
                    def _():
                        # src has an out-edge inside the subgraph: not an
                        # end node
                        rmwn(esc, loadn(rank_of[:], jnp.maximum(src, 0)),
                             NEG)
                    return (P, Pslot, any_valid | ok)

                P0 = jnp.full((8, JW), NEG, jnp.int32)
                S0 = jnp.full((8, JW), VSLOT, jnp.int32)
                P, Pslot, any_valid = jax.lax.fori_loop(
                    0, loadn(in_cnt[:], u), pred_scan,
                    (P0, S0, jnp.bool_(False)))
                P = jnp.where(any_valid, P, virt_row)
                Pslot = jnp.where(any_valid, Pslot, VSLOT)

                scvec = jnp.where(seqm1 == ub, M, X)
                Psh = shift1(P, jj, jlane, NEG)
                Ssh = shift1(Pslot, jj, jlane, VSLOT)
                diag = Psh + scvec
                up = P + G
                choose_diag = diag >= up  # host priority: diag before up
                V = jnp.where(choose_diag, diag, up)
                vmove = jnp.where(choose_diag, 4 * Ssh, 1 + 4 * Pslot)
                row = cummaxj(V - gvec) + gvec
                if band:
                    # diagonal band: node u's expected column is its
                    # backbone key minus the layer's begin; cells more
                    # than wband off that center are masked to NEG, so
                    # later rows, the end-score pick and the traceback
                    # all see banded values
                    cexp = (loadn(key[:], u) + 0.5).astype(jnp.int32) - begin
                    row = jnp.where((wb > 0) & (jnp.abs(jj - cexp) > wb),
                                    NEG, row)
                # left only if strictly better
                mv = jnp.where(row > V, 2, vmove)
                H[pl.ds(u + 1, 1)] = row.reshape(1, 8, JW)
                MV[pl.ds(u + 1, 1)] = mv.reshape(1, 8, JW)
                rmwn(esc, r, loadj(row, Ln))
                return 0

            if colstep:
                # Column-compressed stepping: equal-key ("same column")
                # nodes are adjacent in rank order and have no edges among
                # themselves (ops/colstep.py documents the invariant), so a
                # same-column sibling can ride in the same loop iteration —
                # the trip count drops from n_ranks to n_column_steps.
                # Both nodes still execute in rank order inside the body,
                # so the result is byte-identical to the serial loop even
                # for graphs that violate the invariant (e.g. after an
                # overflow-failed update): rank r's H row / rank_of / esc
                # writes land before rank r+1 reads them.
                def col_cond(c):
                    return c < r_hi

                def col_body(r):
                    ku = loadn(key[:], loadn(order[:], r))
                    dp_body(r, 0)
                    k2 = loadn(key[:], loadn(order[:], r + 1))
                    pair = (r + 1 < r_hi) & (k2 == ku)

                    @pl.when(pair)
                    def _():
                        dp_body(r + 1, 0)

                    return r + 1 + pair.astype(jnp.int32)

                jax.lax.while_loop(col_cond, col_body, r_lo)
            else:
                jax.lax.fori_loop(r_lo, r_hi, dp_body, 0)

            # ---- best end node (first max in rank order) ------------------
            escv = esc[:]
            in_range = (nn_i >= r_lo) & (nn_i < r_hi)
            best_s = jnp.max(jnp.where(in_range, escv, NEG))
            best_r = jnp.min(jnp.where(in_range & (escv == best_s), nn_i,
                                       SN)).astype(jnp.int32)
            best_u = jnp.where(best_s > NEG, loadn(order[:], best_r),
                               jnp.int32(-1))
            if band:
                # score-deficit verify: a terminal score this far below
                # the all-match ceiling means the off-band penalty bound
                # no longer certifies the banded optimum (host mirror:
                # band.poa_deficit_bound)
                hit = hit | ((wb > 0) & (M * Ln - best_s >
                                         2 * (-G) * jnp.maximum(wb // 2, 1)))

            # ---- traceback -------------------------------------------------
            # The walk visits j strictly downward, so the backward
            # next-matched-key / run-remaining pass rides along for free:
            # every j-decrement is either a match (diag: record key[u],
            # reset the run) or an insertion (left: extend the run), and
            # nkey/runrem are exactly what the graph update needs — the
            # old pos_node array and its separate backward sweep are gone.

            def tb_cond(c):
                u, j, steps = c[0], c[1], c[2]
                return (~((u == -1) & (j == 0))) & (steps < N + L + 2)

            def tb_body(c):
                u, j, steps, nk, run = c[:5]
                at_virtual = u == -1
                uc = jnp.maximum(u, 0)
                jm1 = jnp.maximum(j - 1, 0)
                mv_loaded = loadj(MV[pl.ds(uc + 1, 1)][0], j)
                mv = jnp.where(at_virtual, 2, mv_loaded)
                move = mv % 4
                slot = mv // 4
                slot_c = jnp.minimum(slot, E - 1)
                prd = jnp.where(slot == VSLOT, -1, eload(in_src, slot_c, uc))

                take_diag = ~at_virtual & (move == 0)
                take_up = ~at_virtual & (move == 1)
                descend = ~take_up                # j-1 gets its record now
                nk = jnp.where(take_diag, loadn(key[:], uc), nk)
                run = jnp.where(take_diag, 0,
                                jnp.where(descend, run + 1, run))

                @pl.when(descend)
                def _():
                    rmwj(nkey, jm1, nk)
                    rmwj(runrem, jm1, run)

                new_u = jnp.where(take_diag | take_up, prd, u)
                new_j = jnp.where(take_up, j, j - 1)
                out = (new_u, new_j, steps + 1, nk, run)
                if band:
                    # boundary touch: the optimal path came within one
                    # cell of the band edge — the true optimum may lie
                    # outside, so the window must be re-run wider
                    cu = (loadn(key[:], uc) + 0.5).astype(jnp.int32) - begin
                    near = (~at_virtual & (wb > 0) &
                            (jnp.abs(j - cu) >= wb - 1))
                    out = out + (c[5] | near,)
                return out

            if band:
                fu, fj, _, _, _, touch = jax.lax.while_loop(
                    tb_cond, tb_body,
                    (best_u, Ln, jnp.int32(0), jnp.float32(KEY_INF),
                     jnp.int32(0), jnp.bool_(False)))
                hit = hit | touch
            else:
                fu, fj, _, _, _ = jax.lax.while_loop(
                    tb_cond, tb_body,
                    (best_u, Ln, jnp.int32(0), jnp.float32(KEY_INF),
                     jnp.int32(0)))
            failed = failed | ~((fu == -1) & (fj == 0))

            # ---- graph update ----------------------------------------------
            def upd_body(j, c):
                n, failed, prev, prev_key, prev_w = c
                b = loadj(seqv, j)
                wj = loadj(wv, j)
                run_j = loadj(runrem[:], j)
                is_match = run_j == 0       # a zero run marks a match
                nk = loadj(nkey[:], j)
                # at a matched position, nkey[j] IS the matched node's
                # column key (the traceback wrote it) — no key[] reduction
                k0 = nk

                keys = key[:]
                cand = (keys == k0) & (base[:] == b)
                has = cand.any() & is_match
                found = jnp.min(jnp.where(cand, nn_i, SN)).astype(jnp.int32)

                run = run_j.astype(jnp.float32)
                hi2 = jnp.where(nk < KEY_INF, nk, prev_key + 1.0)
                lo2 = jnp.where(prev >= 0, prev_key, hi2 - run - 1.0)
                k_new = lo2 + (hi2 - lo2) / (run + 1.0)
                key_val = jnp.where(is_match, k0, k_new)

                need_new = ~has
                overflow = need_new & (n >= N)
                do_new = need_new & ~overflow
                nid = jnp.where(has, found, jnp.minimum(n, N - 1))

                @pl.when(do_new)
                def _():
                    # insert into sorted order: after all keys <= key_val
                    p = jnp.sum(jnp.where(keys <= key_val, 1, 0)).astype(
                        jnp.int32)
                    rmwn(base, nid, b)
                    rmwn(key, nid, key_val)
                    ordv = order[:]
                    shifted = shift1(ordv, nn_i, nlane, 0)
                    order[:] = jnp.where(
                        nn_i < p, ordv,
                        jnp.where(nn_i == p, nid, shifted))

                touch = ~overflow

                @pl.when(touch)
                def _():
                    rmwn_add(cov, nid, 1)

                n = n + jnp.where(do_new, 1, 0)
                failed = failed | overflow

                # edge prev -> nid, weight w[j-1] + w[j]
                has_prev = touch & (prev >= 0)

                def eslot_scan(e, c2):
                    same_slot = c2
                    src = eload(in_src, e, nid)
                    return jnp.where((src == prev) & (same_slot < 0), e,
                                     same_slot)

                cnt = loadn(in_cnt[:], nid)
                same_slot = jax.lax.fori_loop(
                    0, cnt, eslot_scan, jnp.int32(-1))
                empty_slot = jnp.where(cnt < E, cnt, -1)
                ew = prev_w + wj

                @pl.when(has_prev & (same_slot >= 0))
                def _():
                    ermw_add(in_w, jnp.maximum(same_slot, 0), nid, ew)

                @pl.when(has_prev & (same_slot < 0) & (empty_slot >= 0))
                def _():
                    ermw(in_src, empty_slot, nid, prev)
                    ermw(in_w, empty_slot, nid, ew)
                    rmwn(in_cnt, nid, cnt + 1)

                failed = failed | (has_prev & (same_slot < 0) &
                                   (empty_slot < 0))
                # key[nid] == key_val in every non-overflow case (matched:
                # key_val = k0 = key[found]; new: just written), and under
                # overflow the window is already failed — saves a reduction
                return (n, failed, nid, key_val, wj)

            n, failed, _, _, _ = jax.lax.fori_loop(
                0, Ln, upd_body,
                (n, failed, jnp.int32(-1), jnp.float32(-1.0), jnp.int32(0)))
            return (n, failed, hit) if band else (n, failed)

        @pl.when(n_layers > 0)
        def _():
            start_copy(0, 0)

        def layer_loop(li, carry):
            failed = carry[1]
            slot = jax.lax.rem(li, 2)
            wait_copy(li, slot)

            @pl.when(li + 1 < n_layers)
            def _():
                # prefetch the next layer while this one's DP runs
                start_copy(li + 1, jax.lax.rem(li + 1, 2))

            run = (lens_ref[0, 0, li] > 0) & ~failed
            return jax.lax.cond(run, lambda c: do_layer(li, slot, c),
                                lambda c: c, carry)

        if band:
            n, failed, hit = jax.lax.fori_loop(
                0, n_layers, layer_loop,
                (bb_len, jnp.bool_(False), jnp.bool_(False)))
        else:
            n, failed = jax.lax.fori_loop(
                0, n_layers, layer_loop, (bb_len, jnp.bool_(False)))

        # ---- consensus -----------------------------------------------------
        def score_body(r, c):
            best_u, best_s = c
            u = loadn(order[:], r)

            def slot_scan(e, c2):
                bw, bs, bp = c2
                src = eload(in_src, e, u)
                w = eload(in_w, e, u)
                s = loadn(score[:], jnp.maximum(src, 0))
                better = (w > bw) | ((w == bw) & (s > bs))
                return (jnp.where(better, w, bw), jnp.where(better, s, bs),
                        jnp.where(better, src, bp))

            bw, bs, bp = jax.lax.fori_loop(
                0, loadn(in_cnt[:], u), slot_scan,
                (jnp.int32(NEG), jnp.int32(NEG), jnp.int32(-1)))
            s = jnp.where(bp >= 0, bw + bs, 0)
            rmwn(score, u, s)
            rmwn(pred, u, bp)
            better = s > best_s
            return (jnp.where(better, u, best_u), jnp.maximum(s, best_s))

        summit, _ = jax.lax.fori_loop(0, n, score_body,
                                      (jnp.int32(0), jnp.int32(NEG)))

        # backward walk to a source
        def bcond(c):
            u, cnt = c
            return (u != -1) & (cnt < N)

        def bbody(c):
            u, cnt = c
            rmwn(revbuf, cnt, u)
            return (loadn(pred[:], u), cnt + 1)

        _, cnt_b = jax.lax.while_loop(bcond, bbody, (summit, jnp.int32(0)))

        cons_base_ref[0] = jnp.full((8, NW), -1, jnp.int32)
        cons_cov_ref[0] = jnp.zeros((8, NW), jnp.int32)

        def emit(i, u):
            cons_base_ref[0] = jnp.where(nn_i == i, loadn(base[:], u),
                                         cons_base_ref[0])
            cons_cov_ref[0] = jnp.where(nn_i == i, loadn(cov[:], u),
                                        cons_cov_ref[0])

        def flip_body(i, _):
            emit(i, loadn(revbuf[:], cnt_b - 1 - i))
            return 0

        jax.lax.fori_loop(0, cnt_b, flip_body, 0)

        # forward walk to a sink along heaviest out-edges
        def fcond(c):
            u, cnt, more = c
            return more & (cnt < N)

        def fbody(c):
            u, cnt, _ = c
            ew = jnp.where(in_src[:] == u, in_w[:], NEG)      # (E, 8, NW)
            wv2 = jnp.max(ew, axis=0)                         # (8, NW)
            any_out = jnp.max(wv2) > NEG
            wmax = jnp.max(wv2)
            scorev = score[:]
            cand_s = jnp.where(wv2 == wmax, scorev, NEG)
            smax = jnp.max(cand_s)
            v = jnp.min(jnp.where(cand_s == smax, nn_i, SN)).astype(
                jnp.int32)

            @pl.when(any_out)
            def _():
                emit(cnt, v)

            return (jnp.where(any_out, v, u), cnt + jnp.where(any_out, 1, 0),
                    any_out)

        _, cnt, _ = jax.lax.while_loop(
            fcond, fbody, (summit, cnt_b, jnp.bool_(True)))

        cons_len_ref[0, 0, 0] = cnt
        failed_ref[0, 0, 0] = failed.astype(jnp.int32)
        n_nodes_ref[0, 0, 0] = n
        if band:
            band_hit_ref[0, 0, 0] = hit.astype(jnp.int32)

    def make(batch: int):
        # Mosaic block rules: last two block dims must tile (8,128) or equal
        # the array dims; the blocked tiles satisfy this natively. SMEM
        # residency stays O(D), not O(B*D); layer arrays live in HBM (ANY)
        # and are DMA'd per layer.
        smem3 = lambda w: pl.BlockSpec((1, 1, w), lambda b: (b, 0, 0),
                                       memory_space=pltpu.SMEM)
        vblk = pl.BlockSpec((1, 8, NW), lambda b: (b, 0, 0),
                            memory_space=pltpu.VMEM)
        hbm = pl.BlockSpec(memory_space=pl.ANY)

        scal = jax.ShapeDtypeStruct((batch, 1, 1), jnp.int32)
        return pl.pallas_call(
            kernel,
            grid=(batch,),
            in_specs=[smem3(1), smem3(1), smem3(D), smem3(D), smem3(D),
                      vblk, vblk, hbm, hbm] +
                     ([smem3(1)] if band else []),
            out_specs=[vblk, vblk, smem3(1), smem3(1), smem3(1)] +
                      ([smem3(1)] if band else []),
            out_shape=[
                jax.ShapeDtypeStruct((batch, 8, NW), jnp.int32),
                jax.ShapeDtypeStruct((batch, 8, NW), jnp.int32),
                scal, scal, scal,
            ] + ([scal] if band else []),
            scratch_shapes=[
                pltpu.VMEM((N + 1, 8, JW), jnp.int32),  # H
                pltpu.VMEM((N + 1, 8, JW), jnp.int32),  # MV (move records)
                pltpu.VMEM((8, NW), jnp.int32),         # base
                pltpu.VMEM((8, NW), jnp.float32),       # key
                pltpu.VMEM((8, NW), jnp.int32),         # cov
                pltpu.VMEM((8, NW), jnp.int32),         # order
                pltpu.VMEM((E, 8, NW), jnp.int32),      # in_src
                pltpu.VMEM((E, 8, NW), jnp.int32),      # in_w
                pltpu.VMEM((8, NW), jnp.int32),         # in_cnt
                pltpu.VMEM((8, JW), jnp.float32),       # nkey
                pltpu.VMEM((8, JW), jnp.int32),         # runrem
                pltpu.VMEM((8, NW), jnp.int32),         # score
                pltpu.VMEM((8, NW), jnp.int32),         # pred
                pltpu.VMEM((8, NW), jnp.int32),         # revbuf
                pltpu.VMEM((8, NW), jnp.int32),         # esc (end scores)
                pltpu.VMEM((8, NW), jnp.int32),         # rank_of
                pltpu.VMEM((2, 8, JW), jnp.int32),      # seq_scr (2 slots)
                pltpu.VMEM((2, 8, JW), jnp.int32),      # w_scr
                pltpu.SemaphoreType.DMA((2, 2)),        # per (slot, array)
            ],
            interpret=interpret,
            name="racon_poa_v2",
        )

    @functools.lru_cache(maxsize=8)
    def jitted(batch: int):
        call = make(batch)

        @named("racon_poa_v2")
        def fn(bb_len, n_layers, lens, begins, ends, bb, bbw, seqs, ws,
               *extra):
            # host-shaped inputs -> sublane-blocked tiles (XLA relayouts
            # on device; the pallas kernel sees native (8, W) tiles)
            bbB = jnp.pad(bb.reshape(batch, BB),
                          ((0, 0), (0, SN - BB))).reshape(batch, 8, NW)
            bbwB = jnp.pad(bbw.reshape(batch, BB),
                           ((0, 0), (0, SN - BB))).reshape(batch, 8, NW)
            seqsB = jnp.pad(seqs, ((0, 0), (0, 0), (0, SJ - L)),
                            constant_values=255).reshape(batch, D, 8, JW)
            wsB = jnp.pad(ws, ((0, 0), (0, 0), (0, SJ - L))
                          ).reshape(batch, D, 8, JW)
            args = [bb_len.reshape(batch, 1, 1),
                    n_layers.reshape(batch, 1, 1),
                    lens.reshape(batch, 1, D), begins.reshape(batch, 1, D),
                    ends.reshape(batch, 1, D), bbB, bbwB, seqsB, wsB]
            if band:
                args.append(extra[0].reshape(batch, 1, 1))
            outs = call(*args)
            cb, cc, cl, fl, nn = outs[:5]
            res = (cb.reshape(batch, SN)[:, :N],
                   cc.reshape(batch, SN)[:, :N],
                   cl.reshape(batch, 1), fl.reshape(batch, 1),
                   nn.reshape(batch, 1))
            if band:
                res = res + (outs[5].reshape(batch, 1),)
            return res

        return jax.jit(fn)

    return jitted
