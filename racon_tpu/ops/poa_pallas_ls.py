"""Lane-lockstep fused Pallas POA kernel (`ls`, `racon_poa_ls`).

Same window-consensus semantics as the host oracle (rt_poa.cpp) and the
XLA twin (poa.py), laid out for VPU throughput. A window's DP is a
serial chain of dependent vector operations (per rank: a vector-to-scalar
turn, a dynamic loop of ring-row loads, a shift, ten dependent lane
rolls, a masked reduce, a read-modify-write: ~1000-1300 cycles of
latency a rank on the v5e at one sublane group and at two, whatever a
row holds up to 28 vregs), so one window per program leaves the VPU
idle, and the chain is filled by widening the program until it issues as
many bundles as it waits cycles.  At four groups it does: a DP rank of
class 512 is ~1640 scheduled bundles and a traceback rank ~1050 (counted
from the final bundles of a compile for a described v5e,
tools/kernel_bundles.py --kernel ls), a job's kernel cycles are its
loops' bundles times their trips (PERF.md section 7), and a bundle
taken out of a rank loop is a cycle taken out.  One grid program
therefore runs U x 8 windows in
lock-step under ONE control flow: one window per sublane, U sublane
groups (U = `groups`: 4, 2 or 1; the driver derives it launch by launch
from the per-shard batch, the VMEM sum and the rows the launch really
holds, see poa_driver._group_width: 4 wherever a batch of 64 is more
than half a program of thirty-two past its last full one and VMEM holds
the geometry, which is every geometry the benchmark's one-chip cells
run; 2 at 16 rows a shard):

  * j-rows: (U, JC, 8, 128) — window u*8 + g of the program in group u,
    sublane g, DP column j at [u, j // 128, g, j % 128]. Every row op
    serves all U x 8 windows at once, with lane-only prefix scans; a
    loop branch, a vector-to-scalar turn or a `pl.when` is paid once
    per program step. Nothing crosses the group axis except the loop
    bounds, which are maxima or unions over the program's windows.
  * The graph lives in RANK SPACE: arrays (U, NC, 8, 128) keyed by
    topological rank (= column-key order), with in-edges stored as rank
    DISTANCES (rk_delta). Node insertion is a lane shift; there are no
    node ids at all. Rank distance is bounded in practice: measured max
    34 on the lambda dataset and 16 on the synthetic ONT bench over ~12M
    edges (RT_POA_STATS histograms), so distances are capped at DMAX=64
    and a window with a longer in-subgraph edge fails to the host path
    (the same degradation lattice as every other device limit).
    Insertion is gated per group: a group pays for its own windows'
    insertions only.  What it pays is the number of loop steps it runs
    on the in-edge slot arrays (a step is a serial chain of load, lane
    roll, selects and store on one ref, ~150 cycles on the v5e whether
    it carries 2 vregs an array or 24), so the slots a group uses are
    shifted SLOT_BLOCK at a step.
  * H rows live in a 128-row rank-keyed VMEM ring (RING, U, JC, 8, 128)
    (the distance cap makes older rows dead); completed 64-row chunks
    are DMA'd to an HBM spill buffer under the compute.
  * No move matrix. The traceback re-derives moves from H values exactly
    like the pure-JAX twin (poa.py _traceback, differentially verified
    against the host), walking rank blocks top-down with the spill buffer
    streamed back through the same ring; insertion runs are applied as
    one masked vector op per run instead of one step per base.

Reference parity: the per-window program mirrors rt_poa.cpp /
src/window.cpp (see poa.py's docstring for the layer-by-layer map); the
batch orchestration mirrors the reference's cudapoa batch
(/root/reference/src/cuda/cudabatch.cpp).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..device import named
from .kernel_cache import Program, device_keyed_cache
from .poa import (FAIL_DISTANCE, FAIL_EDGES, FAIL_NODES, FAIL_OTHER,
                  PoaConfig, first_cause)

NEG = -(1 << 28)
G = 8            # windows per kernel program (the sublane dimension)
RING = 128       # H ring rows (must be 2 * BLK)
BLK = 64         # HBM spill chunk = traceback block
DMAX = 64        # max predecessor rank distance the device accepts
KEY_INF = 3.0e38
BIG = 1 << 20    # "no slot" sentinel inside packed slot*256+delta minima
WNONE = BIG * 512
SLOT_BLOCK = 4   # in-edge slots a step of the node-insertion loop shifts

#: The kernel's phases as regions of a device trace (a jax.named_scope in
#: the body lowers to Mosaic tpu.trace_start / trace_stop, which libtpu
#: keeps, and carries into a profile, only where its operator started it
#: with the two flags docs/observability.md names): the five blocks of a
#: layer one after another, and once a program the consensus walk.
#: Flat: none around the layer, none inside a rank, step or slot loop.
(R_SETUP, R_DP, R_ENDS, R_TRACEBACK, R_UPDATE,
 R_CONSENSUS) = REGIONS = ("ls.setup", "ls.dp", "ls.ends", "ls.traceback",
                           "ls.update", "ls.consensus")
#: What a grid program counts of its own loops, summed over its layers:
#: the trips of each (poa.ls.<name> once poa_driver installs them, beside
#: poa.ls.layers, the layers themselves, which the host knows) and,
#: first, the in-edge slots its node insertions were bounded to; the last
#: two are the trips of the scans inside a rank step (the DP's delta_scan,
#: the traceback's mscan).  The kernel's last output, a slot each, in
#: PROGRAM_COUNTS' order.
STEP_COUNTERS = ("steps.dp", "steps.traceback", "steps.update",
                 "insert.firings", "insert.shift_steps",
                 "steps.dp_scan", "steps.tb_scan")
PROGRAM_COUNTS = ("slots_swept",) + STEP_COUNTERS

#: A per-window scalar comes out of a row as a masked sum over lanes, and
#: the v5e's cross-lane add is a float one: an int32 sum lowers to two of
#: them, one a 16-bit half (mask, split, two converts, two vadd.xlane, two
#: vpop, two converts back, shift, add), a sum whose one nonzero term lies
#: inside (-2**24, 2**24) to one, exactly.  So what a rank step reads at
#: one index is packed into words of NARROW_BITS before the reduction.
NARROW_BITS = 24
#: The in-edge record: a rank's E distances, REC_BITS each, REC_SLOTS a
#: word, in slot order.  A distance past REC_MAX reads REC_MAX, which is
#: past DMAX too, so what is valid stays what it was.
REC_BITS = 8
REC_SLOTS = NARROW_BITS // REC_BITS
REC_MAX = (1 << REC_BITS) - 1
assert DMAX < REC_MAX


def record_words(E: int) -> int:
    return -(-E // REC_SLOTS)


def pack_record(deltas):
    """E arrays of distances (>= 0) -> record_words(E) arrays."""
    words = []
    for at in range(0, len(deltas), REC_SLOTS):
        word = jnp.minimum(deltas[at], REC_MAX)
        for k, d in enumerate(deltas[at + 1:at + REC_SLOTS], 1):
            word = word | (jnp.minimum(d, REC_MAX) << (REC_BITS * k))
        words.append(word)
    return words


def unpack_record(words, E: int):
    """-> the E distances of pack_record's words, each capped at REC_MAX."""
    return [(words[e // REC_SLOTS] >> (REC_BITS * (e % REC_SLOTS))) & REC_MAX
            for e in range(E)]


def move_bits(E: int) -> int:
    """Bits of one move's code: a packed slot * 256 + distance of the
    traceback's minima, or one of the two codes past every one of them."""
    return max(12, (E * 256 + 1).bit_length())


def pack_moves(wdiag, wup, vdiag, vup, bits: int):
    """The traceback's two moves at a cell as one word, diag above up: a
    move's packed predecessor where a row explains the cell (w < WNONE),
    else all ones where the virtual row does (v: it needs a rank with no
    valid in-edge, so it excludes the first), else all ones but the last
    bit: no move."""
    virtual = (1 << bits) - 1
    cd = jnp.minimum(wdiag, jnp.where(vdiag, virtual, virtual - 1))
    cu = jnp.minimum(wup, jnp.where(vup, virtual, virtual - 1))
    return (cd << bits) | cu


def no_move(bits: int) -> int:
    """pack_moves' word of a cell neither move explains."""
    none = (1 << bits) - 2
    return (none << bits) | none


def unpack_moves(word, bits: int):
    """-> (diag_ok, wd, wd_virt, wu, wu_virt): whether the diagonal move
    explains the cell, its packed predecessor (0 without one), whether
    it has none (the move goes to the virtual row), and the last two for
    the move up."""
    virtual = (1 << bits) - 1
    cd = word >> bits
    cu = word & virtual
    wd_virt = cd >= virtual - 1
    wu_virt = cu >= virtual - 1
    return (cd != virtual - 1, jnp.where(wd_virt, 0, cd), wd_virt,
            jnp.where(wu_virt, 0, cu), wu_virt)


def lane_sum(x, narrow: bool = False):
    """Sum over the last axis, kept.  `narrow`: the caller's terms are
    int32 whose every partial sum lies inside (-2**24, 2**24) (one
    nonzero term that does, or a count under it): one float reduction,
    exact."""
    if narrow:
        return jnp.sum(x.astype(jnp.float32), axis=-1,
                       keepdims=True).astype(jnp.int32)
    return jnp.sum(x, axis=-1, keepdims=True)


def _round_up(x, m):
    return (x + m - 1) // m * m


def scratch_bytes(cfg: PoaConfig, groups: int = 1) -> int:
    """VMEM the kernel's arrays sum to at `groups` sublane groups a
    program.  Mirrors make()'s scratch_shapes: a 128-row H ring instead
    of the full H matrix, plus rank-space graph arrays and per-layer DMA
    slots; layers stream from HBM, so depth does not appear.  Mosaic's
    own temporaries are left out (vmem_limit_bytes asks for as much
    again where the sum passes what the default limit holds, and
    poa_driver._fits_vmem holds that limit under VMEM_CEILING)."""
    NC = cfg.max_nodes // 128
    JC = _round_up(cfg.max_len + 1, 128) // 128
    lane_bytes = groups * G * 128 * 4
    ring = RING * JC * lane_bytes
    j_rows = (1 + 2 + 2 * 2) * JC * lane_bytes   # H0, nkey/runrem, scr
    aux = max(3, record_words(cfg.max_edges))     # at E = 12, 4 rows
    n_rows = (5 + aux + 2 * cfg.max_edges) * NC * lane_bytes
    io = 4 * NC * lane_bytes                      # bb/bbw in, cons out
    return ring + j_rows + n_rows + io


#: arrays summing to less than this compile under the v5e compiler's
#: default 16 MB scoped-VMEM limit (it took every sum up to 10.85 MiB and
#: refused every one from 11.5 MiB up: 16.3 MB with its own temporaries)
DEFAULT_LIMIT_HOLDS = 11 << 20
#: the most a raised limit may ask for: half the v5e's 128 MiB of VMEM
VMEM_CEILING = 64 << 20


def vmem_limit_bytes(cfg: PoaConfig, groups: int):
    """The scoped-VMEM limit a program is compiled under: None, the
    compiler's default, wherever the arrays' sum is one the default is
    known to hold (the program of eight up to class 1024 on the base
    rung and class 768 on the upper one: 10.32 / 9.61 MiB, the program
    of sixteen up to class 512 on the base rung: 10.85 MiB, the program
    of thirty-two at class 128: 8.06 MiB).  The default is not the
    chip's VMEM, so a larger program, of any width (since PR 47 the
    program of eight too: class 1024's upper rung, 12.64 MiB, asks for
    26), asks for twice its own sum: the arrays, and as much again for
    Mosaic's temporaries, which took 42 % of the sum at one group."""
    total = scratch_bytes(cfg, groups)
    if total < DEFAULT_LIMIT_HOLDS:
        return None
    return _round_up(2 * total, 1 << 20)


@device_keyed_cache(maxsize=32)
def build_lockstep_poa_kernel(cfg: PoaConfig, interpret: bool = False,
                              band: bool = False, groups: int = 1):
    U = groups                          # sublane groups per program
    W = U * G                           # windows per program
    N = cfg.max_nodes
    L = cfg.max_len
    BB = cfg.max_backbone
    E = cfg.max_edges
    D = cfg.depth
    assert N % 128 == 0 and BB <= N < 1 << NARROW_BITS
    NC = N // 128                       # node/rank lane-chunks
    SB = math.gcd(E, SLOT_BLOCK)        # slots a step: whole blocks in E
    JL = _round_up(L + 1, 128)
    JC = JL // 128                      # j lane-chunks
    NW = record_words(E)                # words of a rank's in-edge record
    MB = move_bits(E)                   # bits of a traceback move's code
    M = int(cfg.match)
    X = int(cfg.mismatch)
    GP = int(cfg.gap)

    # The banded build (band=True, RACON_TPU_BAND) adds one SMEM input
    # (wband: per-window half-band width, 0 = flat semantics through the
    # same compiled kernel) and one SMEM output (band_hit: the composite
    # verify signal — see ops/band.py).  Every band op
    # is gated on the Python-level `band` flag so the flat build's jaxpr
    # is unchanged.
    def kernel(*refs):
        if band:
            (bb_len_s, n_layers_s, lens_s, begins_s, ends_s,
             bb_ref, bbw_ref, seqs_hbm, ws_hbm, wband_s,
             cons_base_ref, cons_cov_ref, cl_s, fl_s, nn_s, bh_s, sw_s,
             hbm_H,
             Hring, H0, rk_base, rk_key, rk_cov, rk_cnt, rk_delta, rk_ew,
             esc, aux, nkey, runrem,
             seq_scr, w_scr, dma_sem, flush_sem, tb_sem) = refs
        else:
            (bb_len_s, n_layers_s, lens_s, begins_s, ends_s,
             bb_ref, bbw_ref, seqs_hbm, ws_hbm,
             cons_base_ref, cons_cov_ref, cl_s, fl_s, nn_s, sw_s, hbm_H,
             Hring, H0, rk_base, rk_key, rk_cov, rk_cnt, rk_delta, rk_ew,
             esc, aux, nkey, runrem,
             seq_scr, w_scr, dma_sem, flush_sem, tb_sem) = refs
        b_prog = pl.program_id(0)
        # one scratch, two lives: the layers' in-edge record, then the
        # consensus walk's three arrays, each written before it is read
        rec = [aux.at[w] for w in range(NW)]
        score, spred, revbuf = aux.at[0], aux.at[1], aux.at[2]

        # index vectors carry a unit group axis and broadcast over U
        lane_n = jax.lax.broadcasted_iota(jnp.int32, (1, NC, G, 128), 3)
        chunk_n = jax.lax.broadcasted_iota(jnp.int32, (1, NC, G, 128), 1)
        rr = chunk_n * 128 + lane_n                    # global rank index
        lane_j = jax.lax.broadcasted_iota(jnp.int32, (1, JC, G, 128), 3)
        chunk_j = jax.lax.broadcasted_iota(jnp.int32, (1, JC, G, 128), 1)
        jj = chunk_j * 128 + lane_j                    # global j index
        lane1 = jax.lax.broadcasted_iota(jnp.int32, (1, 1, G, 128), 3)
        giota = jax.lax.broadcasted_iota(jnp.int32, (1, 1, G, 1), 2)
        uiota = jax.lax.broadcasted_iota(jnp.int32, (U, 1, 1, 1), 0)
        gvec = jj * GP
        n_shape = (U, NC, G, 128)
        j_shape = (U, JC, G, 128)
        w_shape = (U, 1, G, 1)

        # ---- helpers ----------------------------------------------------
        # (U, 1, G, 1) per-window scalar-vectors are the working currency;
        # extracts are masked sums (zero elsewhere), so indices must be in
        # range — callers clamp.

        # Arrays are (u, C, G, 128) with u = U, or u = 1 inside the
        # per-group node-insertion block; the index vectors broadcast.
        def glob(x):
            return rr if x.shape[1] == NC else jj

        def lanes_of(x):
            return lane_n if x.shape[1] == NC else lane_j

        def wsum(x, narrow=False):
            """per-window reduction over chunks and lanes -> (u,1,G,1);
            `narrow` as lane_sum's, the chunks added as integers first."""
            return lane_sum(jnp.sum(x, axis=1, keepdims=True), narrow)

        def wmax(x):
            return jnp.max(x, axis=(1, 3), keepdims=True)

        def wmin(x):
            return jnp.min(x, axis=(1, 3), keepdims=True)

        def wany(x):
            return jnp.any(x, axis=(1, 3), keepdims=True)

        # `narrow` on an extract is chosen where it is called, by what
        # the array can hold there: the value read lies in (-2**24,
        # 2**24), so lane_sum reduces it once.  H values (NEG = -2**28
        # plus a score) and the consensus scores keep the int form.
        def _lane_extract(c, idx, narrow):
            """(U,1,G,128) rows -> (U,1,G,1) value at lane idx (masked
            sum)."""
            m = lane1 == (idx % 128)
            return lane_sum(jnp.where(m, c, jnp.zeros_like(c)), narrow)

        def exr(ref, r, narrow=False):
            """ref (U,C,G,128) at global index r (shared scalar) ->
            (U,1,G,1).

            Reads THROUGH the ref with pl.ds — dynamic_slice on a loaded
            value does not lower to Mosaic (caught by the jax.export
            cross-lowering check; interpret mode accepts it silently).
            One (U,1,G,128) VMEM load + a lane mask, not an O(N) masked
            reduction over every chunk."""
            return _lane_extract(ref[:, pl.ds(r // 128, 1)], r, narrow)

        def exs(ref, slot, j):
            """(2,U,JC,G,128) double-buffer ref at (slot, global j): a
            layer's bases (codes under 256) or weights (a quality, or a
            unit: under 256 too), so narrow."""
            return _lane_extract(
                ref[pl.ds(slot, 1), :, pl.ds(j // 128, 1)][0], j, True)

        def ex_v(val, rv, narrow=False):
            """val (U,C,G,128) at per-window indices rv (U,1,G,1)."""
            m = glob(val) == rv
            return wsum(jnp.where(m, val, jnp.zeros_like(val)), narrow)

        def rmw(ref, r, v, active):
            """ref value at shared scalar index r <- v where active."""
            c = ref[:, pl.ds(r // 128, 1)]
            m = (lane1 == (r % 128)) & active
            ref[:, pl.ds(r // 128, 1)] = jnp.where(m, v, c)

        def rmw_v(ref, rv, v, active):
            """masked write at per-window global indices rv (u,1,G,1)."""
            x = ref[...]
            ref[...] = jnp.where((glob(x) == rv) & active, v, x)

        def shift_right(x, fill):
            """lane shift: out[i] = x[i-1], out[0] = fill (global index)."""
            ln = pltpu.roll(x, 1, 3)
            carry = pltpu.roll(ln, 1, 1)
            y = jnp.where(lanes_of(x) == 0, carry, ln)
            return jnp.where(glob(x) == 0, fill, y)

        def shift_left_dyn(x, d, fill):
            """out[i] = x[i + d] (dynamic scalar d >= 0), fill past the
            end; crosses lane chunks."""
            dl = d % 128
            dc = d // 128
            xs = pltpu.roll(x, -dl, 3)
            xc = pltpu.roll(xs, -dc, 1)
            xc2 = pltpu.roll(xs, -(dc + 1), 1)
            y = jnp.where(lanes_of(x) < 128 - dl, xc, xc2)
            top = x.shape[1] * 128
            return jnp.where(glob(x) + d < top, y, fill)

        def cummaxj(x):
            """prefix max over the global j index of a (U,JC,G,128)
            array: radix-4 within lanes, then an exclusive chunk prefix."""
            w = 1
            while w < 128:
                for k in (1, 2, 3):
                    if k * w < 128:
                        x = jnp.maximum(
                            x, jnp.where(lane_j >= k * w,
                                         pltpu.roll(x, k * w, 3), NEG))
                w *= 4
            tot = jnp.max(x, axis=3, keepdims=True)
            p = jnp.broadcast_to(tot, j_shape)
            acc = jnp.full(j_shape, NEG, jnp.int32)
            for k in range(1, JC):
                acc = jnp.maximum(
                    acc, jnp.where(chunk_j >= k, pltpu.roll(p, k, 1), NEG))
            return jnp.maximum(x, acc)

        # window i = u*G + g of the program sits in group u, sublane g
        at_window = [(uiota == i // G) & (giota == i % G) for i in range(W)]

        def scalar_of(v, i):
            return jnp.sum(jnp.where(at_window[i], v, jnp.zeros_like(v)))

        def svec(read):
            """(U,1,G,1) vector from W SMEM scalars (SMEM is
            scalar-only)."""
            v = jnp.zeros(w_shape, jnp.int32)
            for i in range(W):
                v = jnp.where(at_window[i], read(i), v)
            return v

        bb_len = svec(lambda i: bb_len_s[0, 0, i])
        n_layers = svec(lambda i: n_layers_s[0, 0, i])
        max_layers = jnp.max(n_layers)
        if band:
            wbv = svec(lambda i: wband_s[0, 0, i])    # (U,1,G,1) half-band

        # PROGRAM_COUNTS accumulate in the SMEM output itself, each
        # bumped once a layer where its loop bound is already a scalar
        # (the two of the node insertion once a firing, the two of the
        # rank steps' scans once a rank), so no loop carries them
        def bump(name, by):
            slot = PROGRAM_COUNTS.index(name)
            sw_s[0, 0, slot] = sw_s[0, 0, slot] + by

        for slot in range(len(PROGRAM_COUNTS)):
            sw_s[0, 0, slot] = jnp.int32(0)

        # ---- graph init from the backbone chain ------------------------
        # (parity: rt_poa.cpp add_alignment, empty-alignment branch)
        used0 = rr < bb_len
        rk_base[...] = jnp.where(used0, bb_ref[0], -1)
        rk_key[...] = jnp.where(used0, rr.astype(jnp.float32), KEY_INF)
        rk_cov[...] = jnp.where(used0, 1, 0)
        chain = (rr > 0) & used0
        rk_cnt[...] = jnp.where(chain, 1, 0)
        rk_delta[...] = jnp.zeros((E,) + n_shape, jnp.int32)
        rk_delta[0:1] = jnp.where(chain, 1, 0)[None]
        bbw = bbw_ref[0]
        rk_ew[...] = jnp.zeros((E,) + n_shape, jnp.int32)
        rk_ew[0:1] = jnp.where(chain, shift_right(bbw, 0) + bbw, 0)[None]
        H0[...] = jnp.broadcast_to(gvec, j_shape)

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

        def flush_chunk(c):
            pltpu.make_async_copy(
                Hring.at[pl.ds((c * BLK) % RING, BLK)],
                hbm_H.at[b_prog, pl.ds(c * BLK, BLK)],
                flush_sem.at[c % 2]).start()

        def flush_wait(c):
            pltpu.make_async_copy(
                Hring.at[pl.ds((c * BLK) % RING, BLK)],
                hbm_H.at[b_prog, pl.ds(c * BLK, BLK)],
                flush_sem.at[c % 2]).wait()

        # ================= one layer =====================================
        def do_layer(li, slot, carry):
            # n, failed[, hit]: (U,1,G,1) i32
            if band:
                n, failed, hit = carry
            else:
                n, failed = carry
            with jax.named_scope(R_SETUP):
                Ln = svec(lambda i: lens_s[0, i, li])
                begin = svec(lambda i: begins_s[0, i, li])
                end = svec(lambda i: ends_s[0, i, li])
                lact = (li < n_layers) & (Ln > 0) & (failed == 0)

                # full-graph rule (reference: src/window.cpp:88-97)
                offset = (0.01 * bb_len.astype(jnp.float32)).astype(jnp.int32)
                full = (begin < offset) & (end > bb_len - offset)
                lo = jnp.where(full, jnp.float32(-KEY_INF),
                               begin.astype(jnp.float32))
                hi = jnp.where(full, jnp.float32(KEY_INF),
                               end.astype(jnp.float32))

                keys = rk_key[...]
                r_lo = wsum(jnp.where(keys < lo, 1, 0))
                r_hi = jnp.minimum(wsum(jnp.where(keys <= hi, 1, 0)), n)
                r_start = jnp.min(jnp.where(lact, r_lo, N))
                r_end = jnp.max(jnp.where(lact, r_hi, 0))

                seqv = seq_scr[pl.ds(slot, 1)][0]          # (U, JC, G, 128)
                seqm1 = shift_right(seqv, 255)             # lane j: seq[j-1]

                # layer-invariant snapshots (the graph does not change during
                # DP + traceback; Mosaic keeps these as VMEM-backed values)
                delta_v = [rk_delta[e] for e in range(E)]
                H0v = H0[...]
                # and the in-edge record the DP's and the traceback's rank
                # steps read: NW words a rank where the slots are E
                for w, word in enumerate(pack_record(delta_v)):
                    rec[w][...] = word

                # distance cap: an IN-SUBGRAPH edge beyond DMAX fails the
                # window (its H row is evicted from the ring; the host path
                # takes over — the rank-distance histograms say this is rare)
                in_sub = (rr >= r_lo) & (rr < r_hi)
                far = jnp.zeros(w_shape, jnp.int32)
                for e in range(E):
                    bad = ((delta_v[e] > DMAX) & in_sub &
                           ((rr - delta_v[e]) >= r_lo))
                    far = far | wany(bad).astype(jnp.int32)
                failed = first_cause(failed, lact & (far > 0), FAIL_DISTANCE)

                esc[...] = jnp.full(n_shape, NEG, jnp.int32)

            def rank_record(r, act):
                """-> (ds, d_top): rank r's in-edge distances in slot
                order, 0 where the edge is not one of this layer's
                subgraph (or the window not `act`), and the largest of
                them.  A word holds REC_SLOTS distances of REC_BITS:
                inside NARROW_BITS, so one reduction a word."""
                words = [exr(rec[w], r, narrow=True) for w in range(NW)]
                ds = []
                for d_e in unpack_record(words, E):
                    valid = ((d_e > 0) & (d_e <= DMAX) &
                             (r - d_e >= r_lo) & act)
                    ds.append(jnp.where(valid, d_e, 0))
                return ds, functools.reduce(jnp.maximum, ds)

            with jax.named_scope(R_DP):
                # ---- DP over ranks in lock-step -----------------------------
                rs64 = (r_start // BLK) * BLK

                def dp_body(r, _):
                    act = lact & (r >= r_lo) & (r < r_hi)
                    ds, d_top = rank_record(r, act)
                    any_valid = d_top > 0
                    # the scan's bound: the largest distance that is valid
                    # here (so at most DMAX, and r - r_lo)
                    dmax_r = jnp.max(d_top)
                    bump("steps.dp_scan", dmax_r)

                    def delta_scan(d, P):
                        prow = Hring[pl.ds((r - d) % RING, 1)][0]
                        has = ds[0] == d
                        for e in range(1, E):
                            has = has | (ds[e] == d)
                        return jnp.where(has, jnp.maximum(P, prow), P)

                    P0 = jnp.full(j_shape, NEG, jnp.int32)
                    P = jax.lax.fori_loop(1, dmax_r + 1, delta_scan, P0)
                    P = jnp.where(any_valid, P, H0v)

                    ub = exr(rk_base, r, narrow=True)    # a code, or -1
                    scvec = jnp.where(seqm1 == ub, M, X)
                    diag = shift_right(P, NEG) + scvec
                    up = P + GP
                    V = jnp.maximum(diag, up)
                    row = cummaxj(V - gvec) + gvec
                    if band:
                        # diagonal band around the rank's backbone offset:
                        # cells past the per-window half-band are masked to
                        # NEG before the ring write, so later ranks, the end
                        # score and the traceback all see banded values
                        cr = (exr(rk_key, r) + 0.5).astype(jnp.int32) - begin
                        row = jnp.where((wbv > 0) & (jnp.abs(jj - cr) > wbv),
                                        NEG, row)
                    Hring[pl.ds(r % RING, 1)] = row[None]
                    rmw(esc, r, ex_v(row, Ln), act)

                    @pl.when((r + 1) % BLK == 0)
                    def _():
                        flush_chunk((r + 1) // BLK - 1)
                        # the chunk whose ring slots ranks [r+1, r+1+BLK)
                        # will overwrite must have landed in HBM — if this
                        # layer flushed it (its first chunk starts at rs64)
                        @pl.when(r + 1 - RING >= rs64)
                        def _():
                            flush_wait((r + 1 - RING) // BLK)
                    return 0

                # Rank-pair stepping: every serial iteration retires TWO
                # consecutive ranks, halving the trip count. Ranks still
                # execute strictly in order inside the body (rank r's ring
                # row is written before rank r+1's delta scan reads it at
                # d == 1), so the result is that of one rank per iteration.
                # The flush schedule is untouched: rs64 and BLK are even, so
                # the (r+1) % BLK == 0 trigger only ever fires on the second
                # rank of a pair.
                def pair_body(p, _):
                    r = rs64 + 2 * p
                    dp_body(r, 0)

                    @pl.when(r + 1 < r_end)
                    def _():
                        dp_body(r + 1, 0)

                    return 0

                pairs = (r_end - rs64 + 1) // 2
                jax.lax.fori_loop(0, pairs, pair_body, 0)
                bump("steps.dp", 2 * jnp.maximum(pairs, 0))

                # Every flush started is waited on exactly once: a DMA wait
                # with no matching start never returns on the chip (interpret
                # mode does not block, so only the TPU interpreter or silicon
                # shows it).  The loop waited on every full chunk but the
                # last; that one and the partial tail are still in flight.
                @pl.when(r_end // BLK > rs64 // BLK)
                def _():
                    flush_wait(r_end // BLK - 1)

                @pl.when(r_end % BLK != 0)
                def _():
                    flush_chunk(r_end // BLK)
                    flush_wait(r_end // BLK)

            with jax.named_scope(R_ENDS):
                # ---- end-node selection -------------------------------------
                # rank r is an end node iff no in-subgraph node has an edge
                # from it (one masked dynamic shift per distance serves every
                # rank at once)
                dmax_v = functools.reduce(jnp.maximum, delta_v)
                dmax_all = jnp.minimum(
                    jnp.max(jnp.where(in_sub, dmax_v, 0)), DMAX)

                def out_body(d, hm):
                    has_d = delta_v[0] == d
                    for e in range(1, E):
                        has_d = has_d | (delta_v[e] == d)
                    src_ok = has_d & in_sub & ((rr - d) >= r_lo)
                    return hm | shift_left_dyn(src_ok.astype(jnp.int32), d, 0)

                has_out = jax.lax.fori_loop(
                    1, dmax_all + 1, out_body,
                    jnp.zeros(n_shape, jnp.int32))
                endok = in_sub & (has_out == 0)

                escv = jnp.where(endok, esc[...], NEG)
                best_s = wmax(escv)
                best_r = wmin(jnp.where((escv == best_s) & endok, rr, N))
                has_end = best_s > NEG
                failed = first_cause(failed, lact & ~has_end, FAIL_OTHER)
                if band:
                    # score-deficit verify (host mirror:
                    # band.poa_deficit_bound)
                    deficit_bad = (M * Ln - best_s >
                                   2 * (-GP) * jnp.maximum(wbv // 2, 1))
                    hit = hit | jnp.where(lact & (wbv > 0) & deficit_bad, 1, 0)

            with jax.named_scope(R_TRACEBACK):
                # ---- traceback: block-descending re-derivation --------------
                walking = lact & has_end & (failed == 0)
                cur = jnp.where(walking, best_r, -1)
                jcur = jnp.where(walking, Ln, 0)
                nk0 = jnp.full(w_shape, KEY_INF, jnp.float32)
                run0 = jnp.zeros(w_shape, jnp.int32)
                # loop-carried flags are i32 0/1: Mosaic cannot legalize an
                # scf.for / scf.while that carries an i1 vector
                done0 = jnp.where(walking, 0, 1)
                b_top = jnp.max(jnp.where(walking, cur, 0)) // BLK

                def tb_load(b, half):
                    pltpu.make_async_copy(
                        hbm_H.at[b_prog, pl.ds(b * BLK, BLK)],
                        Hring.at[pl.ds(half * BLK, BLK)],
                        tb_sem.at[half]).start()

                def tb_wait(b, half):
                    pltpu.make_async_copy(
                        hbm_H.at[b_prog, pl.ds(b * BLK, BLK)],
                        Hring.at[pl.ds(half * BLK, BLK)],
                        tb_sem.at[half]).wait()

                def ring_row(p):
                    """resident spill row for rank p (blocks b and b-1)."""
                    return Hring[pl.ds(((p // BLK) % 2) * BLK + p % BLK, 1)][0]

                tb_load(b_top, b_top % 2)
                tb_wait(b_top, b_top % 2)

                @pl.when(b_top >= 1)
                def _():
                    tb_load(b_top - 1, (b_top - 1) % 2)

                def tb_rank_work(r, c):
                    cur, jcur, nk, run, done, failed = c[:6]
                    here = (done == 0) & (cur == r)
                    row = ring_row(r)
                    ub = exr(rk_base, r, narrow=True)    # a code, or -1
                    scv = jnp.where(seqm1 == ub, M, X)
                    ds, d_top = rank_record(r, True)
                    any_v = d_top > 0
                    # the scan serves the windows that stand at this rank:
                    # nothing below reads another's minima
                    dmax_r = jnp.max(jnp.where(here, d_top, 0))
                    bump("steps.tb_scan", dmax_r)

                    # min over (slot, delta) packed as slot*256+delta: the
                    # winning predecessor is the FIRST slot whose row explains
                    # the H value (host tie-break: edge insertion order)
                    def mscan(d, c2):
                        wdiag, wup = c2
                        prow = ring_row(r - d)
                        s_of_d = jnp.full(w_shape, BIG, jnp.int32)
                        for e in range(E - 1, -1, -1):
                            s_of_d = jnp.where(ds[e] == d, e, s_of_d)
                        has = s_of_d < BIG
                        pk = s_of_d * 256 + d
                        dm = has & (shift_right(prow, NEG) + scv == row)
                        um = has & (prow + GP == row)
                        wdiag = jnp.minimum(wdiag, jnp.where(dm, pk, WNONE))
                        wup = jnp.minimum(wup, jnp.where(um, pk, WNONE))
                        return (wdiag, wup)

                    W0 = jnp.full(j_shape, WNONE, jnp.int32)
                    wdiag, wup = jax.lax.fori_loop(1, dmax_r + 1, mscan,
                                                   (W0, W0))
                    vdiag = ~any_v & (shift_right(H0v, NEG) + scv == row)
                    vup = ~any_v & (H0v + GP == row)
                    # both moves of every cell as one word of 2 * MB bits
                    moves = pack_moves(wdiag, wup, vdiag, vup, MB)
                    ok = moves != no_move(MB)

                    # insertion run: walk left to the nearest explained cell
                    okm = ok & (jj <= jcur) & here
                    j_stop = wmax(jnp.where(okm, jj, -1))
                    stuck = here & (j_stop < 0)
                    failed = first_cause(failed, stuck, FAIL_OTHER)
                    done = done | jnp.where(stuck, 1, 0)
                    act = here & ~stuck
                    j_stop = jnp.maximum(j_stop, 0)
                    if band:
                        # boundary touch: a column visited at this rank came
                        # within one cell of the band edge (the run's extreme
                        # columns are j_stop and the entry jcur)
                        cr_tb = ((exr(rk_key, r) + 0.5).astype(jnp.int32)
                                 - begin)
                        near = act & (wbv > 0) & (
                            (jnp.abs(j_stop - cr_tb) >= wbv - 1) |
                            (jnp.abs(jcur - cr_tb) >= wbv - 1))
                        hit_tb = c[6] | jnp.where(near, 1, 0)

                    lanes = (jj >= j_stop) & (jj < jcur) & act
                    runrem[...] = jnp.where(lanes, run + (jcur - jj),
                                            runrem[...])
                    nkey[...] = jnp.where(lanes, nk, nkey[...])
                    run = jnp.where(act, run + (jcur - j_stop), run)

                    # the descending move at j_stop (diag > up priority)
                    diag_ok, wd, wd_virt, wu, wu_virt = unpack_moves(
                        ex_v(moves, j_stop, narrow=2 * MB <= NARROW_BITS), MB)
                    take_diag = act & diag_ok
                    take_up = act & ~take_diag

                    kr = exr(rk_key, r)
                    nk = jnp.where(take_diag, kr, nk)
                    mlane = (jj == j_stop - 1) & take_diag
                    runrem[...] = jnp.where(mlane, 0, runrem[...])
                    nkey[...] = jnp.where(mlane, kr, nkey[...])
                    run = jnp.where(take_diag, 0, run)
                    jcur = jnp.where(take_diag, j_stop - 1,
                                     jnp.where(take_up, j_stop, jcur))

                    new_cur = jnp.where(
                        take_diag,
                        jnp.where(wd_virt, -1, r - wd % 256),
                        jnp.where(wu_virt, -1, r - wu % 256))
                    cur = jnp.where(act, new_cur, cur)

                    # a window that reached the virtual row finishes its
                    # remaining insertions in one masked write
                    at_virt = act & (cur == -1)
                    vl = (jj < jcur) & at_virt
                    runrem[...] = jnp.where(vl, run + (jcur - jj), runrem[...])
                    nkey[...] = jnp.where(vl, nk, nkey[...])
                    done = done | jnp.where(at_virt, 1, 0)
                    out = (cur, jcur, nk, run, done, failed)
                    if band:
                        out = out + (hit_tb,)
                    return out

                def tb_rank(i, c):
                    b = c[0]
                    r = b * BLK + (BLK - 1 - i)
                    cc = c[1:]
                    here_any = jnp.any((cc[4] == 0) & (cc[0] == r))
                    cc2 = jax.lax.cond(here_any,
                                       lambda cc: tb_rank_work(r, cc),
                                       lambda cc: cc, cc)
                    return (b,) + cc2

                def tb_block(i, c):
                    b = b_top - i

                    @pl.when(b >= 1)
                    def _():
                        tb_wait(b - 1, (b - 1) % 2)

                    c2 = jax.lax.fori_loop(0, BLK, tb_rank, (b,) + c)[1:]

                    @pl.when(b >= 2)
                    def _():
                        tb_load(b - 2, b % 2)
                    return c2

                if band:
                    cur, jcur, nk, run, done, failed, hit = jax.lax.fori_loop(
                        0, b_top + 1, tb_block,
                        (cur, jcur, nk0, run0, done0, failed, hit))
                else:
                    cur, jcur, nk, run, done, failed = jax.lax.fori_loop(
                        0, b_top + 1, tb_block,
                        (cur, jcur, nk0, run0, done0, failed))
                failed = first_cause(failed, (done == 0) & lact, FAIL_OTHER)
                bump("steps.traceback", (b_top + 1) * BLK)

            with jax.named_scope(R_UPDATE):
                # ---- graph update (parity: rt_poa.cpp add_alignment) --------
                maxL = jnp.max(jnp.where(lact & (failed == 0), Ln, 0))

                # In-edge slots a group's node insertions sweep this layer:
                # a slot at or past a row's rk_cnt is zero in rk_delta and
                # rk_ew (an edge is written at slot rk_cnt, an inserted row
                # starts at 0, rk_cnt moves with its row), and a layer adds
                # at most one in-edge to a node (the path visits a column
                # once), so one more than the group's largest count at the
                # layer's start bounds every slot that holds anything, or
                # comes to hold it, before the next layer.  One
                # vector-to-scalar turn a group a layer, not one a step.
                k_ins = [jnp.minimum(1 + jnp.max(rk_cnt[u:u + 1]), E)
                         for u in range(U)]
                # (E a group a layer would be all of them)
                bump("slots_swept", sum(k_ins))

                def upd_body(j, c):
                    n, failed, prev_r, prev_key, prev_w = c
                    act = lact & (j < Ln) & (failed == 0)
                    b = exs(seq_scr, slot, j)
                    wj = exs(w_scr, slot, j)
                    run_j = exr(runrem, j, narrow=True)      # <= L
                    nk_j = exr(nkey, j)
                    is_match = (run_j == 0) & act
                    k0 = nk_j

                    keys = rk_key[...]
                    basev = rk_base[...]
                    cand = (keys == k0) & (basev == b)
                    has = wany(cand) & is_match
                    found = wmin(jnp.where(cand, rr, N))

                    runf = run_j.astype(jnp.float32)
                    hi2 = jnp.where(nk_j < KEY_INF, nk_j, prev_key + 1.0)
                    lo2 = jnp.where(prev_r >= 0, prev_key, hi2 - runf - 1.0)
                    k_new = lo2 + (hi2 - lo2) / (runf + 1.0)
                    key_val = jnp.where(is_match, k0, k_new)

                    need_new = act & ~has
                    overflow = need_new & (n >= N)
                    do_new = need_new & ~overflow
                    p_ins = wsum(jnp.where(keys <= key_val, 1, 0),
                                 narrow=True)                # <= N
                    nid = jnp.where(has, found, jnp.minimum(p_ins, N - 1))

                    # Each group pays for its own insertions only, under
                    # its own gate.  Everything else in the step is shared.
                    def insert_node(u):
                        grp = pl.ds(u, 1)
                        dn = do_new[u:u + 1]
                        pi = p_ins[u:u + 1]

                        @pl.when(jnp.any(dn))
                        def _():
                            sh = (rr >= pi) & dn
                            new_row = (rr == pi) & dn
                            for ref, fill, val in (
                                    (rk_base, -1, b[u:u + 1]),
                                    (rk_key, KEY_INF, key_val[u:u + 1]),
                                    (rk_cov, 0, 0), (rk_cnt, 0, 0)):
                                v = ref[grp]
                                v = jnp.where(sh, shift_right(v, fill), v)
                                ref[grp] = jnp.where(new_row, val, v)

                            # Slots 0 .. k_ins[u] - 1, rounded up to whole
                            # blocks of SB: a slot at or past a row's rk_cnt
                            # is zero, so shifting it moves nothing.
                            # Measured on the v5e, two 30x ONT jobs of
                            # kernel: all E slots in one pass 12.87 s; a
                            # loop of k_ins steps, a slot a step, 12.00 s
                            # (PR 48; E copies under pl.when the same); SB
                            # slots a step as one load, one shift and one
                            # store (PR 49), x1.026 end to end at SB = 4
                            # against x1.019 / x1.018 / x1.015 at 2 / 6 / 12.
                            # A step costs its serial chain, not its vregs:
                            # bounding the lane-chunks a step shifts to
                            # those the insertion reaches (2 to 6 chunks a
                            # step, down from a bound on the group's node
                            # counts) ran x0.78 to x0.98, each step of that
                            # walk running this loop once more.
                            def shift_slots(i, _):
                                slots = pl.ds(i * SB, SB)
                                vd = rk_delta[slots, grp][:, 0]
                                sd = shift_right(vd, 0)
                                # an edge whose source sits below the
                                # insertion point now spans it: distance
                                # grows by one
                                sd = sd + jnp.where(
                                    (sd > 0) & (rr - 1 - sd < pi), 1, 0)
                                # the inserted row starts with no edges
                                rk_delta[slots, grp] = jnp.where(
                                    new_row, 0,
                                    jnp.where(sh, sd, vd))[:, None]
                                vw = rk_ew[slots, grp][:, 0]
                                rk_ew[slots, grp] = jnp.where(
                                    new_row, 0,
                                    jnp.where(sh, shift_right(vw, 0),
                                              vw))[:, None]
                                return 0

                            steps = (k_ins[u] + SB - 1) // SB
                            jax.lax.fori_loop(0, steps, shift_slots, 0)
                            bump("insert.firings", 1)
                            bump("insert.shift_steps", steps)

                    for u in range(U):
                        insert_node(u)

                    touch = act & ~overflow
                    # a node's coverage: at most a path a layer, <= D + 1
                    rmw_v(rk_cov, nid,
                          ex_v(rk_cov[...], nid, narrow=True) + 1, touch)
                    n = n + jnp.where(do_new, 1, 0)
                    failed = first_cause(failed, overflow, FAIL_NODES)

                    # edge prev -> nid with weight w[j-1] + w[j]
                    prev_r = prev_r + jnp.where(do_new & (prev_r >= p_ins),
                                                1, 0)
                    has_prev = touch & (prev_r >= 0)
                    d_tgt = nid - prev_r
                    cntv = ex_v(rk_cnt[...], nid, narrow=True)   # <= E
                    cnt_max = jnp.max(jnp.where(has_prev, cntv, 0))

                    def same_scan(e, s):
                        de = ex_v(rk_delta[pl.ds(e, 1)][0], nid,
                                  narrow=True)               # < N
                        return jnp.where((s < 0) & (e < cntv) & (de == d_tgt),
                                         e, s)

                    same = jax.lax.fori_loop(
                        0, cnt_max, same_scan,
                        jnp.full(w_shape, -1, jnp.int32))
                    ew = prev_w + wj
                    add_new = has_prev & (same < 0) & (cntv < E)

                    def eslot_write(e, _):
                        m_same = has_prev & (same == e)
                        m_new = add_new & (cntv == e)
                        roww = rk_ew[pl.ds(e, 1)][0]
                        rk_ew[pl.ds(e, 1)] = jnp.where(
                            (rr == nid) & (m_same | m_new),
                            jnp.where(m_same, roww + ew, ew), roww)[None]
                        rowd = rk_delta[pl.ds(e, 1)][0]
                        rk_delta[pl.ds(e, 1)] = jnp.where(
                            (rr == nid) & m_new, d_tgt, rowd)[None]
                        return 0

                    slot_hi = jnp.maximum(
                        cnt_max, jnp.max(jnp.where(add_new, cntv + 1, 0)))
                    jax.lax.fori_loop(0, slot_hi, eslot_write, 0)
                    rmw_v(rk_cnt, nid, cntv + 1, add_new)
                    failed = first_cause(
                        failed, has_prev & (same < 0) & (cntv >= E),
                        FAIL_EDGES)

                    prev_r = jnp.where(act, nid, prev_r)
                    prev_key = jnp.where(act, key_val, prev_key)
                    prev_w = jnp.where(act, wj, prev_w)
                    return (n, failed, prev_r, prev_key, prev_w)

                n, failed, _, _, _ = jax.lax.fori_loop(
                    0, maxL, upd_body,
                    (n, failed,
                     jnp.full(w_shape, -1, jnp.int32),
                     jnp.full(w_shape, -1.0, jnp.float32),
                     jnp.zeros(w_shape, jnp.int32)))
                bump("steps.update", maxL)
            return (n, failed, hit) if band else (n, failed)

        @pl.when(max_layers > 0)
        def _():
            start_copy(0, 0)

        def layer_loop(li, carry):
            slot = jax.lax.rem(li, 2)
            wait_copy(li, slot)

            @pl.when(li + 1 < max_layers)
            def _():
                start_copy(li + 1, jax.lax.rem(li + 1, 2))

            return do_layer(li, slot, carry)

        if band:
            n, failed, hit = jax.lax.fori_loop(
                0, max_layers, layer_loop,
                (bb_len, jnp.zeros(w_shape, jnp.int32),
                 jnp.zeros(w_shape, jnp.int32)))
        else:
            n, failed = jax.lax.fori_loop(
                0, max_layers, layer_loop,
                (bb_len, jnp.zeros(w_shape, jnp.int32)))

        with jax.named_scope(R_CONSENSUS):
            # ================= consensus =====================================
            # (parity: rt_poa.cpp generate_consensus — heaviest bundle)
            score[...] = jnp.zeros(n_shape, jnp.int32)
            spred[...] = jnp.full(n_shape, -1, jnp.int32)
            n_max = jnp.max(n)
            delta_f = [rk_delta[e] for e in range(E)]
            ew_f = [rk_ew[e] for e in range(E)]

            def score_body(r, c):
                best_r, best_s = c
                act = r < n
                cnt_r = exr(rk_cnt, r, narrow=True)          # <= E
                bw = jnp.full(w_shape, NEG, jnp.int32)
                bs = jnp.full(w_shape, NEG, jnp.int32)
                bp = jnp.full(w_shape, -1, jnp.int32)
                for e in range(E):
                    # a distance is under N; an edge's weight at most two
                    # qualities a layer, <= D * 2 * 93
                    d_e = exr(rk_delta.at[e], r, narrow=True)
                    w_e = exr(rk_ew.at[e], r, narrow=True)
                    valid = (d_e > 0) & (e < cnt_r)
                    s_e = ex_v(score[...], jnp.clip(r - d_e, 0, N - 1))
                    better = valid & ((w_e > bw) | ((w_e == bw) & (s_e > bs)))
                    bw = jnp.where(better, w_e, bw)
                    bs = jnp.where(better, s_e, bs)
                    bp = jnp.where(better, r - d_e, bp)
                s = jnp.where(bp >= 0, bw + bs, 0)
                rmw(score, r, s, act)
                rmw(spred, r, bp, act)
                better = act & (s > best_s)
                return (jnp.where(better, r, best_r),
                        jnp.where(better, s, best_s))

            summit, _ = jax.lax.fori_loop(
                0, n_max, score_body,
                (jnp.zeros(w_shape, jnp.int32),
                 jnp.full(w_shape, NEG, jnp.int32)))

            # backward walk to a source (ranks into revbuf)
            def bcond(c):
                u, cnt = c
                return jnp.any((u >= 0) & (cnt < N))

            def bbody(c):
                u, cnt = c
                act = (u >= 0) & (cnt < N)
                rmw_v(revbuf, cnt, u, act)
                pu = ex_v(spred[...], jnp.maximum(u, 0), narrow=True)  # < N
                return (jnp.where(act, pu, u),
                        cnt + jnp.where(act, 1, 0))

            _, cnt_b = jax.lax.while_loop(
                bcond, bbody, (summit, jnp.zeros(w_shape, jnp.int32)))

            cons_base_ref[0] = jnp.full(n_shape, -1, jnp.int32)
            cons_cov_ref[0] = jnp.zeros(n_shape, jnp.int32)
            base_f = rk_base[...]
            cov_f = rk_cov[...]

            def emit(i, u, act):
                bv = ex_v(base_f, u, narrow=True)            # a code, or -1
                cv = ex_v(cov_f, u, narrow=True)             # <= D + 1
                m = (rr == i) & act
                cons_base_ref[0] = jnp.where(m, bv, cons_base_ref[0])
                cons_cov_ref[0] = jnp.where(m, cv, cons_cov_ref[0])

            def flip_body(i, _):
                act = i < cnt_b
                u = ex_v(revbuf[...], jnp.clip(cnt_b - 1 - i, 0, N - 1),
                         narrow=True)                        # a rank, < N
                emit(i, jnp.clip(u, 0, N - 1), act)
                return 0

            jax.lax.fori_loop(0, jnp.max(cnt_b), flip_body, 0)

            # forward walk to a sink along heaviest out-edges
            def fcond(c):
                u, cnt, more = c
                return jnp.any(more > 0)

            def fbody(c):
                u, cnt, more = c
                ew = jnp.full(n_shape, NEG, jnp.int32)
                for e in range(E):
                    m = ((delta_f[e] > 0) & (delta_f[e] == rr - u) &
                         (rr < n))
                    ew = jnp.maximum(ew, jnp.where(m, ew_f[e], NEG))
                w_top = wmax(ew)
                any_out = (more > 0) & (w_top > NEG)
                cand_s = jnp.where(ew == w_top, score[...], NEG)
                smax = wmax(cand_s)
                v = wmin(jnp.where(cand_s == smax, rr, N))
                emit(cnt, jnp.clip(v, 0, N - 1), any_out)
                return (jnp.where(any_out, v, u),
                        cnt + jnp.where(any_out, 1, 0),
                        jnp.where(any_out, 1, 0))

            _, cnt_f, _ = jax.lax.while_loop(
                fcond, fbody,
                (summit, cnt_b,
                 jnp.ones(w_shape, jnp.int32)))

        for i in range(W):
            cl_s[0, 0, i] = scalar_of(cnt_f, i)
            fl_s[0, 0, i] = scalar_of(failed, i)     # 0 or a FAIL_* cause
            nn_s[0, 0, i] = scalar_of(n, i)
            if band:
                bh_s[0, 0, i] = jnp.where(scalar_of(hit, i) > 0, 1, 0)

    def make(batch: int):
        assert batch % W == 0, (batch, U, G)
        nb = batch // W
        # Per-window scalars ride a unit middle dim: Mosaic wants a
        # block's last two dims to equal the array's (or tile 8x128), and
        # a (1, W) block of an (nb, W) array only passes at nb == 1.
        smem2 = pl.BlockSpec((1, 1, W), lambda b: (b, 0, 0),
                             memory_space=pltpu.SMEM)
        smem3 = pl.BlockSpec((1, W, D), lambda b: (b, 0, 0),
                             memory_space=pltpu.SMEM)
        vblk = pl.BlockSpec((1, U, NC, G, 128), lambda b: (b, 0, 0, 0, 0),
                            memory_space=pltpu.VMEM)
        hbm = pl.BlockSpec(memory_space=pl.ANY)

        def n_rows(*lead, dtype=jnp.int32):
            return pltpu.VMEM(lead + (U, NC, G, 128), dtype)

        def j_rows(*lead, dtype=jnp.int32):
            return pltpu.VMEM(lead + (U, JC, G, 128), dtype)

        gshape = jax.ShapeDtypeStruct((nb, 1, W), jnp.int32)
        n_counts = len(PROGRAM_COUNTS)
        smem1 = pl.BlockSpec((1, 1, n_counts), lambda b: (b, 0, 0),
                             memory_space=pltpu.SMEM)
        return pl.pallas_call(
            kernel,
            grid=(nb,),
            in_specs=[smem2, smem2, smem3, smem3, smem3, vblk, vblk,
                      hbm, hbm] + ([smem2] if band else []),
            out_specs=[vblk, vblk, smem2, smem2, smem2] +
                      ([smem2] if band else []) + [smem1, hbm],
            out_shape=[
                jax.ShapeDtypeStruct((nb, U, NC, G, 128), jnp.int32),
                jax.ShapeDtypeStruct((nb, U, NC, G, 128), jnp.int32),
                gshape, gshape, gshape,
            ] + ([gshape] if band else []) + [
                jax.ShapeDtypeStruct((nb, 1, n_counts), jnp.int32),
                jax.ShapeDtypeStruct((nb, N, U, JC, G, 128), jnp.int32),
            ],
            scratch_shapes=[
                j_rows(RING),                                # Hring
                j_rows(),                                    # H0
                n_rows(),                                    # rk_base
                n_rows(dtype=jnp.float32),                   # rk_key
                n_rows(),                                    # rk_cov
                n_rows(),                                    # rk_cnt
                n_rows(E),                                   # rk_delta
                n_rows(E),                                   # rk_ew
                n_rows(),                                    # esc
                n_rows(max(3, NW)),       # aux: record; score, spred, revbuf
                j_rows(dtype=jnp.float32),                   # nkey
                j_rows(),                                    # runrem
                j_rows(2),                                   # seq_scr
                j_rows(2),                                   # w_scr
                pltpu.SemaphoreType.DMA((2, 2)),             # layer DMA
                pltpu.SemaphoreType.DMA((2,)),               # flush
                pltpu.SemaphoreType.DMA((2,)),               # tb load
            ],
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=vmem_limit_bytes(cfg, U)),
            interpret=interpret,
            name="racon_poa_ls",
        )

    @functools.lru_cache(maxsize=8)
    def jitted(batch: int):
        call = make(batch)
        nb = batch // W

        @named("racon_poa_ls")
        def fn(bb_len, n_layers, lens, begins, ends, bb, bbw, seqs, ws,
               *extra):
            # window b * W + u * G + g of the batch: program b, group u,
            # sublane g
            def to_n(x):
                x = jnp.pad(x.reshape(batch, BB), ((0, 0), (0, N - BB)))
                return x.reshape(nb, U, G, NC, 128).transpose(0, 1, 3, 2, 4)

            def to_j(x, fill):
                x = jnp.pad(x, ((0, 0), (0, 0), (0, JL - L)),
                            constant_values=fill)
                return x.reshape(nb, U, G, D, JC, 128).transpose(
                    0, 3, 1, 4, 2, 5)

            args = [bb_len.reshape(nb, 1, W), n_layers.reshape(nb, 1, W),
                    lens.reshape(nb, W, D), begins.reshape(nb, W, D),
                    ends.reshape(nb, W, D), to_n(bb), to_n(bbw),
                    to_j(seqs, 255), to_j(ws, 0)]
            if band:
                args.append(extra[0].reshape(nb, 1, W))
            outs = call(*args)
            cb, cc, cl, fl, nn = outs[:5]
            cb = cb.transpose(0, 1, 3, 2, 4).reshape(batch, N)
            cc = cc.transpose(0, 1, 3, 2, 4).reshape(batch, N)
            res = (cb, cc, cl.reshape(batch, 1), fl.reshape(batch, 1),
                   nn.reshape(batch, 1))
            if band:
                res = res + (outs[5].reshape(batch, 1),)
            # last: each program's PROGRAM_COUNTS
            return res + (outs[-2].reshape(nb, -1),)

        return Program(fn, key=("racon_poa_ls", cfg, interpret, band, U,
                                batch))

    return jitted
