"""Pallas banded global aligner: Hirschberg splitting over distance-only
kernels.

TPU-native replacement for the edlib seam (reference:
/root/reference/src/overlap.cpp:205-224) built for FULL-LENGTH reads. The
moves-matrix design needs O(rows x band) memory per pair,
which caps device-eligible pairs far below ONT read lengths; this engine
keeps only O(band) state per kernel program — the classic
divide-and-conquer (Hirschberg) trick:

  * forward kernel: banded unit-cost DP over a row range, returning ONLY
    the final score row (O(band) VMEM);
  * backward kernel: the mirrored recurrence from the bottom edge;
  * the host picks the optimal crossing column at the midpoint row from
    F + B and splits the problem in two — numpy bookkeeping, batched
    kernel launches, ~log2(n/base) rounds;
  * base-case kernel: subproblems of <= BASE_ROWS rows run the full
    moves-matrix DP in VMEM with in-kernel traceback, emitting op codes.

Layout: a grid program runs GROUP = 8 tasks in lock-step, task g in
sublane g of every (8, w) tile — the lane-lockstep layout of
poa_pallas_ls.py — so a DP row's ops (one rotation of the staged target,
log2(band) prefix-min steps) serve eight tasks for the price of one; one
task per program left seven of the eight sublanes of every vreg empty.
One program has one loop counter and one rotation amount, so whatever
differs per task is folded into the data the host stages
(_pack_launch), per-task scalars are (8, 1) columns broadcast along
lanes, and the loop runs to the program's largest row count while
shorter tasks carry their row through.  The host orders a launch's
tasks by row count before it cuts them into programs; pad slots have no
rows.  The base kernel's traceback follows the same rule: one loop walks
the program's eight tasks back together, a 128-lane chunk of the move
matrix read and one lane of the op tile written a task and trip, to the
program's longest walk.  A task's result does not depend on which tasks
share its program.

Mosaic constraints honored throughout (no scalar VMEM stores — masked row
RMW; no dynamic-lane scalar loads — masked reductions, or a rotation of
the wanted lane to lane 0; 3-D per-program blocks; i32 everywhere).

Costs are unit (edit distance), matching the reference's edlib NW config.
In-band-only contract as the reference's banded CUDA aligner; pairs whose
optimal path escapes the band are detected (INF at a midpoint) and left to
the host engine.

Multi-device: on a mesh of m > 1 chips every launch the partitioner's
gate admits (`will_shard`: at least one row per shard — a launch has at
least GROUP rows and is a power of two, so on four or eight chips that
is every launch) runs under shard_map over the 1-D `windows` axis
(leading batch dim, zero collectives): the same batch striping as the
consensus path and the analogue of the reference's per-GPU aligner
batches (/root/reference/src/cuda/cudapolisher.cpp:96-114).  Each shard
takes B / m consecutive slots, so the host deals the launch's ordered
programs round the shards (`_deal_programs`); a share of fewer than
GROUP rows runs as one program with idle sublanes (`_group_rows`): the
gate knows nothing of GROUP.  ``align.mesh.*`` counts, per launch,
whether it went over the mesh, its real and pad rows there, and the
per-shard programs that were whole or short of eight.  Where a pair is
aligned never changes its CIGAR.
"""

from __future__ import annotations

import collections
import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np

from .. import config, native, obs
from ..device import named
from . import band as _band
from .encoding import PACK, encode
from .kernel_cache import Program, device_keyed_cache

INF = 1 << 28
BASE_ROWS = 256          # subproblems at or below this row count run the
                         # full traceback kernel
ROW_BUCKETS = (512, 1024, 2048, 4096, 8192, 16384, 32768, 49152)
BANDS = (256, 512, 1024, 2048)


def band_for(n: int, m: int, band_hint: int = 0) -> int:
    """Band bucket: 10% of the larger side (reference auto-band rule,
    src/cuda/cudapolisher.cpp:159-163) plus the diagonal drift."""
    need = max(band_hint, abs(m - n) + max(n, m) // 10 + 2)
    for b in BANDS:
        if need <= b:
            return b
    return 0  # host


def _round_up(x, m):
    return (x + m - 1) // m * m


def _shard_over_mesh(build_local, batch, n_in, n_out):
    """Batch-stripe a kernel build over the partitioner's mesh (same
    no-collective striping as the consensus path; reference analogue:
    per-GPU aligner batches,
    /root/reference/src/cuda/cudapolisher.cpp:96-114).  The partitioner
    owns the gate: RACON_TPU_SHARD, the min-batch floor, sticky
    sharded->single-device demotion state, and divisibility.  None =
    don't shard; caller uses the single-device jit."""
    from ..parallel.partitioner import get_partitioner

    part = get_partitioner()
    if not part.will_shard(batch):
        return None
    return part.shard_build(build_local, batch, n_in, n_out)


def _dispatch_shards(batch: int) -> int:
    """Mesh shards a `batch`-row kernel launch dispatches over — mirrors
    _shard_over_mesh's gate so the shard-size accounting matches what
    the (batch-keyed, topology-keyed) jitted kernel actually does."""
    from ..parallel.partitioner import get_partitioner

    part = get_partitioner()
    m = part.batch_axis_size
    return m if (m > 1 and batch % m == 0
                 and part.will_shard(batch)) else 1


# ---------------------------------------------------------------------------
# distance-only kernels
# ---------------------------------------------------------------------------

GROUP = 8                # tasks per grid program, one per sublane of the
                         # int32 vreg tile
MOVE_ROWS = 4            # base kernel: DP rows whose moves share a word


def _row_ops(K, TCAP, scal_ref, t_ref):
    """What both kernels build their DP rows from, on (GROUP, K) tiles:
    the lane iota, the per-task scalars R, S, dmin as (GROUP, 1) columns
    (broadcast along lanes), a left rotation by one traced amount for
    all sublanes, the suffix min along lanes, and the forward DP row."""
    from jax.experimental.pallas import tpu as pltpu

    lane_k = jax.lax.broadcasted_iota(jnp.int32, (GROUP, K), 1)
    R = scal_ref[0, :, 0:1]
    S = scal_ref[0, :, 1:2]
    dmin = scal_ref[0, :, 2:3]

    def lroll(x, amt, width):
        # left-rotate every sublane by one traced amount in [0, width];
        # pltpu.roll only accepts non-negative shifts
        return pltpu.roll(x, jnp.mod(width - amt, width), 1)

    def cummin_fwd(x):
        # prefix min along lanes (left-to-right)
        k = 1
        while k < K:
            sh = jnp.where(lane_k >= k, pltpu.roll(x, k, 1), INF)
            x = jnp.minimum(x, sh)
            k *= 2
        return x

    def cummin_bwd(x):
        # suffix min along lanes (right-to-left)
        k = 1
        while k < K:
            sh = jnp.where(lane_k < K - k, pltpu.roll(x, K - k, 1), INF)
            x = jnp.minimum(x, sh)
            k *= 2
        return x

    def fwd_row(k, qc, row):
        """DP row i = k + 1 = 1..R from row i - 1 (j' = i + dmin + o)
        and the moves that made it: 0 diagonal, 1 up, 2 left."""
        i = k + 1
        jv = i + dmin + lane_k
        # target chars at j'-1 per lane: the host staged
        # ts[x] = t[x + dmin], so lane o wants ts[k + o]
        tc = lroll(t_ref[0], k, TCAP)[:, :K]
        sub = row + jnp.where(tc == qc, 0, 1)
        up = jnp.where(lane_k < K - 1, pltpu.roll(row, K - 1, 1),
                       INF) + 1
        V = jnp.minimum(sub, up)
        mv = jnp.where(V == sub, 0, 1)
        V = jnp.where(jv == 0, i, V)
        mv = jnp.where(jv == 0, 1, mv)
        V = jnp.where((jv < 0) | (jv > S), INF, V)
        nrow = cummin_fwd(V - lane_k) + lane_k
        mv = jnp.where(nrow < V, 2, mv)
        nrow = jnp.minimum(nrow, INF)
        nrow = jnp.where((jv < 0) | (jv > S), INF, nrow)
        return nrow, mv

    return lane_k, R, S, dmin, lroll, cummin_bwd, fwd_row


def _group_rows(b, arrays):
    """Inside a jitted kernel wrapper: pad `b` task rows up to whole
    programs of GROUP with idle rows (all zero: R = 0) and fold them to
    (programs, GROUP, w) tiles.  The host hands whole programs already;
    a mesh shard of fewer than GROUP rows is one program with idle
    sublanes, so the partitioner's gate needs no word about GROUP."""
    nb = -(-b // GROUP)
    return nb, [jnp.pad(a, ((0, nb * GROUP - b), (0, 0)))
                .reshape(nb, GROUP, a.shape[1]) for a in arrays]


def _group_scalars(nb, scal, rows_per_step):
    """(programs, GROUP, 4) task scalars -> the kernels' two views: the
    loop's trip count per program (its largest R in steps of
    `rows_per_step`; an idle row has R = 0 and never sets it) for SMEM,
    and the scalars as a (GROUP, 128) VMEM tile to cut columns from."""
    rmax = jnp.max(scal[:, :, 0], axis=1)
    trips = (rmax + rows_per_step - 1) // rows_per_step
    return trips.reshape(nb, 1, 1), jnp.pad(scal, ((0, 0), (0, 0), (0, 124)))


@device_keyed_cache(maxsize=64)
def _build_edge_kernel(rcap: int, K: int, backward: bool,
                       interpret: bool = False):
    """Batched banded DP over up to `rcap` rows; returns the last row.

    GROUP (8) tasks per grid program run in lock-step, task g in sublane
    g of every (8, w) tile, so each row op — the target rotation, the
    log2(K) prefix-min steps — serves eight tasks for the price of one
    (the layout of poa_pallas_ls.py; one task per program left seven of
    the eight sublanes of each vreg empty).  Per task: query slice q,
    target slice t (rcap + K), scalars R (rows), S (target span), dmin
    (local band offset).  Lane o of a row holds cell (i, j = i + dmin +
    o); the backward kernel mirrors the recurrence (B[i][o] from
    B[i+1][o], B[i+1][o-1]... expressed with opposite shifts).

    One program has one loop counter and one rotation amount, so what
    differs per task lives in the data (_pack_launch): the staged target
    is pre-shifted by each task's dmin (and R, backward) so that step k
    rotates every sublane by the same k, and the backward query arrives
    reversed so that step k reads word/code k in every sublane.  The
    loop runs to the group's largest R; a task past its own R carries
    its row through unchanged, so a task's result does not depend on
    which tasks share its program.

    The query arrives packed PACK (4) codes per int32 word
    (encoding.pack_bases) and each serial iteration retires PACK DP
    rows off one word-column read: the fori_loop runs ceil(R / PACK)
    trips.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    G = GROUP
    TCAP = rcap + K
    QIN = max(128, _round_up(rcap // PACK, 128))
    name = f"racon_hirschberg_edge_{'bwd' if backward else 'fwd'}"

    def kernel(trip_ref, scal_ref, q_ref, t_ref, out_ref):
        lane_k, R, S, dmin, lroll, cummin_bwd, fwd_row = _row_ops(
            K, TCAP, scal_ref, t_ref)

        def bwd_step(k, qc, row):
            # row i = R - 1 - k = R-1 .. 0, per task
            i = R - 1 - k
            jv = i + dmin + lane_k
            # t[j'] per lane: the host staged ts[z] = t[z - rcap + R - 1
            # + dmin], so lane o wants ts[rcap - k + o]
            tc = lroll(t_ref[0], rcap - k, TCAP)[:, :K]
            # B[i][o]: diag = B[i+1][o] + sub(q[i], t[j']);
            # down (consume query) = B[i+1][o-1] + 1;
            # right (consume target) = B[i][o+1] + 1 (suffix chain)
            sub = row + jnp.where(tc == qc, 0, 1)
            down = jnp.where(lane_k >= 1, pltpu.roll(row, 1, 1),
                             INF) + 1
            V = jnp.minimum(sub, down)
            V = jnp.where(jv == S, k + 1, V)          # R - i
            V = jnp.where((jv < 0) | (jv > S), INF, V)
            gv = K - 1 - lane_k
            nrow = cummin_bwd(V - gv) + gv
            nrow = jnp.minimum(nrow, INF)
            nrow = jnp.where((jv < 0) | (jv > S), INF, nrow)
            return nrow

        step = bwd_step if backward else \
            (lambda k, qc, row: fwd_row(k, qc, row)[0])
        # row 0: F[0][j'] = j' ; row R: B[R][j'] = S - j' ; j' in [0, S]
        j0 = (R if backward else 0) + dmin + lane_k
        row = jnp.where((j0 >= 0) & (j0 <= S),
                        S - j0 if backward else j0, INF)

        def body(it, row):
            # one word-column read feeds PACK rows; a task past its
            # own R carries `row` through unchanged
            qword = lroll(q_ref[0], it, QIN)[:, 0:1]
            for p in range(PACK):
                k = it * PACK + p
                qc = (qword >> (8 * p)) & 0xFF
                row = jnp.where(k < R, step(k, qc, row), row)
            return row

        out_ref[0] = jax.lax.fori_loop(0, trip_ref[0, 0, 0], body, row)

    def make(nb):
        smem1 = pl.BlockSpec((1, 1, 1), lambda b: (b, 0, 0),
                             memory_space=pltpu.SMEM)
        vtile = lambda w: pl.BlockSpec((1, G, w), lambda b: (b, 0, 0),
                                       memory_space=pltpu.VMEM)
        return pl.pallas_call(
            kernel,
            grid=(nb,),
            in_specs=[smem1, vtile(128), vtile(QIN), vtile(TCAP)],
            out_specs=vtile(K),
            out_shape=jax.ShapeDtypeStruct((nb, G, K), jnp.int32),
            interpret=interpret,
            name=name,
        )

    def plain(b):
        @named(name)
        def fn(scal, q, t):
            nb, (scal, q, t) = _group_rows(b, (scal, q, t))
            out = make(nb)(*_group_scalars(nb, scal, PACK), q, t)
            return out.reshape(nb * G, K)[:b]

        return Program(fn, key=(name, rcap, K, interpret, b))

    @functools.lru_cache(maxsize=8)
    def jitted(batch):
        sharded = _shard_over_mesh(plain, batch, 3, 1)
        return sharded if sharded is not None else plain(batch)

    return jitted


# ---------------------------------------------------------------------------
# base-case kernel: full moves + in-kernel traceback
# ---------------------------------------------------------------------------

@device_keyed_cache(maxsize=32)
def _build_base_kernel(K: int, interpret: bool = False):
    """Full moves-matrix DP over up to BASE_ROWS rows with the traceback
    in the kernel; returns op codes, their count, ok and the terminal
    distance per task.

    The forward DP is the edge kernel's: GROUP tasks per grid program in
    lock-step, task g in sublane g, inputs staged by _pack_launch.  Each
    loop iteration retires MOVE_ROWS rows and stores their moves as one
    word tile, a byte per row, so the move matrix of eight tasks is
    BASE_ROWS / MOVE_ROWS x K / 128 tiles of (GROUP, 128), a 128-lane
    chunk a leading index (the one (GROUP, K) store of a trip is K / 128
    static stores of the same vregs): 0.5-4 MB of VMEM at K 256-2048.

    The traceback is a serial walk of data-dependent length — scalar
    (i, j) -> row and chunk address -> load -> lane rotation -> vector
    to scalar -> move -> the next (i, j) — so the program's GROUP walks
    run as eight such chains side by side in one loop, which ends with
    the longest: the scheduler overlaps their latencies, and a task that
    has reached (0, 0), left the band (move 3) or filled its op row
    carries its state through.  A step loads the one (1, 128) chunk of
    the one move word row it reads, at whatever K (the chunk is an
    address, not a lane slice: Mosaic refuses one sublane at a dynamic
    row with a dynamic lane offset), and rotates the lane it wants to
    lane 0 (one XLU op; a masked sum over the lanes is two reductions
    and a dozen VPU ops for an int32; reading the whole row and rotating
    K lanes ran the launch 12 % longer at K >= 512 on the chip).  Every
    walking task writes op number `step` at trip `step`, so a trip's ops
    are one lane of a (GROUP, 128) tile held in a register and stored
    whole once a trip (`ops_scr`, laid out to the (GROUP, OPS) output
    after the loop): no load, and no pass over the op row.  The loop is
    bound by its scalar instructions (~230 bundles a trip of eight steps
    at every K, two scalar slots a bundle: tools/kernel_bundles.py), so
    what it carries is kept small: i and j a task, nothing else — the
    tile holds op + 1, which makes a task's op count the lanes of its
    row that are not zero, and a task that left the band stops with
    i = -1, which never reads as finished.  Nothing is recomputed: the
    move byte the DP stored decides each step.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    G, U = GROUP, MOVE_ROWS
    RB = BASE_ROWS
    TCAP = RB + K
    CHUNK = 128              # lanes of a vreg: what a walk step touches
    LOG_CHUNK, LOG_U = CHUNK.bit_length() - 1, U.bit_length() - 1
    # the walk's index arithmetic is shifts and masks
    assert all(x & (x - 1) == 0 for x in (CHUNK, U, RB)), (CHUNK, U, RB)
    OPS = _round_up(RB + K + 2, CHUNK)
    # packed query words (encoding.pack_bases), as in _build_edge_kernel;
    # a word never straddles two iterations
    assert U % PACK == 0, (U, PACK)
    QCAP = max(128, _round_up(RB // PACK, 128))

    def kernel(trip_ref, scal_s, scal_ref, q_ref, t_ref, ops_ref, cnt_ref,
               ok_ref, dist_ref, MVS, fin_scr, ops_scr):
        lane_k, R, S, dmin, lroll, _, fwd_row = _row_ops(
            K, TCAP, scal_ref, t_ref)
        lane_1 = jax.lax.broadcasted_iota(jnp.int32, (1, K), 1)
        lane_g = jax.lax.broadcasted_iota(jnp.int32, (G, CHUNK), 1)
        sub_g = jax.lax.broadcasted_iota(jnp.int32, (G, CHUNK), 0)

        def load_lane(rowvec, iota, idx):
            return jnp.sum(jnp.where(iota == idx, rowvec,
                                     jnp.zeros_like(rowvec)))

        def body(it, row):
            words = {}
            moves = jnp.zeros((G, K), jnp.int32)
            for p in range(U):
                k = it * U + p
                w = p // PACK
                if w not in words:
                    words[w] = lroll(q_ref[0], it * (U // PACK) + w,
                                     QCAP)[:, 0:1]
                qc = (words[w] >> (8 * (p % PACK))) & 0xFF
                nrow, mv = fwd_row(k, qc, row)
                moves = moves | (mv << (8 * p))
                # a task past its own R carries `row` through; its moves
                # there are never read (the walk starts at row R)
                row = jnp.where(k < R, nrow, row)
            for c in range(K // CHUNK):
                MVS[it, c] = moves[:, c * CHUNK:(c + 1) * CHUNK]
            return row

        j0 = dmin + lane_k
        row0 = jnp.where((j0 >= 0) & (j0 <= S), j0, INF)
        fin_scr[:] = jax.lax.fori_loop(0, trip_ref[0, 0, 0], body, row0)

        Rs = [scal_s[0, g, 0] for g in range(G)]
        Ss = [scal_s[0, g, 1] for g in range(G)]
        ds = [scal_s[0, g, 2] for g in range(G)]
        for g in range(G):
            # terminal distance D = DP[R][S]: lane o with R + dmin + o == S
            # (INF when the terminal cell is out of band).  Free with the
            # final row already live — it is the banded mode's exact
            # Ukkonen-verify input (ops/band.py) for base-case-only pairs.
            o_fin = Ss[g] - Rs[g] - ds[g]
            d_at = load_lane(fin_scr[pl.ds(g, 1), :], lane_1,
                             jnp.clip(o_fin, 0, K - 1))
            dist_ref[0, 0, g] = jnp.where((o_fin >= 0) & (o_fin < K),
                                          d_at, INF)

        # traceback from (R, S) to (0, 0), all GROUP tasks in one loop;
        # ops: 0=M 1=I(query) 2=D(target).  Every walking task writes op
        # number `step` at trip `step`, so the trip's ops are one lane
        # of the (GROUP, CHUNK) tile `acc`, stored whole once a trip; it
        # holds op + 1, and 0 where a task no longer walks.  A task walks
        # while (i | j) > 0.
        def cond(c):
            step, i, j, _ = c
            left = functools.reduce(
                jnp.maximum, [a | b for a, b in zip(i, j)])
            return (left > 0) & (step < OPS)

        def bodytb(c):
            step, i, j, acc = c
            at = step & (CHUNK - 1)
            col = lane_g == at
            acc = jnp.where(at == 0, jnp.zeros_like(acc), acc)
            nxt = []
            for g in range(G):
                o = j[g] - i[g] - ds[g]
                in_band = (o >= 0) & (o < K)
                # where the move is not the matrix's (row 0, out of
                # band, a task that has stopped) any word of it will do
                oc = jnp.where(in_band, o, 0)
                r = (i[g] - 1) & (RB - 1)
                row = MVS[r >> LOG_U, oc >> LOG_CHUNK, pl.ds(g, 1), :]
                # row r's byte of lane oc, brought to lane 0
                mv_at = pltpu.roll((row >> ((r & (U - 1)) << 3)) & 0xFF,
                                   (CHUNK - oc) & (CHUNK - 1), 1)[0, 0]
                # op + 1: 0 stopped, 1 M, 2 I, 3 D, 4 left the band
                known = jnp.where(
                    (i[g] | j[g]) > 0,
                    jnp.where(i[g] > 0, jnp.where(in_band, -1, 4), 3), 0)
                op1 = jnp.where(known < 0, mv_at + 1, known)
                acc = jnp.where(col & (sub_g == g), op1, acc)
                # M, I and the band's edge step a row up, M, D and the
                # edge a column left (a bit an op + 1); past the edge the
                # task stops with i = -1, which never reads as finished
                ig = i[g] - ((0b10110 >> op1) & 1)
                nxt.append((jnp.where(op1 == 4, -1, ig),
                            j[g] - ((0b11010 >> op1) & 1)))
            ops_scr[step >> LOG_CHUNK] = acc
            return (step + 1, *(tuple(x) for x in zip(*nxt)), acc)

        for c in range(OPS // CHUNK):
            ops_scr[c] = jnp.zeros((G, CHUNK), jnp.int32)
        _, i, j, _ = jax.lax.while_loop(
            cond, bodytb, (jnp.int32(0), tuple(Rs), tuple(Ss),
                           jnp.zeros((G, CHUNK), jnp.int32)))
        # a task's op count: the lanes of its row that hold an op
        cnt = jnp.zeros((G, CHUNK), jnp.int32)
        for c in range(OPS // CHUNK):
            ops1 = ops_scr[c]
            cnt = cnt + (ops1 > 0).astype(jnp.int32)
            ops_ref[0, :, c * CHUNK:(c + 1) * CHUNK] = jnp.maximum(
                ops1 - 1, 0)
        for g in range(G):
            cnt_ref[0, 0, g] = jnp.sum(cnt[g:g + 1])
            ok_ref[0, 0, g] = ((i[g] | j[g]) == 0).astype(jnp.int32)

    def make(nb):
        smem1 = pl.BlockSpec((1, 1, 1), lambda b: (b, 0, 0),
                             memory_space=pltpu.SMEM)
        # per-task scalars ride a unit middle dim, as in poa_pallas_ls
        smemg = pl.BlockSpec((1, 1, G), lambda b: (b, 0, 0),
                             memory_space=pltpu.SMEM)
        smem4 = pl.BlockSpec((1, G, 4), lambda b: (b, 0, 0),
                             memory_space=pltpu.SMEM)
        vtile = lambda w: pl.BlockSpec((1, G, w), lambda b: (b, 0, 0),
                                       memory_space=pltpu.VMEM)
        gshape = jax.ShapeDtypeStruct((nb, 1, G), jnp.int32)
        return pl.pallas_call(
            kernel,
            grid=(nb,),
            in_specs=[smem1, smem4, vtile(128), vtile(QCAP), vtile(TCAP)],
            out_specs=[vtile(OPS), smemg, smemg, smemg],
            out_shape=[jax.ShapeDtypeStruct((nb, G, OPS), jnp.int32),
                       gshape, gshape, gshape],
            scratch_shapes=[pltpu.VMEM((RB // U, K // CHUNK, G, CHUNK),
                                       jnp.int32),
                            pltpu.VMEM((G, K), jnp.int32),
                            pltpu.VMEM((OPS // CHUNK, G, CHUNK), jnp.int32)],
            interpret=interpret,
            name="racon_hirschberg_base",
        )

    def plain(b):
        @named("racon_hirschberg_base")
        def fn(scal, q, t):
            nb, (scal, q, t) = _group_rows(b, (scal, q, t))
            trips, cols = _group_scalars(nb, scal, U)
            ops, cnt, ok, dist = make(nb)(trips, scal, cols, q, t)
            return (ops.reshape(nb * G, OPS)[:b], cnt.reshape(-1)[:b],
                    ok.reshape(-1)[:b], dist.reshape(-1)[:b])

        return Program(fn, key=("racon_hirschberg_base", K, interpret, b))

    @functools.lru_cache(maxsize=8)
    def jitted(batch):
        sharded = _shard_over_mesh(plain, batch, 3, 4)
        return sharded if sharded is not None else plain(batch)

    return jitted, OPS, QCAP, TCAP


# ---------------------------------------------------------------------------
# host orchestrator
# ---------------------------------------------------------------------------

def _interpret() -> bool:
    import jax as _jax
    return _jax.devices()[0].platform != "tpu"


class _InFlight(set):
    """The launches dispatched and not yet waited for (what the device
    has to do while the host works), and how many launches this set has
    seen go over the mesh and to one device (the report's tally)."""

    sharded = single = 0


class _Launch:
    """One dispatched kernel launch, a member of `in_flight` until
    `wait` has blocked for its outputs; `width` is the slots a grid
    program of it holds, as dispatched."""

    __slots__ = ("in_flight", "outs", "span_args", "width")

    def __init__(self, in_flight, outs, span_args, width):
        self.in_flight, self.outs, self.span_args = in_flight, outs, span_args
        self.width = width
        in_flight.add(self)

    def ready(self):
        """Whether `wait` would return without blocking."""
        return all(x.is_ready() for x in self.outs)

    def wait(self):
        with obs.span("align.wait", cat="launch", **self.span_args):
            outs = tuple(np.asarray(x) for x in self.outs)
        self.in_flight.discard(self)
        self.outs = None
        return outs


# A round's tasks are a table, one int32 row a task: query rows [ia, ib)
# of pair `pair` against its target columns [ja, jb].  In a launch's table
# (one row a slot) a pad slot has pair -1.
PAIR, IA, IB, JA, JB = range(native.TASK_COLS)
_ROW_BUCKETS = np.array(ROW_BUCKETS)


class _Run:
    """What one `align_steps` call's launches read and write, per pair:
    `table` (native.PAIR_COLS int64 columns: the addresses of the pair's
    int32 query and target codes, kept alive in `held`, then n, m and
    gdmin), the band `K` (0 = not aligned here), `banded` (under a band
    override: its root task carries the Ukkonen certificate) and
    `failed`; `segs` collects the base launches' op codes."""

    def __init__(self, pairs, band_overrides, interpret, in_flight):
        self.interpret, self.in_flight = interpret, in_flight
        self.table = np.zeros((len(pairs), native.PAIR_COLS), np.int64)
        self.K = np.zeros(len(pairs), np.int32)
        self.banded = np.zeros(len(pairs), bool)
        self.failed = np.zeros(len(pairs), bool)
        self.held = []
        self.segs = []
        for idx, (q, t) in enumerate(pairs):
            n, m = len(q), len(t)
            K = band_for(n, m)
            if K == 0 or n == 0 or m == 0 or (n + 1) // 2 > ROW_BUCKETS[-1]:
                continue
            kb = band_overrides.get(idx) if band_overrides else None
            if kb is not None and kb < K:
                K = int(kb)
                self.banded[idx] = True
            gdmin = int(np.minimum(0, m - n) - (K - 1 - abs(m - n)) // 2)
            q = np.ascontiguousarray(q, np.int32)
            t = np.ascontiguousarray(t, np.int32)
            self.held.append((q, t))
            self.table[idx] = (q.ctypes.data, t.ctypes.data, n, m, gdmin)
            self.K[idx] = K
        self.n, self.m, self.gdmin = (
            self.table[:, c].astype(np.int32) for c in (2, 3, 4))

    def roots(self, tasks):
        """Mask over a task table: the root task of a banded pair."""
        if not self.banded.any():
            return np.zeros(len(tasks), bool)
        pair = tasks[:, PAIR]
        return ((pair >= 0) & self.banded[pair] & (tasks[:, IA] == 0)
                & (tasks[:, JA] == 0) & (tasks[:, IB] == self.n[pair])
                & (tasks[:, JB] == self.m[pair]))

    def certified(self, pair, distance) -> bool:
        """The exact Ukkonen in-band certificate of a banded pair whose
        global edit distance came out as `distance`."""
        return _band.ukkonen_ok(int(self.n[pair]), int(self.m[pair]),
                                int(self.K[pair]), int(self.gdmin[pair]),
                                int(distance))


def align_steps(pairs, *, interpret=None, band_overrides=None, hits=None,
                in_flight=None):
    """pairs: [(q_codes int32 np, t_codes int32 np)] -> [ops np | None],
    as a generator: wherever it has dispatched launches and would block
    next it yields the launches it is about to wait for, and it returns
    the results.  Whoever drives it may do other host work at a yield —
    advance another cohort, whose launches then queue behind these, or
    come back when the yielded launches are `ready` — and nothing at all
    (`align_pairs`): what is computed never depends on it.  No span is
    open at a yield.

    ops are forward-ordered codes (0=M, 1=I, 2=D); None = host fallback
    (band escape / oversize).

    band_overrides: {pair index: K} runs those pairs under the given
    band (narrower than the flat ``band_for`` bucket) with the exact
    Ukkonen in-band verify (ops/band.py): the terminal distance must
    certify that every optimal AND co-optimal path lies strictly inside
    the band — then midpoints, tie-breaks and traceback coincide with
    the flat kernel's and the result is byte-identical.  A pair whose
    certificate fails is aborted at its first round (no wasted
    recursion), gets result None, and its index is added to `hits` for
    the caller's verify-and-widen ladder.

    in_flight: the `_InFlight` set this call's launches join while they
    are out (`_Launch`); cohorts that share the device share one.

    The host's bookkeeping runs a launch at a time over a table of tasks
    (`_pack_launch`, `_select`, `_collect_base`, `_assemble`: one native
    call each), never a task at a time.
    """
    if interpret is None:
        interpret = _interpret()
    if in_flight is None:
        in_flight = _InFlight()
    run = _Run(pairs, band_overrides, interpret, in_flight)
    active = np.flatnonzero(run.K).astype(np.int32)
    tasks = np.zeros((len(active), native.TASK_COLS), np.int32)
    tasks[:, PAIR], tasks[:, IB], tasks[:, JB] = (
        active, run.n[active], run.m[active])

    while True:
        small = tasks[:, IB] - tasks[:, IA] <= BASE_ROWS
        big = tasks[~small & ~run.failed[tasks[:, PAIR]]]
        if not len(big):
            break
        tasks = np.concatenate(
            [tasks[small], (yield from _split_round(run, big))])

    # base cases
    yield from _solve_base(run, tasks[~run.failed[tasks[:, PAIR]]])

    with obs.span("align.traceback", cat="launch", pairs=len(active)):
        results = _assemble(run)
    if hits is not None:
        # any banded-pair failure is a band hit: a verified-clean banded
        # pair cannot fail mid-recursion (certificate covers co-optima)
        hits.update(np.flatnonzero(run.failed & run.banded).tolist())
    return results


def _drive(steps):
    """A stepped alignment run alone to its end: at each yield it has
    launches out and nobody else to feed the device, so it goes straight
    on to its wait."""
    while True:
        try:
            next(steps)
        except StopIteration as stop:
            return stop.value


def align_pairs(pairs, **kwargs):
    """`align_steps` driven alone to its end: pairs -> [ops np | None]."""
    return _drive(align_steps(pairs, **kwargs))


def _pow2(n):
    b = 1
    while b < n:
        b *= 2
    return b


def _pack_launch(run, tasks, rcap, K, backward):
    """Pack one launch's slots (`tasks`: a row a slot, pair -1 for a pad
    row) into the edge kernel's arrays, in one native call
    (rt_hirschberg.cpp).  The staged target window is clipped to the
    half's band-reachable columns (j <= ib + gdmin + K going forward,
    j >= ia + gdmin going backward) so it fits rcap + K — the full task
    span can be up to 2*rcap + K.

    The kernel rotates all GROUP tasks of a program by one amount per
    step, so each row is staged pre-shifted by what differs per task:
    forward ts[x] = t[j_lo + x + dmin], backward ts[z] = t[j_lo + z -
    rcap + R - 1 + dmin] (255 outside the window; those cells are out of
    [0, S] and masked).  The backward query goes out reversed so that
    step k reads q[R - 1 - k] at index k in every task; the codes go
    out packed PACK to a word.  A pad row has R = 0: it costs its program
    nothing and never sets a group's trip count."""
    return native.hirschberg_pack(
        run.table, tasks, rcap, K, backward,
        max(128, _round_up(rcap // PACK, 128)))


def _deal_programs(group, B):
    """The `B` slots of one launch as a task table: `group` (ordered by
    R, so a program's GROUP tasks are of a length) then pad slots (pair
    -1; R = 0, they ride in the last program).  Over a mesh every shard
    takes B / shards consecutive slots, so the ordered programs are
    dealt round the shards: no device gets all the long ones."""
    slots = np.zeros((B, native.TASK_COLS), np.int32)
    slots[:len(group)] = group
    slots[len(group):, PAIR] = -1
    shards = _dispatch_shards(B)
    if shards > 1:
        deal = np.arange(B).reshape(-1, shards, min(GROUP, B // shards))
        slots = slots[deal.transpose(1, 0, 2).ravel()]
    return slots


def _launch(in_flight, kernel, call, args, n_real, n_single=0, **geom):
    """Dispatch one kernel launch and start its outputs' copy back; the
    `_Launch` it returns is waited for later, so that the host's next
    work runs under this kernel.  ``align.dispatch`` is the jitted call
    that returns device futures (on a program's first use it traces,
    lowers and loads, which shows as ``jit.*`` spans inside) and the
    start of the copies; ``align.wait`` (`_Launch.wait`) is what is left
    of kernel and copy when the host comes to need the outputs.  `n_real`
    of the batch's rows are tasks, the rest pads it to a power of two.

    Counted here, once per launch: whether it went over the mesh
    (``align.mesh.launches.sharded`` / ``.single``) and, if it did, the
    rows per device (count_shard_rows, shared with consensus), its own
    ``align.mesh.rows.real`` / ``.pad`` and the grid programs its shards
    ran: ``align.mesh.programs.whole`` with all GROUP sublanes offered a
    slot, ``.short`` where a shard's share is under GROUP rows (one
    program, idle sublanes); how well the lock-step programs engage —
    ``align.lockstep.rows.real`` the DP rows the tasks asked for,
    ``.slots`` the sublane-rows their programs ran (GROUP x each
    program's largest R); whether the launch found the device fed:
    ``align.queue.behind`` when an earlier launch is still out, else
    ``align.queue.empty`` (the device idles until this one arrives);
    and how the host handles its tasks: ``align.host.tasks.batched``
    those whose pack, select or collect is a share of one per-launch
    call, ``.single`` the `n_single` that also get a step of their own
    (a banded pair's root: the Ukkonen certificate)."""
    B = len(args[0])
    shards = _dispatch_shards(B)
    per_shard = B // shards
    if shards > 1:
        from .batch_exec import count_shard_rows

        count_shard_rows(n_real, B, shards)
        in_flight.sharded += 1
        obs.count("align.mesh.launches.sharded")
        obs.count("align.mesh.rows.real", n_real)
        obs.count("align.mesh.rows.pad", B - n_real)
        obs.count("align.mesh.programs.whole" if per_shard >= GROUP
                  else "align.mesh.programs.short",
                  shards * max(1, per_shard // GROUP))
    else:
        in_flight.single += 1
        obs.count("align.mesh.launches.single")
    # every shard cuts its consecutive share of the slots into programs
    # of GROUP; a share under GROUP slots is one program
    width = min(GROUP, per_shard)
    rows = args[0][:, 0].reshape(-1, width)
    obs.count("align.lockstep.rows.real", int(rows.sum()))
    obs.count("align.lockstep.rows.slots",
              GROUP * int(rows.max(axis=1).sum()))
    obs.count("align.queue.behind" if in_flight else "align.queue.empty")
    span_args = dict(kernel=kernel, B=B, shards=shards, **geom)
    with obs.span("align.dispatch", cat="launch", **span_args):
        outs = call(B)(*args)
        if not isinstance(outs, (tuple, list)):
            outs = (outs,)
        for x in outs:
            x.copy_to_host_async()
    obs.count("align.launches.base" if kernel == "base"
              else "align.launches.edge")
    obs.count("align.tasks.real", n_real)
    obs.count("align.tasks.pad", B - n_real)
    obs.count("align.host.tasks.batched", n_real - n_single)
    obs.count("align.host.tasks.single", n_single)
    return _Launch(in_flight, outs, span_args, width)


def _buckets(order, *keys):
    """Cut `order` (task indices sorted by `keys`) where a key changes:
    [(the bucket's key values, its indices)], in `order`'s order."""
    if not len(order):
        return []
    cols = np.stack([k[order] for k in keys], axis=1)
    cuts = (np.flatnonzero((cols[1:] != cols[:-1]).any(axis=1)) + 1).tolist()
    return [(tuple(cols[a].tolist()), order[a:b])
            for a, b in zip([0] + cuts, cuts + [len(order)])]


def _split_round(run, tasks):
    """One Hirschberg round: split every oversized task at its midpoint.
    Every bucket's forward and backward launch goes out before the first
    wait (``align.round``; a backward pack runs under its forward
    kernel), then one yield, then wait and select bucket by bucket, each
    under the launches of the buckets behind it.  Returns the halves, a
    task table."""
    R = tasks[:, IB] - tasks[:, IA]
    Ks = run.K[tasks[:, PAIR]]
    rcaps = _ROW_BUCKETS[np.searchsorted(_ROW_BUCKETS, (R + 1) // 2)]
    # a launch is a (rcap, K) bucket; a program's eight tasks run to its
    # largest R, so each is ordered by R before it is cut into programs
    # (pad rows, R = 0, ride in the last one)
    buckets = _buckets(np.lexsort((R, Ks, rcaps)), rcaps, Ks)

    issued = []
    out_now = []    # the round's launches, until each is waited for
    out = []
    try:
        with obs.span("align.round", cat="launch", tasks=len(tasks),
                      buckets=len(buckets)):
            for (rcap, K), group in buckets:
                # pad the batch dim to a power of two (at least one
                # program of GROUP tasks) so each (rcap, K) bucket
                # compiles a handful of kernel variants, not one per
                # group size
                B = max(GROUP, _pow2(len(group)))
                geom = dict(rcap=rcap, K=K)
                slots = _deal_programs(tasks[group], B)
                imid = (slots[:, IA] + slots[:, IB]) // 2
                n_single = int(run.roots(slots).sum())
                launches = []
                for backward in (False, True):
                    kernel = "edge_bwd" if backward else "edge_fwd"
                    with obs.span("align.pack", cat="launch", kernel=kernel,
                                  B=B, **geom):
                        # forward over [ia, imid], backward over [imid, ib]
                        half = slots.copy()
                        half[:, IA if backward else IB] = imid
                        args = _pack_launch(run, half, rcap, K, backward)
                    launches.append(_launch(
                        run.in_flight, kernel,
                        _build_edge_kernel(rcap, K, backward, run.interpret),
                        args, len(group), n_single, **geom))
                issued.append((geom, len(group), slots, launches))
                out_now.extend(launches)
        yield out_now
        for geom, n_tasks, slots, (fwd, bwd) in issued:
            (F,), (Bv,) = fwd.wait(), bwd.wait()
            with obs.span("align.select", cat="launch", tasks=n_tasks,
                          **geom):
                out.append(_select(run, slots, F, Bv))
    finally:
        # launches an exception left out leave the set with this round
        run.in_flight.difference_update(out_now)
    return np.concatenate(out)


def _select(run, slots, F, Bv):
    """Pick each task's crossing column at its midpoint row from the
    forward and backward edge rows (F, Bv: a row a slot), in one native
    call; returns its two halves, a task table in slot order.

    Both midpoint rows map lane o to absolute column j = imid + gdmin +
    o (independent of each frame's clipped origin), so the task's
    columns [ja, jb] are a range of lanes, the lane order is the column
    order and the first minimal lane is the first minimal column.  A
    task with no finite lane fails its pair."""
    rows = np.flatnonzero(slots[:, PAIR] >= 0).astype(np.int32)
    tasks = slots[rows]
    pair = tasks[:, PAIR]
    imid = (tasks[:, IA] + tasks[:, IB]) // 2
    lane0 = imid + run.gdmin[pair]
    lane, tot = native.hirschberg_select(
        np.ascontiguousarray(F), np.ascontiguousarray(Bv), rows,
        tasks[:, JA] - lane0, tasks[:, JB] - lane0)
    ok = tot < INF
    # root task of a banded pair: `tot` IS the global edit distance
    # (every path crosses the midpoint row), so check the exact Ukkonen
    # certificate here and abort the whole pair before recursing on an
    # unproven band
    for i in np.flatnonzero(run.roots(tasks) & ok):
        ok[i] = run.certified(pair[i], tot[i])
    run.failed[pair[~ok]] = True
    tasks, imid, jabs = tasks[ok], imid[ok], (lane0 + lane)[ok]
    halves = np.repeat(tasks[:, None, :], 2, axis=1)
    halves[:, 0, IB] = halves[:, 1, IA] = imid
    halves[:, 0, JB] = halves[:, 1, JA] = jabs
    return halves.reshape(-1, native.TASK_COLS)


# Base launches a cohort may have out at once.  A launch holds ~2 MB on
# the device (inputs and outputs of 64 tasks at K 2048) and as much in
# packed host arrays, and a cohort has ~20 of them: eight is ~25 ms of
# kernel ahead of the host, several times the jitter of its ~2 ms of
# work per launch, at 16 MB.  The bound is there for a cohort of 100 kb
# pairs, which has hundreds.
BASE_AHEAD = 8


def _solve_base(run, tasks):
    """The base cases, every launch independent of every other: up to
    BASE_AHEAD go out before the first wait, then one is waited for and
    traced back (under the launches behind it) and the next one packed
    and dispatched, with a yield before each wait."""
    R = tasks[:, IB] - tasks[:, IA]
    Ks = run.K[tasks[:, PAIR]]
    # ordered by R: like rows share a program
    chunks = [(K, group[off:off + 64])
              for (K,), group in _buckets(np.lexsort((R, Ks)), Ks)
              for off in range(0, len(group), 64)]

    def issue(K, chunk):
        kern, _, _, _ = _build_base_kernel(K, run.interpret)
        B = max(GROUP, _pow2(len(chunk)))
        geom = dict(rcap=BASE_ROWS, K=K)
        with obs.span("align.pack", cat="launch", kernel="base", B=B,
                      **geom):
            slots = _deal_programs(tasks[chunk], B)
            args = _pack_launch(run, slots, BASE_ROWS, K, False)
        n_single = int(run.roots(slots).sum())
        return slots, len(chunk), _launch(run.in_flight, "base", kern, args,
                                          len(chunk), n_single, **geom)

    todo = iter(chunks)
    flying = collections.deque(
        issue(*c) for c in itertools.islice(todo, BASE_AHEAD))
    try:
        while flying:
            yield [flying[0][-1]]
            slots, n_tasks, launch = flying.popleft()
            outs = launch.wait()
            with obs.span("align.traceback", cat="launch", tasks=n_tasks,
                          K=launch.span_args["K"]):
                _collect_base(run, slots, outs, launch.width)
            flying.extend(issue(*c) for c in itertools.islice(todo, 1))
    finally:
        run.in_flight.difference_update(launch for *_, launch in flying)


def _collect_base(run, slots, outs, width=GROUP):
    """A base launch's op codes, reversed (the walk runs from the end)
    and laid back to back in slot order by one native call; `run.segs`
    keeps them with each segment's pair, first query row and length.
    `width` is the slots a grid program of the launch held."""
    ops, cnt, ok, dist = outs
    rows = np.flatnonzero(slots[:, PAIR] >= 0)
    # how well the joint walk engages: the ops the real tasks returned
    # over what their programs' trips billed, GROUP x each program's
    # longest walk (a pad slot has R = S = 0 and returns none)
    obs.count("align.traceback.steps.real", int(cnt[rows].sum()))
    obs.count("align.traceback.steps.slots",
              GROUP * int(cnt.reshape(-1, width).max(axis=1).sum()))
    good = ok[rows] != 0
    # base-case-only banded pair: the kernel's terminal distance carries
    # the exact Ukkonen certificate
    for i in np.flatnonzero(run.roots(slots[rows]) & good):
        good[i] = run.certified(slots[rows[i], PAIR], dist[rows[i]])
    run.failed[slots[rows[~good], PAIR]] = True
    rows = rows[good]
    ops = np.ascontiguousarray(ops, np.int32)
    cnt = np.ascontiguousarray(cnt[rows], np.int32)
    if len(cnt) and not 0 <= cnt.min() <= cnt.max() <= ops.shape[1]:
        raise ValueError("base kernel: op count outside its row")
    codes = native.hirschberg_gather(
        ops.ctypes.data + rows * ops.strides[0], cnt, True)
    run.segs.append((codes, slots[rows, PAIR], slots[rows, IA], cnt))


def _assemble(run):
    """Each pair's forward op codes from the base launches' segments,
    ordered by first query row and copied to one array by one native
    call: [ops view | None] per pair (None: not aligned here, or
    failed)."""
    results = [None] * len(run.K)
    served = np.flatnonzero((run.K > 0) & ~run.failed)
    if not len(served):
        return results
    assert run.segs, "a pair that did not fail has base segments"
    codes = np.concatenate([s[0] for s in run.segs])
    pair, ia, cnt = (np.concatenate([s[c] for s in run.segs])
                     for c in (1, 2, 3))
    starts = np.cumsum(cnt, dtype=np.int64) - cnt
    keep = np.flatnonzero(~run.failed[pair])
    keep = keep[np.lexsort((ia[keep], pair[keep]))]
    cnt = np.ascontiguousarray(cnt[keep])
    flat = native.hirschberg_gather(
        codes.ctypes.data + codes.itemsize * starts[keep], cnt, False)
    # a pair's op codes end where its last segment ends
    end_of = np.zeros(len(run.K) + 1, np.int64)
    end_of[1:] = np.cumsum(np.bincount(pair[keep], cnt, len(run.K)))
    for idx in served.tolist():
        results[idx] = flat[end_of[idx]:end_of[idx + 1]]
    return results


def ops_to_cigars(ops_list) -> list:
    """CIGAR strings of forward-ordered op code arrays (0=M, 1=I, 2=D):
    one native run-length pass over them all."""
    off = np.zeros(len(ops_list) + 1, np.uint64)
    np.cumsum([len(ops) for ops in ops_list], out=off[1:])
    flat = np.concatenate(ops_list) if len(ops_list) else ()
    return native.ops_to_cigars(np.ascontiguousarray(flat, np.int32), off)


def ops_to_cigar(ops: np.ndarray) -> str:
    """Run-length encode forward-ordered op codes (0=M,1=I,2=D)."""
    return ops_to_cigars([ops])[0]


def cohort_size(default: int = 64) -> int:
    """Jobs materialized per device cohort (RACON_TPU_ALIGN_COHORT)."""
    env = config.get_raw("RACON_TPU_ALIGN_COHORT")
    return max(1, int(env if env is not None else default))


class _Cohort:
    """One dispatched cohort: its stepped alignment (`align_steps` under
    `_HirschbergOps._steps`), advanced by its own dispatch and unpack
    and, whenever the launches it waits for have come back, on the time
    of the cohort ahead of it."""

    __slots__ = ("steps", "waits_for", "done", "results", "error")

    def __init__(self, steps):
        self.steps = steps
        self.waits_for = ()       # the launches its next step blocks on
        self.done = False
        self.results = None
        self.error = None

    def advance(self):
        """Run to the next yield or to the end, blocking where it has
        to; an exception is the caller's."""
        try:
            self.waits_for = next(self.steps)
        except StopIteration as stop:
            self.done, self.results, self.waits_for = True, stop.value, ()

    def advance_if_ready(self):
        """One step on another cohort's time, if it would not block: the
        host never waits for this cohort while the one ahead has work.
        An exception is kept and raised when this cohort is unpacked, so
        the lattice charges the cohort that failed."""
        if (self.done or self.error is not None
                or not all(launch.ready() for launch in self.waits_for)):
            return
        try:
            self.advance()
        except Exception as e:  # noqa: BLE001 — re-raised by unpack
            self.error = e


class _HirschbergOps:
    """Executor hooks (ops/batch_exec.py) for the Hirschberg engine.

    The engine orchestrates its rounds on the host, as a generator that
    yields where it has launches out and would block (`align_steps`), so
    cohorts pipeline like any engine's chunks: `dispatch` creates a
    cohort's generator and advances it to its first yield (first round
    packed and dispatched), `unpack` drives it to its end and, at each
    of its yields and again between the installs of its jobs, advances
    the other cohort in flight by one step if that step would not block
    (`_advance_others`).  One host thread; the device queue is the
    concurrency: cohort N+1's packs and selects run under cohort N's
    kernels and N's under N+1's, and N is installed while N+1 goes
    through its rounds.  Lattice retries,
    bisection probes and the widen ladder run `attempt`: the same
    generator driven alone to its end — bounded retry,
    bisection-quarantine of a poisoned job and tier death to host are
    what they were.

    Single-copy packing: `pack` encodes each job once into two
    preallocated padded row buffers; the per-job views are what lattice
    retries and bisection probes reuse (the old loop re-materialized
    every pair per attempt with a per-job Python loop)."""

    span_name = "align.cohort"
    pack_span = "align.export"
    install_span = "align.install"

    def __init__(self, pipeline, dims, report, stats, state):
        from ..resilience import lattice as rl

        self.pipeline = pipeline
        self.dims = dims          # job -> (n, m) from the bulk lengths
        self.report = report
        self.stats = stats
        self.state = state        # {"served": int}
        self.pairs = {}           # job -> (q_view, t_view), packed once
        self.band = {}            # job -> band.BandState (banded jobs)
        self.dead = False
        self.cohorts = {}         # first job -> _Cohort, while in flight
        self.in_flight = _InFlight()  # their launches not yet waited for
        # under a watchdog deadline `unpack` runs on a thread the lattice
        # may abandon, and no generator may be left to two threads: then
        # nothing is advanced but the cohort being resolved
        self.step_others = rl.device_timeout() <= 0

    def live_tier(self, ctx, kind):
        # a cohort dispatched before the engine died (`kind` is its own
        # tier) still resolves from the launches it has out
        if kind == "host" or (self.dead and kind is None):
            return "host"
        return "hirschberg"

    def export(self, ctx, group):
        return list(group)

    def pack(self, ctx, chunk):
        qcap = max(1, max(self.dims[j][0] for j in chunk))
        tcap = max(1, max(self.dims[j][1] for j in chunk))
        qbuf = np.zeros((len(chunk), qcap), dtype=np.int32)
        tbuf = np.zeros((len(chunk), tcap), dtype=np.int32)
        obs.count("native.calls.align_job", len(chunk))
        for bi, job in enumerate(chunk):
            qa, ta = self.pipeline.align_job(job)
            if len(qa) <= qcap and len(ta) <= tcap:
                qbuf[bi, :len(qa)] = encode(qa)
                tbuf[bi, :len(ta)] = encode(ta)
                self.pairs[job] = (qbuf[bi, :len(qa)], tbuf[bi, :len(ta)])
            else:
                # lengths-table mismatch (duck-typed pipeline): fall back
                # to a standalone copy for just this job
                self.pairs[job] = (encode(qa).astype(np.int32),
                                   encode(ta).astype(np.int32))
        return None

    def _steps(self, sub):
        """`align_steps` over the packed views of `sub`, band state read
        once at the start; returns one result per job (`_band.HIT` for a
        band hit).  Pure: hit classification and ladder advance happen
        in install()."""
        from ..resilience import faults

        plist = [self.pairs[j] for j in sub]
        overrides = {}
        for bi, j in enumerate(sub):
            st = self.band.get(j)
            if st is not None and st.k is not None:
                overrides[bi] = st.k
        if not overrides:
            return (yield from align_steps(plist, in_flight=self.in_flight))
        forced = False
        try:
            # the deterministic widening-exhaustion drill: an armed
            # band.hit fault turns every banded job of this attempt
            # into a hit, driving the ladder to its flat floor
            faults.check("band.hit", sub)
        except faults.InjectedFault:
            forced = True
        hits = set()
        res = yield from align_steps(plist, band_overrides=overrides,
                                     hits=hits, in_flight=self.in_flight)
        if forced:
            hits.update(overrides)
        return [_band.HIT if bi in hits else res[bi]
                for bi in range(len(sub))]

    def dispatch(self, ctx, kind, packed, chunk):
        from ..resilience import faults

        faults.check("align.run", chunk)
        cohort = _Cohort(self._steps(chunk))
        cohort.advance()
        self.cohorts[chunk[0]] = cohort
        return cohort

    def attempt(self, ctx, kind, sub):
        from ..resilience import faults

        faults.check("align.run", sub)
        return _drive(self._steps(sub))

    def unpack(self, ctx, kind, cohort):
        if cohort.error is not None:
            raise cohort.error
        while not cohort.done:
            cohort.advance()
            self._advance_others(cohort)
        return cohort.results

    def _advance_others(self, cohort):
        """Give every cohort in flight but `cohort` a step, if its
        launches are back."""
        if self.step_others:
            for other in self.cohorts.values():
                if other is not cohort:
                    other.advance_if_ready()

    def span_args(self, ctx, chunk, pipelined):
        return {"jobs": len(chunk)}

    def install(self, ctx, kind, sub, results):
        from ..resilience import faults

        # one run-length pass over the cohort's op codes
        cigars = iter(ops_to_cigars(
            [ops for ops in results if isinstance(ops, np.ndarray)]))
        for job, ops in zip(sub, results):
            if isinstance(ops, _band.Hit):
                # banded verify failed: advance this job's widening
                # ladder; the executor's widen() loop re-attempts it
                st = self.band.get(job)
                if st is not None:
                    n, m = self.dims[job]
                    st.widen(n, m, band_for(n, m), self.report,
                             tier=kind or "hirschberg",
                             cells_counter="align.cells.banded")
                continue
            if ops is None:
                # host aligns it: the optimal path left the band (counted)
                # or band_for refused the pair (counted by run_jobs)
                if band_for(*self.dims[job]):
                    obs.count("align.pairs.host.escaped")
                continue
            st = self.band.get(job)
            if st is not None:
                st.pending = False
            faults.check("align.install", (job,))
            self.pipeline.set_job_cigar(job, next(cigars))
            self._advance_others(None)
            self.state["served"] += 1
            if self.stats is not None:
                self.stats["device"] = self.stats.get("device", 0) + 1
            if self.report is not None:
                self.report.record_served("hirschberg")

    def surrender(self, ctx, items, exported):
        pass  # CIGAR-less jobs fall to the native host pass

    def quarantine(self, ctx, job, exc):
        if self.report is not None:
            self.report.record_quarantine(job, exc)

    def demote(self, ctx, kind, cause):
        import sys

        if self.dead:   # a cohort still in flight when the engine died
            return "host"
        self.dead = True
        print(f"[racon_tpu::align] WARNING: hirschberg engine failed "
              f"({type(cause).__name__}: {cause}); remaining jobs fall "
              f"back to the host aligner", file=sys.stderr)
        if self.report is not None:
            self.report.record_degrade("hirschberg", "host", cause)
        return "host"

    def widen(self, ctx, kind):
        """Band-hit jobs of the current chunk awaiting a widened
        re-attempt (executor verify-and-widen seam).  Clearing `pending`
        here makes the ladder drain: a re-attempt either installs (flat
        floor included — exhausted jobs re-run with no override) or hits
        again, re-arming `pending` one rung higher."""
        retry = [j for j in self.pairs
                 if (st := self.band.get(j)) is not None and st.pending]
        for j in retry:
            self.band[j].pending = False
        return retry

    def done(self, ctx, chunk):
        # keep host memory O(cohort): packed views die with the chunk
        self.cohorts.pop(chunk[0], None)
        for job in chunk:
            self.pairs.pop(job, None)
            self.band.pop(job, None)

    # -- sharded dispatch (optional executor hook) -------------------------
    def demote_shard(self, ctx, kind, cause):
        # A cohort died while its round kernels could have been sharded:
        # drop the partitioner to single-device, flush the builder
        # caches (the batch-keyed jitted closures baked in shard_map
        # wraps), and retry the SAME tier locally before any tier
        # demotion — the sharded -> single-device lattice edge.
        from ..parallel.partitioner import get_partitioner
        from ..resilience import lattice as rl

        part = get_partitioner()
        if (part.disabled is not None or part.batch_axis_size <= 1
                or config.get_raw("RACON_TPU_SHARD") == "0"):
            return False
        if part.demote(f"{type(cause).__name__}: {cause}"):
            rl.record_shard_demotion(self.report, kind, cause)
        _build_edge_kernel.cache_clear()
        _build_base_kernel.cache_clear()
        return True


def run_jobs(pipeline, jobs, cohort: int = None, report=None,
             stats=None, lengths=None) -> int:
    """Align pipeline jobs with the Hirschberg engine; install CIGARs.
    Returns how many the device served (band escapes fall to host).
    Jobs are packed per cohort (single copy into padded buffers) so host
    memory stays O(cohort), not O(total bases).

    Cohorts are length-bucketed by (band, first-round row bucket) so a
    cohort launches geometry-homogeneous kernel batches — one long pair
    no longer drags a cohort of short pairs through its row splits.

    `lengths` is the bulk job-lengths array (the driver fetches it once
    and threads it through); without it, one bulk FFI fetch happens here.

    Each cohort runs through the degradation lattice via the shared
    executor: bounded retry, then bisection (a poisoned job is
    quarantined to the host while the rest of the cohort stays on the
    device).  A cohort-independent failure stops the engine and leaves
    the remaining jobs CIGAR-less for the host — the served count stays
    accurate for the cohorts already installed, whatever point the
    engine died at.  ``stats['device']`` (when the driver passes its
    accounting dict) is incremented per install, so even an exception
    escaping this function cannot erase already-installed work from the
    driver's device count."""
    import sys

    from ..resilience import lattice as rl
    from .batch_exec import BatchExecutor

    if cohort is None:
        cohort = cohort_size()
    if lengths is None and hasattr(pipeline, "align_job_lengths"):
        lengths = pipeline.align_job_lengths()
    if lengths is not None:
        dims = {j: (int(lengths[j, 0]), int(lengths[j, 1])) for j in jobs}
    else:  # duck-typed pipelines without the lengths table
        dims = {}
        for job in jobs:
            qa, ta = pipeline.align_job(job)
            dims[job] = (len(qa), len(ta))

    # Length buckets: band x the first split round's row bucket — the
    # geometry key align_pairs' rounds compile under.  With banded DP on
    # (RACON_TPU_BAND), a job whose Ukkonen band plan beats its flat
    # bucket starts on the narrow band instead (verify-and-widen makes
    # that safe), and the bucket key uses the banded K so cohorts stay
    # geometry-homogeneous.
    banded_on = _band.enabled()
    band_states = {}
    buckets = {}
    by_band = collections.Counter()     # band_for's bucket -> pairs
    for job in jobs:
        n, m = dims[job]
        K = band_for(n, m)
        by_band[K] += 1
        kb = _band.plan_align_band(n, m, K) if banded_on and K else None
        if kb is not None:
            band_states[job] = _band.BandState(kb)
        half = (max(n, 1) + 1) // 2
        rcap = next((rb for rb in ROW_BUCKETS if half <= rb), 0)
        buckets.setdefault((kb if kb is not None else K, rcap),
                           []).append(job)
    if band_states:
        obs.count("band.jobs", len(band_states))
    # where the band ladder put the job's pairs: one counter a band
    # bucket (every key at every job, a zero too), and the pairs band_for
    # holds no bucket for (|m - n| + a tenth of the longer side past the
    # widest band), which the host aligns
    for K in BANDS:
        obs.count(f"align.pairs.band.k{K}", by_band[K])
    obs.count("align.pairs.host.refused", by_band[0])
    # how the job's pairs fill the cohorts: a bucket's last cohort is
    # partial, so many thin buckets mean many launches of few pairs
    cohorts = sum(-(-len(items) // cohort) for items in buckets.values())
    obs.count("align.buckets", len(buckets))
    obs.count("align.cohorts", cohorts)
    obs.count("align.cohorts.partial",
              sum(len(items) % cohort > 0 for items in buckets.values()))
    obs.count("align.cohorts.pairs", len(dims))
    obs.count("align.cohorts.capacity", cohort * cohorts)

    state = {"served": 0}
    ops_obj = _HirschbergOps(pipeline, dims, report, stats, state)
    ops_obj.band = band_states
    executor = BatchExecutor(ops_obj, report=report)
    try:
        for (K, rcap), items in sorted(buckets.items()):
            for off in range(0, len(items), cohort):
                group = items[off:off + cohort]
                if obs.enabled():
                    # Measured-cell counter for the cost model
                    # (obs/costmodel.py): forward+backward distance
                    # passes over the recursion tree ~ 2x the base
                    # max(n,m) x band DP.  align.cells.hirschberg stays
                    # the flat-band count; align.cells.banded is what
                    # the banded plan actually iterates, so the ratio of
                    # the two is the measured cell cut.
                    obs.count("align.cells.hirschberg", sum(
                        2 * max(dims[j][0], dims[j][1])
                        * band_for(dims[j][0], dims[j][1])
                        for j in group))
                    bj = [j for j in group if j in band_states]
                    if bj:
                        obs.count("align.cells.banded", sum(
                            2 * max(dims[j][0], dims[j][1])
                            * band_states[j].k for j in bj))
                executor.submit(None, group)
        executor.flush()
    except Exception as e:  # noqa: BLE001 — lattice boundary
        cause = e.cause if isinstance(e, rl.TierDead) else e
        print(f"[racon_tpu::align] WARNING: hirschberg engine failed "
              f"({type(cause).__name__}: {cause}); remaining jobs fall "
              f"back to the host aligner", file=sys.stderr)
        if report is not None:
            report.record_degrade("hirschberg", "host", cause)
    if report is not None:
        executor.stamp_walls(report)
        # how many of the phase's launches went over the mesh
        report.extra.setdefault("kernels", {}).update(
            launches_sharded=ops_obj.in_flight.sharded,
            launches_single=ops_obj.in_flight.single)
    return state["served"]
