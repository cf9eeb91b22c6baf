"""The Hirschberg driver's host bookkeeping as it was when it worked a
task at a time (commit 3e57bc8): a `Task` object per task, a Python loop
per launch slot.  Kept as the differential-test oracles of the per-launch
calls that replaced them (`align_pallas._pack_launch`, `_select`,
`_collect_base` + `_assemble`, `_deal_programs`, `ops_to_cigars`), and
a base task solved a cell at a time (`base_task`), the oracle of
`racon_hirschberg_base`'s outputs — nothing outside the tests imports
this module."""

import numpy as np

from racon_tpu.ops import band as _band
from racon_tpu.ops.align_pallas import GROUP, INF, PACK, _round_up
from racon_tpu.ops.encoding import pack_bases


class Task:
    __slots__ = ("pair", "ia", "ib", "ja", "jb")

    def __init__(self, pair, ia, ib, ja, jb):
        self.pair, self.ia, self.ib, self.ja, self.jb = pair, ia, ib, ja, jb

    def row(self):
        return [self.pair, self.ia, self.ib, self.ja, self.jb]


def half(t, backward):
    """The edge task of one half of `t`: forward over [ia, imid],
    backward over [imid, ib]."""
    imid = (t.ia + t.ib) // 2
    return Task(t.pair, imid if backward else t.ia,
                t.ib if backward else imid, t.ja, t.jb)


def task_arrays(pairs, slots, bands, rcap, K, backward):
    """One launch's slots (a Task, or None for a pad row) -> scal, qs,
    ts, a slot at a time."""
    B = len(slots)
    TCAP = rcap + K
    scal = np.zeros((B, 4), np.int32)
    qs = np.zeros((B, rcap), np.int32)
    ts = np.full((B, TCAP), 255, np.int32)
    for bi, t in enumerate(slots):
        if t is None:
            continue
        q, tt = pairs[t.pair]
        _, gdmin = bands[t.pair]
        R = t.ib - t.ia
        if backward:
            j_lo = max(t.ja, t.ia + gdmin)
            j_hi = t.jb
        else:
            j_lo = t.ja
            j_hi = min(t.jb, t.ib + gdmin + K)
        S = j_hi - j_lo
        assert 0 <= S <= TCAP, (S, TCAP)
        dmin = gdmin + t.ia - j_lo
        scal[bi] = (R, S, dmin, 0)
        qrow = q[t.ia:t.ib]
        qs[bi, :R] = qrow[::-1] if backward else qrow
        shift = dmin + (R - 1 - rcap if backward else 0)
        lo, hi = max(0, -shift), min(TCAP, S - shift)
        if hi > lo:
            ts[bi, lo:hi] = tt[j_lo + lo + shift:j_lo + hi + shift]
    qs = pack_bases(qs, width=max(128, _round_up(rcap // PACK, 128)))
    return scal, qs, ts


def deal_programs(tasks, B, shards):
    """The `B` slots of one launch: `tasks` then pad slots (None), the
    programs dealt round `shards` shards."""
    slots = tasks + [None] * (B - len(tasks))
    if shards > 1:
        deal = np.arange(B).reshape(-1, shards, min(GROUP, B // shards))
        slots = [slots[i] for i in deal.transpose(1, 0, 2).ravel()]
    return slots


def select(slots, F, Bv, bands, verify, failed, out):
    """Each task's crossing column at its midpoint row, a task at a
    time; its two halves go to `out`."""
    for gi, t in enumerate(slots):
        if t is None:
            continue
        imid = (t.ia + t.ib) // 2
        K_, gdmin = bands[t.pair]
        jmid = imid + gdmin - t.ja + np.arange(K_)
        span = t.jb - t.ja
        fv = np.full(span + 1, INF, np.int64)
        bv = np.full(span + 1, INF, np.int64)
        m = (jmid >= 0) & (jmid <= span)
        fv[jmid[m]] = F[gi][m]
        bv[jmid[m]] = Bv[gi][m]
        tot = fv + bv
        jstar = int(np.argmin(tot))
        if tot[jstar] >= INF:
            failed.add(t.pair)
            continue
        v = verify.get(t.pair) if verify else None
        if (v is not None and t.ia == 0 and t.ib == v[0]
                and t.ja == 0 and t.jb == v[1]):
            if not _band.ukkonen_ok(v[0], v[1], v[2], v[3],
                                    int(tot[jstar])):
                failed.add(t.pair)
                continue
        jabs = t.ja + jstar
        out.append(Task(t.pair, t.ia, imid, t.ja, jabs))
        out.append(Task(t.pair, imid, t.ib, jabs, t.jb))


def collect_base(slots, outs, segments, verify, failed):
    """A base launch's op codes, reversed into each pair's segments."""
    ops, cnt, ok, dist = outs
    for bi, t in enumerate(slots):
        if t is None:
            continue
        v = verify.get(t.pair) if verify else None
        if (v is not None and t.ia == 0 and t.ib == v[0]
                and t.ja == 0 and t.jb == v[1]):
            if (not ok[bi] or not _band.ukkonen_ok(
                    v[0], v[1], v[2], v[3], int(dist[bi]))):
                failed.add(t.pair)
                continue
        if not ok[bi]:
            failed.add(t.pair)
            continue
        seg = ops[bi, :cnt[bi]][::-1].astype(np.int32)
        segments.setdefault(t.pair, []).append((t.ia, seg))


def assemble(segments, failed, n_pairs):
    """Each pair's segments, sorted by first query row and joined."""
    results = [None] * n_pairs
    for idx, segs in segments.items():
        if idx in failed:
            continue
        segs.sort(key=lambda s: s[0])
        results[idx] = np.concatenate([s[1] for s in segs])
    return results


_OPC = np.frombuffer(b"MID", dtype=np.uint8)


def ops_to_cigar(ops: np.ndarray) -> str:
    """Run-length encode forward-ordered op codes, a run at a time."""
    if len(ops) == 0:
        return ""
    change = np.nonzero(np.diff(ops))[0]
    starts = np.concatenate([[0], change + 1])
    ends = np.concatenate([change + 1, [len(ops)]])
    out = []
    for s, e in zip(starts, ends):
        out.append(f"{e - s}{chr(_OPC[ops[s]])}")
    return "".join(out)


def base_task(q, t, R, S, dmin, K, OPS):
    """What `racon_hirschberg_base` returns for one task, a cell at a
    time: the banded DP of query rows q[:R] against target columns
    t[:S] (row i holds columns i + dmin .. i + dmin + K - 1), a move a
    cell with the kernel's preference (diagonal before up, left only
    where it is strictly cheaper), and the walk back from (R, S).
    -> (ops, cnt, ok, dist), ops as the kernel lays them: from the end,
    zero past cnt."""
    D = np.full((R + 1, S + 1), INF, np.int64)
    mv = np.zeros((R + 1, S + 1), np.int64)

    def band(i):
        return range(max(0, i + dmin), min(S, i + dmin + K - 1) + 1)

    for j in band(0):
        D[0, j] = j
    for i in range(1, R + 1):
        for j in band(i):
            sub = (D[i - 1, j - 1] + (q[i - 1] != t[j - 1])
                   if j - 1 in band(i - 1) else INF)
            up = D[i - 1, j] + 1 if j in band(i - 1) else INF
            D[i, j], mv[i, j] = (i, 1) if j == 0 else min((sub, 0), (up, 1))
            if j - 1 in band(i) and D[i, j - 1] + 1 < D[i, j]:
                D[i, j], mv[i, j] = D[i, j - 1] + 1, 2
        D[i] = np.minimum(D[i], INF)
    ops = np.zeros(OPS, np.int32)
    i, j, cnt, ok = R, S, 0, True
    while (i > 0 or j > 0) and cnt < OPS and ok:
        m = 2 if i == 0 else mv[i, j] if j in band(i) else 3
        ops[cnt] = m
        cnt += 1
        ok = m != 3
        i -= m != 2
        j -= m != 1
    return ops, cnt, int(ok and i == 0 and j == 0), \
        int(D[R, S]) if S in band(R) else INF
