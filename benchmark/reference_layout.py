"""What polishing a raw layout from PAF asks of the program, recomputed
from the cell's files alone (draft FASTA, reads FASTQ, PAF): numpy only,
nothing of the program.

1. Each overlap's error (1 - shorter span / longer span, from the PAF's
   own coordinates) and whether ``-e`` drops it; of a read's consecutive
   lines the longest stands (contig polishing; racon's order of the two
   rules, :func:`paf_overlaps`).
2. Each kept pair aligned *optimally* at unit costs over the whole matrix
   (``reference_align.py``'s recurrence, with the moves kept a block of
   rows at a time so that a 20 kb pair fits), and from that alignment, per
   window of the draft, racon's rules (``rt_pipeline.cpp``
   ``build_windows``, upstream ``src/polisher.cpp:407-461``): one piece
   per overlap and window, from the first to the last aligned pair inside
   the window; a piece of fewer read bases than 2 % of the window is
   dropped (``dropped_short``), then one whose mean base quality is under
   ``-q`` (``dropped_quality``); what is left is admitted, with its begin
   and end on the backbone.
3. The nodes an exact partial-order graph of the admitted layers holds
   (``reference_depth.window_demand``, fed the alignments as SAM records):
   the upper bound the driver's rung rule is held to.

An optimal alignment is not unique, so a piece's first or last base can
differ by a few from the program's; counts per window are what is
compared, never paths.
"""

from __future__ import annotations

import os
import tempfile

import numpy as np

from . import reference_depth

_COMP = np.zeros(256, np.uint8)
for _a, _b in zip(b"ACGT", b"TGCA"):
    _COMP[_a] = _b
_OPS = "MID"


def read_fastq(path: str) -> dict:
    """name -> (bases, qualities as Phred numbers), both uint8 arrays."""
    out = {}
    with open(path, "rb") as f:
        lines = f.read().split(b"\n")
    for i in range(0, len(lines) - 3, 4):
        out[lines[i][1:].split()[0].decode()] = (
            np.frombuffer(lines[i + 1], np.uint8),
            np.frombuffer(lines[i + 3], np.uint8) - 33)
    return out


def paf_overlaps(path: str, error_threshold: float) -> list:
    """One dict a PAF line, in file order: the line's own fields, its
    ``error``, and ``kept`` by racon's rule for contig polishing
    (upstream ``src/polisher.cpp:285-309``), applied within each run of
    consecutive lines of one read, in order: a line over the threshold is
    dropped; one that stands drops every later line of the run that is
    not longer, and is itself dropped by the first later one that is."""
    rows = []
    with open(path) as f:
        for line in f:
            c = line.rstrip("\n").split("\t")
            q_span, t_span = int(c[3]) - int(c[2]), int(c[8]) - int(c[7])
            length = max(q_span, t_span)
            rows.append(dict(
                name=c[0], q_len=int(c[1]), q_begin=int(c[2]),
                q_end=int(c[3]), reverse=c[4] == "-", target=c[5],
                t_begin=int(c[7]), t_end=int(c[8]), length=length,
                error=1.0 - min(q_span, t_span) / length, kept=True))
    lo = 0
    while lo < len(rows):
        hi = lo + 1
        while hi < len(rows) and rows[hi]["name"] == rows[lo]["name"]:
            hi += 1
        for i in range(lo, hi):
            if not rows[i]["kept"]:
                continue
            if rows[i]["error"] > error_threshold:
                rows[i]["kept"] = False
                continue
            for j in range(i + 1, hi):
                if not rows[j]["kept"]:
                    continue
                if rows[i]["length"] >= rows[j]["length"]:
                    rows[j]["kept"] = False
                else:
                    rows[i]["kept"] = False
                    break
        lo = hi
    return rows


def on_target_strand(row: dict, seq: np.ndarray, qual: np.ndarray) -> tuple:
    """(bases, qualities, begin, end) of an overlap's read as racon
    aligns it: on the target's strand, with the overlap's span there."""
    if row["reverse"]:
        return (_COMP[seq][::-1], qual[::-1], row["q_len"] - row["q_end"],
                row["q_len"] - row["q_begin"])
    return seq, qual, row["q_begin"], row["q_end"]


def _rows(qa, ta, row, cols, moves=None):
    """Run the unit-cost recurrence over the query bases ``qa`` from the
    DP row ``row``; returns the last row.  With ``moves`` (len(qa) x
    len(ta) + 1, uint8) the move into every cell is kept: 0 diagonal, 1
    from above (a query base alone), 2 from the left."""
    for i, c in enumerate(qa):
        diag = row[:-1] + (ta != c)
        up = row[1:] + 1
        new = np.empty_like(row)
        new[0] = row[0] + 1
        np.minimum(diag, up, out=new[1:])
        out = np.minimum.accumulate(new - cols) + cols
        if moves is not None:
            mv = moves[i]
            mv[0] = 1
            mv[1:] = np.where(out[1:] == diag, 0,
                              np.where(out[1:] == up, 1, 2))
        row = out
    return row


def align(q: np.ndarray, t: np.ndarray, block: int = 512) -> tuple:
    """(cost, op codes 0=M 1=I 2=D from the first column to the last) of
    an optimal unit-cost global alignment of ``q`` to ``t``: the whole
    matrix, its moves recomputed a block of rows at a time from the rows
    kept at the blocks' tops."""
    n, m = len(q), len(t)
    cols = np.arange(m + 1, dtype=np.int64)
    tops = [cols.copy()]
    for lo in range(0, n, block):
        tops.append(_rows(q[lo:lo + block], t, tops[-1], cols))
    cost = int(tops[-1][-1]) if n else m
    ops, j = [], m
    for b in range(len(tops) - 2, -1, -1):
        lo = b * block
        part = q[lo:lo + block]
        moves = np.empty((len(part), m + 1), np.uint8)
        _rows(part, t, tops[b], cols, moves)
        i = len(part)
        while i > 0:
            mv = int(moves[i - 1, j])
            ops.append(mv)
            i -= mv != 2
            j -= mv != 1
    ops += [2] * j
    return cost, np.array(ops[::-1], np.uint8)


def stray_bases(q: np.ndarray, t: np.ndarray, ops: np.ndarray) -> int:
    """Query bases an alignment puts off the target between its first and
    its last aligned pair: aligned to another base, or inserted.  What
    makes a window's graph grow."""
    on_q, on_t = ops != 2, ops != 1
    qi, ti = np.cumsum(on_q) - on_q, np.cumsum(on_t) - on_t
    m = np.flatnonzero(ops == 0)
    if not len(m):
        return 0
    return (int((ops[m[0]:m[-1] + 1] == 1).sum())
            + int((q[qi[m]] != t[ti[m]]).sum()))


def cigar(ops: np.ndarray) -> str:
    if not len(ops):
        return ""
    edge = np.flatnonzero(np.diff(ops)) + 1
    lo = np.concatenate([[0], edge])
    hi = np.concatenate([edge, [len(ops)]])
    return "".join(f"{b - a}{_OPS[ops[a]]}" for a, b in zip(lo, hi))


def window_layers(draft_path: str, reads_path: str, paf_path: str, *,
                  window_length: int, quality_threshold: float,
                  error_threshold: float, nodes: bool = True) -> dict:
    """``overlaps`` (:func:`paf_overlaps`' rows, the kept ones with their
    ``cost``), and per window of the first draft contig, as int64 arrays:
    ``offered``, ``dropped_short``, ``dropped_quality``, ``admitted``,
    ``layer_bases`` (read bases of the admitted layers) and, with
    ``nodes``, ``nodes`` (the exact graph's count); ``layers`` lists each
    window's admitted (begin, end) on the backbone, end inclusive."""
    (contig, draft), = list(reference_depth.read_fasta(draft_path).items())[:1]
    reads = read_fastq(reads_path)
    w = int(window_length)
    n_win = (len(draft) + w - 1) // w
    counts = {k: np.zeros(n_win, np.int64) for k in (
        "offered", "dropped_short", "dropped_quality", "admitted",
        "layer_bases")}
    layers = [[] for _ in range(n_win)]
    rows = paf_overlaps(paf_path, error_threshold)
    sam = [f"@SQ\tSN:{contig}\tLN:{len(draft)}"]
    for row in rows:
        if not row["kept"] or row["target"] != contig:
            continue
        seq, qual, lo, hi = on_target_strand(row, *reads[row["name"]])
        row["cost"], ops = align(seq[lo:hi], draft[row["t_begin"]:row["t_end"]])
        on_q, on_t = ops != 2, ops != 1
        m = ops == 0
        m_q = lo + (np.cumsum(on_q) - on_q)[m]
        m_t = row["t_begin"] + (np.cumsum(on_t) - on_t)[m]
        win = m_t // w
        edge = np.flatnonzero(np.diff(win)) + 1
        first = np.concatenate([[0], edge])
        last = np.concatenate([edge, [len(win)]]) - 1
        total = np.concatenate([[0], np.cumsum(qual.astype(np.int64))])
        for a, b in zip(first, last):
            k, q0, q1 = int(win[a]), int(m_q[a]), int(m_q[b]) + 1
            counts["offered"][k] += 1
            if q1 - q0 < 0.02 * w:
                counts["dropped_short"][k] += 1
            elif (total[q1] - total[q0]) / (q1 - q0) < quality_threshold:
                counts["dropped_quality"][k] += 1
            else:
                counts["admitted"][k] += 1
                counts["layer_bases"][k] += q1 - q0
                layers[k].append((int(m_t[a]) - k * w, int(m_t[b]) - k * w))
        clips = (f"{lo}S" if lo else "", f"{len(seq) - hi}S"
                 if len(seq) > hi else "")
        sam.append("\t".join((
            row["name"], "16" if row["reverse"] else "0", contig,
            str(row["t_begin"] + 1), "60", clips[0] + cigar(ops) + clips[1],
            "*", "0", "0", seq.tobytes().decode(), "*")))
    out = dict(counts, overlaps=rows, layers=layers)
    if nodes:
        with tempfile.TemporaryDirectory() as d:
            path = os.path.join(d, "reference.sam")
            with open(path, "w") as f:
                f.write("\n".join(sam) + "\n")
            demand = reference_depth.window_demand(
                draft_path, reads_path, path, window_length=w,
                quality_threshold=quality_threshold,
                error_threshold=error_threshold)
        out["nodes"] = demand["nodes"]
        out["depth_layers"] = demand["layers"]
    return out
