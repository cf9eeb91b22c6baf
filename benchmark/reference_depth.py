"""What a deep polish job asks of a window graph, recomputed from the
cell's files alone (draft FASTA, reads FASTQ, SAM): numpy only, nothing
of the program.

Per window of the draft, from the SAM's own CIGARs:

* the layers racon's rules admit (``rt_pipeline.cpp`` ``build_windows``,
  upstream ``src/polisher.cpp:407-461``): one piece per overlap and
  window, from the first to the last aligned pair inside the window; a
  piece of fewer read bases than 2 % of the window length is dropped, as
  is one whose mean base quality is under ``-q``; an overlap whose error
  (1 - shorter span / longer span) is over ``-e`` is dropped whole; a
  read keeps its longest overlap only (contig polishing);
* what the depth cap (upstream's ``MAX_DEPTH_PER_WINDOW``) leaves: the
  first ``depth_cap`` layers in order of their begin on the backbone;
* the nodes an *exact* partial-order graph of those layers needs: the
  backbone's bases, plus every distinct (column, substituted base),
  plus every distinct (gap slot, place in the inserted run, inserted
  base).  Deletions add edges, never nodes.

It is the truth's graph, not SPOA's: the program aligns each layer to
the graph by dynamic programming, which merges an insertion with a
neighbouring mismatch, slides an inserted base along a homopolymer and
so lands on fewer nodes than the CIGARs spell out.  On the deep cell's
data the program's graphs hold 0.70-0.78 of this count at 85-130
layers and 0.85-0.95 of it under 40 (tests/test_deep_cell.py pins the
band).  The count is therefore an upper bound of what a served window
used, and the yardstick for the driver's rung rule: a rung that holds
this many nodes holds the window.
"""

from __future__ import annotations

import re

import numpy as np

_CIGAR = re.compile(rb"(\d+)([MIDNSHP=X])")
_OP_CODE = {b"M": 0, b"=": 0, b"X": 0, b"I": 1, b"D": 2, b"N": 2,
            b"S": 3, b"H": 4, b"P": 4}


def read_fasta(path: str) -> dict:
    """name -> bases (uint8) of a FASTA."""
    out, name, parts = {}, None, []
    with open(path, "rb") as f:
        for line in f:
            if line.startswith(b">"):
                if name is not None:
                    out[name] = np.frombuffer(b"".join(parts), np.uint8)
                name, parts = line[1:].split()[0].decode(), []
            else:
                parts.append(line.strip())
    if name is not None:
        out[name] = np.frombuffer(b"".join(parts), np.uint8)
    return out


def read_fastq_qualities(path: str) -> dict:
    """name -> PHRED + 33 bytes (uint8), read strand, of a FASTQ whose
    records are four lines each (the generator's)."""
    out = {}
    with open(path, "rb") as f:
        while True:
            head = f.readline()
            if not head:
                return out
            f.readline()
            f.readline()
            out[head[1:].split()[0].decode()] = np.frombuffer(
                f.readline().strip(), np.uint8)


def _ops(cigar: bytes) -> tuple:
    """(op code per CIGAR run, run length) with M/=/X 0, I 1, D/N 2,
    S 3, H/P 4."""
    runs = _CIGAR.findall(cigar)
    return (np.array([_OP_CODE[o] for _, o in runs], np.int8),
            np.array([int(n) for n, _ in runs], np.int64))


class Overlap:
    """One SAM record, walked: the aligned pairs and the inserted bases
    on the target's strand, each with its window."""

    def __init__(self, name, flag, t_begin, cigar, seq, window_length):
        code, length = _ops(cigar)
        op = np.repeat(code, length)
        op = op[op < 4]                       # hard clips hold no bases
        on_q = (op == 0) | (op == 1) | (op == 3)
        on_t = (op == 0) | (op == 2)
        q = np.cumsum(on_q) - on_q            # read index before each op
        t = t_begin + np.cumsum(on_t) - on_t  # target index before it
        self.name, self.reverse = name, bool(flag & 16)
        self.seq = np.frombuffer(seq, np.uint8)
        m = op == 0
        self.m_q, self.m_t = q[m], t[m]
        ins = op == 1
        self.i_q = q[ins]
        # an inserted base hangs off the column before it; its place in
        # the run is its distance from the run's first base
        self.i_t = t[ins] - 1
        first = np.concatenate([[True], np.diff(np.flatnonzero(ins)) > 1]) \
            if ins.any() else np.zeros(0, bool)
        start = np.maximum.accumulate(np.where(first, self.i_q, 0)) \
            if ins.any() else self.i_q
        self.i_k = self.i_q - start
        self.q_span = int(self.m_q[-1] - self.m_q[0] + 1) if m.any() else 0
        self.t_span = int(self.m_t[-1] - self.m_t[0] + 1) if m.any() else 0
        self.w = window_length

    def error(self) -> float:
        lo, hi = sorted((self.q_span, self.t_span))
        return 1.0 - lo / hi if hi else 1.0

    def pieces(self):
        """(window, begin in window, first read index, last read index)
        per window the overlap has an aligned pair in."""
        win = self.m_t // self.w
        edge = np.flatnonzero(np.diff(win)) + 1
        lo = np.concatenate([[0], edge])
        hi = np.concatenate([edge, [len(win)]]) - 1
        return (win[lo], self.m_t[lo] - win[lo] * self.w,
                self.m_q[lo], self.m_q[hi])


def window_demand(draft_path: str, reads_path: str, sam_path: str, *,
                  window_length: int, quality_threshold: float,
                  error_threshold: float, depth_cap: int = 200) -> dict:
    """Per window of the first (only) draft contig, as int64 arrays of
    one entry a window: ``bb_len``, ``layers`` (admitted, after the
    cap), ``capped`` (admitted layers the cap dropped), ``layer_bases``
    (read bases of the kept layers) and ``nodes`` (what an exact graph
    of the kept layers holds)."""
    (contig, draft), = list(read_fasta(draft_path).items())[:1]
    quals = read_fastq_qualities(reads_path)
    w = int(window_length)
    n_win = (len(draft) + w - 1) // w

    best = {}                                 # read -> its longest overlap
    with open(sam_path, "rb") as f:
        for line in f:
            if line.startswith(b"@"):
                continue
            c = line.rstrip(b"\n").split(b"\t")
            if c[2].decode() != contig or c[5] == b"*":
                continue
            ov = Overlap(c[0].decode(), int(c[1]), int(c[3]) - 1, c[5],
                         c[9], w)
            if not ov.q_span or ov.error() > error_threshold:
                continue
            if ov.name not in best or ov.q_span > best[ov.name].q_span:
                best[ov.name] = ov

    # every admitted piece, then the cap in order of begin
    rows = []                                 # (window, begin, seq no, q0, q1)
    kept = list(best.values())
    for no, ov in enumerate(kept):
        win, begin, q0, q1 = ov.pieces()
        n_bases = q1 - q0 + 1
        ok = n_bases >= 0.02 * w
        qual = quals.get(ov.name)
        if qual is not None and len(qual):
            qual = (qual[::-1] if ov.reverse else qual).astype(np.int64) - 33
            total = np.concatenate([[0], np.cumsum(qual)])
            ok &= (total[q1 + 1] - total[q0]) / n_bases >= quality_threshold
        rows += [(int(a), int(b), no, int(c), int(d)) for a, b, c, d in
                 zip(win[ok], begin[ok], q0[ok], q1[ok])]
    rows.sort()
    layers = np.zeros(n_win, np.int64)
    capped = np.zeros(n_win, np.int64)
    layer_bases = np.zeros(n_win, np.int64)
    admitted = {}                             # seq no -> {window: (q0, q1)}
    for win, _begin, no, q0, q1 in rows:
        if layers[win] >= depth_cap:
            capped[win] += 1
            continue
        layers[win] += 1
        layer_bases[win] += q1 - q0 + 1
        admitted.setdefault(no, {})[win] = (q0, q1)

    # distinct new nodes per window: substitutions and inserted bases of
    # the kept pieces, as integer keys
    keys = []
    for no, per_window in admitted.items():
        ov = kept[no]
        lo = np.full(n_win, np.iinfo(np.int64).max)
        hi = np.full(n_win, -1)
        for win, (q0, q1) in per_window.items():
            lo[win], hi[win] = q0, q1
        win = ov.m_t // w
        base = ov.seq[ov.m_q]
        sub = (base != draft[ov.m_t]) & (ov.m_q >= lo[win]) \
            & (ov.m_q <= hi[win])
        # key: window, column, place in run (0 = a substitution), base
        keys.append(((win[sub] * w + ov.m_t[sub] % w) * 4096) * 256
                    + base[sub])
        if len(ov.i_q):
            iw = np.clip(ov.i_t, 0, len(draft) - 1) // w
            inside = (ov.i_q > lo[iw]) & (ov.i_q < hi[iw])
            place = np.minimum(ov.i_k[inside] + 1, 4095)
            keys.append(((iw[inside] * w + ov.i_t[inside] % w) * 4096
                         + place) * 256 + ov.seq[ov.i_q[inside]])
    new = np.unique(np.concatenate(keys)) if keys else np.zeros(0, np.int64)
    extra = np.bincount(new // (256 * 4096 * w), minlength=n_win)[:n_win]
    bb_len = np.minimum(w, len(draft) - np.arange(n_win) * w)
    return {"bb_len": bb_len.astype(np.int64), "layers": layers,
            "capped": capped, "layer_bases": layer_bases,
            "nodes": bb_len + extra}
