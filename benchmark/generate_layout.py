"""Data for upstream's own suite scenario: a *raw layout* as the draft.

A module beside ``generate.py`` (which later PRs may not edit), named by
a configuration as ``"generator": "generate_layout:mode_layout"``.  It
reuses ``generate._mutate`` (the ONT error draw), ``_rngs`` and
``_relabel``.

racon exists to "correct raw contigs generated by rapid assembly methods
which do not include a consensus step"; its test suite polishes a miniasm
layout of lambda phage (``sample_layout.fasta``: pieces of uncorrected
reads, joined where the next read takes over) with the 236 reads it was
laid out from.  This mode makes such a set from a seed:

``genome.fasta``
    the truth, ``genome_mbp`` x 1e6 random bases (NC_001416's 48 502)
``reads.fastq``
    ``reads`` reads of ``read_bases`` bases in all, exactly: gamma(4)
    lengths scaled to that sum, each a stretch of the genome under
    ``generate._mutate`` at ``error_rate`` split ``error_mix`` ways, in
    its own strand, with **per-base qualities**: a read's mean drawn from
    N(``qual_mean``, ``qual_sd``), each base N(0, ``qual_base_sd``) about
    it, and a base in error (a substituted or inserted base, or the base
    after a deletion) ``qual_error_drop`` lower
``draft.fasta``
    the layout: a greedy path through the reads themselves, from the
    first read that starts ``end_loss`` bases in to the last that ends
    ``end_loss`` bases before the end; each read gives its bases, errors
    and all, from where it took over to where the next one does
``overlaps.paf``
    one line a read: the read's true span on the layout, found through
    the two op streams (read -> genome -> layout piece), each end pulled
    in by up to ``end_jitter`` genome bases, as a mapper's chain stops at
    its last anchor and not at the read's last base
"""

from __future__ import annotations

import os

import numpy as np

from . import generate

#: the traffic file's ``data.generator_rev`` must equal this.  The data
#: cache's key hashes ``generate.py`` and the parameters only, so an edit
#: here that changes the bytes raises both numbers and so makes new data.
GENERATOR_REV = 1

#: an overlap shorter than this on the layout is not reported (a mapper's
#: chain needs a few anchors)
LEAST_SPAN = 200


def read_lengths(lrng, reads: int, read_bases: int, longest: int) -> np.ndarray:
    """``reads`` gamma(4) lengths that sum to ``read_bases`` exactly."""
    mean = read_bases / reads
    raw = np.clip(lrng.gamma(4.0, mean / 4.0, reads), min(500, mean), longest)
    lens = np.maximum(1, np.round(raw * (read_bases / raw.sum()))).astype(
        np.int64)
    lens[int(np.argmax(lens))] += read_bases - int(lens.sum())
    return lens


def _cut(fwd: np.ndarray, ops: np.ndarray, n: int, from_left: bool):
    """The first (or, with ``from_left`` false, the last) ``n`` read
    bases of a mutated stretch, with the op stream that belongs to them:
    (read, ops, genome bases dropped at the cut side)."""
    if not from_left:
        fwd, ops, dropped = _cut(fwd[::-1], ops[::-1], n, True)
        return fwd[::-1], ops[::-1], dropped
    on_read = np.cumsum(ops != 1)
    stop = int(np.searchsorted(on_read, n)) + 1     # ops kept
    return fwd[:n], ops[:stop], int((ops[stop:] != 2).sum())


def error_bases(truth: np.ndarray, fwd: np.ndarray,
                ops: np.ndarray) -> np.ndarray:
    """Mask over the read's bases (genome orientation): a substituted
    base, an inserted base, or the base that follows a deletion."""
    on_read = ops != 1
    read_op = ops[on_read]                          # per read base: 0 or 2
    bad = read_op == 2
    matched = np.nonzero(read_op == 0)[0]
    on_genome = ops != 2
    truth_at = (np.cumsum(on_genome) - 1)[ops == 0]
    bad[matched[fwd[matched] != truth[truth_at]]] = True
    after_del = np.nonzero(on_read[1:] & (ops[:-1] == 1))[0] + 1
    bad[(np.cumsum(on_read) - 1)[after_del]] = True
    return bad


def genome_to_read(ops: np.ndarray) -> np.ndarray:
    """``out[g]`` = read bases before genome base ``g`` of the stretch,
    ``out[L]`` = the read's length (as ``generate_frag``'s)."""
    on_read = ops != 1
    before = np.cumsum(on_read) - on_read
    return np.append(before[ops != 2], int(on_read.sum()))


class Read:
    def __init__(self, name, start, strand, truth, fwd, ops, qual):
        self.name, self.start, self.strand = name, start, strand
        self.end = start + len(truth)
        self.fwd, self.qual = fwd, qual             # genome orientation
        self.g2r = genome_to_read(ops)
        self.n = len(fwd)

    def at(self, g: int) -> int:
        """Read bases before genome position ``g``."""
        return int(self.g2r[g - self.start])


def layout_path(made: list, g_len: int, end_loss: int,
                least_overlap: int) -> list:
    """The reads a rapid assembler would lay out: from the first read
    that starts at least ``end_loss`` bases in, always on to the read
    that overlaps the current one by ``least_overlap`` bases or more and
    reaches furthest, never past ``g_len - end_loss``."""
    inside = [r for r in made if r.start >= end_loss
              and r.end <= g_len - end_loss]
    if not inside:
        raise ValueError("no read lies inside the layout's reach")
    cur = min(inside, key=lambda r: (r.start, r.name))
    path = [cur]
    while True:
        nxt = [r for r in inside if cur.start < r.start
               <= cur.end - least_overlap and r.end > cur.end]
        if not nxt:
            return path
        cur = max(nxt, key=lambda r: (r.end, r.name))
        path.append(cur)


def lay_out(path: list):
    """(layout bases, genome -> layout table, first genome base, one past
    the last).  ``table[g - first]`` = layout bases before genome base
    ``g``, for ``first <= g <= last``."""
    pieces, table, offset = [], [], 0
    for k, r in enumerate(path):
        stop = path[k + 1].start if k + 1 < len(path) else r.end
        lo, hi = r.at(r.start), r.at(stop)
        pieces.append(r.fwd[lo:hi])
        table.append(offset + r.g2r[:stop - r.start] - lo)
        offset += hi - lo
    table.append([offset])
    return (np.concatenate(pieces), np.concatenate(table), path[0].start,
            path[-1].end)


def mode_layout(outdir: str, seed: int, *, genome_mbp: float, reads: int,
                read_bases: int, error_rate: float, error_mix=(5, 3, 3),
                end_loss: int = 500, layout_overlap: int = 1000,
                end_jitter: int = 40, qual_mean: float = 12.0,
                qual_sd: float = 2.0, qual_base_sd: float = 3.0,
                qual_error_drop: float = 5.0,
                generator_rev: int = GENERATOR_REV, layout_seed=None,
                data_seed=None, **_ignored) -> dict:
    """See the module's text.  ``layout_seed`` / ``data_seed`` as in
    ``generate.mode_ont``: with both fixed ``seed`` only relabels the
    four letters."""
    if generator_rev != GENERATOR_REV:
        raise ValueError(f"traffic asks for generator_rev {generator_rev}, "
                         f"generate_layout.py is at {GENERATOR_REV}")
    rng, lrng, relabel = generate._rngs(seed, data_seed, layout_seed)
    g_len = int(round(genome_mbp * 1e6))
    sub, ins, dele = (error_rate * m / sum(error_mix) for m in error_mix)
    genome = generate.BASES[rng.integers(0, 4, g_len)]
    made = []
    for i, n in enumerate(read_lengths(lrng, reads, read_bases,
                                       int(0.8 * g_len))):
        n = int(n)
        nominal = min(int(round(n / (1 + ins - dele))), g_len)
        padded = min(int(nominal * 1.1) + 64, g_len)
        start = int(lrng.integers(0, g_len - nominal + 1))
        from_left = start + padded <= g_len
        lo = start if from_left else g_len - padded
        fwd, ops = generate._mutate(genome[lo:lo + padded], rng, sub, ins,
                                    dele)
        fwd, ops, dropped = _cut(fwd, ops, n, from_left)
        if len(fwd) != n:
            raise ValueError(f"read{i}: {len(fwd)} bases of {n}")
        lo += 0 if from_left else dropped
        truth = genome[lo:lo + int((ops != 2).sum())]
        bad = error_bases(truth, fwd, ops)
        qual = np.clip(np.rint(lrng.normal(qual_mean, qual_sd)
                               + lrng.normal(0.0, qual_base_sd, n)
                               - qual_error_drop * bad), 1, 60).astype(
                                   np.uint8)
        made.append(Read(f"read{i}", lo, bool(lrng.integers(0, 2)), truth,
                         fwd, ops, qual))

    path = layout_path(made, g_len, end_loss, layout_overlap)
    layout, table, first, last = lay_out(path)
    if relabel is not None:
        genome, layout = relabel[genome], relabel[layout]

    os.makedirs(outdir, exist_ok=True)
    with open(os.path.join(outdir, "genome.fasta"), "w") as f:
        f.write(">genome\n" + genome.tobytes().decode() + "\n")
    with open(os.path.join(outdir, "draft.fasta"), "w") as f:
        f.write(">layout\n" + layout.tobytes().decode() + "\n")
    overlaps = 0
    with open(os.path.join(outdir, "reads.fastq"), "w") as fq, \
            open(os.path.join(outdir, "overlaps.paf"), "w") as paf:
        for r in made:
            fwd = r.fwd if relabel is None else relabel[r.fwd]
            own = generate._COMP[fwd][::-1] if r.strand else fwd
            qual = (r.qual[::-1] if r.strand else r.qual) + 33
            fq.write(f"@{r.name}\n{own.tobytes().decode()}\n+\n"
                     f"{qual.tobytes().decode()}\n")
            lo = max(r.start, first) + int(lrng.integers(0, end_jitter + 1))
            hi = min(r.end, last) - int(lrng.integers(0, end_jitter + 1))
            if hi - lo < LEAST_SPAN:
                continue
            qs, qe = r.at(lo), r.at(hi)
            if r.strand:
                qs, qe = r.n - qe, r.n - qs
            ts, te = int(table[lo - first]), int(table[hi - first])
            paf.write(f"{r.name}\t{r.n}\t{qs}\t{qe}\t"
                      f"{'-' if r.strand else '+'}\tlayout\t{len(layout)}\t"
                      f"{ts}\t{te}\t{min(qe - qs, te - ts)}\t"
                      f"{max(qe - qs, te - ts)}\t60\n")
            overlaps += 1
    return {"truth_bp": g_len, "reads": len(made),
            "read_bases": int(sum(r.n for r in made)),
            "draft_bp": int(len(layout)), "layout_pieces": len(path),
            "layout_first": int(first), "layout_last": int(last),
            "overlaps": overlaps}
