"""Data for fragment correction (``racon -f``): every read is a target.

A module beside ``generate.py`` (which later PRs may not edit), named by
a traffic file as ``"generator": "generate_frag:mode_frag"``.  It reuses
``generate._mutate`` (the ONT error draw), ``_rngs`` and ``_relabel``.

Reads are drawn from a *circular* genome, as E. coli's is: every read
then has its full depth of partners and none sits at a contig end.  The
files (names fixed by ``prepare.inputs``):

``reads.fastq``
    the reads, each in its own orientation, qualities all ``!`` (which
    ``rt_sequence.cpp`` treats as absent: upstream's without-qualities
    fragment scenario; a read and its target must agree in quality
    length, and a FASTA target has none)
``draft.fasta``
    the same reads under the same names: the targets
``overlaps.paf``
    one line per *ordered* pair of reads that share at least
    ``min_overlap`` bases of genome (A->B and B->A; a pair that meets at
    both ends of the circle gets a line per meeting), coordinates carried
    from the genome into each read's own through its op stream, strand
    relative
``genome.fasta``
    one record per read, in target order: the read's true bases in the
    read's orientation, so that an edit distance over the concatenated
    records is the sum over reads
"""

from __future__ import annotations

import os

import numpy as np

from . import generate

#: the traffic file's ``data.generator_rev`` must equal this.  The data
#: cache's key hashes ``generate.py`` and the parameters only, so an edit
#: here that changes the bytes raises both numbers and so makes new data.
GENERATOR_REV = 1


def _genome_to_read(ops: np.ndarray) -> np.ndarray:
    """For an op stream (0=M, 1=D, 2=I, genome orientation) over L genome
    bases: ``out[g]`` = read bases before genome base ``g``, ``out[L]`` =
    the read's length."""
    on_read = ops != 1
    before = np.cumsum(on_read) - on_read
    return np.append(before[ops != 2], int(on_read.sum()))


def _own(lo: int, hi: int, n: int, strand: bool) -> tuple:
    """A half-open interval of the read in genome orientation, in the
    read's own."""
    return (n - hi, n - lo) if strand else (lo, hi)


class _Read:
    def __init__(self, name, start, length, strand, fwd, ops, truth):
        self.name, self.start, self.length = name, start, length
        self.strand = strand
        self.fwd = fwd                      # read, genome orientation
        self.g2r = _genome_to_read(ops)
        self.truth = truth                  # genome segment, same
        self.n = len(fwd)

    def own(self, seq: np.ndarray) -> bytes:
        seq = generate._COMP[seq][::-1] if self.strand else seq
        return seq.tobytes()


def _meetings(a: _Read, b: _Read, g_len: int, least: int):
    """Genome intervals, as offsets into each read's segment, that the
    arcs of ``a`` and ``b`` share: up to two on a circle."""
    for shift in (-g_len, 0, g_len):
        lo = max(a.start, b.start + shift)
        hi = min(a.start + a.length, b.start + shift + b.length)
        if hi - lo >= least:
            yield (lo - a.start, hi - a.start,
                   lo - b.start - shift, hi - b.start - shift)


def mode_frag(outdir: str, seed: int, *, reads: int, coverage: int,
              mean_read: int, sub: float, ins: float, dele: float,
              min_overlap: int = 500, generator_rev: int = GENERATOR_REV,
              genome_mbp: float = None, layout_seed=None, data_seed=None,
              **_ignored) -> dict:
    """``reads`` reads of gamma(4) lengths around ``mean_read`` (clipped
    to [min(500, mean), genome - 1]) at ``coverage`` on a circle of
    ``reads x mean_read / coverage`` bases.  ``genome_mbp``, where given,
    has to say the same length.  ``layout_seed`` / ``data_seed`` as in
    ``generate.mode_ont``: with both fixed ``seed`` only relabels the
    four letters."""
    if generator_rev != GENERATOR_REV:
        raise ValueError(f"traffic asks for generator_rev {generator_rev}, "
                         f"generate_frag.py is at {GENERATOR_REV}")
    g_len = int(round(reads * mean_read / coverage))
    if genome_mbp is not None and int(round(genome_mbp * 1e6)) != g_len:
        raise ValueError(f"genome_mbp {genome_mbp} is not reads x mean_read "
                         f"/ coverage = {g_len} bp")
    rng, lrng, relabel = generate._rngs(seed, data_seed, layout_seed)
    genome = generate.BASES[rng.integers(0, 4, g_len)]
    made = []
    for i in range(reads):
        length = int(np.clip(lrng.gamma(4.0, mean_read / 4.0),
                             min(500, int(mean_read)), g_len - 1))
        start = int(lrng.integers(0, g_len))
        truth = genome[(start + np.arange(length)) % g_len]
        fwd, ops = generate._mutate(truth, rng, sub, ins, dele)
        strand = bool(lrng.integers(0, 2))
        if relabel is not None:
            fwd, truth = relabel[fwd], relabel[truth]
        made.append(_Read(f"read{i}", start, length, strand, fwd, ops,
                          truth))

    os.makedirs(outdir, exist_ok=True)
    with open(os.path.join(outdir, "reads.fastq"), "w") as fq, \
            open(os.path.join(outdir, "draft.fasta"), "w") as fa, \
            open(os.path.join(outdir, "genome.fasta"), "w") as ft:
        for r in made:
            bases = r.own(r.fwd).decode()
            fq.write(f"@{r.name}\n{bases}\n+\n{'!' * r.n}\n")
            fa.write(f">{r.name}\n{bases}\n")
            ft.write(f">{r.name}\n{r.own(r.truth).decode()}\n")
    pairs = 0
    with open(os.path.join(outdir, "overlaps.paf"), "w") as paf:
        for a in made:
            for b in made:
                if a is b:
                    continue
                for alo, ahi, blo, bhi in _meetings(a, b, g_len,
                                                    min_overlap):
                    qs, qe = _own(int(a.g2r[alo]), int(a.g2r[ahi]), a.n,
                                  a.strand)
                    ts, te = _own(int(b.g2r[blo]), int(b.g2r[bhi]), b.n,
                                  b.strand)
                    paf.write(
                        f"{a.name}\t{a.n}\t{qs}\t{qe}\t"
                        f"{'-' if a.strand != b.strand else '+'}\t"
                        f"{b.name}\t{b.n}\t{ts}\t{te}\t"
                        f"{min(qe - qs, te - ts)}\t{max(qe - qs, te - ts)}"
                        "\t60\n")
                    pairs += 1
    return {"truth_bp": sum(r.length for r in made), "reads": len(made),
            "read_bases": sum(r.n for r in made), "targets": len(made),
            "pairs": pairs, "genome_bp": g_len}
