"""The benchmark's own seeded workload generator.

A copy of ``racon_tpu/tools/simulate.py`` (PR 22), kept here so that a
later change to the program's generator cannot move the yardstick: the
``ont`` mode's bytes are pinned by ``benchmark/tests`` and equal
simulate.py's as of this PR.  The copy adds the ``paired_short`` mode
(fixed-length paired reads, names suffixed 1/2 as
``racon_tpu/tools/preprocess.py`` renames Illumina pairs).

A traffic file selects a mode by ``"generator": "<mode>"``; a new mode is
a new ``mode_<name>`` function here (later PRs may not edit this file, so
they add a module beside it and name it ``"<module>:<function>"``).

Every mode writes ``genome.fasta`` (truth), ``draft.fasta``,
``reads.fastq`` and the overlap files asked for (``paf``, ``sam``) into
``outdir`` and returns a dict of facts about what it made (bases, reads,
pairs), which the harness stores next to the data.
"""

from __future__ import annotations

import hashlib
import importlib
import itertools
import os

import numpy as np

BASES = np.frombuffer(b"ACGT", dtype=np.uint8)
_OP_CHARS = np.frombuffer(b"MDI", dtype=np.uint8)
_COMP = np.zeros(256, dtype=np.uint8)
for _a, _b in zip(b"ACGT", b"TGCA"):
    _COMP[_a] = _b


def source_hash() -> str:
    """Hash of this file: part of the data cache's key."""
    with open(os.path.abspath(__file__), "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()[:16]


def resolve(mode: str):
    """``"ont"`` -> ``mode_ont`` here; ``"module:function"`` -> that
    function of a module under ``benchmark/``."""
    if ":" in mode:
        mod, fn = mode.split(":", 1)
        return getattr(importlib.import_module(f"benchmark.{mod}"), fn)
    try:
        return globals()[f"mode_{mode}"]
    except KeyError:
        raise ValueError(f"unknown generator mode {mode!r}") from None


def _cigar_from_ops(ops: np.ndarray, start: int, end: int):
    """RLE an op-code array (0=M, 1=D, 2=I) into a CIGAR string, clipping
    leading/trailing deletion runs (invalid in SAM) by moving the target
    coordinates inward. Returns (cigar, start, end)."""
    lo = 0
    while lo < len(ops) and ops[lo] == 1:
        lo += 1
    hi = len(ops)
    while hi > lo and ops[hi - 1] == 1:
        hi -= 1
    start += lo
    end -= len(ops) - hi
    ops = ops[lo:hi]
    if not len(ops):
        return "", start, end
    bounds = np.nonzero(np.diff(ops))[0] + 1
    starts = np.concatenate([[0], bounds])
    ends = np.concatenate([bounds, [len(ops)]])
    cigar = "".join(f"{e - s}{chr(_OP_CHARS[ops[s]])}"
                    for s, e in zip(starts, ends))
    return cigar, start, end


def _mutate(seg: np.ndarray, rng, sub: float, ins: float, dele: float):
    """One read's errors.  Returns (read in target orientation, op
    stream 0=M/1=D/2=I in target orientation).  The draw order is
    simulate.py's, so the ``ont`` mode reproduces its bytes."""
    length = len(seg)
    r = rng.random(length)
    sub_mask = r < sub
    seg = seg.copy()
    seg[sub_mask] = BASES[rng.integers(0, 4, int(sub_mask.sum()))]
    keep = rng.random(length) >= dele
    seg = seg[keep]
    ins_mask = rng.random(len(seg)) < ins
    n_ins = int(ins_mask.sum())
    if n_ins:
        out = np.empty(len(seg) + n_ins, dtype=np.uint8)
        pos = np.nonzero(ins_mask)[0]
        out_idx = np.arange(len(seg)) + np.cumsum(ins_mask) - ins_mask
        out[out_idx] = seg
        ins_at = pos + np.arange(1, n_ins + 1)
        out[ins_at] = BASES[rng.integers(0, 4, n_ins)]
        seg = out
    ops_orig = np.where(keep, 0, 1).astype(np.uint8)
    ins_after = np.zeros(length, dtype=np.int64)
    if n_ins:
        ins_after[np.nonzero(keep)[0]] = ins_mask.astype(np.int64)
    shift = np.concatenate([[0], np.cumsum(ins_after)[:-1]])
    ops = np.full(length + int(ins_after.sum()), 2, dtype=np.uint8)
    ops[np.arange(length) + shift] = ops_orig
    return seg, ops


def _relabel(seed: int) -> np.ndarray:
    """The seed's permutation of A, C, G, T as a byte table: one of 24,
    the identity at ``seed % 24 == 0``.  Every comparison of two bases
    comes out the same under it, so the relabelled data set is the same
    job: the same graphs, bands, rejections and edit distances."""
    table = np.arange(256, dtype=np.uint8)
    table[BASES] = list(itertools.permutations(BASES))[seed % 24]
    return table


def _rngs(seed: int, data_seed, layout_seed):
    """(rng of the bases, rng of the layout, byte table or None)."""
    fixed = data_seed is not None
    rng = np.random.default_rng(data_seed if fixed else seed)
    lrng = rng if layout_seed is None else np.random.default_rng(layout_seed)
    return rng, lrng, _relabel(seed) if fixed else None


def _genome_and_draft(rng, g_len: int, draft_error: float):
    genome = BASES[rng.integers(0, 4, g_len)]
    draft = genome.copy()
    derr = rng.random(g_len) < draft_error
    draft[derr] = BASES[rng.integers(0, 4, int(derr.sum()))]
    return genome, draft


class _Writer:
    """The output files of one data set, simulate.py's formats."""

    def __init__(self, outdir: str, genome, draft, formats, qual_phred: int,
                 contig: str = "contig", relabel=None):
        os.makedirs(outdir, exist_ok=True)
        self.relabel = relabel
        if relabel is not None:
            genome, draft = relabel[genome], relabel[draft]
        self.paths = {k: os.path.join(outdir, v) for k, v in (
            ("genome", "genome.fasta"), ("draft", "draft.fasta"),
            ("reads", "reads.fastq"), ("paf", "overlaps.paf"),
            ("sam", "overlaps.sam"))}
        self.contig = contig
        self.t_len = len(draft)
        self.qual = chr(33 + int(qual_phred))
        with open(self.paths["genome"], "w") as f:
            f.write(">genome\n" + genome.tobytes().decode() + "\n")
        with open(self.paths["draft"], "w") as f:
            f.write(f">{contig}\n" + draft.tobytes().decode() + "\n")
        self._reads = open(self.paths["reads"], "w")
        self._paf = open(self.paths["paf"], "w") if "paf" in formats \
            else None
        self._sam = open(self.paths["sam"], "w") if "sam" in formats \
            else None
        if self._sam:
            self._sam.write("@HD\tVN:1.6\tSO:unsorted\n"
                            f"@SQ\tSN:{contig}\tLN:{self.t_len}\n")
        self.read_bases = 0
        self.n_reads = 0

    def read(self, name: str, start: int, end: int, strand: bool,
             fwd: np.ndarray, ops: np.ndarray) -> None:
        """One read: ``fwd`` is the read in target orientation, aligned
        to draft[start:end] by the true op stream ``ops``."""
        if self.relabel is not None:
            fwd = self.relabel[fwd]
        seg = _COMP[fwd][::-1] if strand else fwd
        n = len(seg)
        self.read_bases += n
        self.n_reads += 1
        self._reads.write(f"@{name}\n{seg.tobytes().decode()}\n+\n"
                          f"{self.qual * n}\n")
        if self._paf:
            self._paf.write(
                f"{name}\t{n}\t0\t{n}\t{'-' if strand else '+'}\t"
                f"{self.contig}\t{self.t_len}\t{start}\t{end}\t"
                f"{min(n, end - start)}\t{max(n, end - start)}\t60\n")
        if self._sam:
            cigar, cg_start, _ = _cigar_from_ops(ops, start, end)
            self._sam.write(
                f"{name}\t{16 if strand else 0}\t{self.contig}\t"
                f"{cg_start + 1}\t60\t{cigar}\t*\t0\t0\t"
                f"{fwd.tobytes().decode()}\t{self.qual * len(fwd)}\n")

    def close(self) -> dict:
        for f in (self._reads, self._paf, self._sam):
            if f:
                f.close()
        return {"truth_bp": self.t_len, "reads": self.n_reads,
                "read_bases": self.read_bases}


def mode_ont(outdir: str, seed: int, *, genome_mbp: float, coverage: int,
             mean_read: int, sub: float, ins: float, dele: float,
             draft_error: float, formats=("paf", "sam"),
             qual_phred: int = 15, layout_seed=None, data_seed=None,
             **_ignored) -> dict:
    """Long reads, gamma(4) lengths around ``mean_read`` clipped to
    [min(500, mean), 40000]: simulate.py's single-contig output, byte for
    byte, at equal parameters.  With ``layout_seed`` the reads' lengths,
    positions and strands come from that seed and only the bases (genome,
    draft errors, read errors) from ``seed``: every seed then gives the
    same depth profile.  With ``data_seed`` the bases come from that seed
    too, and ``seed`` only chooses how the four letters are relabelled
    (:func:`_relabel`): every seed then gives other bytes and exactly the
    same amount of work, which a fixed layout alone does not (graph sizes,
    hence capacity rejections and band rungs, follow the errors)."""
    rng, lrng, relabel = _rngs(seed, data_seed, layout_seed)
    g_len = int(genome_mbp * 1e6)
    genome, draft = _genome_and_draft(rng, g_len, draft_error)
    w = _Writer(outdir, genome, draft, formats, qual_phred, relabel=relabel)
    n_reads = max(1, int(g_len * coverage / mean_read))
    for i in range(n_reads):
        lo = min(500, int(mean_read))
        length = int(np.clip(lrng.gamma(4.0, mean_read / 4.0), lo, 40000))
        length = min(length, g_len)
        start = int(lrng.integers(0, g_len - length + 1))
        fwd, ops = _mutate(genome[start:start + length], rng, sub, ins,
                           dele)
        strand = bool(lrng.integers(0, 2))
        w.read(f"read{i}", start, start + length, strand, fwd, ops)
    return w.close()


def mode_paired_short(outdir: str, seed: int, *, genome_mbp: float,
                      coverage: int, read_length: int, insert_mean: int,
                      insert_sd: int, sub: float, ins: float, dele: float,
                      draft_error: float, formats=("sam",),
                      qual_phred: int = 30, layout_seed=None,
                      data_seed=None, **_ignored) -> dict:
    """Paired short reads of one fixed sequenced length: each pair is the
    two ends of a fragment (insert ~ N(mean, sd)), mate 1 on the forward
    and mate 2 on the reverse strand or the other way round, named
    ``frag<i>1`` / ``frag<i>2``.  ``read_length`` genome bases are
    sequenced per mate; indels make a few mates a base or two off it.
    ``layout_seed`` and ``data_seed`` as in :func:`mode_ont`."""
    rng, lrng, relabel = _rngs(seed, data_seed, layout_seed)
    g_len = int(genome_mbp * 1e6)
    genome, draft = _genome_and_draft(rng, g_len, draft_error)
    w = _Writer(outdir, genome, draft, formats, qual_phred, relabel=relabel)
    L = int(read_length)
    n_pairs = max(1, int(g_len * coverage / (2 * L)))
    inserts = np.clip(lrng.normal(insert_mean, insert_sd, n_pairs),
                      L, g_len).astype(np.int64)
    starts = lrng.integers(0, g_len - inserts + 1)
    flips = lrng.integers(0, 2, n_pairs).astype(bool)
    for i in range(n_pairs):
        left, right = int(starts[i]), int(starts[i] + inserts[i] - L)
        for mate, (pos, strand) in enumerate(
                ((left, False), (right, True))):
            fwd, ops = _mutate(genome[pos:pos + L], rng, sub, ins, dele)
            # the left mate always reads the forward strand; a flipped
            # fragment only swaps which mate is called 1
            w.read(f"frag{i}{(mate ^ int(flips[i])) + 1}", pos, pos + L,
                   strand, fwd, ops)
    out = w.close()
    out["pairs"] = n_pairs
    return out
