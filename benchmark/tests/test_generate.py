"""The generator: pinned bytes of the ONT mode, the paired short-read
mode, and that the host path polishes what it writes."""

import hashlib
import os

import pytest

from benchmark import generate, prepare

ONT = dict(genome_mbp=0.05, coverage=30, mean_read=8000, sub=0.05,
           ins=0.03, dele=0.03, draft_error=0.01)

#: sha256 prefixes of the ONT mode's files at 0.05 Mbp, equal to
#: racon_tpu/tools/simulate.py's bytes as of PR 22 and pinned here so
#: that a change to the program's generator cannot move the yardstick
PINNED = {
    11: {"genome.fasta": "19bb4ff80fcfec0d", "draft.fasta": "8c709b29d983b259",
         "reads.fastq": "7811c63cceb98a39", "overlaps.paf": "3288900bcc0cd142",
         "overlaps.sam": "6bdebe96f7115dd5"},
    12: {"genome.fasta": "605eb9f67ad19d16", "draft.fasta": "056158e558c31832",
         "reads.fastq": "8810a3babd45f491", "overlaps.paf": "0554ff2a045d5401",
         "overlaps.sam": "24f5cb25fa2cc84a"},
}


def digest(path):
    with open(path, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()[:16]


@pytest.mark.parametrize("seed", sorted(PINNED))
def test_ont_bytes_are_pinned(tmp_path, seed):
    generate.mode_ont(str(tmp_path), seed, **ONT)
    assert {f: digest(tmp_path / f) for f in PINNED[seed]} == PINNED[seed]


def test_ont_equals_the_programs_generator_today(tmp_path):
    simulate = pytest.importorskip("racon_tpu.tools.simulate")
    generate.mode_ont(str(tmp_path / "bench"), 11, **ONT)
    simulate.generate(str(tmp_path / "prog"), mbp=0.05, coverage=30,
                      mean_read=8000, seed=11)
    for f in PINNED[11]:
        assert digest(tmp_path / "bench" / f) == digest(
            tmp_path / "prog" / f), f


def test_formats_choose_files_not_bytes(tmp_path):
    generate.mode_ont(str(tmp_path), 11, formats=("sam",), **ONT)
    assert digest(tmp_path / "overlaps.sam") == PINNED[11]["overlaps.sam"]
    assert not os.path.exists(tmp_path / "overlaps.paf")


SHORT = dict(genome_mbp=0.02, coverage=40, read_length=150, insert_mean=400,
             insert_sd=40, sub=0.008, ins=0.001, dele=0.001,
             draft_error=0.01)


def test_paired_short_reads(tmp_path):
    facts = generate.mode_paired_short(str(tmp_path), 3, **SHORT)
    assert facts["pairs"] == 0.02e6 * 40 // 300
    assert facts["reads"] == 2 * facts["pairs"]
    names, lens = [], []
    with open(tmp_path / "reads.fastq") as f:
        for i, line in enumerate(f):
            if i % 4 == 0:
                names.append(line[1:].strip())
            elif i % 4 == 1:
                lens.append(len(line.strip()))
    assert len(set(names)) == len(names)
    assert {n[:-1] for n in names if n.endswith("1")} == \
        {n[:-1] for n in names if n.endswith("2")}
    assert min(lens) >= 146 and max(lens) <= 154
    assert sum(1 for n in lens if n == 150) > 0.7 * len(lens)
    flags = {}
    with open(tmp_path / "overlaps.sam") as f:
        for line in f:
            if not line.startswith("@"):
                c = line.split("\t")
                flags.setdefault(c[0][:-1], set()).add(c[1])
    # one mate forward, one reverse, in every pair
    assert all(v == {"0", "16"} for v in flags.values())
    # same seed, same bytes; another seed, other bytes
    again = tmp_path / "again"
    generate.mode_paired_short(str(again), 3, **SHORT)
    assert digest(again / "reads.fastq") == digest(tmp_path / "reads.fastq")
    generate.mode_paired_short(str(again), 4, **SHORT)
    assert digest(again / "reads.fastq") != digest(tmp_path / "reads.fastq")


def test_host_path_polishes_the_short_read_sam(tmp_path):
    racon_tpu = pytest.importorskip("racon_tpu")
    from racon_tpu import native

    generate.mode_paired_short(str(tmp_path), 3, **SHORT)
    polisher = racon_tpu.create_polisher(
        str(tmp_path / "reads.fastq"), str(tmp_path / "overlaps.sam"),
        str(tmp_path / "draft.fasta"), backend="cpu", window_length=200,
        quality_threshold=10.0, error_threshold=0.3, trim=True,
        fragment_correction=False, match=3, mismatch=-5, gap=-4,
        num_threads=2)
    polisher.initialize()
    (_, polished), = polisher.polish(True)

    truth = prepare.read_fasta(tmp_path / "genome.fasta")
    draft_ed = native.edit_distance(
        prepare.read_fasta(tmp_path / "draft.fasta"), truth)
    polished_ed = native.edit_distance(polished.encode(), truth)
    assert draft_ed > 100
    assert polished_ed < 0.1 * draft_ed


def test_mode_lookup():
    assert generate.resolve("ont") is generate.mode_ont
    assert generate.resolve("generate:mode_ont") is generate.mode_ont
    with pytest.raises(ValueError):
        generate.resolve("no_such_mode")


def _paf_layout(path):
    with open(path) as f:
        return [(c[4], c[7], c[8]) for c in (ln.split("\t") for ln in f)]


def test_layout_seed_fixes_the_work_and_not_the_bases(tmp_path):
    """With ``layout_seed`` every seed has the same reads in the same
    places (the same depth profile, the same batches) and other bases."""
    for seed in (1, 2):
        generate.mode_ont(str(tmp_path / str(seed)), seed, layout_seed=22,
                          **ONT)
    assert _paf_layout(tmp_path / "1" / "overlaps.paf") == \
        _paf_layout(tmp_path / "2" / "overlaps.paf")
    for f in ("genome.fasta", "draft.fasta", "reads.fastq"):
        assert digest(tmp_path / "1" / f) != digest(tmp_path / "2" / f)
    # and without it the layout follows the seed, as simulate.py's does
    generate.mode_ont(str(tmp_path / "free"), 2, **ONT)
    assert _paf_layout(tmp_path / "free" / "overlaps.paf") != \
        _paf_layout(tmp_path / "2" / "overlaps.paf")


def _revcomp(line: bytes) -> bytes:
    return line.rstrip(b"\n").translate(
        bytes.maketrans(b"ACGT", b"TGCA"))[::-1] + b"\n"


def _split(path):
    """(sequences, everything else) of a FASTA, FASTQ or SAM file."""
    seqs, rest = [], []
    with open(path, "rb") as f:
        for i, line in enumerate(f):
            if path.suffix == ".sam" and not line.startswith(b"@"):
                cols = line.split(b"\t")
                seqs.append(cols.pop(9))
                rest.append(cols)
            elif path.suffix == ".fasta" and i % 2 == 1 \
                    or path.suffix == ".fastq" and i % 4 == 1:
                seqs.append(line)
            else:
                rest.append(line)
    return seqs, rest


@pytest.mark.parametrize("mode, params, files", [
    (generate.mode_ont, ONT, ("overlaps.paf", "overlaps.sam")),
    (generate.mode_paired_short, SHORT, ("overlaps.sam",))],
    ids=["ont", "paired_short"])
def test_data_seed_makes_every_seed_the_same_job_in_other_letters(
        tmp_path, mode, params, files):
    """With ``data_seed`` a seed chooses one of the 24 relabellings of
    A, C, G, T and nothing else: seed 0 gives the data seed's own bytes,
    every other seed those bytes with the letters exchanged."""
    files += ("genome.fasta", "draft.fasta", "reads.fastq")
    plain, base, other = (tmp_path / d for d in ("plain", "s0", "s5"))
    mode(str(plain), 2, layout_seed=22, **params)
    mode(str(base), 24, layout_seed=22, data_seed=2, **params)
    mode(str(other), 5, layout_seed=22, data_seed=2, **params)
    table = generate._relabel(5)
    assert sorted(table[generate.BASES]) == sorted(generate.BASES)
    assert (table[generate.BASES] != generate.BASES).any()
    for f in files:
        assert digest(base / f) == digest(plain / f), f
        seqs, rest = _split(base / f)
        seqs5, rest5 = _split(other / f)
        assert rest5 == rest, f       # names, places, CIGARs, qualities
        assert f == "overlaps.paf" or seqs5 != seqs
        if f == "reads.fastq":        # as sequenced: some are reversed
            back = [_revcomp(_revcomp(s).translate(bytes(table)))
                    for s in seqs]
            assert all(s5 in (s.translate(bytes(table)), b)
                       for s5, s, b in zip(seqs5, seqs, back))
        else:                         # in the draft's orientation
            assert seqs5 == [s.translate(bytes(table)) for s in seqs], f


def test_host_path_is_blind_to_the_relabelling(tmp_path):
    """The reference polishes a relabelled job into the relabelled
    output: the work does not depend on which seed chose the letters."""
    racon_tpu = pytest.importorskip("racon_tpu")
    out = {}
    for seed in (0, 7):
        d = tmp_path / str(seed)
        generate.mode_ont(str(d), seed, layout_seed=22, data_seed=2,
                          formats=("sam",), **ONT)
        polisher = racon_tpu.create_polisher(
            str(d / "reads.fastq"), str(d / "overlaps.sam"),
            str(d / "draft.fasta"), backend="cpu", window_length=500,
            quality_threshold=10.0, error_threshold=0.3, trim=True,
            fragment_correction=False, match=5, mismatch=-4, gap=-8,
            num_threads=2)
        polisher.initialize()
        (_, out[seed]), = polisher.polish(True)
    table = bytes(generate._relabel(7))
    assert out[0] != out[7]
    assert out[0].encode().translate(table) == out[7].encode()
