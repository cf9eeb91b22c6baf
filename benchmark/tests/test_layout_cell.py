"""The raw-layout generator (``generate_layout.mode_layout``), the plain
reference ``reference_layout.py`` and the reader ``reducers/layout.py``."""

import hashlib

import numpy as np
import pytest

from benchmark import (generate, generate_layout, prepare, reference_align,
                       reference_layout)
from benchmark.reducers import layout
from racon_tpu import native

PROFILE = dict(error_rate=0.17, qual_mean=13.0, qual_sd=2.0,
               qual_base_sd=3.0, qual_error_drop=5.0, data_seed=2,
               layout_seed=22)
FULL = dict(PROFILE, genome_mbp=0.048502, reads=236, read_bases=1658216)
SMALL = dict(PROFILE, genome_mbp=0.006, reads=30, read_bases=45000)

#: sha256 prefixes of the small set at seed 0 (the identity relabelling):
#: an edit that moves them has to raise GENERATOR_REV
PINNED = {"reads.fastq": "3b3ea1a3d4bda621",
          "draft.fasta": "4abcd41ac79aad46",
          "overlaps.paf": "b5ab73e76d776fb7",
          "genome.fasta": "916a5a0c92955ab2"}


def _digest(path):
    with open(path, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()[:16]


def _paf(path):
    with open(path) as f:
        return [line.split("\t") for line in f]


@pytest.fixture(scope="module")
def full(tmp_path_factory):
    d = tmp_path_factory.mktemp("lambda236")
    return d, generate_layout.mode_layout(str(d), 0, **FULL)


@pytest.fixture(scope="module")
def small(tmp_path_factory):
    d = tmp_path_factory.mktemp("lambda30")
    return d, generate_layout.mode_layout(str(d), 0, **SMALL)


def test_the_full_set_is_the_sources_size(full):
    d, facts = full
    reads = reference_layout.read_fastq(str(d / "reads.fastq"))
    assert len(reads) == facts["reads"] == 236
    assert sum(len(s) for s, _ in reads.values()) \
        == facts["read_bases"] == 1658216
    assert len(prepare.read_fasta(str(d / "genome.fasta"))) == 48502
    draft = prepare.read_fasta(str(d / "draft.fasta"))
    # a layout loses the ends: upstream's is ~47.5 kb
    assert 46500 < len(draft) == facts["draft_bp"] < 48000
    assert facts["layout_last"] - facts["layout_first"] \
        == pytest.approx(len(draft), rel=0.01)
    lines = _paf(d / "overlaps.paf")
    assert len(lines) == facts["overlaps"] == 236
    assert len({c[0] for c in lines}) == 236       # one line a read
    lens = np.array([len(s) for s, _ in reads.values()])
    assert lens.min() >= 400 and lens.max() <= 0.8 * 48502
    assert 0.4 < lens.std() / lens.mean() < 0.6    # gamma(4): 0.5


def test_the_draft_is_a_raw_layout(full):
    """Pieces of reads: the draft differs from the stretch of genome it
    spans by about the reads' own error rate, with insertions and
    deletions, and from the whole genome by the lost ends more."""
    d, facts = full
    truth = prepare.read_fasta(str(d / "genome.fasta"))
    draft = prepare.read_fasta(str(d / "draft.fasta"))
    core = truth[facts["layout_first"]:facts["layout_last"]]
    inside = native.edit_distance(draft, core)
    assert 0.12 * len(core) < inside < 0.18 * len(core)
    lost = len(truth) - len(core)
    assert 800 < lost < 1500
    whole = native.edit_distance(draft, truth)
    assert inside + 0.9 * lost < whole <= inside + lost
    assert 4 <= facts["layout_pieces"] <= 12


def test_qualities_vary_and_mark_the_errors(small):
    d, _ = small
    reads = reference_layout.read_fastq(str(d / "reads.fastq"))
    means = np.array([q.mean() for _, q in reads.values()])
    assert means.std() > 1.0 and 9 < means.mean() < 15
    assert (means < 10).any() and (means > 10).sum() > 20
    allq = np.concatenate([q for _, q in reads.values()])
    assert allq.min() >= 1 and len(np.unique(allq)) > 15


def test_paf_coordinates_land_near_a_realignment(small):
    """At the PAF's coordinates a read's span and the layout's align at
    about twice the error rate; the ends are pulled in (a mapper's chain
    stops at its last anchor), never pushed out."""
    d, facts = small
    reads = reference_layout.read_fastq(str(d / "reads.fastq"))
    draft = np.frombuffer(prepare.read_fasta(str(d / "draft.fasta")),
                          np.uint8)
    rows = reference_layout.paf_overlaps(str(d / "overlaps.paf"), 0.3)
    assert all(r["kept"] for r in rows) and len(rows) == facts["overlaps"]
    for r in rows[::5]:
        seq, _, lo, hi = reference_layout.on_target_strand(
            r, *reads[r["name"]])
        assert 0 <= lo < hi <= len(seq)
        assert 0 <= r["t_begin"] < r["t_end"] <= len(draft)
        cost = native.edit_distance(seq[lo:hi].tobytes(),
                                    draft[r["t_begin"]:r["t_end"]].tobytes())
        assert cost < 0.42 * (hi - lo)
        assert r["error"] < 0.15


def test_relabelling_keeps_the_work(small, tmp_path):
    d, facts = small
    other = generate_layout.mode_layout(str(tmp_path), 5, **SMALL)
    assert other == facts
    assert _digest(tmp_path / "overlaps.paf") == _digest(d / "overlaps.paf")
    table = generate._relabel(5)
    for name in ("genome.fasta", "draft.fasta"):
        a = np.frombuffer(prepare.read_fasta(str(d / name)), np.uint8)
        b = np.frombuffer(prepare.read_fasta(str(tmp_path / name)), np.uint8)
        assert (a != b).any() and (table[a] == b).all()
    a = reference_layout.read_fastq(str(d / "reads.fastq"))
    b = reference_layout.read_fastq(str(tmp_path / "reads.fastq"))
    for name in a:
        assert (a[name][1] == b[name][1]).all()        # qualities stay
        assert len(a[name][0]) == len(b[name][0])
    # the same job: the host path leaves the same edits
    assert _digest(tmp_path / "reads.fastq") != _digest(d / "reads.fastq")


def test_a_free_seed_draws_another_set(tmp_path):
    free = {k: v for k, v in SMALL.items()
            if k not in ("data_seed", "layout_seed")}
    a = generate_layout.mode_layout(str(tmp_path / "a"), 7, **free)
    b = generate_layout.mode_layout(str(tmp_path / "b"), 8, **free)
    assert a["read_bases"] == b["read_bases"] == 45000
    assert _digest(tmp_path / "a" / "genome.fasta") \
        != _digest(tmp_path / "b" / "genome.fasta")


def test_generator_refuses_a_stale_revision(tmp_path):
    with pytest.raises(ValueError, match="generator_rev"):
        generate_layout.mode_layout(str(tmp_path), 0, generator_rev=0,
                                    **SMALL)


def test_generator_bytes_are_pinned(small):
    d, _ = small
    assert {f: _digest(d / f) for f in PINNED} == PINNED


# -- the plain reference --------------------------------------------------------

def test_align_is_optimal_and_its_ops_consume_both(small):
    rng = np.random.default_rng(3)
    bases = np.frombuffer(b"ACGT", np.uint8)
    for n, m in ((0, 5), (7, 0), (300, 300), (700, 520), (530, 1100)):
        t = bases[rng.integers(0, 4, m)]
        q = generate._mutate(bases[rng.integers(0, 4, n)] if n != m else t,
                             rng, 0.08, 0.05, 0.05)[0][:n] if n else t[:0]
        for block in (64, 512):
            cost, ops = reference_layout.align(q, t, block=block)
            assert cost == reference_align.edit_distance(q.tobytes(),
                                                         t.tobytes())
            got, qi, ti = reference_align.cigar_cost(
                reference_layout.cigar(ops), q.tobytes(), t.tobytes())
            assert (got, qi, ti) == (cost, len(q), len(t))


def test_overlap_rule_is_racons_order():
    import io
    import tempfile

    def line(name, q0, q1, t0, t1):
        return f"{name}\t1000\t{q0}\t{q1}\t+\tlayout\t9000\t{t0}\t{t1}\t1\t1\t60\n"
    with tempfile.NamedTemporaryFile("w", suffix=".paf") as f:
        f.write(line("a", 0, 400, 0, 900))      # error 0.56: dropped
        f.write(line("a", 0, 800, 0, 820))      # stands
        f.write(line("a", 0, 300, 0, 300))      # shorter: dropped
        f.write(line("b", 0, 500, 100, 600))    # alone: stands
        f.write(line("a", 0, 200, 0, 200))      # a new run of a: stands
        f.flush()
        rows = reference_layout.paf_overlaps(f.name, 0.3)
    assert [r["kept"] for r in rows] == [False, True, False, True, True]
    assert rows[0]["error"] == pytest.approx(1 - 400 / 900)


# -- the reader ---------------------------------------------------------------------

def _run(*jobs):
    return {"jobs": [{"counters": {}, "phases": {}, "spans": s}
                     for s in jobs], "notes": {}, "facts": {}, "trace": None}


def test_span_share_on_a_recorded_jobs_spans():
    # a served job's root span and its two ends, nanoseconds (start, dur)
    job = {"job": [(1_000, 4_000_000_000)],
           "job.open": [(1_000, 60_000_000)],
           "job.close": [(3_900_000_000, 100_000_000)],
           "phase.align": [(100_000_000, 2_600_000_000)]}
    slow = dict(job, **{"job.close": [(3_900_000_000, 300_000_000)]})
    run = _run(job, job, slow)
    assert layout.span_share(run, ["job.open", "job.close"], "job") \
        == pytest.approx(4.0)
    assert layout.span_share(_run(slow), ["job.open", "job.close"], "job") \
        == pytest.approx(9.0)


def test_span_share_reads_nothing_without_the_spans():
    assert layout.span_share(_run({"phase.poa": [(0, 5)]}),
                             ["job.open"], "job") is None
    assert layout.span_share(_run({"job": [(0, 5)]}), ["job.open"],
                             "job") is None
