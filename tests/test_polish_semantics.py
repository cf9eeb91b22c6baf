"""Polish-phase semantics: drop-unpolished behavior and window trimming
(reference behavior: src/polisher.cpp:520-527 emit rule)."""

import random

import pytest

import racon_tpu


def _dataset(tmp_path, rng, with_orphan_target=True):
    """Two targets; the second gets no overlaps (stays unpolished)."""
    t0 = "".join(rng.choice("ACGT") for _ in range(300))
    t1 = "".join(rng.choice("ACGT") for _ in range(250))
    with open(tmp_path / "targets.fasta", "w") as f:
        f.write(f">t0\n{t0}\n")
        if with_orphan_target:
            f.write(f">t1\n{t1}\n")
    with open(tmp_path / "reads.fasta", "w") as rf, \
            open(tmp_path / "ovl.paf", "w") as of:
        for i in range(4):
            rf.write(f">r{i}\n{t0}\n")
            of.write(f"r{i}\t{len(t0)}\t0\t{len(t0)}\t+\tt0\t{len(t0)}\t0\t"
                     f"{len(t0)}\t{len(t0)}\t{len(t0)}\t60\n")
    return t0, t1


def test_drop_unpolished_default(tmp_path):
    rng = random.Random(2)
    t0, _ = _dataset(tmp_path, rng)
    p = racon_tpu.CpuPolisher(str(tmp_path / "reads.fasta"),
                              str(tmp_path / "ovl.paf"),
                              str(tmp_path / "targets.fasta"),
                              window_length=100, match=5, mismatch=-4,
                              gap=-8)
    p.initialize()
    res = p.polish(True)
    # only the covered target survives
    assert [n.split()[0] for n, _ in res] == ["t0"]
    assert res[0][1] == t0


def test_include_unpolished(tmp_path):
    rng = random.Random(2)
    t0, t1 = _dataset(tmp_path, rng)
    p = racon_tpu.CpuPolisher(str(tmp_path / "reads.fasta"),
                              str(tmp_path / "ovl.paf"),
                              str(tmp_path / "targets.fasta"),
                              window_length=100, match=5, mismatch=-4,
                              gap=-8)
    p.initialize()
    res = p.polish(False)
    names = [n.split()[0] for n, _ in res]
    assert names == ["t0", "t1"]
    assert res[1][1] == t1  # orphan target passes through unmodified


def test_no_trimming_keeps_low_coverage_ends(tmp_path):
    """--no-trimming analogue: TGS trim off must never shorten consensus
    below the trimmed variant (reference: src/window.cpp:125-146 gated by
    the trim flag, src/main.cpp:24)."""
    import os

    from tests.conftest import DATA
    if not os.path.isdir(DATA):
        import pytest
        pytest.skip("lambda data unavailable")

    def run(trim):
        p = racon_tpu.CpuPolisher(DATA + "sample_reads.fastq.gz",
                                  DATA + "sample_overlaps.sam.gz",
                                  DATA + "sample_layout.fasta.gz",
                                  trim=trim, match=5, mismatch=-4, gap=-8)
        p.initialize()
        return p.polish(True)

    trimmed = run(True)[0][1]
    untrimmed = run(False)[0][1]
    assert len(untrimmed) > len(trimmed)
