"""Short-read (Illumina-style) polishing scenario — the analogue of
BASELINE.json config 4 (short-read polish, SAM input, small windows):
paired-end reads renamed by the preprocess tool, mean read length <= 1000
selects NGS windows (no trim; reference: src/polisher.cpp:277-278,
src/window.cpp:125), window length 200."""

import io
import random

import racon_tpu
from racon_tpu import native
from racon_tpu.tools import preprocess, simulate


def test_bench_sr_profile_dataset_polishes(tmp_path):
    """The bench's short-read profile (150 bp @ ~1% error — the
    short-read bench workload) must produce a dataset the host
    pipeline actually polishes: reads are short-read-sized, windows are
    NGS-class, and the polished contig lands closer to the genome than
    the draft started."""
    paths = simulate.generate(str(tmp_path / "sr"), mbp=0.02, coverage=30,
                              mean_read=150, sub=0.008, ins=0.001,
                              dele=0.001)
    with open(paths["reads"]) as f:
        lens = [len(line.strip()) for i, line in enumerate(f) if i % 4 == 1]
    assert sum(lens) / len(lens) < 300, "not a short-read profile"

    p = racon_tpu.create_polisher(paths["reads"], paths["overlaps_sam"],
                                  paths["draft"], backend="cpu",
                                  window_length=500,
                                  quality_threshold=10.0,
                                  error_threshold=0.3, match=5,
                                  mismatch=-4, gap=-8, num_threads=1)
    p.initialize()
    res = p.polish(True)
    assert len(res) == 1
    genome = open(paths["genome"]).read().split("\n", 1)[1].replace("\n", "")
    draft = open(paths["draft"]).read().split("\n", 1)[1].replace("\n", "")
    ed_draft = native.edit_distance(draft.encode(), genome.encode())
    ed_pol = native.edit_distance(res[0][1].encode(), genome.encode())
    assert ed_pol < ed_draft / 4, (ed_pol, ed_draft)


def make_dataset(tmp_path, rng, genome_len=2000, read_len=150, coverage=20):
    truth = "".join(rng.choice("ACGT") for _ in range(genome_len))
    # Draft with ~1.5% substitution errors.
    draft = list(truth)
    n_err = int(genome_len * 0.015)
    err_pos = rng.sample(range(genome_len), n_err)
    for pos in err_pos:
        draft[pos] = rng.choice([c for c in "ACGT" if c != draft[pos]])
    draft = "".join(draft)

    with open(tmp_path / "draft.fasta", "w") as f:
        f.write(f">chr\n{draft}\n")

    # Paired reads sharing a name (renamed 1/2 by preprocess), high quality.
    n_reads = genome_len * coverage // read_len
    pairs_fq = io.StringIO()
    records = []
    for i in range(n_reads // 2):
        for _ in range(2):
            start = rng.randint(0, genome_len - read_len)
            seq = truth[start:start + read_len]
            pairs_fq.write(f"@frag{i} extra\n{seq}\n+\n{'I' * read_len}\n")
            records.append((start, seq))

    with open(tmp_path / "pairs.fastq", "w") as f:
        f.write(pairs_fq.getvalue())

    # Rename pairs to unique names (the preprocess contract).
    renamed = io.StringIO()
    preprocess.parse_file(str(tmp_path / "pairs.fastq"), set(), renamed)
    with open(tmp_path / "reads.fastq", "w") as f:
        f.write(renamed.getvalue())
    names = [l[1:].strip() for l in renamed.getvalue().splitlines()[::4]]

    # SAM with exact positions (reads come from truth; the draft's
    # substitutions become the windows' correction work).
    with open(tmp_path / "aln.sam", "w") as f:
        f.write("@HD\tVN:1.6\n@SQ\tSN:chr\tLN:%d\n" % genome_len)
        for name, (start, seq) in zip(names, records):
            f.write(f"{name}\t0\tchr\t{start + 1}\t60\t{read_len}M\t*\t0\t0\t"
                    f"{seq}\t{'I' * read_len}\n")
    return truth, draft


def test_short_read_polish(tmp_path):
    rng = random.Random(17)
    truth, draft = make_dataset(tmp_path, rng)
    assert native.edit_distance(draft.encode(), truth.encode()) > 20

    p = racon_tpu.CpuPolisher(str(tmp_path / "reads.fastq"),
                              str(tmp_path / "aln.sam"),
                              str(tmp_path / "draft.fasta"),
                              window_length=200, quality_threshold=10.0,
                              error_threshold=0.3,
                              match=5, mismatch=-4, gap=-8, num_threads=1)
    p.initialize()
    res = p.polish(True)
    assert len(res) == 1
    polished = res[0][1].encode()
    # Short high-quality reads should correct nearly every draft error.
    ed = native.edit_distance(polished, truth.encode())
    assert ed <= 3, ed


def test_short_read_polish_device_path(tmp_path, monkeypatch):
    rng = random.Random(23)
    truth, draft = make_dataset(tmp_path, rng, genome_len=1000, coverage=16)

    from racon_tpu.ops import poa_driver

    captured = {}
    orig = poa_driver.run_consensus_phase

    def spy(*a, **k):
        stats = orig(*a, **k)
        captured.update(stats)
        return stats

    monkeypatch.setattr(poa_driver, "run_consensus_phase", spy)
    monkeypatch.setenv("RACON_TPU_PALLAS", "1")
    monkeypatch.setenv("RACON_TPU_BATCH_WINDOWS", "8")
    p = racon_tpu.TpuPolisher(str(tmp_path / "reads.fastq"),
                              str(tmp_path / "aln.sam"),
                              str(tmp_path / "draft.fasta"),
                              window_length=200, quality_threshold=10.0,
                              error_threshold=0.3,
                              match=5, mismatch=-4, gap=-8, num_threads=1)
    p.initialize()
    res = p.polish(True)
    assert len(res) == 1
    ed = native.edit_distance(res[0][1].encode(), truth.encode())
    assert ed <= 3, ed
    # the device (default ls tier) must actually have served: a silent
    # per-window host fallback would hide a broken kernel behind correct
    # output
    assert captured["device"] > 0
    assert captured["host_fallback"] == 0 and captured["failed"] == 0
