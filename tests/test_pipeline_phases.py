"""Cross-phase align/POA pipelining (RACON_TPU_PIPELINE_PHASES): target
chunking, bounded handoff queue, ordered install (byte-identical output),
merged phase reports, span-overlap evidence in traces, and the
pack/kernel wall split surfaced by the shared executor."""

import json
import os
import subprocess
import sys

import pytest

import racon_tpu
from racon_tpu.polisher import TpuPolisher, _split_fasta
from racon_tpu.obs import costmodel
from racon_tpu.resilience.report import PhaseReport
from racon_tpu.tools import simulate

from test_faults import _ARGS, _assert_report_sums, _oracle, _tpu_run, \
    _write_dataset

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------- _split_fasta

def test_split_fasta_balanced_roundtrip(tmp_path):
    src = tmp_path / "t.fasta"
    recs = [(f"c{i}", "ACGT" * (10 * (i + 1))) for i in range(5)]
    src.write_text("".join(f">{n}\n{s}\n" for n, s in recs))
    chunks = _split_fasta(str(src), 3, str(tmp_path))
    assert chunks is not None and len(chunks) == 3
    # verbatim record text, original order, nothing lost or duplicated
    joined = "".join(open(c).read() for c in chunks)
    assert joined == src.read_text()
    for c in chunks:
        assert open(c).read().startswith(">")


def test_split_fasta_chunk_count_capped_by_records(tmp_path):
    src = tmp_path / "t.fasta"
    src.write_text(">a\nACGT\n>b\nGGCC\n")
    chunks = _split_fasta(str(src), 6, str(tmp_path))
    assert chunks is not None and len(chunks) == 2


def test_split_fasta_rejects_unsplittable(tmp_path):
    one = tmp_path / "one.fasta"
    one.write_text(">only\nACGT\n")
    assert _split_fasta(str(one), 3, str(tmp_path)) is None
    junk = tmp_path / "junk.fasta"
    junk.write_text("this is not fasta\n>late\nACGT\n")
    assert _split_fasta(str(junk), 3, str(tmp_path)) is None
    assert _split_fasta(str(tmp_path / "missing.fasta"), 3,
                        str(tmp_path)) is None


def test_split_fasta_gzip(tmp_path):
    import gzip

    src = tmp_path / "t.fasta.gz"
    with gzip.open(src, "wt") as f:
        f.write(">a\nAAAA\n>b\nCCCC\n>c\nGGGG\n")
    chunks = _split_fasta(str(src), 2, str(tmp_path))
    assert chunks is not None and len(chunks) == 2
    assert "".join(open(c).read() for c in chunks) == \
        ">a\nAAAA\n>b\nCCCC\n>c\nGGGG\n"


# ------------------------------------------- pipelined vs sequential

def test_pipelined_byte_identical_to_sequential(tmp_path, monkeypatch):
    paths = _write_dataset(tmp_path, overlaps="paf", n_reads=2)
    oracle = _oracle(paths)
    seq_res, seq_p = _tpu_run(paths, monkeypatch, {})
    pipe_res, pipe_p = _tpu_run(paths, monkeypatch,
                                {"RACON_TPU_PIPELINE_PHASES": "1"})
    assert pipe_p._pipelined, "3-contig FASTA target must pipeline"
    assert pipe_res == seq_res == oracle
    # merged per-chunk reports keep the served-sum invariant and the
    # full-run totals
    d = _assert_report_sums(pipe_p)
    ds = _assert_report_sums(seq_p)
    assert d["phases"]["consensus"]["total"] == \
        ds["phases"]["consensus"]["total"] == 6
    assert d["phases"]["alignment"]["total"] == \
        ds["phases"]["alignment"]["total"] == 6


def test_journal_forces_sequential(tmp_path, monkeypatch, capsys):
    paths = _write_dataset(tmp_path)
    oracle = _oracle(paths)
    monkeypatch.setenv("RACON_TPU_PIPELINE_PHASES", "1")
    for k, v in {"RACON_TPU_PALLAS": "0",
                 "RACON_TPU_BATCH_WINDOWS": "8"}.items():
        monkeypatch.setenv(k, v)
    p = racon_tpu.create_polisher(*paths, backend="tpu",
                                  journal_path=str(tmp_path / "j.wal"),
                                  **_ARGS)
    assert not p._pipelined       # journal needs run-global window indices
    p.initialize()
    assert p.polish(True) == oracle


def test_non_fasta_extension_forces_sequential(tmp_path, monkeypatch):
    monkeypatch.setenv("RACON_TPU_PIPELINE_PHASES", "1")
    p = TpuPolisher("r.fa", "o.paf", str(tmp_path / "target.txt"), **_ARGS)
    assert p._pipelined
    assert p._split_target() is None


def test_single_contig_forces_sequential(tmp_path, monkeypatch):
    paths = _write_dataset(tmp_path, n_targets=1)
    oracle = _oracle(paths)
    res, p = _tpu_run(paths, monkeypatch,
                      {"RACON_TPU_PIPELINE_PHASES": "1"})
    assert not p._pipelined       # fewer than two contigs -> sequential
    assert res == oracle


def test_handoff_depth_floor(tmp_path, monkeypatch):
    paths = _write_dataset(tmp_path)
    oracle = _oracle(paths)
    res, p = _tpu_run(paths, monkeypatch, {
        "RACON_TPU_PIPELINE_PHASES": "1",
        "RACON_TPU_HANDOFF_DEPTH": "0",    # clamped to 1
    })
    assert p._pipelined
    assert res == oracle


# --------------------------------------------------- report merging

def test_phase_report_merge():
    a = PhaseReport("consensus", ("xla", "host"))
    a.total = 4
    a.record_served("xla", 3)
    a.record_served("host", 1)
    a.retries = 1
    a.add_wall("xla", 0.5)
    a.extra["pack_wall_s"] = 0.25
    a.extra["kernel_wall_s"] = 1.0
    b = PhaseReport("consensus", ("xla", "host"))
    b.total = 2
    b.record_served("xla", 2)
    b.bisections = 2
    b.record_quarantine(7, RuntimeError("poison"))
    b.add_wall("xla", 0.25)
    b.extra["pack_wall_s"] = 0.5
    b.extra["note"] = "x"
    a.merge(b)
    assert a.total == 6
    assert a.served == {"xla": 5, "host": 1}
    assert a.retries == 1 and a.bisections == 2
    assert a.quarantined == [7]
    assert a.wall_s["xla"] == 0.75
    assert a.extra["pack_wall_s"] == 0.75       # numeric extras sum
    assert a.extra["kernel_wall_s"] == 1.0
    assert a.extra["note"] == "x"
    assert sum(a.served.values()) == a.total    # invariant survives merge


# ------------------------------------------------ overlap computation

def _doc(*events):
    return {"traceEvents": [
        {"ph": "X", "name": n, "ts": ts, "dur": dur, "pid": 1, "tid": 1}
        for n, ts, dur in events]}


def test_overlap_us_two_pointer():
    doc = _doc(("phase.align", 0, 100), ("phase.align", 300, 100),
               ("phase.poa", 50, 100), ("phase.poa", 500, 50))
    assert costmodel.overlap_us(doc, "phase.align", "phase.poa") == 50
    assert costmodel.overlap_us(doc, "phase.align", "phase.stitch") == 0
    assert costmodel.union_intervals([(0, 10), (5, 20), (30, 40)]) == \
        [(0, 20), (30, 40)]
    assert costmodel.phase_overlaps_us(doc) == {"align+poa": 50.0}


def test_sequential_trace_has_no_phase_overlap():
    doc = _doc(("phase.align", 0, 100), ("phase.poa", 100, 100),
               ("phase.stitch", 200, 10))
    assert costmodel.phase_overlaps_us(doc) == {}
    v = costmodel.validate_trace(doc, costmodel.PROFILES["cpu-host"])
    assert "phase_overlap_s" not in v


def test_validate_trace_stamps_phase_overlap():
    doc = _doc(("phase.align", 0, 1_000_000), ("phase.poa", 500_000,
                                               1_000_000))
    v = costmodel.validate_trace(doc, costmodel.PROFILES["cpu-host"])
    assert v["phase_overlap_s"] == {"align+poa": 0.5}


def test_obs_cli_overlap_exit_codes(tmp_path):
    tr = tmp_path / "trace.json"
    tr.write_text(json.dumps(_doc(("align.cohort", 0, 100),
                                  ("poa.bucket", 50, 100))))
    flat = tmp_path / "flat.json"
    flat.write_text(json.dumps(_doc(("align.cohort", 0, 100),
                                    ("poa.bucket", 200, 100))))

    def run(trace, arg):
        return subprocess.run(
            [sys.executable, "-m", "racon_tpu.obs", str(trace),
             "--overlap", arg, "--json"],
            cwd=ROOT, capture_output=True, text=True)

    ok = run(tr, "align.cohort:poa.bucket")
    assert ok.returncode == 0, ok.stderr
    d = json.loads(ok.stdout)
    assert d["overlap_us"] == 50 and d["spans_a"] == d["spans_b"] == 1
    assert run(flat, "align.cohort:poa.bucket").returncode == 3
    assert run(tr, "malformed-no-colon").returncode == 2


# ------------------------------------------------ bench pack/kernel split

def test_bench_pack_split_and_backfill():
    sys.path.insert(0, ROOT)
    import bench

    # summary() shape: phase-keyed, extras riding along per phase
    rep = {
        "alignment": {"served": {}, "extra": {"pack_wall_s": 0.1,
                                              "kernel_wall_s": 0.9}},
        "consensus": {"served": {}, "extra": {"kernel_wall_s": 2.0}},
        "stitch": {"served": {}},
        "unknown_knobs": ["RACON_TPU_TYPO"],   # non-phase key tolerated
    }
    split = bench.pack_split(rep)
    assert split == {
        "alignment": {"pack_wall_s": 0.1, "kernel_wall_s": 0.9},
        "consensus": {"pack_wall_s": None, "kernel_wall_s": 2.0},
    }
    assert bench.pack_split(None) == {}        # pre-executor entries
    assert bench.pack_split({"x": {"served": {}}}) == {}
    # normalize_entry backfills older log entries (report embedded or not)
    e = bench.normalize_entry({"mbp": 1.0, "report": rep})
    assert e["pack_split"]["alignment"]["kernel_wall_s"] == 0.9
    e2 = bench.normalize_entry({"mbp": 1.0})
    assert e2["pack_split"] is None
    # entries that already carry the field are left alone
    e3 = bench.normalize_entry({"mbp": 1.0, "pack_split": {"k": 1}})
    assert e3["pack_split"] == {"k": 1}


# ------------------------------------------------ simulate --contigs

def test_simulate_multi_contig(tmp_path):
    paths = simulate.generate(str(tmp_path), mbp=0.006, coverage=3,
                              mean_read=900, contigs=3)
    draft = open(paths["draft"]).read()
    names = [ln[1:] for ln in draft.splitlines() if ln.startswith(">")]
    assert names == ["contig0", "contig1", "contig2"]
    seqs = [ln for ln in draft.splitlines() if not ln.startswith(">")]
    assert sum(len(s) for s in seqs) == 6000
    sq = [ln for ln in open(paths["overlaps_sam"]).read().splitlines()
          if ln.startswith("@SQ")]
    assert len(sq) == 3
    for row in open(paths["overlaps"]).read().splitlines():
        cols = row.split("\t")
        tname, t_len, t_start, t_end = (cols[5], int(cols[6]),
                                        int(cols[7]), int(cols[8]))
        assert tname in names
        assert 0 <= t_start < t_end <= t_len == 2000   # local coordinates


def test_simulate_single_contig_unchanged(tmp_path):
    paths = simulate.generate(str(tmp_path / "a"), mbp=0.002, coverage=3,
                              mean_read=500)
    draft = open(paths["draft"]).read()
    assert draft.startswith(">contig\n")
    explicit = simulate.generate(str(tmp_path / "b"), mbp=0.002,
                                 coverage=3, mean_read=500, contigs=1)
    assert open(explicit["draft"]).read() == draft
    assert open(explicit["reads"]).read() == \
        open(paths["reads"]).read()


# --------------------------------- e2e: traced pipelined polish (CLI)

@pytest.mark.slow
def test_traced_pipelined_polish_overlap_and_pack_split(tmp_path):
    """The acceptance run: pipelined and sequential CLI polishes are
    byte-identical; the pipelined trace shows align/POA span overlap
    (asserted through `python -m racon_tpu.obs --overlap`, the same
    check CI runs); the report's phase-1 split shows pack < kernel."""
    data = tmp_path / "data"
    simulate.generate(str(data), mbp=0.004, coverage=6, mean_read=800,
                      contigs=3)
    paths = [str(data / "reads.fastq"), str(data / "overlaps.paf"),
             str(data / "draft.fasta")]

    def cli(tag, env=None):
        trace = str(tmp_path / f"{tag}.trace.json")
        report = str(tmp_path / f"{tag}.report.json")
        # -w 100: small windows keep the per-geometry XLA compiles (the
        # dominant cost on the CPU backend) to seconds instead of minutes
        cmd = [sys.executable, "-m", "racon_tpu.cli", "--tpu",
               "-w", "100", "--trace", trace, "--report", report, *paths]
        full_env = dict(os.environ, JAX_PLATFORMS="cpu",
                        RACON_TPU_PALLAS="0",
                        RACON_TPU_BATCH_WINDOWS="8",
                        RACON_TPU_DEVICE_ALIGNER="hirschberg")
        full_env.pop("RACON_TPU_FAULT", None)
        full_env.pop("XLA_FLAGS", None)
        full_env.update(env or {})
        r = subprocess.run(cmd, cwd=ROOT, env=full_env,
                           capture_output=True, timeout=540)
        assert r.returncode == 0, r.stderr.decode()[-3000:]
        return r.stdout, trace, report

    seq_out, seq_trace, _ = cli("seq")
    pipe_out, pipe_trace, pipe_report = cli(
        "pipe", env={"RACON_TPU_PIPELINE_PHASES": "1"})
    assert pipe_out == seq_out and pipe_out.startswith(b">")

    def overlap(trace, pair):
        return subprocess.run(
            [sys.executable, "-m", "racon_tpu.obs", trace,
             "--overlap", pair], cwd=ROOT, capture_output=True)

    # phase spans AND the executors' inner spans ran concurrently
    assert overlap(pipe_trace, "phase.align:phase.poa").returncode == 0
    assert overlap(pipe_trace, "align.cohort:poa.bucket").returncode == 0
    # the sequential trace shows none — exit 3 is the CI failure signal
    assert overlap(seq_trace, "phase.align:phase.poa").returncode == 3
    # the validate join still works on an overlapped trace and stamps
    # the concurrency it found
    doc = json.load(open(pipe_trace))
    v = costmodel.validate_trace(doc, costmodel.PROFILES["cpu-host"])
    assert v["phase_overlap_s"]["align+poa"] > 0
    # phase-1 split: packing is cheaper than the kernels it feeds
    rep = json.load(open(pipe_report))
    al = rep["phases"]["alignment"]["extra"]
    assert 0 < al["pack_wall_s"] < al["kernel_wall_s"]
