"""Input-validation death tests — the reference's EXPECT_DEATH strategy
(/root/reference/test/racon_test.cpp:53-84) via subprocess exit codes."""

import os
import subprocess
import sys

from tests.conftest import DATA, requires_data

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BIN = os.path.join(ROOT, "racon_tpu", "native", "build", "racon_tpu")


def run_bin(*args):
    return subprocess.run([BIN, *args], capture_output=True, text=True,
                          timeout=120)


@requires_data
def test_window_length_error():
    r = run_bin("-w", "0", DATA + "sample_reads.fastq.gz",
                DATA + "sample_overlaps.paf.gz",
                DATA + "sample_layout.fasta.gz")
    assert r.returncode == 1
    assert "invalid window length" in r.stderr


def test_sequences_extension_error():
    r = run_bin("reads.txt", "o.paf", "t.fa")
    assert r.returncode == 1
    assert "unsupported format extension" in r.stderr
    assert ".fasta" in r.stderr


@requires_data
def test_overlaps_extension_error():
    r = run_bin(DATA + "sample_reads.fastq.gz", "o.bed", "t.fa")
    assert r.returncode == 1
    assert ".mhap" in r.stderr


@requires_data
def test_target_extension_error():
    r = run_bin(DATA + "sample_reads.fastq.gz",
                DATA + "sample_overlaps.paf.gz", "t.bed")
    assert r.returncode == 1
    assert "unsupported format extension" in r.stderr


def test_missing_inputs():
    r = run_bin()
    assert r.returncode == 1
    assert "missing input" in r.stderr


@requires_data
def test_missing_file():
    r = run_bin(DATA + "sample_reads.fastq.gz",
                DATA + "sample_overlaps.paf.gz", "/nonexistent/x.fasta")
    assert r.returncode == 1
    assert "unable to open" in r.stderr


def test_removed_kernel_selector_warns_and_runs(tmp_path):
    """RACON_TPU_POA_KERNEL chose between two Pallas consensus kernels
    until PR 31 and stopped a --tpu run on a bad value.  There is one
    kernel now: a leftover setting, whatever its value, is an unknown
    knob (one warning line), not an error, and the run goes through.
    Self-contained (builds its own inputs)."""
    target = "ACGT" * 30
    with open(tmp_path / "t.fasta", "w") as f:
        f.write(f">t\n{target}\n")
    with open(tmp_path / "r.fasta", "w") as f:
        for i in range(3):
            f.write(f">r{i}\n{target}\n")
    with open(tmp_path / "o.sam", "w") as f:
        f.write("@HD\tVN:1.6\n")
        for i in range(3):
            f.write(f"r{i}\t0\tt\t1\t60\t{len(target)}M\t*\t0\t0\t{target}"
                    f"\t*\n")
    code = (
        "import sys; sys.path.insert(0, %r); "
        "from __graft_entry__ import _force_cpu; _force_cpu(1); "
        "from racon_tpu.cli import main; "
        "sys.exit(main(['--tpu', %r, %r, %r]))"
    ) % (ROOT, str(tmp_path / "r.fasta"), str(tmp_path / "o.sam"),
         str(tmp_path / "t.fasta"))
    r = subprocess.run([sys.executable, "-c", code],
                       env=dict(os.environ, RACON_TPU_POA_KERNEL="bogus"),
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    warned = [l for l in r.stderr.splitlines()
              if "unknown RACON_TPU_* environment variable" in l]
    assert len(warned) == 1 and "RACON_TPU_POA_KERNEL" in warned[0]
    assert "Traceback" not in r.stderr
    assert r.stdout.startswith(">t") and target in r.stdout


def test_malformed_fault_spec_clean_error(tmp_path):
    """A malformed RACON_TPU_FAULT spec must surface as a single-line
    error + exit 1 from the CLI (reference-style), not a mid-run
    traceback. Self-contained: builds its own inputs."""
    target = "ACGT" * 30
    with open(tmp_path / "t.fasta", "w") as f:
        f.write(f">t\n{target}\n")
    with open(tmp_path / "r.fasta", "w") as f:
        for i in range(3):
            f.write(f">r{i}\n{target}\n")
    with open(tmp_path / "o.sam", "w") as f:
        f.write("@HD\tVN:1.6\n")
        for i in range(3):
            f.write(f"r{i}\t0\tt\t1\t60\t{len(target)}M\t*\t0\t0\t{target}"
                    f"\t*\n")
    code = (
        "import sys; sys.path.insert(0, %r); "
        "from __graft_entry__ import _force_cpu; _force_cpu(1); "
        "from racon_tpu.cli import main; "
        "sys.exit(main(['--tpu', %r, %r, %r]))"
    ) % (ROOT, str(tmp_path / "r.fasta"), str(tmp_path / "o.sam"),
         str(tmp_path / "t.fasta"))
    for bad in ("poa.run.bogus", "poa.run.ls:frobnicate=1",
                "poa.run.ls:batch=x"):
        r = subprocess.run([sys.executable, "-c", code],
                           env=dict(os.environ, RACON_TPU_FAULT=bad),
                           capture_output=True, text=True, timeout=300)
        assert r.returncode == 1, (bad, r.stderr[-500:])
        assert "RACON_TPU_FAULT" in r.stderr
        assert "Traceback" not in r.stderr
