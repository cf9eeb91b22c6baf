"""Preemption tolerance: the crash-safe window journal, SIGKILL resume,
the wedge classifier, and the bulk align-job-lengths FFI.

The headline contract (ISSUE acceptance): a polish killed mid-run with
SIGKILL, resumed via `--resume-journal`, produces byte-identical output
to an uninterrupted run, and the run report counts resumed vs freshly
computed windows.  Everything here runs on the CPU backend in tier-1.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import racon_tpu
from racon_tpu.pipeline import Pipeline
from racon_tpu.resilience import faults, lattice, watchdog
from racon_tpu.resilience.journal import Journal, input_fingerprint

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

sys.path.insert(0, ROOT)  # for `import bench` (repo-root script)

_ARGS = dict(window_length=100, quality_threshold=10, error_threshold=0.3,
             match=5, mismatch=-4, gap=-8, num_threads=1)


def _write_dataset(tmp_path, n_targets=3, n_reads=4):
    """Identical-read PAF dataset (same shape as test_faults.py): w=100
    over 200 bp targets -> 6 windows, all byte-stable across backends."""
    import random
    rng = random.Random(11)
    with open(tmp_path / "targets.fasta", "w") as tf, \
            open(tmp_path / "reads.fasta", "w") as rf, \
            open(tmp_path / "ovl.paf", "w") as of:
        for t in range(n_targets):
            seq = "".join(rng.choice("ACGT") for _ in range(200))
            tf.write(f">t{t}\n{seq}\n")
            for i in range(n_reads):
                rf.write(f">t{t}r{i}\n{seq}\n")
                of.write(f"t{t}r{i}\t200\t0\t200\t+\tt{t}\t200\t0\t200"
                         f"\t200\t200\t60\n")
    return (str(tmp_path / "reads.fasta"), str(tmp_path / "ovl.paf"),
            str(tmp_path / "targets.fasta"))


def _cli(paths, *extra, env=None, window=100):
    cmd = [sys.executable, "-m", "racon_tpu.cli",
           "-w", str(window), "-q", "10", "-e", "0.3",
           "-m", "5", "-x", "-4", "-g", "-8", *extra, *paths]
    full_env = dict(os.environ, JAX_PLATFORMS="cpu")
    full_env.pop("RACON_TPU_FAULT", None)
    full_env.update(env or {})
    return subprocess.run(cmd, cwd=ROOT, env=full_env, capture_output=True)


# ------------------------------------------------------------ unit: faults

def test_new_fault_points_registered():
    assert {"journal.append", "journal.replay",
            "watchdog.call"} <= faults.KNOWN_POINTS


def test_parse_kill_spec():
    (spec,) = faults.parse_spec("journal.append:batch=3:kill=1")
    assert spec.kill and spec.batch == 3
    (spec,) = faults.parse_spec("journal.append:kill=0")
    assert not spec.kill
    with pytest.raises(ValueError):
        faults.parse_spec("journal.append:kill=x")


# ------------------------------------------------------- unit: fingerprint

def test_fingerprint_sensitivity(tmp_path):
    paths = _write_dataset(tmp_path)
    fp = input_fingerprint(paths, _ARGS, "cpu")
    assert fp == input_fingerprint(paths, _ARGS, "cpu")
    assert fp != input_fingerprint(paths, _ARGS, "tpu")
    assert fp != input_fingerprint(paths, dict(_ARGS, window_length=50),
                                   "cpu")
    # thread count legally varies between the killed and resumed run
    assert fp == input_fingerprint(paths, dict(_ARGS, num_threads=8), "cpu")
    with open(paths[0], "a") as f:
        f.write(">extra\nACGT\n")
    assert fp != input_fingerprint(paths, _ARGS, "cpu")


def test_journal_roundtrip_and_torn_tail(tmp_path):
    jp = str(tmp_path / "j.jsonl")
    j = Journal(jp, "f" * 64)
    j.append_window(0, 0, 3, "xla", b"ACGT", True)
    j.append_cigar(2, "hirschberg", "4=")
    j.close()
    r = Journal(jp, "f" * 64, resume=True)
    assert r.resumed
    assert r.windows[0].payload == b"ACGT" and r.windows[0].polished
    assert r.cigars[2].cigar == "4="
    r.close()
    # chop mid-record: the torn tail is dropped, the prefix survives
    size = os.path.getsize(jp)
    with open(jp, "r+b") as f:
        f.truncate(size - 5)
    t = Journal(jp, "f" * 64, resume=True)
    assert t.windows[0].payload == b"ACGT" and 2 not in t.cigars
    t.close()


def test_journal_fingerprint_mismatch_modes(tmp_path):
    jp = str(tmp_path / "j.jsonl")
    Journal(jp, "a" * 64).close()
    from racon_tpu.resilience.journal import JournalError
    with pytest.raises(JournalError):
        Journal(jp, "b" * 64, resume=True, on_mismatch="error")
    fresh = Journal(jp, "b" * 64, resume=True, on_mismatch="fresh")
    assert not fresh.resumed and not fresh.windows
    fresh.close()


# --------------------------------------------- e2e: SIGKILL -> resume (CLI)

def test_sigkill_mid_polish_resume_byte_identical(tmp_path):
    """The acceptance criterion: kill -9 mid-run, resume, same bytes."""
    paths = _write_dataset(tmp_path)
    baseline = _cli(paths)
    assert baseline.returncode == 0, baseline.stderr.decode()

    jp = str(tmp_path / "run.journal")
    killed = _cli(paths, "--journal", jp,
                  env={"RACON_TPU_FAULT": "journal.append:batch=3:kill=1"})
    assert killed.returncode == -9        # died by SIGKILL, not cleanly
    with open(jp) as f:
        lines = f.read().splitlines()
    assert 1 < len(lines) < 7             # header + a strict subset served

    rp = str(tmp_path / "resume_report.json")
    resumed = _cli(paths, "--resume-journal", jp, "--report", rp)
    assert resumed.returncode == 0, resumed.stderr.decode()
    assert resumed.stdout == baseline.stdout
    with open(rp) as f:
        cons = json.load(f)["phases"]["consensus"]
    assert cons["served"]["journal"] == len(lines) - 1
    assert cons["served"]["journal"] + cons["served"]["host"] == 6


def test_resume_with_torn_last_line(tmp_path):
    paths = _write_dataset(tmp_path)
    baseline = _cli(paths)
    jp = str(tmp_path / "run.journal")
    full = _cli(paths, "--journal", jp)
    assert full.returncode == 0 and full.stdout == baseline.stdout
    size = os.path.getsize(jp)
    with open(jp, "r+b") as f:
        f.truncate(size - 10)             # SIGKILL mid-append simulacrum
    resumed = _cli(paths, "--resume-journal", jp)
    assert resumed.returncode == 0
    assert resumed.stdout == baseline.stdout
    assert b"torn trailing" in resumed.stderr


def test_resume_wrong_params_refused(tmp_path):
    paths = _write_dataset(tmp_path)
    jp = str(tmp_path / "run.journal")
    assert _cli(paths, "--journal", jp).returncode == 0
    r = _cli(paths, "--resume-journal", jp, window=50)
    assert r.returncode == 1
    err = r.stderr.decode()
    assert "refusing to resume" in err
    assert "Traceback" not in err         # single-line contract


# ------------------------------------------ e2e: device-path journal resume

def test_tpu_journal_resume_mixes_tiers(tmp_path, monkeypatch):
    paths = _write_dataset(tmp_path)
    for k, v in {"RACON_TPU_PALLAS": "0",
                 "RACON_TPU_BATCH_WINDOWS": "8"}.items():
        monkeypatch.setenv(k, v)
    jp = str(tmp_path / "run.journal")
    p = racon_tpu.create_polisher(*paths, backend="tpu", journal_path=jp,
                                  **_ARGS)
    p.initialize()
    oracle = p.polish(True)
    assert p.report.as_dict()["phases"]["consensus"]["served"]["xla"] == 6

    # keep header + 3 window records: a run killed mid-batch
    with open(jp) as f:
        lines = f.read().splitlines(keepends=True)
    with open(jp, "w") as f:
        f.writelines(lines[:4])

    p2 = racon_tpu.create_polisher(*paths, backend="tpu", journal_path=jp,
                                   resume_journal=True, **_ARGS)
    p2.initialize()
    assert p2.polish(True) == oracle
    cons = p2.report.as_dict()["phases"]["consensus"]
    assert cons["served"]["journal"] == 3 and cons["served"]["xla"] == 3

    # the resumed journal is now complete: a third run replays everything
    p3 = racon_tpu.create_polisher(*paths, backend="tpu", journal_path=jp,
                                   resume_journal=True, **_ARGS)
    p3.initialize()
    assert p3.polish(True) == oracle
    cons = p3.report.as_dict()["phases"]["consensus"]
    assert cons["served"]["journal"] == 6 and cons["served"]["xla"] == 0


def test_env_knob_arms_autoresume(tmp_path, monkeypatch):
    paths = _write_dataset(tmp_path)
    jp = str(tmp_path / "auto.journal")
    monkeypatch.setenv("RACON_TPU_JOURNAL", jp)
    p = racon_tpu.create_polisher(*paths, backend="cpu", **_ARGS)
    p.initialize()
    oracle = p.polish(True)
    with open(jp) as f:
        assert len(f.read().splitlines()) == 7   # header + 6 windows
    p2 = racon_tpu.create_polisher(*paths, backend="cpu", **_ARGS)
    p2.initialize()
    assert p2.polish(True) == oracle
    cons = p2.report.as_dict()["phases"]["consensus"]
    assert cons["served"]["journal"] == 6 and cons["served"]["host"] == 0


# -------------------------------------------------------------- unit: wedge

def test_wedge_tracker_streaks(monkeypatch):
    monkeypatch.setenv("RACON_TPU_WEDGE_LIMIT", "2")
    t = watchdog.WedgeTracker()
    assert t.record_timeout("xla") == 1 and not t.is_wedged("xla")
    t.record_success("xla")               # slow-but-alive clears the streak
    assert t.streak("xla") == 0
    t.record_timeout("xla")
    t.record_timeout("xla")
    assert t.is_wedged("xla") and not t.is_wedged("ls")
    monkeypatch.setenv("RACON_TPU_WEDGE_LIMIT", "0")
    assert not t.is_wedged("xla")         # 0 disables classification


def test_wedged_tier_short_circuits_lattice(monkeypatch):
    monkeypatch.setenv("RACON_TPU_WEDGE_LIMIT", "2")
    watchdog.reset()
    watchdog.tracker().record_timeout("xla")
    watchdog.tracker().record_timeout("xla")
    calls = []
    with pytest.raises(lattice.TierWedged):
        lattice.serve_with_bisect([1, 2], lambda sub: calls.append(sub),
                                  tier="xla", retries=3)
    assert not calls                      # no deadline burned on a wedge
    watchdog.reset()


def test_wedged_tier_degrades_to_host_e2e(tmp_path, monkeypatch):
    paths = _write_dataset(tmp_path)
    p0 = racon_tpu.create_polisher(*paths, backend="cpu", **_ARGS)
    p0.initialize()
    oracle = p0.polish(True)
    for k, v in {"RACON_TPU_PALLAS": "0",
                 "RACON_TPU_BATCH_WINDOWS": "8",
                 "RACON_TPU_DEVICE_TIMEOUT": "0.3",
                 "RACON_TPU_WEDGE_LIMIT": "2",
                 # invocation 0 (pipelined submit) fails synchronously so
                 # the lattice's retries run under the watchdog; every
                 # later invocation hangs -> two consecutive timeouts ->
                 # wedged -> demote, instead of one deadline per retry
                 "RACON_TPU_FAULT": ("poa.run.xla:batch=0:count=1,"
                                     "poa.run.xla:hang=1")}.items():
        monkeypatch.setenv(k, v)
    p = racon_tpu.create_polisher(*paths, backend="tpu", **_ARGS)
    p.initialize()
    assert p.polish(True) == oracle
    cons = p.report.as_dict()["phases"]["consensus"]
    assert cons["served"]["host"] == 6
    assert any(d["from"] == "xla" and d["to"] == "host"
               for d in cons["degradations"])
    assert "WatchdogTimeout" in json.dumps(cons["causes"])


# --------------------------------------------------------- unit: bulk FFI

def test_align_job_lengths_bulk_matches_loop(tmp_path):
    paths = _write_dataset(tmp_path)
    p = Pipeline(*paths, **_ARGS)
    p.prepare()
    assert p.num_align_jobs() > 0
    bulk = p.align_job_lengths()
    loop = p._align_job_lengths_loop()
    assert bulk.dtype == np.uint32 and bulk.shape == loop.shape
    assert np.array_equal(bulk, loop)
    assert int(bulk[0, 0]) == 200 and int(bulk[0, 1]) == 200


# ------------------------------------------------------ unit: bench honesty

def test_bench_normalize_entry_malformed_partial_summaries():
    """The committed log is hand-editable and spans writer generations:
    backfill must cope with entries missing BOTH phase_wall and report,
    and with report summaries whose tier walls are partial/absent."""
    import bench
    # neither phase_wall nor report: no phase_wall invented, cost_model
    # backfills null
    bare = bench.normalize_entry({"value": 0.01})
    assert "phase_wall" not in bare and bare["cost_model"] is None
    # report present but not a dict / summary rows without wall_s: only
    # the well-formed rows yield a backfilled wall
    assert "phase_wall" not in bench.normalize_entry(
        {"value": 0.01, "report": "corrupt"})
    mixed = bench.normalize_entry({"value": 0.01, "report": {
        "alignment": {"served": {"hirschberg": 5}},     # wall_s absent
        "consensus": {"wall_s": {"ls": 1.5, "host": 0.5}},
        "stitch": {"wall_s": "not-a-dict"},
        "parse": 3.0,                                    # not even a dict
    }})
    assert mixed["phase_wall"] == {"consensus": 2.0}
    # an explicit stamp (even {}) is the writer's claim: never overwritten
    stamped = bench.normalize_entry(
        {"value": 0.01, "phase_wall": {},
         "report": {"consensus": {"wall_s": {"ls": 1.0}}}})
    assert stamped["phase_wall"] == {}
    # an existing cost_model stamp survives untouched
    cm = {"profile": "cpu-host", "phases": {}, "ok": True}
    assert bench.normalize_entry(
        {"value": 0.01, "cost_model": cm})["cost_model"] == cm


# ------------------------------------------- the journal's CIGAR contract

def _paf_job(tmp_path, monkeypatch):
    """Twelve CIGAR-less pairs over three targets, the Hirschberg engine
    pinned (interpreted here) at cohorts of three: the input paths."""
    import random

    from tests.test_align import mutate

    rng = random.Random(17)
    with open(tmp_path / "targets.fasta", "w") as tf, \
            open(tmp_path / "reads.fasta", "w") as rf, \
            open(tmp_path / "ovl.paf", "w") as of:
        # two row buckets, the later one's jobs first in job order; the
        # 300 bp pairs are base cases whole
        for t, n in enumerate((1100, 300, 580)):
            seq = bytes(rng.choice(b"ACGT") for _ in range(n))
            tf.write(f">t{t}\n{seq.decode()}\n")
            for i in range(4):
                read = mutate(seq, 0.06, rng)
                rf.write(f">t{t}r{i}\n{read.decode()}\n")
                of.write(f"t{t}r{i}\t{len(read)}\t0\t{len(read)}\t+\tt{t}\t"
                         f"{n}\t0\t{n}\t{n}\t{n}\t60\n")
    paths = (str(tmp_path / "reads.fasta"), str(tmp_path / "ovl.paf"),
             str(tmp_path / "targets.fasta"))
    for k, v in {"RACON_TPU_PALLAS": "0", "RACON_TPU_BATCH_WINDOWS": "8",
                 "RACON_TPU_DEVICE_ALIGNER": "hirschberg",
                 "RACON_TPU_ALIGN_COHORT": "3"}.items():
        monkeypatch.setenv(k, v)
    return paths


def test_paf_job_journals_one_fsynced_cigar_record_a_pair(tmp_path,
                                                          monkeypatch):
    """The Hirschberg engine installs a cohort's CIGARs from one native
    run-length pass; what the journal is given has not moved: one
    `cigar` record a pair, each its own append with its own flush and
    fsync, in install order (bucket by bucket, cohort by cohort, job by
    job), holding the CIGAR the per-run loop gives for the same ops —
    and a run resumed from it replays every pair and polishes the same
    bytes."""
    from racon_tpu.ops import align_pallas
    from racon_tpu.ops.encoding import encode
    from racon_tpu.resilience import journal as journal_mod
    from tests import hirschberg_oracle as oracle

    paths = _paf_job(tmp_path, monkeypatch)

    events = []
    real_append = journal_mod.Journal.append_cigar

    def append_cigar(self, job, tier, cigar):
        events.append(("cigar", job))
        real_append(self, job, tier, cigar)

    monkeypatch.setattr(journal_mod.Journal, "append_cigar", append_cigar)
    monkeypatch.setattr(journal_mod.os, "fsync",
                        lambda fd: events.append(("fsync",)))

    jp = str(tmp_path / "run.journal")
    p = racon_tpu.create_polisher(*paths, backend="tpu", journal_path=jp,
                                  **_ARGS)
    p.initialize()
    first = p.polish(True)
    assert p.report.as_dict()["phases"]["alignment"]["served"][
        "hirschberg"] == 12

    # what each pair's CIGAR is, from the same ops by the per-run loop
    pipe = Pipeline(*paths, **_ARGS)
    pipe.prepare()
    want, bucket = {}, {}
    for job in range(pipe.num_align_jobs()):
        q, t = (encode(a).astype(np.int32) for a in pipe.align_job(job))
        (ops,) = align_pallas.align_pairs([(q, t)], interpret=True)
        want[job] = oracle.ops_to_cigar(ops)
        half = (len(q) + 1) // 2
        bucket[job] = (align_pallas.band_for(len(q), len(t)),
                       next(rb for rb in align_pallas.ROW_BUCKETS
                            if half <= rb))
    order = sorted(want, key=lambda j: (bucket[j], j))
    assert len(set(bucket.values())) == 2 and len(set(want.values())) > 6

    with open(jp) as f:
        records = [json.loads(line) for line in f]
    cigars = [r for r in records if r["kind"] == "cigar"]
    assert [r["i"] for r in cigars] == order
    assert [r["cigar"] for r in cigars] == [want[j] for j in order]
    assert all(r["tier"] == "hirschberg" for r in cigars)
    # every record its own append, flushed and fsynced before the next
    at = events.index(("cigar", order[0]))
    assert events[at:at + 24] == [e for j in order
                                  for e in (("cigar", j), ("fsync",))]

    p2 = racon_tpu.create_polisher(*paths, backend="tpu", journal_path=jp,
                                   resume_journal=True, **_ARGS)
    p2.initialize()
    assert p2.polish(True) == first
    served = p2.report.as_dict()["phases"]["alignment"]["served"]
    assert served["journal"] == 12 and not served.get("hirschberg")


def test_cigars_journaled_under_a_retired_tier_replay(tmp_path, monkeypatch):
    """A `cigar` record's `tier` is a label (PR 46 retired the `xla`
    aligner; a journal an older run wrote under that name is still a
    journal of this job): the records replay, no pair goes to the
    engine again, and the FASTA is byte-identical."""
    paths = _paf_job(tmp_path, monkeypatch)
    jp = str(tmp_path / "run.journal")
    p = racon_tpu.create_polisher(*paths, backend="tpu", journal_path=jp,
                                  **_ARGS)
    p.initialize()
    first = p.polish(True)

    with open(jp) as f:
        records = [json.loads(line) for line in f]
    cigars = [r for r in records if r["kind"] == "cigar"]
    assert len(cigars) == 12
    for r in cigars:
        r["tier"] = "xla"
    with open(jp, "w") as f:
        f.writelines(json.dumps(r, sort_keys=True) + "\n" for r in records)

    p2 = racon_tpu.create_polisher(*paths, backend="tpu", journal_path=jp,
                                   resume_journal=True, **_ARGS)
    p2.initialize()
    assert p2.polish(True) == first
    served = p2.report.as_dict()["phases"]["alignment"]["served"]
    assert served["journal"] == 12
    assert not served.get("hirschberg") and not served["host"]
