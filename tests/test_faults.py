"""Resilience layer: every degradation-lattice edge driven deterministically
via RACON_TPU_FAULT on the CPU backend, asserting (a) the polished output
stays byte-identical to the CpuPolisher oracle under each fault and (b) the
run report's per-tier served counts sum to the total job/window count.

Edges covered here: xla -> host (tier death), bisect-quarantine (poisoned
window), transient retry, watchdog timeout, window-export quarantine,
hirschberg -> host (engine death mid-phase, served count preserved —
ADVICE.md), and — in a bounded single-device subprocess, where the pallas
kernel builds unsharded — ls -> xla.
"""

import json
import os
import random
import subprocess
import sys
import time

import pytest

import racon_tpu
from racon_tpu.resilience import faults, lattice, report

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------- unit: spec

def test_parse_spec_valid():
    specs = faults.parse_spec(
        "poa.run.ls:batch=2:raise=MosaicError, align.run:window=5:count=1,"
        "poa.run.xla:hang=0.5")
    assert [s.point for s in specs] == ["poa.run.ls", "align.run",
                                       "poa.run.xla"]
    assert specs[0].batch == 2 and specs[0].raise_name == "MosaicError"
    assert specs[1].window == 5 and specs[1].count == 1
    assert specs[2].hang == 0.5


@pytest.mark.parametrize("bad", [
    "bogus.point",
    "poa.run.ls:frobnicate=1",
    "poa.run.ls:batch=x",
    "poa.run.ls:raise=NoSuchError",
    "poa.run.ls:batch",
])
def test_parse_spec_malformed(bad):
    with pytest.raises(ValueError) as ei:
        faults.parse_spec(bad)
    msg = str(ei.value)
    assert msg.startswith("RACON_TPU_FAULT") and "\n" not in msg


def test_check_fires_and_counts(monkeypatch):
    monkeypatch.setenv("RACON_TPU_FAULT", "poa.run.ls:batch=1:count=1")
    faults.reset()
    faults.check("poa.run.ls")                     # invocation 0: no fire
    with pytest.raises(faults.MosaicError):
        faults.check("poa.run.ls")                 # invocation 1: fires
    faults.check("poa.run.ls")                     # spent
    faults.reset()                                 # fresh schedule
    faults.check("poa.run.ls")
    with pytest.raises(faults.MosaicError):
        faults.check("poa.run.ls")


# ------------------------------------------------------------- unit: lattice

def test_watchdog_passthrough_and_timeout():
    assert lattice.call_with_watchdog(lambda: 42) == 42
    assert lattice.call_with_watchdog(lambda: 42, timeout=5) == 42
    with pytest.raises(ValueError):
        lattice.call_with_watchdog(lambda: (_ for _ in ()).throw(
            ValueError("boom")), timeout=5)
    t0 = time.perf_counter()
    with pytest.raises(lattice.WatchdogTimeout):
        lattice.call_with_watchdog(lambda: time.sleep(2), timeout=0.2)
    assert time.perf_counter() - t0 < 1.5


def test_serve_with_bisect_retry_then_success():
    calls = []

    def attempt(sub):
        calls.append(list(sub))
        if len(calls) == 1:
            raise RuntimeError("transient")
        return sum(sub)

    rep = report.PhaseReport("t", ("x",))
    pairs, quarantined = lattice.serve_with_bisect(
        [1, 2, 3], attempt, tier="x", report=rep, retries=1)
    assert pairs == [([1, 2, 3], 6)] and quarantined == []
    assert rep.retries == 1 and rep.bisections == 0


def test_serve_with_bisect_quarantines_poisoned_item():
    def attempt(sub):
        if 3 in sub:
            raise RuntimeError("poisoned")
        return list(sub)

    rep = report.PhaseReport("t", ("x",))
    pairs, quarantined = lattice.serve_with_bisect(
        [1, 2, 3, 4], attempt, tier="x", report=rep, retries=0)
    served = [i for sub, _ in pairs for i in sub]
    assert sorted(served) == [1, 2, 4]
    assert [i for i, _ in quarantined] == [3]
    assert rep.bisections >= 1


def test_serve_with_bisect_tier_dead_when_all_fail():
    def attempt(sub):
        raise RuntimeError("dead tier")

    with pytest.raises(lattice.TierDead):
        lattice.serve_with_bisect([1, 2, 3, 4], attempt, tier="x",
                                  retries=0)


def test_serve_with_bisect_cached_first():
    attempts = []

    def attempt(sub):
        attempts.append(list(sub))
        return "fresh"

    pairs, quarantined = lattice.serve_with_bisect(
        [1, 2], attempt, tier="x", retries=0, cached=lambda: "cached")
    assert pairs == [([1, 2], "cached")] and not attempts


# ------------------------------------------------------------ e2e fixtures

def _write_dataset(tmp_path, overlaps="sam", n_targets=3, n_reads=4):
    """Identical-read dataset: device- and host-served consensus are both
    exactly the target sequence, so polished output is byte-comparable to
    the CpuPolisher oracle under any serving mix."""
    rng = random.Random(11)
    targets = []
    with open(tmp_path / "targets.fasta", "w") as tf, \
            open(tmp_path / "reads.fasta", "w") as rf, \
            open(tmp_path / ("ovl.sam" if overlaps == "sam" else "ovl.paf"),
                 "w") as of:
        if overlaps == "sam":
            of.write("@HD\tVN:1.6\n")
        for t in range(n_targets):
            seq = "".join(rng.choice("ACGT") for _ in range(200))
            targets.append(seq)
            tf.write(f">t{t}\n{seq}\n")
            for i in range(n_reads):
                rf.write(f">t{t}r{i}\n{seq}\n")
                if overlaps == "sam":
                    of.write(f"t{t}r{i}\t0\tt{t}\t1\t60\t200M\t*\t0\t0\t"
                             f"{seq}\t*\n")
                else:
                    of.write(f"t{t}r{i}\t200\t0\t200\t+\tt{t}\t200\t0\t200"
                             f"\t200\t200\t60\n")
    ovl = str(tmp_path / ("ovl.sam" if overlaps == "sam" else "ovl.paf"))
    return (str(tmp_path / "reads.fasta"), ovl,
            str(tmp_path / "targets.fasta"))


_ARGS = dict(window_length=100, quality_threshold=10, error_threshold=0.3,
             match=5, mismatch=-4, gap=-8, num_threads=1)


def _oracle(paths):
    p = racon_tpu.create_polisher(*paths, backend="cpu", **_ARGS)
    p.initialize()
    return p.polish(True)


def _tpu_run(paths, monkeypatch, env):
    base = {"RACON_TPU_PALLAS": "0", "RACON_TPU_BATCH_WINDOWS": "8"}
    for k, v in {**base, **env}.items():
        monkeypatch.setenv(k, v)
    p = racon_tpu.create_polisher(*paths, backend="tpu", **_ARGS)
    p.initialize()
    res = p.polish(True)
    return res, p


def _assert_report_sums(p):
    d = p.report.as_dict()
    assert d["phases"], "run produced no phase reports"
    for phase in d["phases"].values():
        assert sum(phase["served"].values()) == phase["total"], phase
    json.dumps(d)  # must be JSON-serializable end to end
    return d


# -------------------------------------------------- e2e: consensus lattice

def test_clean_run_report_sums(tmp_path, monkeypatch):
    paths = _write_dataset(tmp_path)
    oracle = _oracle(paths)
    res, p = _tpu_run(paths, monkeypatch, {})
    assert res == oracle
    d = _assert_report_sums(p)
    cons = d["phases"]["consensus"]
    assert cons["served"]["xla"] == 6          # 3 targets x 2 windows
    assert cons["served"]["host"] == 0
    assert cons["retries"] == 0 and cons["quarantined"] == []
    assert d["fault_spec"] == ""


def test_xla_tier_death_degrades_to_host(tmp_path, monkeypatch):
    paths = _write_dataset(tmp_path)
    oracle = _oracle(paths)
    res, p = _tpu_run(paths, monkeypatch, {"RACON_TPU_FAULT": "poa.run.xla"})
    assert res == oracle
    d = _assert_report_sums(p)
    cons = d["phases"]["consensus"]
    assert cons["served"]["host"] == 6 and cons["served"]["xla"] == 0
    assert any(dg["from"] == "xla" and dg["to"] == "host"
               for dg in cons["degradations"])
    assert "MosaicError" in json.dumps(cons["causes"])


def test_poisoned_window_bisected_and_quarantined(tmp_path, monkeypatch):
    paths = _write_dataset(tmp_path)
    oracle = _oracle(paths)
    res, p = _tpu_run(paths, monkeypatch,
                      {"RACON_TPU_FAULT": "poa.run.xla:window=2"})
    assert res == oracle
    d = _assert_report_sums(p)
    cons = d["phases"]["consensus"]
    # only the poisoned window reaches the host; the rest stay on device
    assert cons["quarantined"] == [2]
    assert cons["served"]["host"] == 1 and cons["served"]["xla"] == 5
    assert cons["bisections"] >= 1
    assert not cons["degradations"]


def test_transient_fault_retried_at_tier(tmp_path, monkeypatch):
    paths = _write_dataset(tmp_path)
    oracle = _oracle(paths)
    res, p = _tpu_run(paths, monkeypatch,
                      {"RACON_TPU_FAULT": "poa.run.xla:batch=0:count=1"})
    assert res == oracle
    d = _assert_report_sums(p)
    cons = d["phases"]["consensus"]
    assert cons["served"]["xla"] == 6 and cons["served"]["host"] == 0
    assert cons["retries"] >= 1
    assert not cons["degradations"] and cons["quarantined"] == []


def test_hung_device_call_hits_watchdog(tmp_path, monkeypatch):
    paths = _write_dataset(tmp_path)
    oracle = _oracle(paths)
    # The deadline must separate a hang from a healthy call, and a first
    # call that compiles outlasts any deadline short enough for a test
    # (four such "timeouts" in a row declare the tier wedged).  So: one
    # device (a timeout on a sharded batch rebuilds the kernel for
    # single-device dispatch — a second cold compile), the kernel
    # compiled by a clean run first, and a deadline ~8x the warm call.
    _tpu_run(paths, monkeypatch, {"RACON_TPU_SHARD": "0"})
    res, p = _tpu_run(paths, monkeypatch, {
        # invocation 0 (pipelined submit) fails synchronously; invocation 1
        # (the lattice's retry attempt) hangs and trips the watchdog;
        # invocation 2 succeeds — all windows still served on device
        "RACON_TPU_FAULT": ("poa.run.xla:batch=0:count=1,"
                            "poa.run.xla:batch=1:count=1:hang=3"),
        "RACON_TPU_DEVICE_TIMEOUT": "1.0",
        "RACON_TPU_SHARD": "0",
    })
    assert res == oracle
    d = _assert_report_sums(p)
    cons = d["phases"]["consensus"]
    assert cons["served"]["xla"] == 6
    assert "WatchdogTimeout" in json.dumps(cons["causes"])


def test_window_export_failure_quarantined(tmp_path, monkeypatch):
    paths = _write_dataset(tmp_path)
    oracle = _oracle(paths)
    res, p = _tpu_run(paths, monkeypatch,
                      {"RACON_TPU_FAULT": "window.export:window=1"})
    assert res == oracle
    d = _assert_report_sums(p)
    cons = d["phases"]["consensus"]
    assert cons["quarantined"] == [1]
    assert cons["served"]["host"] == 1 and cons["served"]["xla"] == 5


# -------------------------------------------------- e2e: alignment lattice

def test_hirschberg_engine_death_preserves_served_count(tmp_path,
                                                        monkeypatch):
    """The ADVICE.md regression: the engine dies after the first cohort,
    and the phase stats must still report that cohort as device-served
    (the old driver reported device=0, host=n)."""
    paths = _write_dataset(tmp_path, overlaps="paf", n_reads=2)
    oracle = _oracle(paths)
    kill = ",".join(f"align.run:batch={i}" for i in range(1, 12))
    res, p = _tpu_run(paths, monkeypatch, {
        "RACON_TPU_DEVICE_ALIGNER": "hirschberg",
        "RACON_TPU_ALIGN_COHORT": "2",
        "RACON_TPU_FAULT": kill,
    })
    assert res == oracle
    d = _assert_report_sums(p)
    al = d["phases"]["alignment"]
    assert al["total"] == 6                      # 3 targets x 2 reads
    # cohort 0 (2 jobs) was served before the engine died mid-phase
    assert al["served"]["hirschberg"] == 2
    assert al["served"]["host"] == 4
    assert any(dg["from"] == "hirschberg" and dg["to"] == "host"
               for dg in al["degradations"])


def test_alignment_poisoned_job_quarantined(tmp_path, monkeypatch):
    paths = _write_dataset(tmp_path, overlaps="paf", n_reads=2)
    oracle = _oracle(paths)
    res, p = _tpu_run(paths, monkeypatch, {
        "RACON_TPU_DEVICE_ALIGNER": "hirschberg",
        "RACON_TPU_ALIGN_COHORT": "4",
        "RACON_TPU_FAULT": "align.run:window=3",
    })
    assert res == oracle
    d = _assert_report_sums(p)
    al = d["phases"]["alignment"]
    assert 3 in al["quarantined"]
    assert al["served"]["hirschberg"] == 5 and al["served"]["host"] == 1
    assert al["bisections"] >= 1
    assert al["extra"]["kernel_wall_s"] > 0      # the executor's wall split


def test_alignment_report_names_live_tiers_only(tmp_path, monkeypatch):
    """One device aligner since PR 46: the lattice's alignment tiers are
    the engine and the host, and a clean PAF run's report carries those
    and the journal's replay count, nothing of the tier that went."""
    assert lattice.ALIGN_TIERS == ("hirschberg", "host")
    paths = _write_dataset(tmp_path, overlaps="paf", n_reads=2)
    res, p = _tpu_run(paths, monkeypatch, {
        "RACON_TPU_DEVICE_ALIGNER": "hirschberg"})
    assert res == _oracle(paths)
    al = _assert_report_sums(p)["phases"]["alignment"]
    assert set(al["served"]) == {"hirschberg", "host", "journal"}
    assert al["served"]["hirschberg"] == al["total"] == 6
    assert not al["degradations"]


def test_align_compile_fault_degrades_to_host(tmp_path, monkeypatch):
    paths = _write_dataset(tmp_path, overlaps="paf", n_reads=2)
    oracle = _oracle(paths)
    res, p = _tpu_run(paths, monkeypatch, {
        "RACON_TPU_DEVICE_ALIGNER": "hirschberg",
        "RACON_TPU_FAULT": "align.compile",
    })
    assert res == oracle
    d = _assert_report_sums(p)
    al = d["phases"]["alignment"]
    assert al["served"]["host"] == 6 and al["served"]["hirschberg"] == 0


def test_poa_compile_fault_degrades_to_host(tmp_path, monkeypatch):
    """poa.compile.xla: the XLA-twin kernel *build* dies (compile seam,
    not the run seam); consensus must degrade xla -> host with output
    still matching the oracle."""
    paths = _write_dataset(tmp_path)
    oracle = _oracle(paths)
    res, p = _tpu_run(paths, monkeypatch,
                      {"RACON_TPU_FAULT": "poa.compile.xla"})
    assert res == oracle
    d = _assert_report_sums(p)
    cons = d["phases"]["consensus"]
    assert cons["served"]["host"] == 6 and cons["served"]["xla"] == 0
    assert any(dg["from"] == "xla" and dg["to"] == "host"
               for dg in cons["degradations"])


def test_native_call_fault_surfaces(tmp_path, monkeypatch):
    """native.call: the host (native) engine is the lattice floor — an
    injected fault there has nowhere to degrade to and must surface as
    the injected exception, not as silent corruption."""
    paths = _write_dataset(tmp_path)
    monkeypatch.setenv("RACON_TPU_FAULT", "native.call:count=1")
    p = racon_tpu.create_polisher(*paths, backend="cpu", **_ARGS)
    p.initialize()
    with pytest.raises(faults.InjectedFault):
        p.polish(True)


# ------------------------------------- pallas tiers (single-device subproc)

def test_pallas_chain_ls_xla(tmp_path):
    """ls -> xla, in a single-device subprocess (one lockstep group, not
    the eight the in-process 8-virtual-device mesh would interpret).
    The pallas run point is killed; the chunk must degrade to the XLA
    twin and the output must match the host oracle."""
    paths = _write_dataset(tmp_path)
    code = f"""
import sys
sys.path.insert(0, {ROOT!r})
from __graft_entry__ import _force_cpu; _force_cpu(1)
import json
import racon_tpu

args = dict(window_length=100, quality_threshold=10, error_threshold=0.3,
            match=5, mismatch=-4, gap=-8, num_threads=1)
paths = {paths!r}
p0 = racon_tpu.create_polisher(*paths, backend="cpu", **args)
p0.initialize()
oracle = p0.polish(True)

import os
os.environ["RACON_TPU_PALLAS"] = "1"
os.environ["RACON_TPU_BATCH_WINDOWS"] = "8"
os.environ["RACON_TPU_FAULT"] = "poa.run.ls"
p = racon_tpu.create_polisher(*paths, backend="tpu", **args)
p.initialize()
res = p.polish(True)
assert res == oracle, "faulted output diverged from the host oracle"
d = p.report.as_dict()
cons = d["phases"]["consensus"]
assert sum(cons["served"].values()) == cons["total"], cons
edges = {{(dg["from"], dg["to"]) for dg in cons["degradations"]}}
assert ("ls", "xla") in edges, edges
assert cons["served"]["xla"] == cons["total"], cons
print("PALLAS-CHAIN-OK", json.dumps(cons["served"]))
"""
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=570)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "PALLAS-CHAIN-OK" in r.stdout


def test_pallas_compile_faults_chain_to_xla(tmp_path):
    """poa.compile.ls: the pallas kernel *build* is killed at the
    compile seam; the chunk must degrade ls -> xla and the output must
    match the host oracle (compile-seam twin of the run-seam chain
    above)."""
    paths = _write_dataset(tmp_path)
    code = f"""
import sys
sys.path.insert(0, {ROOT!r})
from __graft_entry__ import _force_cpu; _force_cpu(1)
import json
import racon_tpu

args = dict(window_length=100, quality_threshold=10, error_threshold=0.3,
            match=5, mismatch=-4, gap=-8, num_threads=1)
paths = {paths!r}
p0 = racon_tpu.create_polisher(*paths, backend="cpu", **args)
p0.initialize()
oracle = p0.polish(True)

import os
os.environ["RACON_TPU_PALLAS"] = "1"
os.environ["RACON_TPU_BATCH_WINDOWS"] = "8"
os.environ["RACON_TPU_FAULT"] = "poa.compile.ls"
p = racon_tpu.create_polisher(*paths, backend="tpu", **args)
p.initialize()
res = p.polish(True)
assert res == oracle, "faulted output diverged from the host oracle"
d = p.report.as_dict()
cons = d["phases"]["consensus"]
assert sum(cons["served"].values()) == cons["total"], cons
edges = {{(dg["from"], dg["to"]) for dg in cons["degradations"]}}
assert ("ls", "xla") in edges, edges
assert cons["served"]["xla"] == cons["total"], cons
print("COMPILE-CHAIN-OK", json.dumps(cons["served"]))
"""
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=570)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "COMPILE-CHAIN-OK" in r.stdout


def test_polish_byte_identical_under_fault_demotion(tmp_path, monkeypatch):
    """End-to-end polish with the lockstep kernel serving (interpreted,
    one device), one window poisoned via RACON_TPU_FAULT: it is
    bisected out and quarantined to the host, the rest stay on the
    kernel, and the polished output stays byte-identical to the CPU
    oracle."""
    paths = _write_dataset(tmp_path)
    oracle = _oracle(paths)
    res, p = _tpu_run(paths, monkeypatch, {
        "RACON_TPU_PALLAS": "1", "RACON_TPU_SHARD": "0",
        "RACON_TPU_FAULT": "poa.run.ls:window=2",
    })
    assert res == oracle
    d = _assert_report_sums(p)
    cons = d["phases"]["consensus"]
    assert cons["served"]["ls"] == 5 and cons["served"]["host"] == 1
    assert cons["quarantined"] == [2]
