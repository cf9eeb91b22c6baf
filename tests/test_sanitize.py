"""Runtime invariant sanitizer (racon_tpu/analysis/sanitize.py).

Contracts:
* armed runs are byte-identical to unarmed runs (the sanitizer observes,
  never alters) and a clean tree produces zero findings;
* each detector fires on its injected fault (`sanitize.nan`,
  `sanitize.stats`) with the polished output still untouched;
* the kernel-cache hook keys on device topology (fresh kernel on a
  topology change, stale entries never served);
* `--sanitize-report` renders report JSON with lint-style exit codes.
"""

import json
import random
import threading

import numpy as np
import pytest

import racon_tpu
from racon_tpu.analysis import sanitize
from racon_tpu.analysis.__main__ import main as analysis_main


@pytest.fixture(autouse=True)
def _fresh_findings():
    sanitize.reset()
    yield
    sanitize.reset()


# ------------------------------------------------------------- unit: records

def test_record_dedup_and_cap():
    for _ in range(3):
        sanitize.record("nonfinite", "k[out 0]", "nan")
    fs = sanitize.findings()
    assert len(fs) == 1 and fs[0].count == 3
    for i in range(2 * sanitize._MAX_FINDINGS):
        sanitize.record("parity", f"w{i}", "d")
    assert len(sanitize.findings()) <= sanitize._MAX_FINDINGS + 1
    sanitize.reset()
    assert sanitize.findings() == []


def test_enabled_follows_knob(monkeypatch):
    assert not sanitize.enabled()
    monkeypatch.setenv("RACON_TPU_SANITIZE", "1")
    assert sanitize.enabled()


# ------------------------------------------------------- unit: kernel proxy

def test_wrap_kernel_flags_nonfinite_output():
    def kernel(x):
        return (np.array([1.0, np.nan], dtype=np.float32),
                np.array([3], dtype=np.int32))

    proxied = sanitize.wrap_kernel("build_fake", kernel)
    out = proxied(None)
    assert np.isnan(out[0][1])  # output passes through unchanged
    assert [f.kind for f in sanitize.findings()] == ["nonfinite"]
    assert "build_fake" in sanitize.findings()[0].where


def test_wrap_kernel_transitively_wraps_factories():
    def factory():
        return lambda: np.array([np.inf], dtype=np.float32)

    proxied = sanitize.wrap_kernel("build_factory", factory)
    proxied()()
    assert [f.kind for f in sanitize.findings()] == ["nonfinite"]


def test_wrap_kernel_clean_outputs_record_nothing():
    def kernel():
        return (np.zeros(4, dtype=np.float32), np.zeros(4, dtype=np.uint8))

    sanitize.wrap_kernel("build_ok", kernel)()
    assert sanitize.findings() == []


# ------------------------------------------------------ unit: seam checkers

def test_check_consensus_outputs_flags_bad_rows():
    cons_base = np.array([[0, 1, 2, 3], [0, 9, 0, 0]], dtype=np.int32)
    cons_cov = np.ones_like(cons_base)
    cons_len = np.array([4, 3], dtype=np.int32)
    failed = np.array([0, 0], dtype=np.int32)
    sanitize.check_consensus_outputs(
        (cons_base, cons_cov, cons_len, failed), [0, 1], "t")
    kinds = [f.kind for f in sanitize.findings()]
    assert kinds == ["consensus-range"]  # base code 9 on row 1

    sanitize.reset()
    sanitize.check_consensus_outputs(
        (cons_base, cons_cov, np.array([4, 99]), failed), [0, 1], "t")
    assert any("cons_len" in f.detail for f in sanitize.findings())

    sanitize.reset()
    sanitize.check_consensus_outputs(
        (cons_base[:1], cons_cov[:1], cons_len[:1], failed[:1]), [0], "t")
    assert sanitize.findings() == []


def test_check_consensus_nan_fault_poisons_copy_only(monkeypatch):
    monkeypatch.setenv("RACON_TPU_FAULT", "sanitize.nan")
    from racon_tpu.resilience import faults
    faults.reset()
    cons_base = np.zeros((1, 4), dtype=np.int32)
    sanitize.check_consensus_outputs(
        (cons_base, cons_base, np.array([4]), np.array([0])), [0], "t")
    assert [f.kind for f in sanitize.findings()] == ["nonfinite"]
    assert (cons_base == 0).all()  # the driver's array is untouched


def test_check_parity():
    sanitize.check_parity(b"ACGT", b"ACGT", 0, "t")
    sanitize.check_parity("ACGT", b"ACGT", 1, "t")
    assert sanitize.findings() == []
    sanitize.check_parity(b"ACGT", b"ACGA", 2, "t")
    assert [f.kind for f in sanitize.findings()] == ["parity"]


def test_parity_stride_parses_and_gates(monkeypatch):
    monkeypatch.setenv("RACON_TPU_SANITIZE_PARITY", "4")
    assert sanitize.parity_stride() == 4
    assert sanitize.parity_due(8) and not sanitize.parity_due(9)
    monkeypatch.setenv("RACON_TPU_SANITIZE_PARITY", "0")
    assert not sanitize.parity_due(0)
    monkeypatch.setenv("RACON_TPU_SANITIZE_PARITY", "bogus")
    assert sanitize.parity_stride() == 0


# ------------------------------------------------------- unit: stats guard

def test_guarded_stats_flags_cross_thread_writes():
    g = sanitize.GuardedStats({"device": 0}, "t")
    g["device"] = 1          # owner thread: fine
    assert sanitize.findings() == []
    t = threading.Thread(target=g.__setitem__, args=("device", 2))
    t.start()
    t.join()
    assert g["device"] == 2  # the write itself is never blocked
    assert [f.kind for f in sanitize.findings()] == ["racy-stats"]


def test_guard_stats_passthrough_when_disarmed():
    d = {"x": 1}
    assert sanitize.guard_stats(d, "t") is d


def test_guard_stats_wraps_when_armed(monkeypatch):
    monkeypatch.setenv("RACON_TPU_SANITIZE", "1")
    g = sanitize.guard_stats({"x": 1}, "t")
    assert isinstance(g, sanitize.GuardedStats) and g["x"] == 1


# --------------------------------------------- kernel cache: topology keyed

def test_device_keyed_cache_topology_change_builds_fresh(monkeypatch):
    import jax

    from racon_tpu.ops.kernel_cache import device_keyed_cache

    builds = []

    @device_keyed_cache(maxsize=8)
    def build(cap):
        builds.append(cap)
        return object()  # unique sentinel per build

    class Dev:
        def __init__(self, platform):
            self.platform = platform

    monkeypatch.setattr(jax, "devices", lambda: [Dev("cpu")] * 8)
    k8 = build(100)
    assert build(100) is k8 and builds == [100]

    # fewer devices: a fresh kernel, never the stale 8-device one
    monkeypatch.setattr(jax, "devices", lambda: [Dev("cpu")] * 4)
    k4 = build(100)
    assert k4 is not k8 and len(builds) == 2

    # platform change at the same count: fresh again
    monkeypatch.setattr(jax, "devices", lambda: [Dev("tpu")] * 4)
    kt = build(100)
    assert kt is not k4 and kt is not k8 and len(builds) == 3

    # returning to the original topology serves its cached entry
    monkeypatch.setattr(jax, "devices", lambda: [Dev("cpu")] * 8)
    assert build(100) is k8 and len(builds) == 3


def test_device_keyed_cache_returns_proxy_when_armed(monkeypatch):
    import jax

    from racon_tpu.ops.kernel_cache import device_keyed_cache

    @device_keyed_cache(maxsize=4)
    def build():
        return lambda: np.array([np.nan], dtype=np.float32)

    class Dev:
        platform = "cpu"

    monkeypatch.setattr(jax, "devices", lambda: [Dev()])
    monkeypatch.setenv("RACON_TPU_SANITIZE", "1")
    build()()
    assert [f.kind for f in sanitize.findings()] == ["nonfinite"]


# ----------------------------------------------------------- e2e: polishing

def _write_dataset(tmp_path, n_targets=3, n_reads=4):
    """Identical-read SAM dataset (as in test_faults): every window's
    consensus is exactly the target, so host and device recomputes agree
    and byte-identity is checkable against the CPU oracle."""
    rng = random.Random(11)
    with open(tmp_path / "targets.fasta", "w") as tf, \
            open(tmp_path / "reads.fasta", "w") as rf, \
            open(tmp_path / "ovl.sam", "w") as of:
        of.write("@HD\tVN:1.6\n")
        for t in range(n_targets):
            seq = "".join(rng.choice("ACGT") for _ in range(200))
            tf.write(f">t{t}\n{seq}\n")
            for i in range(n_reads):
                rf.write(f">t{t}r{i}\n{seq}\n")
                of.write(f"t{t}r{i}\t0\tt{t}\t1\t60\t200M\t*\t0\t0\t"
                         f"{seq}\t*\n")
    return (str(tmp_path / "reads.fasta"), str(tmp_path / "ovl.sam"),
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


def test_armed_run_byte_identical_and_clean(tmp_path, monkeypatch):
    paths = _write_dataset(tmp_path)
    oracle = _oracle(paths)
    res, p = _tpu_run(paths, monkeypatch, {"RACON_TPU_SANITIZE": "1"})
    assert res == oracle
    section = p.report.as_dict()["sanitize"]
    assert section["armed"] is True
    assert section["findings"] == []


def test_armed_run_parity_every_window(tmp_path, monkeypatch):
    paths = _write_dataset(tmp_path)
    oracle = _oracle(paths)
    res, p = _tpu_run(paths, monkeypatch,
                      {"RACON_TPU_SANITIZE": "1",
                       "RACON_TPU_SANITIZE_PARITY": "1"})
    assert res == oracle
    assert p.report.as_dict()["sanitize"]["findings"] == []


def test_unarmed_report_says_disarmed(tmp_path, monkeypatch):
    paths = _write_dataset(tmp_path, n_targets=1, n_reads=2)
    _, p = _tpu_run(paths, monkeypatch, {})
    section = p.report.as_dict()["sanitize"]
    assert section["armed"] is False and section["findings"] == []


def test_nan_fault_caught_output_untouched(tmp_path, monkeypatch):
    paths = _write_dataset(tmp_path)
    oracle = _oracle(paths)
    res, p = _tpu_run(paths, monkeypatch,
                      {"RACON_TPU_SANITIZE": "1",
                       "RACON_TPU_FAULT": "sanitize.nan"})
    assert res == oracle  # detector-only poisoning, polish unaffected
    kinds = {f["kind"] for f in p.report.as_dict()["sanitize"]["findings"]}
    assert "nonfinite" in kinds


def test_stats_fault_caught(tmp_path, monkeypatch):
    paths = _write_dataset(tmp_path)
    oracle = _oracle(paths)
    res, p = _tpu_run(paths, monkeypatch,
                      {"RACON_TPU_SANITIZE": "1",
                       "RACON_TPU_FAULT": "sanitize.stats"})
    assert res == oracle
    kinds = {f["kind"] for f in p.report.as_dict()["sanitize"]["findings"]}
    assert "racy-stats" in kinds


# -------------------------------------------------- CLI: --sanitize-report

def _report_json(tmp_path, findings, armed=True):
    path = tmp_path / "report.json"
    path.write_text(json.dumps(
        {"sanitize": {"armed": armed, "findings": findings}}))
    return str(path)


def test_cli_sanitize_report_clean(tmp_path, capsys):
    rc = analysis_main(["--sanitize-report", _report_json(tmp_path, [])])
    assert rc == 0
    assert "SANITIZE OK" in capsys.readouterr().out


def test_cli_sanitize_report_findings_fail(tmp_path, capsys):
    rc = analysis_main(["--sanitize-report", _report_json(tmp_path, [
        {"kind": "parity", "where": "poa._install[xla]",
         "detail": "window 8: device != host", "count": 2}])])
    assert rc == 1
    out = capsys.readouterr().out
    assert "SANITIZE FAIL" in out and "parity" in out and "x2" in out


def test_cli_sanitize_report_json_mode(tmp_path, capsys):
    rc = analysis_main(["--json", "--sanitize-report",
                        _report_json(tmp_path, [])])
    assert rc == 0
    assert json.loads(capsys.readouterr().out) == {"armed": True,
                                                   "findings": []}


def test_cli_sanitize_report_unreadable_or_legacy(tmp_path):
    assert analysis_main(["--sanitize-report",
                          str(tmp_path / "missing.json")]) == 2
    legacy = tmp_path / "legacy.json"
    legacy.write_text("{}")
    assert analysis_main(["--sanitize-report", str(legacy)]) == 2
