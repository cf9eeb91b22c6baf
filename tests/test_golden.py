"""Golden end-to-end accuracy tests on the lambda-phage dataset — the same
strategy as the reference suite (/root/reference/test/racon_test.cpp:86-295):
run the full pipeline, pin the exact edit distance of the polished contig
(reverse-complemented) against NC_001416, pin output counts/lengths for
fragment correction.

Our pinned numbers sit next to the reference's for comparison (this
framework's POA/aligner are new implementations, so the numbers differ the
way the reference's own CUDA numbers differ from its CPU numbers):

  scenario                      ours   reference-CPU  reference-GPU
  PAF + qualities               1283   1312           1385
  PAF no qualities              1443   1566           1607
  SAM + qualities               1315   1317           1541
  SAM no qualities              1769   1770           1661
  PAF + qualities, w=1000       1304   1289           4168
  PAF + qualities, unit scores  1338   1321           1361
  fragment kC count/bp          40/401215   40/401246
  fragment kF PAF count/bp      236/1657837 236/1658216
  fragment kF FASTA count/bp    236/1662904 236/1663982
  fragment kF MHAP count/bp     236/1657837 236/1658216

4 of 6 polish scenarios are at-or-better than the reference CPU; the two
worse (w=1000, unit scores) are within 1.3%. The load-bearing semantic:
layer add-order uses unstable std::sort on begin position, mirroring the
reference's sort call (see rt_window.cpp). Like the reference's pins, the
exact values encode the standard library's deterministic-but-unspecified
equal-key permutation (libstdc++ here).

Slow scenarios (host global alignment of every all-vs-all overlap on this
1-core box) are gated behind RACON_TPU_FULL_GOLDEN=1.
"""

import os

import pytest

import racon_tpu
from racon_tpu import native
from racon_tpu.tools import golden_scenarios as gs
from tests.conftest import DATA, revcomp, requires_data

FULL = os.environ.get("RACON_TPU_FULL_GOLDEN") == "1"
HW = os.environ.get("RACON_TPU_HW_TESTS") == "1"

ARGS = gs.ARGS  # single source: the args the pinned numbers are defined by


pytestmark = requires_data

def polish(seqs, ovl, tgt, backend="cpu", drop=True, **kw):
    a = dict(ARGS)
    a.update(kw)
    p = racon_tpu.create_polisher(DATA + seqs, DATA + ovl, DATA + tgt,
                                  backend=backend, **a)
    p.initialize()
    return p.polish(drop)


def run_scenario(name, backend="cpu"):
    """Run one golden_scenarios entry; returns the polish result list."""
    if name in gs.POLISH:
        reads, ovl, tgt, extra = gs.POLISH[name]
    else:
        reads, ovl, tgt, extra = gs.FRAGMENT[name]
    extra = dict(extra)
    drop = extra.pop("drop", True)
    return polish(reads, ovl, tgt, backend=backend, drop=drop, **extra)


def ed_vs_reference(res, lambda_reference):
    assert len(res) == 1
    return native.edit_distance(revcomp(res[0][1].encode()), lambda_reference)


def test_consensus_sam_with_qualities(lambda_reference):
    res = run_scenario("sam")
    assert ed_vs_reference(res, lambda_reference) == \
        gs.HOST_POLISH["sam"]  # reference: 1317


def test_consensus_sam_without_qualities(lambda_reference):
    res = run_scenario("sam_noq")
    assert ed_vs_reference(res, lambda_reference) == \
        gs.HOST_POLISH["sam_noq"]  # reference: 1770


def test_consensus_paf_with_qualities(lambda_reference):
    res = run_scenario("paf")
    assert ed_vs_reference(res, lambda_reference) == \
        gs.HOST_POLISH["paf"]  # reference: 1312


@pytest.mark.skipif(not FULL, reason="slow on 1-core host; "
                    "set RACON_TPU_FULL_GOLDEN=1")
def test_consensus_paf_without_qualities(lambda_reference):
    res = run_scenario("paf_noq")
    assert ed_vs_reference(res, lambda_reference) == \
        gs.HOST_POLISH["paf_noq"]  # reference: 1566


@pytest.mark.skipif(not FULL, reason="slow on 1-core host; "
                    "set RACON_TPU_FULL_GOLDEN=1")
def test_consensus_paf_larger_window(lambda_reference):
    res = run_scenario("paf_w1000")
    assert ed_vs_reference(res, lambda_reference) == \
        gs.HOST_POLISH["paf_w1000"]  # reference: 1289


@pytest.mark.skipif(not FULL, reason="slow on 1-core host; "
                    "set RACON_TPU_FULL_GOLDEN=1")
def test_consensus_paf_unit_scores(lambda_reference):
    res = run_scenario("unit")
    assert ed_vs_reference(res, lambda_reference) == \
        gs.HOST_POLISH["unit"]  # reference: 1321


@pytest.mark.skipif(not FULL, reason="slow on 1-core host; "
                    "set RACON_TPU_FULL_GOLDEN=1")
def test_fragment_correction_kc(lambda_reference):
    res = run_scenario("kc")
    count, total = gs.HOST_FRAGMENT["kc"]  # reference: 40 / 401246
    assert len(res) == count
    assert sum(len(d) for _, d in res) == total


def _on_tpu():
    try:
        import jax
        return jax.devices()[0].platform == "tpu"
    except Exception:  # noqa: BLE001
        return False


@pytest.mark.skipif(not (FULL or HW),
                    reason="slow (device path in interpret/CPU mode); set "
                    "RACON_TPU_FULL_GOLDEN=1, or RACON_TPU_HW_TESTS=1 on "
                    "a TPU machine (fast there, and asserts the exact pin)")
@pytest.mark.parametrize("name", list(gs.POLISH) + list(gs.FRAGMENT))
def test_device_path_golden(name, lambda_reference, monkeypatch):
    """TPU-path accuracy for EVERY golden scenario (the reference pins 10
    accelerator numbers next to the CPU ones, racon_test.cpp:297-507).

    On real TPU hardware each measured pin from golden_scenarios.py is
    asserted EXACTLY; scenarios whose pin is still None skip with a
    pointer to the pin tool (never a silent pass). E.g. 'paf' is pinned
    1282, measured on a v5e (2026-07-29, pin_device_golden.py) — one edit
    from the host path's 1283 (a DP score-tie resolved differently on
    device), better than the reference's CPU 1312 and GPU 1385. The
    hardware branch needs RACON_TPU_HW_TESTS=1 (conftest otherwise forces
    the virtual CPU mesh). On the CPU backend (interpret mode) only the
    historical 'paf' scenario runs — within a small band of the host
    golden; the other 9 would take hours in interpret mode on this box.
    """
    if HW and not _on_tpu():
        # never let a JAX that fell back to the CPU pass the loose band
        # off as a re-verified hardware pin
        pytest.fail("RACON_TPU_HW_TESTS=1 but the JAX platform is not tpu "
                    "— hardware pin not exercised")
    is_polish = name in gs.POLISH
    # the device pins isolate the consensus path: phase 1 on the host
    # aligner, matching pin_device_golden.py's pinned measurement
    # conditions (the hirschberg-on-TPU default postdates the paf pin)
    monkeypatch.setenv("RACON_TPU_DEVICE_ALIGNER", "host")
    if _on_tpu():
        pin = (gs.DEVICE_POLISH if is_polish else gs.DEVICE_FRAGMENT)[name]
        if pin is None:
            pytest.skip(f"device pin for {name!r} not yet measured — run "
                        f"racon_tpu/tools/pin_device_golden.py {name} on a "
                        "healthy chip and record it in golden_scenarios.py")
        res = run_scenario(name, backend="tpu")
        if is_polish:
            assert ed_vs_reference(res, lambda_reference) == pin
        else:
            count, total = pin
            assert len(res) == count
            assert sum(len(d) for _, d in res) == total
    else:
        if name != "paf":
            pytest.skip("interpret-mode device golden runs only the 'paf' "
                        "scenario (hours per scenario on a 1-core host); "
                        "full coverage is the RACON_TPU_HW_TESTS=1 branch")
        # Off the chip the XLA twin serves (Pallas defaults off there):
        # this branch checks the driver + band.  The interpreted ls
        # kernel's λ run is tests/test_ls_band.py (one device, nightly);
        # its correctness is pinned by tests/test_pallas_ls.py.
        res = run_scenario(name, backend="tpu")
        ed = ed_vs_reference(res, lambda_reference)
        assert abs(ed - gs.HOST_POLISH["paf"]) <= 15, ed


@pytest.mark.skipif(not FULL, reason="very slow on 1-core host; "
                    "set RACON_TPU_FULL_GOLDEN=1")
def test_fragment_correction_kf_fasta(lambda_reference):
    """kF with FASTA reads (no qualities) — reference pins 236/1,663,982
    (test/racon_test.cpp:270-276, GPU 1,663,732)."""
    res = run_scenario("kf_fasta")
    count, total = gs.HOST_FRAGMENT["kf_fasta"]  # reference: 236 / 1663982
    assert len(res) == count
    assert sum(len(d) for _, d in res) == total


@pytest.mark.skipif(not FULL, reason="very slow on 1-core host; "
                    "set RACON_TPU_FULL_GOLDEN=1")
def test_fragment_correction_kf_paf(lambda_reference):
    res = run_scenario("kf_paf")
    count, total = gs.HOST_FRAGMENT["kf_paf"]  # reference: 236 / 1658216
    assert len(res) == count
    assert sum(len(d) for _, d in res) == total


@pytest.mark.skipif(not FULL, reason="very slow on 1-core host; "
                    "set RACON_TPU_FULL_GOLDEN=1")
def test_fragment_correction_kf_mhap(lambda_reference):
    """kF with MHAP overlaps — the reference's 10th pinned scenario
    (test/racon_test.cpp:288-294, 236/1,658,216 == its PAF kF): the MHAP
    ordinal transmutation must resolve to the identical result."""
    res = run_scenario("kf_mhap")
    count, total = gs.HOST_FRAGMENT["kf_mhap"]
    assert len(res) == count
    assert sum(len(d) for _, d in res) == total
    assert (count, total) == gs.HOST_FRAGMENT["kf_paf"]  # format parity
