"""Banded DP + verify-and-widen ladder (ops/band.py) on both hot kernels.

Adversarial fixtures for the exactness contract: banded runs must be
BYTE-IDENTICAL to the flat oracle — boundary-optimum pairs, pairs that
force one widening, pairs that exhaust the ladder through the
``banded -> flat`` lattice edge, and the deterministic ``band.hit``
fault drill — with the band.* counters recording exactly what happened.
"""

import random

import numpy as np
import pytest

from racon_tpu import obs
from racon_tpu.ops import align_pallas, band
from racon_tpu.ops.encoding import encode


def _rand(rng, n):
    return bytes(rng.choice(b"ACGT") for _ in range(n))


def _mut(rng, seq, rate):
    out = bytearray()
    for c in seq:
        r = rng.random()
        if r < rate / 3:
            out.append(rng.choice(b"ACGT"))
        elif r < 2 * rate / 3:
            pass
        elif r < rate:
            out.append(c)
            out.append(rng.choice(b"ACGT"))
        else:
            out.append(c)
    return bytes(out)


def _shifted_pair(rng, n, shift, cut, ins):
    """A pair with net length delta ~0 whose optimal path strays `shift`
    diagonals off the corridor: a `shift`-base block deleted at `cut`
    and a random block inserted at `ins` — w0 (delta + slack) plans a
    narrow band the true path escapes."""
    q = _rand(rng, n)
    t = q[:cut] + q[cut + shift:ins] + _rand(rng, shift) + q[ins:]
    return q, t


def _enc(q, t):
    return (encode(np.frombuffer(q, np.uint8)).astype(np.int32),
            encode(np.frombuffer(t, np.uint8)).astype(np.int32))


class _FakePipe:
    """Duck-typed align pipeline for run_jobs (no lengths table)."""

    def __init__(self, pairs):
        self.pairs = pairs
        self.cigars = {}

    def align_job(self, j):
        q, t = self.pairs[j]
        return (np.frombuffer(q, np.uint8), np.frombuffer(t, np.uint8))

    def set_job_cigar(self, j, c):
        self.cigars[j] = c


def _counters():
    snap = obs.snapshot() or {}
    return snap.get("counters") or {}


# ------------------------------------------------------------ band planning


def test_plan_and_verify_units():
    # w0 = delta + slack, bucketed under the flat band
    assert band.bucket_for(1) == 128
    assert band.bucket_for(128) == 128
    assert band.bucket_for(129) == 256
    assert band.bucket_for(99999) is None
    assert band.plan_align_band(800, 800, 256) == 128
    assert band.plan_align_band(800, 1200, 256) is None   # w0 >= flat band
    assert band.plan_align_band(800, 800, 0) is None      # host-bound pair
    assert band.plan_align_band(2600, 2600, 512, widenings=3) == 256
    # exact Ukkonen certificate: corridor covered, distance within bound
    n = m = 800
    k = 128
    gdmin = min(0, m - n) - (k - 1 - abs(m - n)) // 2
    assert band.ukkonen_ok(n, m, k, gdmin, 10)
    assert not band.ukkonen_ok(n, m, k, gdmin, 2 * k)     # bound exceeded
    assert not band.ukkonen_ok(n, m, k, gdmin, None)      # no distance
    assert not band.ukkonen_ok(800, 1200, k, gdmin, 0)    # corridor escapes


# ------------------------------------------------------- aligner, direct API


def test_align_banded_byte_identity_direct():
    """band_overrides under the exact verify: served pairs are
    byte-identical to the flat oracle; escapes are flagged as hits."""
    rng = random.Random(101)
    pairs = []
    for _ in range(3):
        q = _rand(rng, 800)
        pairs.append((q, _mut(rng, q, 0.03)))
    enc = [_enc(q, t) for q, t in pairs]
    flat = align_pallas.align_pairs(enc, interpret=True)
    hits = set()
    banded = align_pallas.align_pairs(
        enc, interpret=True, band_overrides={i: 128 for i in range(3)},
        hits=hits)
    served = 0
    for i in range(3):
        assert flat[i] is not None
        if i in hits:
            assert banded[i] is None    # hit pairs abort, never mis-serve
            continue
        served += 1
        np.testing.assert_array_equal(banded[i], flat[i])
    assert served >= 1, "3% pairs should mostly verify in-band"


def test_align_boundary_optimum_byte_identity():
    """Boundary-optimum adversarial fixture: a single deletion block
    pushes the optimal path to the band edge — the certificate must
    either serve it byte-identically or flag a hit, never mis-serve."""
    rng = random.Random(7)
    q = _rand(rng, 820)
    t = q[:400] + q[460:]            # 60-base deletion: corridor spans 60
    enc = [_enc(q, t)]
    flat = align_pallas.align_pairs(enc, interpret=True)
    hits = set()
    banded = align_pallas.align_pairs(enc, interpret=True,
                                      band_overrides={0: 128}, hits=hits)
    assert flat[0] is not None
    if 0 in hits:
        assert banded[0] is None
    else:
        np.testing.assert_array_equal(banded[0], flat[0])


def test_align_escape_is_a_hit_not_a_wrong_answer():
    """A path that strays ~100 diagonals off a ±64 band MUST be flagged."""
    rng = random.Random(13)
    q, t = _shifted_pair(rng, 800, 100, 200, 550)
    enc = [_enc(q, t)]
    hits = set()
    banded = align_pallas.align_pairs(enc, interpret=True,
                                      band_overrides={0: 128}, hits=hits)
    assert hits == {0}
    assert banded[0] is None


# --------------------------------------------------- aligner, run_jobs ladder


def test_run_jobs_banded_matches_flat_oracle(monkeypatch):
    """End-to-end verify-and-widen through run_jobs + BatchExecutor: a
    clean pair installs off the narrow band, the escape pair rides the
    banded -> flat lattice edge, and every CIGAR equals the flat run's."""
    rng = random.Random(29)
    qa = _rand(rng, 800)
    pairs = {0: (qa, _mut(rng, qa, 0.03)),
             1: _shifted_pair(rng, 800, 100, 200, 550)}

    flat_pipe = _FakePipe(pairs)
    monkeypatch.setenv("RACON_TPU_BAND", "0")
    served = align_pallas.run_jobs(flat_pipe, list(pairs))
    assert served == 2

    obs.reset()
    obs.configure(metrics=True)
    try:
        band_pipe = _FakePipe(pairs)
        monkeypatch.setenv("RACON_TPU_BAND", "1")
        served = align_pallas.run_jobs(band_pipe, list(pairs))
        assert served == 2
        assert band_pipe.cigars == flat_pipe.cigars   # byte-identical
        c = _counters()
        assert c.get("band.jobs") == 2
        assert c.get("band.hits", 0) >= 1             # the shifted pair
        assert c.get("band.fallbacks", 0) >= 1        # banded -> flat edge
        assert c.get("align.cells.banded", 0) > 0
        # the banded plan iterates fewer cells than the flat band
        assert c["align.cells.banded"] < c["align.cells.hirschberg"]
    finally:
        obs.reset()


def test_run_jobs_fault_drill_exhausts_ladder(monkeypatch):
    """Armed band.hit fault: every banded attempt is classified a hit,
    the ladder drains to its flat floor, output stays byte-identical."""
    rng = random.Random(31)
    qa = _rand(rng, 800)
    pairs = {0: (qa, _mut(rng, qa, 0.03))}

    flat_pipe = _FakePipe(pairs)
    monkeypatch.setenv("RACON_TPU_BAND", "0")
    assert align_pallas.run_jobs(flat_pipe, [0]) == 1

    obs.reset()
    obs.configure(metrics=True)
    try:
        monkeypatch.setenv("RACON_TPU_BAND", "1")
        monkeypatch.setenv("RACON_TPU_FAULT", "band.hit")
        from racon_tpu.resilience import faults
        faults.reset()
        drill_pipe = _FakePipe(pairs)
        assert align_pallas.run_jobs(drill_pipe, [0]) == 1
        assert drill_pipe.cigars == flat_pipe.cigars
        c = _counters()
        assert c.get("band.jobs") == 1
        assert c.get("band.hits", 0) >= 1
        assert c.get("band.fallbacks") == 1
    finally:
        obs.reset()
        faults.reset()


def test_run_jobs_one_widening_rung(monkeypatch):
    """A pair whose flat band is 512 and whose path strays ~100
    diagonals: the 128 rung hits, the 256 rung verifies — exactly one
    widening, no fallback, byte-identical CIGAR."""
    rng = random.Random(37)
    q, t = _shifted_pair(rng, 2600, 100, 900, 1800)
    assert align_pallas.band_for(len(q), len(t)) == 512
    pairs = {0: (q, t)}

    flat_pipe = _FakePipe(pairs)
    monkeypatch.setenv("RACON_TPU_BAND", "0")
    assert align_pallas.run_jobs(flat_pipe, [0]) == 1

    obs.reset()
    obs.configure(metrics=True)
    try:
        band_pipe = _FakePipe(pairs)
        monkeypatch.setenv("RACON_TPU_BAND", "1")
        monkeypatch.setenv("RACON_TPU_BAND_SLACK", "80")
        assert align_pallas.run_jobs(band_pipe, [0]) == 1
        assert band_pipe.cigars == flat_pipe.cigars
        c = _counters()
        assert c.get("band.hits") == 1
        assert c.get("band.widenings") == 1
        assert c.get("band.fallbacks", 0) == 0
    finally:
        obs.reset()


# ----------------------------------------------------------- POA, kernel API


def _poa_batch(cfg, B, seed, roll=0):
    rng = np.random.default_rng(seed)
    L = cfg.max_backbone // 2
    bb = np.zeros((B, cfg.max_backbone), np.uint8)
    bbw = np.zeros((B, cfg.max_backbone), np.int32)
    bl = np.zeros(B, np.int32)
    nl = np.zeros(B, np.int32)
    seqs = np.zeros((B, cfg.depth, cfg.max_len), np.uint8)
    ws = np.zeros((B, cfg.depth, cfg.max_len), np.int32)
    lens = np.zeros((B, cfg.depth), np.int32)
    bg = np.zeros((B, cfg.depth), np.int32)
    en = np.zeros((B, cfg.depth), np.int32)
    for b in range(B):
        truth = rng.integers(0, 4, L).astype(np.uint8)
        bb[b, :L] = truth
        bl[b] = L
        nl[b] = cfg.depth
        for li in range(cfg.depth):
            layer = truth.copy()
            pos = rng.integers(0, L, 3)
            layer[pos] = (layer[pos] + 1) % 4
            if roll:
                layer[10:] = np.roll(layer[10:], roll)
            seqs[b, li, :L] = layer
            ws[b, li, :L] = 1
            lens[b, li] = L
            bg[b, li] = 0
            en[b, li] = L - 1
    return (bb, bbw, bl, nl, seqs, ws, lens, bg, en)


@pytest.mark.parametrize("groups", [1, 2, 4], ids=["u1", "u2", "u4"])
def test_poa_banded_kernel_byte_identity(groups):
    """The banded POA build: wband=0 reproduces the flat kernel
    byte-for-byte (the ladder's floor runs through the same compiled
    build), a generous band matches the flat oracle with no hit, and a
    pathologically narrow band on drifted layers raises band_hit; in
    programs of eight, sixteen and thirty-two (wband and band_hit carry
    the group axis with the rest)."""
    from racon_tpu.ops import poa, poa_driver
    from racon_tpu.ops.poa_pallas_ls import build_lockstep_poa_kernel as build

    cfg = poa.PoaConfig(max_nodes=256, max_len=128, max_backbone=128,
                        max_edges=8, depth=4, match=5, mismatch=-4, gap=-8)
    B = 8 * groups
    flat = build(cfg, interpret=True, groups=groups)(B)
    banded = build(cfg, interpret=True, band=True, groups=groups)(B)

    def run(kern, packed9, wband):
        is_banded = wband is not None
        w = np.full(B, wband if is_banded else 0, np.int32)
        outs = poa_driver._submit(kern, packed9 + (w,), True, is_banded)
        return poa_driver._unpack(outs, True, is_banded)

    packed9 = _poa_batch(cfg, B, 0)
    fb, fc, fl, ff = run(flat, packed9, None)
    assert not ff.any()

    for w in (0, 8):   # flat floor through the banded build; generous band
        zb, zc, zl, zf, zh = run(banded, packed9, w)
        assert not zf.any() and not zh.any()
        assert (zl == fl).all()
        for b in range(B):
            np.testing.assert_array_equal(zb[b, :zl[b]], fb[b, :fl[b]])
            np.testing.assert_array_equal(zc[b, :zl[b]], fc[b, :fl[b]])

    drift9 = _poa_batch(cfg, B, 1, roll=5)
    nb, nc, nl_, nf, nh = run(banded, drift9, 1)
    assert (nh | nf).any(), "drifted layers at wband=1 must flag a hit"


# -------------------------------------------------------- POA, driver ladder


def _polish_dataset(tmp_path, seed=5, n=240, reads=4):
    rng = random.Random(seed)
    target = "".join(rng.choice("ACGT") for _ in range(n))
    with open(tmp_path / "t.fasta", "w") as f:
        f.write(f">t\n{target}\n")
    with open(tmp_path / "r.fasta", "w") as f:
        for i in range(reads):
            f.write(f">r{i}\n{target}\n")
    with open(tmp_path / "o.sam", "w") as f:
        f.write("@HD\tVN:1.6\n")
        for i in range(reads):
            f.write(f"r{i}\t0\tt\t1\t60\t{n}M\t*\t0\t0\t{target}\t*\n")
    return target


def _polish(tmp_path):
    import racon_tpu

    p = racon_tpu.TpuPolisher(str(tmp_path / "r.fasta"),
                              str(tmp_path / "o.sam"),
                              str(tmp_path / "t.fasta"),
                              window_length=80, match=5, mismatch=-4,
                              gap=-8)
    p.initialize()
    return p.polish(True)


def test_poa_banded_driver_byte_identity(tmp_path, monkeypatch):
    """RACON_TPU_BAND=1 through the full consensus driver (pallas ls,
    interpret): polished output byte-identical to the flat run, banded
    windows counted."""
    target = _polish_dataset(tmp_path)
    monkeypatch.setenv("RACON_TPU_PALLAS", "1")
    monkeypatch.setenv("RACON_TPU_BATCH_WINDOWS", "4")

    monkeypatch.setenv("RACON_TPU_BAND", "0")
    flat = _polish(tmp_path)

    try:
        monkeypatch.setenv("RACON_TPU_BAND", "1")
        monkeypatch.setenv("RACON_TPU_BAND_SLACK", "8")
        # the polisher constructor resets + re-arms obs itself, so the
        # metrics knob (not a direct obs.configure) is what survives
        monkeypatch.setenv("RACON_TPU_METRICS", "1")
        banded = _polish(tmp_path)
        assert [s for _, s in banded] == [s for _, s in flat]
        assert banded[0][1] == target
        c = _counters()
        assert c.get("band.jobs", 0) > 0
        assert c.get("poa.cells.banded", 0) > 0
    finally:
        obs.reset()


def test_poa_banded_fault_drill_exhausts_ladder(tmp_path, monkeypatch):
    """Armed band.hit fault through the consensus driver: every banded
    window widens RACON_TPU_BAND_MAX_WIDENINGS times, takes the
    banded -> flat edge, and still polishes byte-identically."""
    from racon_tpu.resilience import faults

    target = _polish_dataset(tmp_path)
    monkeypatch.setenv("RACON_TPU_PALLAS", "1")
    monkeypatch.setenv("RACON_TPU_BATCH_WINDOWS", "4")

    monkeypatch.setenv("RACON_TPU_BAND", "0")
    flat = _polish(tmp_path)

    try:
        monkeypatch.setenv("RACON_TPU_BAND", "1")
        monkeypatch.setenv("RACON_TPU_BAND_SLACK", "8")
        monkeypatch.setenv("RACON_TPU_BAND_MAX_WIDENINGS", "2")
        monkeypatch.setenv("RACON_TPU_FAULT", "band.hit")
        monkeypatch.setenv("RACON_TPU_METRICS", "1")
        faults.reset()
        banded = _polish(tmp_path)
        assert [s for _, s in banded] == [s for _, s in flat]
        c = _counters()
        jobs = c.get("band.jobs", 0)
        assert jobs > 0
        # every banded window: 2 widenings then the fallback edge
        assert c.get("band.widenings") == 2 * jobs
        assert c.get("band.fallbacks") == jobs
        assert c.get("band.hits") == 3 * jobs
    finally:
        obs.reset()
        faults.reset()


# ------------------------------------------------------------ bench stamp


def test_bench_band_stamp_and_normalize_entry():
    """bench.py's banded-evidence stamp: (cells_banded, band_hit_rate)
    from a counter snapshot, explicit double-None when banding never
    engaged; normalize_entry backfills both keys on pre-banding logs."""
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    try:
        import bench
    finally:
        sys.path.remove(root)

    snap = {"counters": {"band.jobs": 8, "band.hits": 2,
                         "align.cells.banded": 1000,
                         "poa.cells.banded": 2000}}
    cells, rate = bench.band_stamp(snap)
    assert cells == {"align": 1000, "poa": 2000}
    assert rate == 0.25
    # banding on, zero hits: a measured 0.0, not "not measured"
    assert bench.band_stamp({"counters": {"band.jobs": 3}}) == (None, 0.0)
    assert bench.band_stamp({"counters": {}}) == (None, None)
    assert bench.band_stamp(None) == (None, None)

    old = bench.normalize_entry({"value": 1.0})
    assert old["cells_banded"] is None and old["band_hit_rate"] is None
    fresh = {"value": 1.0, "cells_banded": {"align": 5}, "band_hit_rate": 0.1,
             "cost_model": None, "pack_split": None, "serial_steps": None,
             "peak_rss_mb": None, "budget_mb": None}
    assert bench.normalize_entry(dict(fresh)) == fresh
