"""Analytic per-tier cost model for the kernel grid + roofline accounting.

ROADMAP item 3's hardware-free half: before cutting serial DP steps we
need to *predict* where the cycles go — per POA bucket (DEPTH_BUCKETS x
128-lane window class, tier ls/xla) and per aligner band — and
check those predictions against what `--trace` actually measured.  The
vocabulary is the one AnySeq/GPU and gpuPairHMM use to justify DP
optimizations: cell updates per second against a machine roofline.

Three layers:

* **CostEstimate** — closed-form FLOPs / HBM bytes / serial DP steps per
  window (POA) or per job (aligner), parameterized by bucket shape.
  Where a lowered kernel is on hand, `lowered_cost()` asks
  ``jax.stages.Lowered.cost_analysis()`` instead and falls back to the
  closed forms (the XLA estimate has no notion of our serial rank loop,
  so serial steps always come from the closed form).
* **MachineProfile** — peak FLOP/s, HBM bandwidth, serial-step latency,
  host engine cell rates, and the prediction-error bound the profile
  *declares* it can hold.  ``cpu-host`` (this repo's CI box class) and
  ``tpu-v5e`` (published peaks; serial step from dp_cost_probe, see
  docs/benchmarks.md) ship built in.
* **Roofline verdict** — predicted wall = max(compute, bandwidth,
  serial-step term); whichever term wins classifies the bucket as
  compute-bound / bandwidth-bound / serial-step-bound.  The measured
  0.188x story is the serial-step term winning by ~40x, which is why
  the serial-step cut landed: the Pallas POA tier divides its step
  count by POA_RANK_PACK (rank-pair stepping,
  ops/poa_pallas_ls.py) and the packed Hirschberg kernels divide theirs by
  ALIGN_ROW_PACK (ops/encoding.PACK rows per iteration).

Everything here is stdlib-only (the obs package contract): the kernel
grid constants are mirrored from ``racon_tpu.ops`` and pinned equal by
tests/test_costmodel.py, so this module stays importable without jax.
"""

from __future__ import annotations

import math
import re
from dataclasses import dataclass
from typing import Dict, List, NamedTuple, Optional, Tuple

# -- kernel grid constants (mirrored from racon_tpu.ops; parity-tested) ----

#: poa_driver.DEPTH_BUCKETS — layer-count buckets windows batch into.
DEPTH_BUCKETS = (8, 32, 200)
#: (pair length, band) rows the model prints for the aligner: one per
#: compiled band of align_pallas.BANDS, at a pair of 4 x band bases.
ALIGN_BUCKETS = tuple((4 * band, band) for band in (256, 512, 1024, 2048))
#: poa_pallas_ls.G — windows per sublane group of a lane-lockstep
#: program (amortizes the serial rank loop across G windows).  A program
#: runs one, two or four groups (poa_driver._group_width); this model does not
#: follow the width, its serial term stays that of one group.
LS_GROUP = 8
#: poa_driver.AUDIT_WINDOW_LENGTHS — the window lengths the grid is
#: audited (and documented) at.
AUDIT_WINDOW_LENGTHS = (500, 1000)

POA_TIERS = ("ls", "xla")

#: Graph ranks per backbone position: POA graphs grow past the backbone
#: as divergent layer bases fork nodes.  λ at ~30x measured ~2x
#: (docs/benchmarks.md: ~1000 ranks over a 500-base backbone).
NODE_GROWTH = 2.0

#: Ranks retired per serial iteration by the lockstep Pallas kernel's
#: pair loop (poa_pallas_ls.py `pair_body`: two consecutive ranks,
#: unconditionally).  The XLA twin keeps the one-rank-per-step scan.
POA_RANK_PACK = 2.0

#: ops.encoding.PACK — query bases packed per int32 word by the packed
#: Hirschberg kernels; each serial loop iteration scores PACK adjacent
#: DP rows, dividing the row-scan trip count.
ALIGN_ROW_PACK = 4.0

#: ops.band.BAND_BUCKETS — the verify-and-widen ladder's compiled band
#: rungs (RACON_TPU_BAND); the top rungs coincide with the flat
#: aligner's BANDS, so the ladder's ceiling is the flat kernel.
BAND_BUCKETS = (128, 256, 512, 1024, 2048)
#: config default for RACON_TPU_BAND_SLACK — the half-band margin added
#: to the length delta when planning w0.
BAND_SLACK = 32

#: Vector ops per DP cell (sub/ins/del merge, weight add, move select,
#: cummax contribution) — same math in all three tiers.
POA_FLOPS_PER_CELL = 14.0
#: HBM bytes per admitted layer base (u8 code + i32 weight streamed in).
POA_LAYER_BYTES = 5.0
#: Aligner DP: add/min/select + move byte per cell.
ALIGN_FLOPS_PER_CELL = 10.0


def window_class(bb_len: int) -> int:
    """128-lane geometry class (mirror of poa_driver.window_class)."""
    return max(128, (bb_len + 127) // 128 * 128)


def band_need(n: int, m: int) -> int:
    """Band the aligner actually needs for an (n, m) pair — the 10%%
    auto-band rule (mirror of align_pallas.band_for's `need`)."""
    return abs(m - n) + max(n, m) // 10 + 2


class CostEstimate(NamedTuple):
    """Predicted work for one unit (window / align job / batch)."""

    flops: float          # vector FLOPs (or int-ops; the VPU doesn't care)
    hbm_bytes: float      # bytes that must cross HBM
    serial_steps: float   # latency-chained DP steps (rank loop / row scan)

    def scaled(self, k: float) -> "CostEstimate":
        return CostEstimate(self.flops * k, self.hbm_bytes * k,
                            self.serial_steps * k)

    def plus(self, other: "CostEstimate") -> "CostEstimate":
        return CostEstimate(self.flops + other.flops,
                            self.hbm_bytes + other.hbm_bytes,
                            self.serial_steps + other.serial_steps)


ZERO = CostEstimate(0.0, 0.0, 0.0)


@dataclass(frozen=True)
class MachineProfile:
    """What the machine can do — the denominator under every estimate.

    ``error_bound_ratio`` is the bound the profile *declares*: `obs
    validate` fails (exit 3) when max(pred/meas, meas/pred) on a modeled
    phase exceeds it.  The CPU profile's bound is deliberately loose
    (XLA-on-CPU throughput varies ~4x across host classes); the TPU
    profile is the calibration target and declares a tight one.
    """

    name: str
    description: str
    clock_hz: float              # core clock (cycles tables only)
    peak_flops: float            # sustained vector FLOP/s for one program
    hbm_bytes_per_s: float       # sustained HBM bandwidth
    serial_step_s: float         # latency per serial DP step
    host_poa_cells_per_s: float  # host SIMD POA engine
    host_align_cells_per_s: float  # host Myers aligner
    error_bound_ratio: float     # declared validate bound (>= 1)
    #: ``jax.devices()[0].device_kind`` strings this profile describes;
    #: 'auto' resolves a TPU by this and nothing else
    device_kinds: Tuple[str, ...] = ()


PROFILES: Dict[str, MachineProfile] = {p.name: p for p in (
    MachineProfile(
        name="cpu-host",
        description="1-core x86 host running the XLA twin kernels "
                    "(the CI traced-bench configuration); host engines "
                    "are the native SIMD paths",
        clock_hz=3.0e9,
        # XLA CPU executes the scan-based DP kernels essentially
        # scalar + dispatch-bound; calibrated against traced runs of
        # the XLA twin on this repo's dev box.
        peak_flops=2.0e9,
        hbm_bytes_per_s=1.0e10,
        # One serial DP step on this profile is one XLA while-loop
        # iteration over the whole window batch — dispatch-dominated on
        # CPU, measured at ~2.6 ms/step on the 1-core dev box (traced
        # 0.002 Mbp forced-device bench: 28.7k steps -> 73.6 s poa
        # phase). This is what makes the forced-device dry run hundreds
        # of times slower than the host SIMD path, and it is why the
        # error bound below is wide: runner-class machines differ in
        # dispatch overhead far more than in FLOP rate.
        serial_step_s=2.5e-3,
        host_poa_cells_per_s=1.2e9,    # 1.57 Gcells/s AVX-512 measured,
                                       # derated for short-window overhead
        host_align_cells_per_s=6.0e8,  # banded block-Myers, measured class
        error_bound_ratio=8.0,
    ),
    MachineProfile(
        name="tpu-v5e",
        description="one TPU v5e chip. Peaks as published (Google Cloud "
                    "documentation, 'TPU v5e'): 197 TFLOP/s bf16, 393 "
                    "TOP/s int8, 819 GB/s HBM; the clock follows from "
                    "the bf16 peak (4 MXUs x 128x128 MACs x 2). The "
                    "int32 DP kernels run on the VPU, far below the MXU "
                    "peak, so the compute term is a floor, not an "
                    "estimate. serial_step_s is NOT measured on this "
                    "device: it is the dp_cost_probe figure of "
                    "2026-07-29, kept until ROADMAP S2 measures the "
                    "shipped kernels from a trace",
        device_kinds=("TPU v5 lite", "TPU v5e"),
        clock_hz=1.5e9,
        peak_flops=1.97e14,
        hbm_bytes_per_s=8.19e11,
        serial_step_s=2.7e-6,
        host_poa_cells_per_s=1.5e9,  # host VM SIMD engines
        host_align_cells_per_s=1.0e9,
        error_bound_ratio=2.5,
    ),
)}


def profile(name: str) -> MachineProfile:
    """Look up a machine profile; raises KeyError with the valid names."""
    try:
        return PROFILES[name]
    except KeyError:
        raise KeyError(f"unknown machine profile {name!r}; "
                       f"available: {sorted(PROFILES)}") from None


def resolve_profile(name: str, platform: Optional[str] = None,
                    device_kind: Optional[str] = None) -> MachineProfile:
    """'auto' picks cpu-host off a TPU and, on one, the profile that
    lists the run's ``device_kind``; a TPU no profile describes is an
    error, never a default.  Anything else must be a registered name."""
    if name not in ("", "auto", None):
        return profile(name)
    if platform != "tpu":
        return PROFILES["cpu-host"]
    for prof in PROFILES.values():
        if device_kind in prof.device_kinds:
            return prof
    known = sorted(k for p in PROFILES.values() for k in p.device_kinds)
    raise KeyError(
        f"no machine profile for TPU device kind {device_kind!r} (known: "
        f"{known}); add its published peaks to costmodel.PROFILES or "
        f"name a profile explicitly")


# -- closed-form estimates -------------------------------------------------

def poa_window_cost(depth: int, wl_class: int, tier: str) -> CostEstimate:
    """Predicted work for ONE window of `depth` admitted layers in a
    `wl_class` geometry class served by `tier`.

    The DP: each layer aligns against the window graph — ranks x layer
    length cells, with the rank loop latency-chained (each rank's row
    depends on its predecessors' rows).  Graph update + consensus ride
    inside the same rank-step constants.
    """
    ranks = NODE_GROWTH * wl_class
    cells = depth * ranks * wl_class
    flops = cells * POA_FLOPS_PER_CELL
    # HBM traffic: layer bases/weights streamed in, consensus out; the H
    # matrix lives in VMEM (the ls ring), so it does not cross HBM.
    hbm = depth * wl_class * POA_LAYER_BYTES + 2 * wl_class * 5
    steps = depth * ranks
    if tier == "ls":
        # The Pallas loop retires a rank pair per serial iteration, and
        # G windows share one program's rank loop: the serial term
        # amortizes per window, the cell work does not.
        steps /= POA_RANK_PACK * LS_GROUP
    return CostEstimate(flops, hbm, steps)


def align_job_cost(cap: int, band: int) -> CostEstimate:
    """Predicted work for ONE Hirschberg job of `cap` rows at `band`:
    fwd+bwd distance passes over the recursion tree ~ 2x the base DP,
    no stored matrix.
    """
    cells = 2.0 * float(cap) * band
    # Row scans across recursion levels; the packed kernels score
    # ALIGN_ROW_PACK adjacent rows per serial iteration.
    steps = 4.0 * cap / ALIGN_ROW_PACK
    hbm = cap * 2.0            # sequences only; no moves matrix
    return CostEstimate(cells * ALIGN_FLOPS_PER_CELL, hbm, steps)


def banded_align_job_cost(cap: int, k: int) -> CostEstimate:
    """Predicted work for ONE Hirschberg job served on band rung `k`
    (RACON_TPU_BAND): the fwd+bwd distance passes iterate ``2*cap*k``
    cells instead of ``2*cap*band_for(cap)`` — the in-loop cell bill
    divides by the band ratio.  The serial row scan is UNCHANGED: the
    band narrows each row's live lanes, it does not shorten the
    latency chain (same rows, fewer columns per row)."""
    return align_job_cost(cap, k)


def banded_poa_window_cost(depth: int, wl_class: int, w: int,
                           tier: str) -> CostEstimate:
    """Predicted work for ONE banded POA window at runtime half-band
    `w` (wband): each rank's row keeps ``2*w + 1`` live columns around
    its backbone offset instead of the full class width, so the cell
    (and FLOP) bill scales by ``(2w+1)/wl_class``.  Rank-loop length —
    the serial term — is unchanged; HBM traffic still streams every
    admitted layer base once."""
    ranks = NODE_GROWTH * wl_class
    width = min(float(wl_class), 2.0 * w + 1.0)
    cells = depth * ranks * width
    flops = cells * POA_FLOPS_PER_CELL
    hbm = depth * wl_class * POA_LAYER_BYTES + 2 * wl_class * 5
    steps = depth * ranks
    if tier == "ls":
        steps /= POA_RANK_PACK * LS_GROUP
    return CostEstimate(flops, hbm, steps)


def banded_cell_ratio(kind: str, *, cap: int = 0, band: int = 0, k: int = 0,
                      wl_class: int = 0, w: int = 0) -> float:
    """Predicted flat/banded in-loop cell ratio for one unit — the
    quantity dp_cost_probe's ``--gate`` measures on silicon and
    docs/benchmarks.md tabulates.  kind 'align': flat band `band` vs
    rung `k`; kind 'poa': class width `wl_class` vs half-band `w`."""
    if kind == "align":
        return float(band) / max(1, k)
    return float(wl_class) / max(1.0, min(float(wl_class), 2.0 * w + 1.0))


def roofline(est: CostEstimate, prof: MachineProfile):
    """(seconds, verdict): predicted wall is the max of the three
    roofline terms; the winning term names the bound."""
    terms = {
        "compute-bound": est.flops / prof.peak_flops,
        "bandwidth-bound": est.hbm_bytes / prof.hbm_bytes_per_s,
        "serial-step-bound": est.serial_steps * prof.serial_step_s,
    }
    verdict = max(terms, key=lambda k: terms[k])
    return terms[verdict], verdict


def host_poa_seconds(cells: float, prof: MachineProfile) -> float:
    return cells / prof.host_poa_cells_per_s


def host_align_seconds(cells: float, prof: MachineProfile) -> float:
    return cells / prof.host_align_cells_per_s


# -- optional jax.stages.Lowered.cost_analysis ----------------------------

def lowered_cost(lowered) -> Optional[CostEstimate]:
    """FLOPs/bytes from a ``jax.stages.Lowered`` (or anything exposing
    ``cost_analysis()``), serial steps left 0 — XLA's estimate has no
    notion of the rank loop's latency chain, so callers must merge this
    with a closed form for the serial term.  Returns None when the
    backend provides no cost analysis (CPU often returns {} or raises).
    """
    try:
        ca = lowered.cost_analysis()
    except Exception:  # noqa: BLE001 — optional-path probe
        return None
    if not isinstance(ca, dict) or not ca:
        return None
    flops = float(ca.get("flops", 0.0))
    byt = float(ca.get("bytes accessed", 0.0))
    if flops <= 0.0 and byt <= 0.0:
        return None
    return CostEstimate(flops, byt, 0.0)


def lowered_poa_cost(depth: int, wl_class: int, tier: str
                     ) -> Optional[CostEstimate]:
    """Best-effort: lower the real POA kernel for this bucket and read
    XLA's own FLOPs/bytes, keeping the closed-form serial term.  Imports
    jax and traces the kernel — minutes-cheap on CPU for the xla tier,
    potentially slow for pallas tiers; callers gate it (``obs model
    --lowered``).  Any failure returns None (closed form stands)."""
    try:
        import jax
        import numpy as np

        from ..ops import poa as poa_mod
        from ..ops import poa_driver

        cfg = poa_driver.make_config(wl_class, depth, 5, -4, -8)
        if tier != "xla":
            return None   # pallas lowerings carry no useful cost_analysis
        kernel = poa_mod.build_poa_kernel(cfg)
        B = 1
        args = (
            np.zeros((B, cfg.max_backbone), np.uint8),
            np.zeros((B, cfg.max_backbone), np.int32),
            np.ones(B, np.int32),
            np.zeros(B, np.int32),
            np.zeros((B, cfg.depth, cfg.max_len), np.uint8),
            np.zeros((B, cfg.depth, cfg.max_len), np.int32),
            np.zeros((B, cfg.depth), np.int32),
            np.zeros((B, cfg.depth), np.int32),
            np.zeros((B, cfg.depth), np.int32),
        )
        est = lowered_cost(jax.jit(kernel).lower(*args))
        del jax
        if est is None:
            return None
        closed = poa_window_cost(depth, wl_class, tier)
        return CostEstimate(est.flops, est.hbm_bytes or closed.hbm_bytes,
                            closed.serial_steps)
    except Exception:  # noqa: BLE001 — optional-path probe
        return None


# -- the predicted grid (obs model) ----------------------------------------

def model_rows(prof: MachineProfile,
               window_lengths=AUDIT_WINDOW_LENGTHS,
               tiers=POA_TIERS, depth: Optional[int] = None,
               lowered: bool = False) -> List[dict]:
    """One row per (tier, depth bucket, window class) plus one per
    aligner bucket: predicted FLOPs / HBM bytes / serial steps /
    wall+cycles per unit, and the roofline verdict."""
    rows = []
    classes = sorted({window_class(w) for w in window_lengths})
    for tier in tiers:
        for d in DEPTH_BUCKETS if depth is None else (depth,):
            for c in classes:
                est = None
                if lowered:
                    est = lowered_poa_cost(d, c, tier)
                if est is None:
                    est = poa_window_cost(d, c, tier)
                s, verdict = roofline(est, prof)
                rows.append({
                    "kind": "poa", "tier": tier, "depth": d, "class": c,
                    "flops": est.flops, "hbm_bytes": est.hbm_bytes,
                    "serial_steps": est.serial_steps,
                    "predicted_s": s,
                    "predicted_cycles": s * prof.clock_hz,
                    "verdict": verdict,
                })
    for cap, band in ALIGN_BUCKETS:
        est = align_job_cost(cap, band)
        s, verdict = roofline(est, prof)
        rows.append({
            "kind": "align", "tier": "hirschberg", "cap": cap,
            "band": band,
            "flops": est.flops, "hbm_bytes": est.hbm_bytes,
            "serial_steps": est.serial_steps,
            "predicted_s": s, "predicted_cycles": s * prof.clock_hz,
            "verdict": verdict,
        })
    return rows


# -- validation against a measured trace ----------------------------------

_POA_CELLS = re.compile(r"^poa\.cells\.d(\d+)\.c(\d+)$")
_POA_WINDOWS = re.compile(r"^poa\.windows\.d(\d+)\.c(\d+)$")
_SHARD_ROWS = re.compile(r"^shard\.rows\.d(\d+)$")


def infer_n_devices(counters: Dict[str, int]) -> int:
    """Device count from the per-device shard-row counters the executor
    emits on every sharded dispatch (`shard.rows.d<i>`); 1 when the run
    never sharded."""
    n = 0
    for k in counters:
        m = _SHARD_ROWS.match(k)
        if m:
            n = max(n, int(m.group(1)) + 1)
    return max(1, n)


def _over_devices(est: CostEstimate, n: int) -> CostEstimate:
    """Spread a device-side estimate over n mesh shards: FLOPs and HBM
    traffic divide (data-parallel rows), the latency-chained serial
    steps do NOT — every shard runs the same lockstep DP loop on its
    slice, concurrently."""
    if n <= 1:
        return est
    return CostEstimate(est.flops / n, est.hbm_bytes / n,
                        est.serial_steps)

#: Trace phase span name -> run-report phase name (bench.py's
#: `phase_wall` keys use the report names).
PHASE_ALIASES = {"align": "alignment", "poa": "consensus"}


def _err_pct(pred: float, meas: float) -> Optional[float]:
    if meas <= 0.0:
        return None
    return 100.0 * (pred - meas) / meas


def _ratio(pred: float, meas: float) -> Optional[float]:
    if pred <= 0.0 or meas <= 0.0:
        return None
    return max(pred / meas, meas / pred)


def _dominant_tier(counters: Dict[str, int], phase: str,
                   candidates) -> Optional[str]:
    best, best_n = None, 0
    for t in candidates:
        n = counters.get(f"served.{phase}.{t}", 0)
        if n > best_n:
            best, best_n = t, n
    return best


def predict_from_counters(counters: Dict[str, int],
                          prof: MachineProfile,
                          n_devices: Optional[int] = None) -> dict:
    """Turn the measured-cell counters (the drivers count them per
    bucket, see docs/observability.md) into predicted per-phase walls
    plus a per-bucket table.

    POA: `poa.cells.d<D>.c<C>` = sum over the bucket's windows of
    (admitted depth x class C) — the serial-step count at graph growth 1.
    Aligner: `align.cells.hirschberg` = the flat-band DP cells of the
    pairs the device engine took, `align.cells.total` the need-band
    cells over ALL phase-1 jobs (host share included).

    `n_devices` divides the device-side FLOP/byte bill (data-parallel
    mesh sharding; serial steps are NOT divided — shards run their DP
    loops concurrently).  None = infer from the `shard.rows.d<i>`
    counters, EXCEPT on the cpu-host profile, where forced-host virtual
    devices share the same cores and sharding adds no real throughput
    (the CI `obs validate` bound must not assume an 8x that can't
    exist); an explicit count always wins.
    """
    if n_devices is None:
        n_devices = (1 if prof.name == "cpu-host"
                     else infer_n_devices(counters))
    n_devices = max(1, int(n_devices))
    # ---- consensus / POA
    tier = _dominant_tier(counters, "consensus", POA_TIERS) or "ls"
    total_served = sum(v for k, v in counters.items()
                       if k.startswith("served.consensus."))
    host_served = counters.get("served.consensus.host", 0)
    host_frac = host_served / total_served if total_served else 0.0
    buckets = []
    poa_est = ZERO
    poa_host_cells = 0.0
    for name, raw in sorted(counters.items()):
        m = _POA_CELLS.match(name)
        if not m:
            continue
        d, c = int(m.group(1)), int(m.group(2))
        steps1 = float(raw)                      # sum(depth_i) * C
        ranks_steps = steps1 * NODE_GROWTH       # rank-loop steps
        cells = ranks_steps * c                  # DP cells
        step_div = LS_GROUP * POA_RANK_PACK if tier == "ls" else 1.0
        est = CostEstimate(cells * POA_FLOPS_PER_CELL,
                           steps1 * POA_LAYER_BYTES,
                           ranks_steps / step_div)
        dev_share = 1.0 - host_frac
        dev_est = _over_devices(est.scaled(dev_share), n_devices)
        sec, verdict = roofline(dev_est, prof)
        sec += host_poa_seconds(cells * host_frac, prof)
        windows = counters.get(f"poa.windows.d{d}.c{c}")
        buckets.append({"kind": "poa", "tier": tier, "depth": d,
                        "class": c, "windows": windows,
                        "cells": cells, "serial_steps": est.serial_steps,
                        "predicted_s": sec, "verdict": verdict})
        poa_est = poa_est.plus(dev_est)
        poa_host_cells += cells * host_frac
    poa_s, poa_verdict = roofline(poa_est, prof)
    poa_s += host_poa_seconds(poa_host_cells, prof)

    # ---- alignment
    a_est = ZERO
    dev_cells = 0.0
    hs_cells = counters.get("align.cells.hirschberg", 0)
    if hs_cells:
        est = _over_devices(
            CostEstimate(hs_cells * ALIGN_FLOPS_PER_CELL,
                         hs_cells * 0.1,
                         hs_cells * (4.0 / ALIGN_ROW_PACK) / 256.0),
            n_devices)
        a_est = a_est.plus(est)
        dev_cells += float(hs_cells)
        sec, verdict = roofline(est, prof)
        buckets.append({"kind": "align", "tier": "hirschberg",
                        "cells": float(hs_cells), "predicted_s": sec,
                        "verdict": verdict})
    # banded-DP info rows (RACON_TPU_BAND): the actually-iterated cells
    # of banded jobs/windows.  Informational only — the flat-equivalent
    # bill is already inside the hirschberg / poa bucket estimates
    # above, so these are NOT added to the phase totals (no double
    # count); the flat-vs-banded cell ratio is the measured saving.
    for phase, cname, fpc in (
            ("align", "align.cells.banded", ALIGN_FLOPS_PER_CELL),
            ("poa", "poa.cells.banded", POA_FLOPS_PER_CELL)):
        bnd = counters.get(cname, 0)
        if bnd:
            best = _over_devices(
                CostEstimate(bnd * fpc, bnd * 0.1, 0.0), n_devices)
            sec, verdict = roofline(best, prof)
            buckets.append({"kind": "banded", "phase": phase,
                            "tier": "banded", "cells": float(bnd),
                            "predicted_s": sec, "verdict": verdict})
    align_s, align_verdict = roofline(a_est, prof)
    # the host aligner serves whatever the device buckets did not cover
    total_cells = counters.get("align.cells.total", 0)
    host_cells = max(0.0, float(total_cells) - dev_cells)
    align_s += host_align_seconds(host_cells, prof)
    if host_cells and host_cells >= dev_cells:
        align_verdict = "host-served"

    return {
        "buckets": buckets,
        "n_devices": n_devices,
        "phases": {
            "poa": {"predicted_s": poa_s, "verdict": poa_verdict,
                    "tier": tier,
                    "serial_steps": poa_est.serial_steps},
            "align": {"predicted_s": align_s, "verdict": align_verdict,
                      "serial_steps": a_est.serial_steps},
        },
    }


# -- span-interval math (phase pipelining makes spans overlap) -------------

def span_intervals(doc: dict, name: str) -> List[tuple]:
    """Sorted [(start_us, end_us)] of every complete event named `name`
    (exact match) in the trace."""
    out = []
    for ev in doc.get("traceEvents", []):
        if isinstance(ev, dict) and ev.get("ph") == "X" \
                and ev.get("name") == name:
            ts = float(ev.get("ts", 0))
            out.append((ts, ts + float(ev.get("dur", 0))))
    return sorted(out)


def union_intervals(intervals) -> List[tuple]:
    """Merge possibly-overlapping intervals into disjoint ones."""
    merged: List[list] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [tuple(iv) for iv in merged]


def overlap_us(doc: dict, name_a: str, name_b: str) -> float:
    """Total wall (µs) during which a span named `name_a` and one named
    `name_b` were simultaneously open — the phase-pipelining evidence
    (`align.cohort` vs `poa.bucket`: nonzero iff alignment cohorts were
    in flight while POA buckets dispatched)."""
    a = union_intervals(span_intervals(doc, name_a))
    b = union_intervals(span_intervals(doc, name_b))
    total = 0.0
    i = j = 0
    while i < len(a) and j < len(b):
        lo = max(a[i][0], b[j][0])
        hi = min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total


def phase_overlaps_us(doc: dict) -> Dict[str, float]:
    """Nonzero pairwise overlaps between ``phase.*`` span families,
    keyed ``"a+b"``.  Sequential runs return {} (disjoint phase walls);
    pipelined runs show ``align+poa`` > 0."""
    names = sorted({ev["name"] for ev in doc.get("traceEvents", [])
                    if isinstance(ev, dict) and ev.get("ph") == "X"
                    and isinstance(ev.get("name"), str)
                    and ev["name"].startswith("phase.")})
    out: Dict[str, float] = {}
    for i, na in enumerate(names):
        for nb in names[i + 1:]:
            ov = overlap_us(doc, na, nb)
            if ov > 0:
                out[f"{na[len('phase.'):]}+{nb[len('phase.'):]}"] = ov
    return out


def _bucket_walls_us(doc: dict) -> Dict[tuple, float]:
    """Measured submit-side wall per (kind, key) from the bucket/cohort
    spans.  Pipelined drains can land inside a neighboring bucket's span
    (documented in docs/observability.md), so these are first-order."""
    walls: Dict[tuple, float] = {}
    for ev in doc.get("traceEvents", []):
        if not (isinstance(ev, dict) and ev.get("ph") == "X"):
            continue
        args = ev.get("args") or {}
        if ev.get("name") == "poa.bucket":
            key = ("poa", int(args.get("depth", -1)),
                   int(args.get("wl_class", -1)))
        elif ev.get("name") == "align.cohort":
            key = ("align", args.get("tier", "?"),
                   int(args.get("cap", 0) or 0))
        else:
            continue
        walls[key] = walls.get(key, 0.0) + float(ev.get("dur", 0))
    return walls


def validate_trace(doc: dict, prof: MachineProfile) -> dict:
    """Join predictions against a measured trace.

    Returns {profile, phases: {name: {predicted_s, measured_s,
    error_pct, ratio, within_bound}}, buckets: [...], dropped_events,
    ok}.  Only the modeled phases (align, poa) gate `ok`; a phase with
    no measured wall or no counted cells is reported but not gated.
    """
    metrics = (doc.get("racon_tpu") or {}).get("metrics") or {}
    counters = metrics.get("counters") or {}
    pred = predict_from_counters(counters, prof)

    measured: Dict[str, float] = {}
    for ev in doc.get("traceEvents", []):
        if isinstance(ev, dict) and ev.get("ph") == "X" \
                and isinstance(ev.get("name"), str) \
                and ev["name"].startswith("phase."):
            p = ev["name"][len("phase."):]
            measured[p] = measured.get(p, 0.0) + ev.get("dur", 0) / 1e6

    phases = {}
    ok = True
    for name, row in pred["phases"].items():
        meas = measured.get(name)
        p_s = row["predicted_s"]
        entry = dict(row, measured_s=meas)
        if meas is not None and p_s > 0.0:
            entry["error_pct"] = _err_pct(p_s, meas)
            r = _ratio(p_s, meas)
            entry["ratio"] = r
            within = r is not None and r <= prof.error_bound_ratio
            entry["within_bound"] = within
            ok = ok and within
        else:
            entry["within_bound"] = None   # nothing to gate on
        phases[name] = entry

    # join per-bucket predictions against the bucket/cohort span walls
    bwalls = _bucket_walls_us(doc)
    for b in pred["buckets"]:
        if b["kind"] == "poa":
            key = ("poa", b["depth"], b["class"])
        else:
            key = ("align", b["tier"], b.get("cap", 0))
        us = bwalls.get(key)
        if us is not None:
            b["measured_s"] = us / 1e6
            b["error_pct"] = _err_pct(b["predicted_s"], us / 1e6)

    dropped = (doc.get("otherData") or {}).get("dropped_events", 0)
    # Pipelined runs overlap phase.align / phase.poa in wall time; the
    # per-phase measured walls above are summed span durations (work
    # time), so the prediction join stays valid — the overlap is surfaced
    # so a reader knows the phases did not execute back to back.
    overlaps = {k: round(v / 1e6, 6)
                for k, v in phase_overlaps_us(doc).items()}
    return {
        "profile": prof.name,
        "error_bound_ratio": prof.error_bound_ratio,
        "phases": phases,
        "buckets": pred["buckets"],
        **({"phase_overlap_s": overlaps} if overlaps else {}),
        "dropped_events": dropped,
        "ok": ok,
    }


# -- bench.py integration --------------------------------------------------

def bench_cost_model(snapshot: Optional[dict], phase_wall: Dict[str, float],
                     profile_name: str = "auto",
                     platform: Optional[str] = None,
                     n_devices: Optional[int] = None,
                     device_kind: Optional[str] = None) -> Optional[dict]:
    """The `cost_model` stamp for a bench JSON entry: predicted vs
    measured per modeled phase, error %%, and the profile used.  Returns
    None when the run collected no metrics (cost model disarmed).
    `n_devices` threads through to predict_from_counters (None = infer
    from shard counters on device profiles)."""
    if not snapshot or not isinstance(snapshot.get("counters"), dict):
        return None
    prof = resolve_profile(profile_name, platform, device_kind)
    pred = predict_from_counters(snapshot["counters"], prof,
                                 n_devices=n_devices)
    out = {"profile": prof.name, "n_devices": pred["n_devices"],
           "phases": {}}
    ok = True
    for span_name, row in pred["phases"].items():
        report_name = PHASE_ALIASES.get(span_name, span_name)
        meas = phase_wall.get(report_name)
        p_s = row["predicted_s"]
        entry = {"predicted_s": round(p_s, 4),
                 "measured_s": meas,
                 "serial_steps": round(row.get("serial_steps", 0.0), 1),
                 "verdict": row["verdict"]}
        if meas and p_s > 0.0:
            entry["error_pct"] = round(_err_pct(p_s, meas), 1)
            r = _ratio(p_s, meas)
            entry["within_bound"] = (r is not None
                                     and r <= prof.error_bound_ratio)
            ok = ok and entry["within_bound"]
        out["phases"][report_name] = entry
    out["ok"] = ok
    return out


# -- rendering -------------------------------------------------------------

def _fmt_si(v: Optional[float]) -> str:
    if v is None:
        return "-"
    if v == 0:
        return "0"
    mag = int(math.floor(math.log10(abs(v)) / 3)) if v else 0
    mag = max(0, min(mag, 4))
    return f"{v / 1000 ** mag:.3g}{('', 'k', 'M', 'G', 'T')[mag]}"


def render_model(rows: List[dict], prof: MachineProfile) -> str:
    lines = [f"machine profile: {prof.name} "
             f"(clock {prof.clock_hz / 1e9:.2f} GHz, "
             f"peak {_fmt_si(prof.peak_flops)}FLOP/s, "
             f"HBM {_fmt_si(prof.hbm_bytes_per_s)}B/s, "
             f"serial step {prof.serial_step_s * 1e6:.2f} us)",
             f"{'kernel':<22s} {'flops':>8s} {'bytes':>8s} "
             f"{'steps':>8s} {'wall':>10s} {'cycles':>9s}  verdict"]
    for r in rows:
        if r["kind"] == "poa":
            name = f"poa.{r['tier']} d{r['depth']} c{r['class']}"
        else:
            name = f"align.{r['tier']} c{r['cap']} b{r['band']}"
        lines.append(
            f"{name:<22s} {_fmt_si(r['flops']):>8s} "
            f"{_fmt_si(r['hbm_bytes']):>8s} "
            f"{_fmt_si(r['serial_steps']):>8s} "
            f"{r['predicted_s'] * 1e3:>8.3f}ms "
            f"{_fmt_si(r['predicted_cycles']):>9s}  {r['verdict']}")
    return "\n".join(lines)


def render_validation(v: dict) -> str:
    lines = [f"cost-model validation (profile {v['profile']}, "
             f"declared bound {v['error_bound_ratio']:.1f}x)"]
    if v["dropped_events"]:
        lines.append(f"WARNING: trace dropped {v['dropped_events']} "
                     f"span(s) past the bounded buffer — measured walls "
                     f"below may be incomplete")
    lines.append("-- phases " + "-" * 48)
    for name, row in sorted(v["phases"].items()):
        meas = row.get("measured_s")
        err = row.get("error_pct")
        gate = row.get("within_bound")
        mark = ("ok" if gate else "PAST BOUND") if gate is not None \
            else "not gated"
        lines.append(
            f"  phase.{name:<10s} predicted {row['predicted_s']:>9.3f}s  "
            f"measured {'-' if meas is None else f'{meas:9.3f}s'}  "
            f"err {'-' if err is None else f'{err:+7.1f}%'}  "
            f"[{row['verdict']}] {mark}")
    if v.get("phase_overlap_s"):
        lines.append("-- phase overlap (pipelined run) " + "-" * 25)
        for k, s in sorted(v["phase_overlap_s"].items()):
            lines.append(f"  {k:<18s} {s:9.3f}s concurrent")
    if v["buckets"]:
        lines.append("-- buckets " + "-" * 47)
        for b in v["buckets"]:
            if b["kind"] == "poa":
                name = f"poa d{b['depth']} c{b['class']}"
                extra = f" x{b['windows']}" if b.get("windows") else ""
            elif b["kind"] == "banded":
                name = f"banded {b['phase']}"
                extra = ""
            else:
                name = f"align {b['tier']}" + (
                    f" c{b['cap']}" if b.get("cap") else "")
                extra = ""
            meas = b.get("measured_s")
            err = b.get("error_pct")
            lines.append(
                f"  {name:<18s}{extra:<6s} cells {_fmt_si(b['cells']):>7s} "
                f"pred {b['predicted_s'] * 1e3:>9.2f}ms "
                f"meas {'-' if meas is None else f'{meas * 1e3:9.2f}ms'} "
                f"err {'-' if err is None else f'{err:+6.0f}%'} "
                f"[{b['verdict']}]")
    verdict = "OK" if v["ok"] else "PREDICTION ERROR PAST DECLARED BOUND"
    lines.append(f"verdict: {verdict}")
    return "\n".join(lines)
