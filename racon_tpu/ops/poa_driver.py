"""Consensus-phase driver: packs windows into padded, depth-bucketed device
batches, runs the batched POA kernel, trims and installs results, and
re-runs anything the device rejected on the host POA engine.

Mirrors the reference's CUDA polish orchestration
(/root/reference/src/cuda/cudapolisher.cpp:216-378): depth cap per window
(MAX_DEPTH_PER_WINDOW=200, :226), per-entry rejection of oversized layers
(cudabatch.cpp:141-160), failed windows re-polished on the host
(:354-378), and the host-side trim identical to the CPU path
(cudabatch.cpp:230-256).

Graph capacity comes in rungs (NODE_RUNGS).  The base rung is
RACON_TPU_NODE_FACTOR x the window class (3 x: 1536 node slots at -w
500), which holds a long-read window up to ~55 layers of the ONT profile
on a polished draft, or ~33 of 17 % reads on a raw layout; the upper
rung is UPPER_NODE_FACTOR x (5 x: 2560), sized for DEPTH_CAP layers.  A
window's rung is chosen before any kernel runs, from what the job tells
of it (node_estimate: its layers' bases and how many of them its
alignments put off the backbone, read against NODE_ENVELOPE), and is part
of the bucket key (depth bucket, window class, rung); a window that
outgrows its rung all the same is redone on the host and counted by
cause (poa.windows.overflow.*) and as a miss of the rule
(poa.windows.rung.miss.d<bucket>).

Failure handling runs through the explicit degradation lattice
(racon_tpu/resilience/lattice.py): tiers ls -> xla -> host, with
per-tier bounded retry, a per-device-call watchdog, and batch bisection
so one poisoned window is quarantined to the host instead of demoting the
whole run a tier.  Every seam carries a named fault-injection point
(resilience/faults.py) so each edge is deterministically testable in CI.
"""

from __future__ import annotations

import functools
import sys
import time
from typing import List

import numpy as np

from .. import config, obs
from ..resilience import faults
from ..resilience import lattice as rl
from ..resilience.journal import replay_windows
from ..resilience.report import PhaseReport
from . import band as _band
from . import poa
from .batch_exec import BatchExecutor, pipeline_depth as _pipeline_depth
from .encoding import decode, encode

#: The most layers a window packs for the device: the reference's
#: MAX_DEPTH_PER_WINDOW (cudapolisher.cpp:226).  What the cap means, for
#: every configuration (benchmark/reference_cap.py recomputes all of it
#: from a job's files alone; tests/test_cap_cell.py holds the driver to
#: it):
#:
#: (a) Which layers stay.  A window's layers come in the order the host
#:     engine consumes them: by begin on the backbone, as std::sort
#:     (libstdc++'s introsort, not stable) leaves the layers in the order
#:     build_windows added them, the overlaps' order in the file
#:     (rt_window.cpp on the host path, rt_capi.cpp's export on this one:
#:     the same call on the same input).  Of the layers that pass the
#:     length admission (1 to cfg.max_len bases: admit_layers) the device
#:     path packs the first DEPTH_CAP in that order and drops the rest.
#:     Ties, the ~200 window-spanning reads that all begin at 0, are the
#:     introsort permutation's: kept because the golden scenarios were
#:     measured better under it than under a stable order (rt_window.cpp;
#:     the λ data is not on this machine, so the measurement could not be
#:     made again), and recomputable: reference_cap.std_sort_order is the
#:     algorithm transcribed.  The invariant either way: no kept layer
#:     begins after a dropped one.
#: (b) The trim.  A window the device serves is trimmed by the sequences
#:     it admitted (backbone + packed layers; coverage under
#:     admitted // 2 goes at both ends: upstream's accelerator rule,
#:     cudabatch.cpp:139-163,233), a window the host path polishes by its
#:     full count (window.cpp:125-146).  The two agree wherever nothing
#:     was dropped.  A window the kernel gives up (poa.FAIL_CAUSES) is
#:     redone on the host from every layer and trimmed by the full
#:     count, capped or not, as upstream re-polishes a failed window on
#:     its CPU path (cudapolisher.cpp:354-378).
#: (c) The host path (backend "cpu", the benchmark's oracle) takes every
#:     layer, as upstream's CPU path does.
#:
#: Counted once a chunk or a launch: poa.layers.admitted + poa.layers.capped
#: are the layers the windows offered past the length admission,
#: poa.layers.capped.bases the dropped layers' bases, poa.windows.capped
#: the windows that lost a layer, poa.windows.capped.redone those of them
#: the kernel gave up, poa.windows.trim.admitted / .full the windows
#: trimmed under each rule.
DEPTH_CAP = 200
DEPTH_BUCKETS = (8, 32, DEPTH_CAP)

#: Graph-capacity rungs, smallest first: make_config's `rung` indexes
#: _rung_factors(), the counters and span args carry the name.
NODE_RUNGS = ("base", "upper")

#: max_nodes of the upper rung = this x the window class: what
#: DEPTH_CAP layers need.  NODE_ENVELOPE reads 4.10 nodes a backbone
#: base at 200 effective layers of the ONT profile and 4.17 at 240; 5
#: leaves a fifth of room over that.
UPPER_NODE_FACTOR = 5

#: (growth, nodes per backbone base): the most nodes the host engine's
#: graphs held among windows whose growth is under the next key.  A
#: window's **growth** is what the job itself tells before any kernel
#: runs: sqrt(layer bases x stray bases) / backbone length, the geometric
#: mean of how deep the window is covered and how deep it is covered by
#: bases its alignments put off the backbone (another base, or inserted:
#: the native breaking-point walk counts them, Pipeline.window_growth).
#: Layers alone do not tell: 33 layers hold 2.4 nodes a backbone base
#: where reads stray by 7 % (the ONT profile on a 1 % draft) and 2.9
#: where they stray by 18 % (17 % reads on a raw layout that is itself a
#: read).  Strays alone do not either: a backbone error sends every
#: layer astray onto one node.  Against growth the graphs of both fall on
#: one curve (within 3 %): read off rt_poa.cpp's graphs, the oracle both
#: kernels are verified against, over 2 460 windows of 128-500 bp: the
#: ONT profile (5/3/3 % sub/ins/del) at 30x to 100x from SAM and PAF,
#: fragment correction at 30x (unit scores), and a raw layout at 34x
#: (7.7/4.6/4.6 % on both sides); not fitted, not padded.  Past growth 40
#: (138 layers of the ONT profile) the values are the ONT profile's at
#: 160, 200 and 240 layers.  It is concave: match + gap (5 - 8) outscores
#: a mismatch (-4) and a fresh insertion (-8), so the denser the graph,
#: the more of a new layer's strays land on nodes that are already
#: there.  The exact graph of the same layers
#: (benchmark/reference_depth.py) holds half as many nodes again at 100
#: layers; it bounds this from above.
NODE_ENVELOPE = ((0, 1.43), (2, 1.82), (4, 2.20), (6, 2.47), (8, 2.68),
                 (10, 2.85), (12, 2.97), (14, 3.12), (16, 3.26), (18, 3.35),
                 (20, 3.52), (24, 3.67), (28, 3.79), (32, 3.88), (36, 3.90),
                 (44, 3.97), (54, 4.10), (65, 4.17))


def _sanitize():
    """The runtime sanitizer module (lazy: the analysis package must not
    load on the production import path).  Its entry points self-gate on
    RACON_TPU_SANITIZE, so callers just call through."""
    from ..analysis import sanitize
    return sanitize

#: The window lengths the static jaxpr audit traces the consensus kernel
#: grid at: the CLI default (-w 500) and the large-window scenario
#: (-w 1000).  Each maps to its 128-lane class exactly as
#: run_consensus_phase buckets real windows.
AUDIT_WINDOW_LENGTHS = (500, 1000)

#: Declared compile budget for the audited POA grid (audit_grid): one
#: geometry per (depth bucket, window class) on the base rung —
#: len(DEPTH_BUCKETS) x len(AUDIT_WINDOW_LENGTHS) = 6 — plus one per
#: window class on the upper rung: 6 + 2 = 8 — and a program for each
#: width a geometry's launches choose between (audit_widths): two, of
#: thirty-two windows and of sixteen, for the four geometries of class
#: 512; one, of sixteen, for the four of class 1024, where VMEM holds
#: no more on either rung (20.65 / 25.27 MiB of arrays under limits of
#: 42 / 51 MiB): 4 x 2 + 4 = 12.  Revisited on purpose when class 1024
#: got its upper rung inside the lockstep kernel (PR 47): the count
#: stays 12, because that rung's one program was the XLA twin's until
#: then and is the kernel's program of sixteen now.
#: Revisited on purpose for the node rungs (PR 35), again when windows
#: of every depth were let climb (PR 41): a climber of at most 32 layers
#: runs in the DEPTH_CAP bucket's upper-rung program, padded in depth,
#: so the upper rung still has one geometry a window class; and for the
#: program of thirty-two (PR 44, 8 -> 12): a launch whose last such
#: program would be half pad or more runs as programs of sixteen
#: (_group_width), so a geometry holds both, built together wherever
#: the geometry is built.  What let the second one in: since PR 42 a
#: program a process does not trace costs its start ~15 ms.  The upper
#: rung's are built by the first job that needs them, not by every
#: process's warm-up, so a process that never sees such a window
#: builds 3 geometries per window class.  A
#: deliberate literal, not a product: widening DEPTH_BUCKETS, the
#: audited window set, the rungs, GROUP_WIDTHS or any geometry change
#: that splits signatures must consciously revisit this number or the
#: jaxpr audit (racon_tpu/analysis) fails tier-1 —
#: silent recompile blow-ups are the single biggest TPU serving-latency
#: cliff.
POA_RECOMPILE_BUDGET = 12


def audit_grid(window_lengths=AUDIT_WINDOW_LENGTHS) -> list:
    """(depth bucket, window class, rung) of every consensus program the
    driver can ask for at these window lengths: each depth bucket on the
    base rung, and the DEPTH_CAP bucket on every rung above it (where
    _consensus_phase puts every window that climbs)."""
    classes = sorted({window_class(max(int(w), 1)) for w in window_lengths})
    grid = [(d, c, 0) for d in DEPTH_BUCKETS for c in classes]
    grid += [(DEPTH_CAP, c, r) for c in classes
             for r in range(1, len(_rung_factors()))]
    return grid


#: windows a batch on a TPU (_batch_size); audit_widths derives the
#: lockstep programs of a geometry at it
TPU_BATCH = 64


def audit_widths(cfg) -> tuple:
    """Group widths of the programs a process can build for one geometry
    of audit_grid at a TPU's batch on one chip: the lockstep kernel's
    (_group_widths), or 0 alone, the XLA twin's one program, where the
    lockstep kernel does not admit the geometry."""
    return _group_widths(cfg, TPU_BATCH) if _fits_vmem(cfg) else (0,)


def _batch_asked():
    """The batch somebody asked for (RACON_TPU_BATCH_WINDOWS), or None."""
    env = config.get_raw("RACON_TPU_BATCH_WINDOWS")
    return max(1, int(env)) if env else None


def _batch_size() -> int:
    return _batch_asked() or (TPU_BATCH if _platform() == "tpu" else 4)


def _band_active(kind: str) -> bool:
    """Banded POA dispatch: RACON_TPU_BAND on and the Pallas tier serving
    (the XLA twin and the host floor always run flat — they are the
    byte-identity oracles the verify-and-widen ladder bottoms out on)."""
    return kind == "ls" and _band.enabled()


def _initial_poa_band(wx, keep, cfg):
    """w₀ (half-band) for a window: the worst admitted layer's
    length-vs-span delta plus the slack knob; None (flat) when the band
    would not be meaningfully narrower than the full DP row."""
    if not keep:
        return None
    delta = max(abs(int(wx.lens[j]) - (int(wx.ends[j]) - int(wx.begins[j])))
                for j in keep)
    w0 = delta + _band.slack()
    return w0 if 2 * w0 + 1 < cfg.max_len // 2 else None


def _shard_n(B: int) -> int:
    """Mesh shards this driver dispatches a B-window batch over (1 =
    single device: sharding off, demoted, batch too small, or a 1-wide
    batch axis)."""
    from ..parallel.partitioner import get_partitioner

    part = get_partitioner()
    return part.batch_axis_size if part.will_shard(B) else 1


def _device_batch(use_pallas: bool) -> int:
    """Batch size for the kernel geometry, padded UP to a mesh multiple
    when the batch will shard (the old round-DOWN spilled remainder
    windows to the slow path; pad rows are 1-base/0-layer windows and
    show up in `shard.pad_rows`); with the Pallas tier on, the lockstep
    kernel additionally needs the per-shard batch to be a multiple of
    its sublane group G (the XLA twin takes any batch).  The batch
    does not follow the kernel's group width, the width follows the
    batch and what a launch holds of it (_group_width): 64 windows run
    as programs of thirty-two or sixteen; a batch of 8 somebody asked
    for stays 8.  The batch follows the mesh: a TPU's own batch
    (nobody asked for one) that the lockstep kernel will run over m
    shards gives every shard at least one widest program,
    m x GROUP_WIDTHS[0] x G rows, so 64 on one chip and on two, 128 on
    four, 256 on eight; a launch's real rows are then split evenly over
    the shards (_mesh_order)."""
    B = _batch_size()
    m = _shard_n(B)
    if use_pallas:
        from .poa_pallas_ls import G
        if m > 1 and _batch_asked() is None and _platform() == "tpu":
            B = max(B, m * GROUP_WIDTHS[0] * G)
        q = G * m
        return max(1, (B + q - 1) // q) * q
    return ((B + m - 1) // m) * m


def _node_factor() -> int:
    """The base rung: max_nodes = factor * window_length for every
    window the estimate says it holds, which is every window of a 30x
    job.  The default 3 matches the geometry every recorded pin was
    measured under; repeat-dense windows (4 of λ's 96) overflow it and
    fall back to the host — the reference's per-entry capacity
    rejection is the analogous knob
    (/root/reference/src/cuda/cudabatch.cpp:141-160).  The upper rung
    is not a knob: UPPER_NODE_FACTOR, derived from NODE_ENVELOPE at
    DEPTH_CAP."""
    return max(1, config.get_int("RACON_TPU_NODE_FACTOR"))


def _rung_factors() -> tuple:
    """max_nodes / window class of each rung, smallest first: the knob's
    base rung, then the upper one unless the knob already reaches it."""
    base = _node_factor()
    return (base, UPPER_NODE_FACTOR) if base < UPPER_NODE_FACTOR else (base,)


def window_growth(bb_len: int, layer_bytes: int, stray_bytes: int) -> float:
    """NODE_ENVELOPE's key for a window: the geometric mean of its layer
    bases and its stray bases, per backbone base."""
    return float(np.sqrt(float(layer_bytes) * float(stray_bytes))
                 / max(bb_len, 1))


def node_estimate(bb_len: int, layer_bytes: int, stray_bytes: int) -> int:
    """Nodes a long-read window's graph will hold at most, before any
    kernel runs: the backbone times NODE_ENVELOPE at the window's growth
    (the entry at or under it: what the table says, no more)."""
    growth = window_growth(bb_len, layer_bytes, stray_bytes)
    keys, values = zip(*NODE_ENVELOPE)
    at = max(int(np.searchsorted(keys, growth, side="right")) - 1, 0)
    return int(np.ceil(bb_len * values[at]))


def window_class(bb_len: int) -> int:
    """Kernel-geometry class for a backbone length: ceil to the 128-lane
    grid. Windows bucket by (depth, class) so one long-window target in a
    mixed run no longer inflates every bucket's geometry — short windows
    pay their own class's DP ranges, not the global maximum's."""
    return max(128, (bb_len + 127) // 128 * 128)


def make_config(window_length: int, depth: int, match: int, mismatch: int,
                gap: int, rung: int = 0) -> poa.PoaConfig:
    def ceil128(x):
        return (x + 127) // 128 * 128

    max_backbone = ceil128(window_length)
    max_len = ceil128(window_length + window_length // 2)
    max_nodes = ceil128(_rung_factors()[rung] * window_length)
    return poa.PoaConfig(max_nodes=max_nodes, max_len=max_len,
                         max_backbone=max_backbone, max_edges=12,
                         depth=depth, match=match, mismatch=mismatch,
                         gap=gap)


def tgs_trim(codes: np.ndarray, cov: np.ndarray, n_seqs: int):
    """Low-coverage end trim (reference: src/window.cpp:125-146)."""
    avg = (n_seqs - 1) // 2
    n = len(codes)
    begin = 0
    while begin < n and cov[begin] < avg:
        begin += 1
    end = n - 1
    while end >= 0 and cov[end] < avg:
        end -= 1
    if begin >= end:
        return codes  # chimeric suspicion: keep untrimmed
    return codes[begin:end + 1]


def run_consensus_phase(pipeline, *, match: int, mismatch: int, gap: int,
                        trim: bool, progress: bool = False,
                        journal=None) -> dict:
    """Device consensus for every eligible window; host for the rest.

    Streaming: a cheap metadata pass (window_info — no bases copied) sizes
    the geometry and buckets windows by depth; window bases are exported
    chunk-by-chunk at pack time, so driver memory is O(batch). Packing of
    chunk N+1 overlaps device execution of chunk N through JAX async
    dispatch — the analogue of the reference's greedy batch fill running
    concurrently with kernel execution
    (/root/reference/src/cuda/cudapolisher.cpp:83-145).  A window the
    device path gives up on (kernel `failed` flag, export error, too few
    admissible layers, surrender, quarantine) goes to the native thread
    pool the moment it is found (_HostFallback) and is redone there while
    the chip runs the next batches; the driver joins the pool once, after
    the last install, and only then writes the host windows' journal
    records and stats, in arrival order.

    Returns stats {device:…, host_fallback:…, backbone:…, failed:…,
    layers_dropped:…, report: PhaseReport} — the report's per-tier served
    counts sum to the window count, clean or fault-injected.

    With `journal` (resilience/journal.py) armed, windows already in the
    journal are replayed up front (served tier "journal") and every
    freshly served window — device, host fallback, or backbone — is
    appended as it is installed, so a crash loses at most the in-flight
    batch.
    """
    fallback = _HostFallback(pipeline, trim)
    try:
        return _consensus_phase(pipeline, fallback, match, mismatch, gap,
                                trim, progress, journal)
    except BaseException:  # noqa: BLE001 — re-raised: drain, not handle
        fallback.drain()    # no pool worker outlives a failing phase
        raise


def _consensus_phase(pipeline, fallback, match, mismatch, gap, trim,
                     progress, journal) -> dict:
    n = pipeline.num_windows()
    report = PhaseReport("consensus",
                         rl.CONSENSUS_TIERS + ("backbone", "journal"))
    report.total = n
    stats = {"device": 0, "host_fallback": 0, "backbone": 0, "failed": 0,
             "layers_dropped": 0, "layers_capped": 0, "windows_capped": 0,
             "report": report}
    # Runtime-sanitizer guard (no-op passthrough when unarmed): flags
    # stats mutations from any thread but this driver thread.
    stats = _sanitize().guard_stats(stats, "poa_driver.run_consensus_phase")

    replayed = replay_windows(pipeline, journal, n, report)

    # Metadata pass: geometry + depth buckets, no layer bytes touched.
    jobs = []          # (window_idx, estimated depth, backbone len, nodes)
    # how far each window's layers stray from its backbone, one crossing;
    # a pipeline that cannot tell keeps its windows on the base rung
    strays = (pipeline.window_growth()[:, 0]
              if hasattr(pipeline, "window_growth") else None)
    with obs.span("poa.metadata", windows=n):
        for i in range(n):
            if i in replayed:
                continue
            (n_seqs, bb_len, _rank, is_tgs, layer_bytes,
             tid) = pipeline.window_info(i)
            k = n_seqs - 1
            if k < 2:
                # <3 sequences incl. backbone: backbone passthrough
                # (reference: src/window.cpp:68-71)
                try:
                    wx = pipeline.export_window(i)
                except Exception as e:  # noqa: BLE001 — export seam
                    fallback.append(i)
                    report.record_quarantine(i, e)
                    continue
                pipeline.set_consensus(i, wx.backbone.tobytes(), False)
                if journal is not None:
                    journal.append_window(i, tid, wx.rank, "backbone",
                                          wx.backbone.tobytes(), False)
                stats["backbone"] += 1
                continue
            # Short accurate reads (racon's NGS windows: mean read under
            # 1 kb) never outgrow the base rung: at DEPTH_CAP layers of
            # 0.8 % substitutions a column holds under two nodes.  The
            # envelope is the long reads'.
            jobs.append((i, min(k, DEPTH_CAP), bb_len,
                         node_estimate(bb_len, layer_bytes, int(strays[i]))
                         if is_tgs and strays is not None else bb_len))
    # per-window ctypes calls (ROADMAP S5), counted once per loop
    obs.count("native.calls.window_info", n - len(replayed))
    report.record_served("backbone", stats["backbone"])

    # windows whose node estimate no rung this class admits holds: they run
    # on the top one and the host redoes those whose graph outgrows it
    beyond = 0
    if jobs:
        use_pallas = _use_pallas()
        B = _device_batch(use_pallas)
        # what the serving kernels were: compiled or interpreted Pallas,
        # and the batch / shard geometry they were dispatched at
        report.extra["kernels"] = {
            "interpreted": use_pallas and _platform() != "tpu",
            "batch": B, "shards": _shard_n(B)}
        # Bucket by (depth, backbone class, node rung) to bound padding
        # waste in all three: a deep window's graph needs node arrays a
        # shallow one would pay for in every whole-array step (_node_rung).
        # Layers dropped at pack time (oversized/empty) only shrink
        # a window's true depth, so a window always fits the bucket its
        # estimate chose; and short windows run in their own 128-grid
        # geometry class instead of the dataset-max geometry (one long
        # target in a mixed run used to inflate every bucket's DP ranges).
        # Note the layer-admission shift that rides along with per-class
        # geometry: a layer is admitted against ITS WINDOW'S class
        # max_len (cfg.max_len = 2x the 128-ceiled backbone class), not
        # the dataset-wide maximum — so a long stray layer over a short
        # backbone is dropped at pack time where the old single-geometry
        # driver would have admitted it.  Dropped layers only thin the
        # POA coverage (consensus still forms; parity with the reference
        # is kept by the golden tests); the count is surfaced as
        # report.extra["layers_dropped_maxlen"] so a serving-mix or
        # accuracy shift on mixed-length datasets is attributable.
        buckets = {}
        capacities = {}    # window class -> its rungs' max_nodes
        for win, depth, bb, est_nodes in jobs:
            bucket = next(b for b in DEPTH_BUCKETS if depth <= b)
            wl_class = window_class(bb)
            if wl_class not in capacities:
                capacities[wl_class] = _rung_capacities(
                    wl_class, use_pallas, match, mismatch, gap)
            rung = _node_rung(est_nodes, capacities[wl_class])
            beyond += est_nodes > capacities[wl_class][-1]
            if rung:
                # a rung above the base has one program a window class,
                # the DEPTH_CAP bucket's, whatever the window's depth
                bucket = DEPTH_CAP
            buckets.setdefault((bucket, wl_class, rung),
                               []).append((win, depth, bb))
        report.extra["rung_windows"] = {
            name: sum(len(b) for (_, _, r), b in buckets.items()
                      if NODE_RUNGS[r] == name) for name in NODE_RUNGS}

        # geometries (cfg, kind) whose kernel already failed, with the
        # cause — seeded from warm-up failures so the measured run never
        # retries a kernel the warm-up proved dead
        dead_geoms = dict(_WARM_DEAD)
        # The shared executor (ops/batch_exec.py) owns the in-flight
        # queue: JAX dispatch is async, so with depth Q the host
        # packs/exports chunks N+1..N+Q while chunk N executes — the
        # analogue of the reference's continuous batch fill running
        # concurrently with kernel execution (cudapolisher.cpp:83-145).
        # This driver is only the bucket policy on top of it.
        ops = _ConsensusOps(pipeline, B, trim, stats, fallback, report,
                            journal, dead_geoms)
        executor = BatchExecutor(ops, report=report)
        ops.queued = executor.in_flight
        # windows in a class under the job's largest: the targets' tails
        # (one per contig; one per read in fragment correction)
        nominal = max(c for _, c, _ in buckets)
        obs.count("poa.windows.tail", sum(
            len(b) for (_, c, _), b in buckets.items() if c < nominal))
        for (depth_bucket, wl_class, rung), bucket_jobs in sorted(
                buckets.items()):
            obs.count(f"poa.windows.d{depth_bucket}.c{wl_class}",
                      len(bucket_jobs))
            # Measured-cell counter for the cost model (obs/costmodel.py):
            # sum of (admitted depth x class) over the bucket's windows —
            # the serial-step count at graph growth 1.  True depth, not
            # the bucket cap: padding layers are all-zero rows the model
            # must not bill as DP work.
            obs.count(f"poa.cells.d{depth_bucket}.c{wl_class}",
                      sum(d for _, d, _ in bucket_jobs) * wl_class)
            # Bucket spans cover submit-side work; with pipelining a
            # chunk of bucket X may *drain* inside bucket Y's span — the
            # async-dispatch overlap the trace is there to make visible.
            with obs.span("poa.bucket", depth=depth_bucket,
                          wl_class=wl_class, rung=NODE_RUNGS[rung],
                          windows=len(bucket_jobs)):
                cfg = make_config(wl_class, depth_bucket, match, mismatch,
                                  gap, rung)
                entry_kind = _pick_tier(cfg, use_pallas)
                # a tier the warm-up proved dead is skipped below
                # without a retry; the demotion still belongs in this
                # run's report
                kind = entry_kind
                while (cfg, kind) in _WARM_DEAD:
                    nxt = _next_tier(kind)
                    report.record_degrade(kind, nxt, _WARM_DEAD[(cfg, kind)])
                    kind = nxt
                # (Per-bucket depth is kept deliberately: the fused
                # kernel's VMEM footprint is depth-independent now, but
                # packing and host->device transfer scale with the padded
                # depth — a single DEPTH_CAP geometry would ship ~25x
                # zeros for the shallow buckets on every chunk to save
                # compiles that the lru + persistent compilation caches
                # already amortize.)
                # Sequential loops run lock-step across the batch, so keep
                # batches depth-homogeneous — and length-homogeneous
                # within equal depth: a lockstep program's DP range is the
                # union over its 8 or 16 windows, so mixing a short window
                # into a long program bills it the long program's ranks.
                bucket_jobs.sort(key=lambda job: (job[1], job[2]))
                ctx = _BucketCtx(cfg, entry_kind, NODE_RUNGS[rung])
                for off in range(0, len(bucket_jobs), B):
                    executor.submit(
                        ctx, [win for win, _, _ in bucket_jobs[off:off + B]])
                if progress:
                    print(f"[racon_tpu::poa] bucket depth<={depth_bucket} "
                          f"len<={wl_class} nodes<={cfg.max_nodes}: "
                          f"{len(bucket_jobs)} windows", file=sys.stderr)
        executor.flush()
        # feeder split: host pack wall vs blocked kernel wall, stamped
        # for bench.py's machine-checkable criterion
        executor.stamp_walls(report)

    # the key at every job, a zero too
    obs.count("poa.windows.rung.beyond", beyond)
    t0 = time.perf_counter()
    with obs.span("poa.host_fallback", windows=len(fallback)):
        fallback.join(journal, stats)
    obs.count("native.calls.consensus_cpu_one", len(fallback))
    if journal is not None:
        obs.count("native.calls.window_info", len(fallback))
    report.add_wall("host", time.perf_counter() - t0)
    report.record_served("host", stats["host_fallback"])
    report.extra["device_rejected"] = stats["failed"]
    # layers dropped by this class's max_len admission (per-class geometry
    # change, ADVICE.md): attributes serving-mix shifts on mixed-length
    # datasets
    report.extra["layers_dropped_maxlen"] = stats["layers_dropped"]
    # what DEPTH_CAP dropped from the windows the device path packed
    report.extra["capped_windows"] = stats["windows_capped"]
    report.extra["capped_layers"] = stats["layers_capped"]
    return stats


class _HostFallback:
    """The windows the device path gave up on, redone on the host while
    the chip runs on.  The five producers see a list (`append`, `extend`);
    each append hands the window to the *native* thread pool at once
    (Pipeline.consensus_cpu_submit: a pool worker owns its aligner slot,
    whereas every outside thread shares one, so Python threads calling
    consensus_cpu_one would corrupt each other) and remembers the order
    of arrival.  `join` waits for the pool once, then writes what the
    serial loop wrote per window, on the calling thread and in that
    order: the journal's "host" record and stats["host_fallback"].  An
    empty fallback makes no native call.  `trim`: the job trims its
    consensus, so where its windows are long reads' every window polished
    here is trimmed by its full count (DEPTH_CAP's rule (b))."""

    def __init__(self, pipeline, trim: bool = False):
        self._pipeline = pipeline
        self._trim = trim
        self._order: List[int] = []

    def __len__(self) -> int:
        return len(self._order)

    def append(self, i: int) -> None:
        self._pipeline.consensus_cpu_submit(i)
        self._order.append(i)

    def extend(self, idxs) -> None:
        for i in idxs:
            self.append(i)

    def join(self, journal, stats) -> List[int]:
        """Wait for every submitted window (a failed one raises here),
        write their records; returns the windows in arrival order."""
        order, pipeline = self._order, self._pipeline
        if not order:
            # the key at every job, a zero too, beside .trim.admitted
            obs.count("poa.windows.trim.full", 0)
            return order
        polished, hidden = pipeline.consensus_cpu_join(order)
        # how often the overlap engaged: host work that had ended before
        # the driver came to wait for it, and the rest
        obs.count("poa.fallback.hidden", hidden)
        obs.count("poa.fallback.exposed", len(order) - hidden)
        # a job's windows are of one type (rt_pipeline.cpp)
        trims = self._trim and pipeline.window_info(order[0])[3]
        obs.count("poa.windows.trim.full", sum(polished) if trims else 0)
        for i, was_polished in zip(order, polished):
            if journal is not None:
                _, _, rank, _, _, tid = pipeline.window_info(i)
                journal.append_window(i, tid, rank, "host",
                                      pipeline.get_consensus(i),
                                      was_polished)
            stats["host_fallback"] += 1
        return order

    def drain(self) -> None:
        """The phase is failing: wait the pool out, write nothing."""
        if self._order:
            try:
                self._pipeline.consensus_cpu_join(())
            except Exception:  # noqa: BLE001 — the phase's own error wins
                pass


def observed_window_lengths(draft_path: str, w: int) -> set:
    """Every window length the consensus phase will actually derive.

    run_consensus_phase buckets kernel geometry by the OBSERVED backbone
    classes, not the nominal -w (the metadata pass above). Windows are
    fixed-size chunks of draft contigs (rt_pipeline.cpp window build), so
    the set is computable from the draft FASTA alone: per contig, w for
    the full chunks plus the tail remainder. Warming only the nominal w
    would leave the tail-class geometries to compile inside the timed
    pass.  Shared by bench.py's prewarm and the pipelined polisher's
    warm-up thread (polisher.py)."""
    import gzip

    lens = set()

    def add(contig_len):
        if contig_len <= 0:
            return
        if contig_len >= w:
            lens.add(w)
        rem = contig_len % w
        if contig_len < w:
            lens.add(contig_len)
        elif rem:
            lens.add(rem)

    opener = gzip.open if draft_path.endswith(".gz") else open
    cur = 0
    with opener(draft_path, "rt") as f:
        for line in f:
            if line.startswith(">"):
                add(cur)
                cur = 0
            else:
                cur += len(line.strip())
    add(cur)
    return lens or {1}


# (cfg, kind) -> the exception that killed that kernel during warm-up;
# consulted by run_consensus_phase so the measured run dispatches
# straight to the tier the warm-up landed on instead of re-paying a
# compile-and-fail, and records the demotion in its report.
_WARM_DEAD: dict = {}


def warm_geometries(window_lengths, match: int, mismatch: int,
                    gap: int) -> None:
    """Compile (or load from the persistent cache) every kernel geometry
    the consensus phase can pick for these window lengths (an int or an
    iterable of observed backbone lengths — each maps to its 128-grid
    class, exactly as run_consensus_phase buckets them).

    One all-padding batch per program of a (depth bucket, class), so one
    a width of the lockstep kernel (_group_widths), runs in milliseconds
    but forces the full compile — so a benchmark's measured pass never
    pays compile time, whatever depth/length mix the real dataset
    produces. Tiers that fail here are recorded in _WARM_DEAD so the
    measured run skips them."""
    if isinstance(window_lengths, int):
        window_lengths = [window_lengths]
    classes = sorted({window_class(max(w, 1)) for w in window_lengths})
    use_pallas = _use_pallas()
    B = _device_batch(use_pallas)
    import itertools
    for depth_bucket, wl_class in itertools.product(DEPTH_BUCKETS, classes):
        cfg = make_config(wl_class, depth_bucket, match, mismatch, gap)
        kind = _pick_tier(cfg, use_pallas)
        while kind != "host":
            kernel, kind = _live_tier(cfg, B, kind, _WARM_DEAD)
            if kind == "host":
                break
            try:
                faults.check(f"poa.run.{kind}", ())
                pallas = kind == "ls"
                banded = _band_active(kind)
                packed = _pack([], cfg, B)
                for program in _programs(kernel):
                    _unpack(_submit(program, packed, pallas, banded),
                            pallas, banded)
                break
            except Exception as e:  # noqa: BLE001 — same degrade
                # philosophy as run_consensus_phase: a Mosaic failure
                # on one geometry must not abort the caller — warm
                # the tier it will actually fall back to, and
                # remember the failure so the measured run doesn't
                # retry it
                _WARM_DEAD[(cfg, kind)] = e
                nxt = _next_tier(kind)
                _warn_degrade(e, nxt)
                kind = nxt


def _pick_tier(cfg, use_pallas: bool) -> str:
    """Entry tier for a geometry: the lockstep Pallas kernel if Pallas
    is on and its scratch fits VMEM, else the XLA twin."""
    return "ls" if use_pallas and _fits_vmem(cfg) else "xla"


def _rung_capacities(wl_class: int, use_pallas: bool, match: int,
                     mismatch: int, gap: int) -> tuple:
    """max_nodes of the rungs a window of this class may climb, smallest
    first.  With the Pallas tier on, a rung whose node arrays the
    lockstep kernel cannot hold in VMEM at one group (_fits_vmem: the
    upper rung past class 2560; class 1024, -w 1000, gets (3072, 5120))
    is left out: such windows stay on the rung below, the ones that
    outgrow it go to the host, and poa.windows.rung.beyond counts the
    windows whose estimate no rung of their class holds."""
    caps = []
    for rung in range(len(_rung_factors())):
        cfg = make_config(wl_class, DEPTH_CAP, match, mismatch, gap, rung)
        if rung and use_pallas and not _fits_vmem(cfg):
            break
        caps.append(cfg.max_nodes)
    return tuple(caps)


def _node_rung(est_nodes: int, capacities) -> int:
    """Index into NODE_RUNGS of the rung a window runs on: the smallest
    that holds its node estimate, the top one if none does (a window
    that overflows the rung it ran on goes to the host, counted).  A
    window of any depth may climb: 30 layers that stray by a sixth on a
    raw backbone need what 60 of the ONT profile do.  A climber runs in
    the DEPTH_CAP bucket's program (the caller's rule), so the programs
    a process can build stay at POA_RECOMPILE_BUDGET."""
    return next((r for r, cap in enumerate(capacities) if est_nodes <= cap),
                len(capacities) - 1)


def _next_tier(kind: str) -> str:
    """The lattice tier below `kind`."""
    return "xla" if kind == "ls" else "host"


def _live_tier(cfg, B, kind, dead_geoms, report=None):
    """Kernel for the best LIVE tier at or below `kind` for this geometry,
    stepping past tiers proven dead and tiers whose kernel build fails
    (compile failures demote exactly like runtime failures).  Returns
    (kernel, kind); kernel is None iff kind == 'host'."""
    while kind != "host":
        if (cfg, kind) in dead_geoms:
            kind = _next_tier(kind)
            continue
        try:
            return _build_kernel(cfg, B, kind == "ls"), kind
        except Exception as e:  # noqa: BLE001 — compile seam
            dead_geoms[(cfg, kind)] = e
            nxt = _next_tier(kind)
            if report is not None:
                report.record_failure(kind, e)
                report.record_degrade(kind, nxt, e)
            _warn_degrade(e, nxt)
            kind = nxt
    return None, "host"


def _warn_degrade(e, to_kind: str) -> None:
    tier = "the XLA kernel" if to_kind == "xla" else "the host engine"
    print(f"[racon_tpu::poa] WARNING: kernel tier failed "
          f"({type(e).__name__}: {e}); falling back to {tier}",
          file=sys.stderr)


class _BucketCtx:
    """Per-(depth, class, rung) bucket context the executor threads
    through the ops hooks: the geometry, its entry tier, its rung's name
    and the kernel handle the most recent live_tier resolution built."""

    __slots__ = ("cfg", "entry_kind", "rung", "kernel")

    def __init__(self, cfg, entry_kind, rung=NODE_RUNGS[0]):
        self.cfg = cfg
        self.entry_kind = entry_kind
        self.rung = rung
        self.kernel = None


class _ConsensusOps:
    """poa_driver's hooks for the shared executor (ops/batch_exec.py):
    bucket policy, pack/submit/unpack, and the journal/sanitizer/report
    seams.  Failure semantics are exactly the pre-extraction driver's:
    per tier bounded retry, then batch bisection (a poisoned window is
    quarantined to the host while the rest of the batch stays on the
    device); a batch-independent failure demotes the geometry one tier,
    down to the host floor."""

    span_name = "poa.chunk"
    pack_span = "poa.pack"
    install_span = "poa.install"

    def __init__(self, pipeline, B, trim, stats, fallback, report,
                 journal, dead_geoms):
        self.pipeline = pipeline
        self.B = B
        self.trim = trim
        self.stats = stats
        self.fallback = fallback
        self.report = report
        self.journal = journal
        self.dead_geoms = dead_geoms
        # verify-and-widen ladder state (ops/band.py): window idx ->
        # BandState; _band_retry holds hit windows awaiting the
        # executor's widen loop
        self.band = {}
        self._band_retry = []
        # how many dispatched launches are still out (the executor's
        # queue, set by _consensus_phase); nothing without an executor
        self.queued = lambda: 0

    def _widths(self, chunk, cfg):
        """Per-window half-band widths for _pack (0 = flat), creating
        ladder state on first touch."""
        if not _band.enabled():
            return None
        widths = {}
        for i, wx, keep in chunk:
            st = self.band.get(i)
            if st is None:
                st = _band.BandState(_initial_poa_band(wx, keep, cfg))
                self.band[i] = st
                if st.k:
                    obs.count("band.jobs")
                    if obs.enabled():
                        obs.count("poa.cells.banded",
                                  len(keep) * (2 * st.k + 1))
            widths[i] = st.k or 0
        return widths

    def live_tier(self, ctx, kind):
        # best LIVE tier for this geometry (earlier chunks or the warm-up
        # may have proven tiers dead)
        ctx.kernel, kind = _live_tier(ctx.cfg, self.B,
                                      kind or ctx.entry_kind,
                                      self.dead_geoms, self.report)
        return kind

    def export(self, ctx, idxs):
        return _export_chunk(self.pipeline, idxs, ctx.cfg, self.fallback,
                             self.stats, self.report)

    def pack(self, ctx, chunk):
        # Always pad to B: a dataset-size-dependent final-chunk shape
        # would force an extra jit compile per distinct remainder (padded
        # windows are 1-base/0-layer — free).
        return _pack(chunk, ctx.cfg, self.B, self._widths(chunk, ctx.cfg),
                     self.shard_multiple(ctx, chunk))

    def _launch(self, ctx, kind, packed, n_real):
        """Count and dispatch one packed batch of `n_real` windows;
        returns the device futures and the batch's _mesh_order, which
        is what unpack needs to undo the layout.  A lockstep
        launch runs the program of the width its fullest shard's real
        rows call for (_group_width), out of the kernel the last
        live_tier built (which keyed on the same partitioner state
        shard_multiple reads)."""
        m = self.shard_multiple(ctx, None)
        kernel, groups, raised, slots_all = ctx.kernel, 0, False, None
        layers = None
        if kind == "ls":
            from .poa_pallas_ls import G, vmem_limit_bytes
            groups = _group_width(ctx.cfg, self.B // m, -(-n_real // m))
            kernel = kernel.programs[groups]
            raised = vmem_limit_bytes(ctx.cfg, groups) is not None
            slots_all = _insert_slots_all(packed[3], groups,
                                          ctx.cfg.max_edges)
            layers = _program_layers(packed[3], groups * G)
        _count_launch(n_real, packed, groups, ctx.rung, m, raised,
                      self.queued() > 0)
        return (_submit(kernel, packed, kind == "ls", _band_active(kind),
                        ctx.rung), _mesh_order(n_real, self.B, m), slots_all,
                layers)

    def dispatch(self, ctx, kind, packed, chunk):
        faults.check(f"poa.run.{kind}", [i for i, _, _ in chunk])
        return self._launch(ctx, kind, packed, len(chunk))

    def attempt(self, ctx, kind, sub):
        faults.check(f"poa.run.{kind}", [i for i, _, _ in sub])
        packed = self.pack(ctx, sub)
        return self.unpack(ctx, kind,
                           self._launch(ctx, kind, packed, len(sub)))

    def unpack(self, ctx, kind, launched):
        outs, order, slots_all, layers = launched
        return _unpack(outs, kind == "ls", _band_active(kind), ctx.rung,
                       order, slots_all, layers)

    def span_args(self, ctx, chunk, pipelined):
        return {"windows": len(chunk), "pipelined": pipelined}

    def install(self, ctx, kind, sub, results):
        forced = False
        if _band_active(kind):
            # the widening-exhaustion drill: an armed band.hit fault
            # classifies every banded window as a hit instead of raising,
            # driving the ladder deterministically to its flat floor
            try:
                faults.check("band.hit", [i for i, _, _ in sub])
            except faults.InjectedFault:
                forced = True
        retry = _install(self.pipeline, sub, results, self.trim, self.stats,
                         self.fallback, self.report, kind, self.journal,
                         band_states=self.band,
                         band_cap=ctx.cfg.max_len // 2, force_hit=forced,
                         depth_bucket=ctx.cfg.depth)
        if retry:
            self._band_retry.extend(retry)

    def widen(self, ctx, kind):
        # executor widen hook: hit windows re-dispatched at their widened
        # (or flat, wband=0) band through the same tier
        retry, self._band_retry = self._band_retry, []
        return retry

    def surrender(self, ctx, items, exported):
        if exported:
            self.fallback.extend(i for i, _, _ in items)
        else:
            self.fallback.extend(items)

    def quarantine(self, ctx, item, exc):
        self.fallback.append(item[0])
        self.report.record_quarantine(item[0], exc)

    def demote(self, ctx, kind, cause):
        self.dead_geoms[(ctx.cfg, kind)] = cause
        nxt = _next_tier(kind)
        self.report.record_degrade(kind, nxt, cause)
        _warn_degrade(cause, nxt)
        return nxt

    # -- sharded dispatch (optional executor hooks) ------------------------
    def shard_multiple(self, ctx, chunk):
        # _pack always pads to B, so the executor's pad-to-multiple is a
        # no-op here; returning m>1 is purely the shard-size accounting
        # (and must match the kernel the last live_tier built — _shard_n
        # re-reads the same partitioner state _build_kernel keyed on)
        m = _shard_n(self.B)
        return m if m > 1 and self.B % m == 0 else 1

    def demote_shard(self, ctx, kind, cause):
        if self.shard_multiple(ctx, None) <= 1:
            return False
        from ..parallel.partitioner import get_partitioner

        if get_partitioner().demote(f"{type(cause).__name__}: {cause}"):
            rl.record_shard_demotion(self.report, kind, cause)
        return True


def _use_pallas() -> bool:
    env = config.get_raw("RACON_TPU_PALLAS")
    if env is not None:
        return env == "1"
    import jax
    return jax.devices()[0].platform == "tpu"


def _n_devices() -> int:
    import jax
    return len(jax.devices())


def _platform() -> str:
    import jax
    return jax.devices()[0].platform


#: sublane groups a lockstep program may run, widest first
GROUP_WIDTHS = (4, 2, 1)


def _fits_vmem(cfg, groups: int = 1) -> bool:
    """Whether the lockstep Pallas kernel's VMEM arrays
    (poa_pallas_ls.scratch_bytes) fit at `groups` sublane groups a
    program.  One rule for every width: the scoped-VMEM limit the
    program needs (poa_pallas_ls.vmem_limit_bytes: none where the
    arrays' sum is one the compiler's default 16 MB holds, which it did
    up to 10.85 MiB and refused from 11.5 MiB up; else twice the sum,
    the arrays and as much again for Mosaic's temporaries) may not pass
    half the chip's VMEM (VMEM_CEILING); a program of eight ships with
    a raised limit where it needs one, as the wider ones do (the upper
    rung of classes 896 and 1024, 11.39 / 12.64 MiB a group, and every
    class past 1024: the compiler refuses them under its default).  At
    NODE_FACTOR 3 the rule gives, by class (base rung / upper rung):
    thirty-two windows a program up to 768 / 512, sixteen up to 1536 /
    1280 (-w 1000 is class 1024: 20.65 / 25.27 MiB under limits of 42 /
    51), eight up to 3200 / 2560; past them the XLA twin
    (tests/test_pallas_ls.py and tests/test_deep_cell.py hold the table,
    tests/test_tpu_lowering.py compiles class 1024's rows and the last
    row of each width for a described v5e).  A program the chip's
    compiler refuses all the same demotes its geometry to the XLA twin
    through the lattice (_live_tier), as any build failure does."""
    from . import poa_pallas_ls as ls

    limit = ls.vmem_limit_bytes(cfg, groups)
    return limit is None or limit <= ls.VMEM_CEILING


def _group_widths(cfg, shard_batch: int) -> tuple:
    """The widths a launch of this geometry chooses between
    (_group_width), widest first, so the lockstep programs the geometry
    holds: the widest of GROUP_WIDTHS the per-shard batch divides into
    and VMEM holds and, under a program of thirty-two, the program of
    sixteen.  A function of the window class, the per-shard batch and
    the VMEM sum, nothing else: 64 windows on one chip give (4, 2), 16
    a shard on four chips (2,), a batch of 8 (1,)."""
    from .poa_pallas_ls import G

    fits = [u for u in GROUP_WIDTHS
            if shard_batch % (u * G) == 0 and _fits_vmem(cfg, u)] or [1]
    return tuple(fits[:2] if fits[0] > 2 else fits[:1])


def _group_width(cfg, shard_batch: int, real_rows=None) -> int:
    """Sublane groups U the lockstep programs of one launch run, so
    U x 8 windows under one control flow.  A program costs the same
    whether its groups hold windows or pad rows (insertion alone is
    gated per group), and a program of thirty-two never costs more than
    two of sixteen, so a launch runs at the geometry's widest width
    (_group_widths) where the last such program of its fullest shard
    would be more than half real, and at the next width down where it
    would not (46 real rows: two programs of thirty-two cost what three
    of sixteen do; 8 real rows: one of thirty-two costs half as much
    again as one of sixteen).  `real_rows` is what the launch's fullest
    shard holds: every real row on one chip, ceil(real rows / shards) on
    a mesh, where a launch's real rows are split evenly (_mesh_order);
    None or 0, a batch of pad rows alone, gives the widest.  A geometry
    whose widest program is sixteen windows or eight runs every launch
    at it, as before there was a wider one."""
    from .poa_pallas_ls import G

    widths = _group_widths(cfg, shard_batch)
    last = min(real_rows or 0, shard_batch) % (widths[0] * G)
    if len(widths) == 1 or last == 0 or 2 * last > widths[0] * G:
        return widths[0]
    return widths[1]


class _LockstepPrograms:
    """The lockstep kernel of one geometry: a program a width its
    launches may run at (_group_widths), all built with the geometry.
    Called, it runs the widest, which is what a full batch runs as."""

    __slots__ = ("programs",)

    def __init__(self, programs: dict):
        self.programs = programs

    def __call__(self, *args):
        return self.programs[max(self.programs)](*args)


def _programs(kernel) -> list:
    """Every program behind a kernel handle: the lockstep kernel's one
    a width, the XLA twin itself."""
    if isinstance(kernel, _LockstepPrograms):
        return list(kernel.programs.values())
    return [kernel]


def _build_kernel(cfg, B, use_pallas):
    """Memoization front for _build_kernel_cached: the lockstep Pallas
    kernel or the XLA twin for a B-window batch.  The device topology
    (count + platform) is part of the key: reconfiguring JAX devices
    after a first build must never serve a stale sharded/interpreted
    kernel (ADVICE.md)."""
    kind = "ls" if use_pallas else "xla"
    faults.check(f"poa.compile.{kind}")
    # Banded builds ride the cache key: the flat and banded variants
    # of a geometry are distinct compiled kernels (extra wband input /
    # band_hit output), and the flat one is the ladder's oracle.
    banded = _band_active(kind)
    # Shard count resolved here (not in the cached builder) so the key
    # is explicit: a will_shard flip — knob, demotion, mesh change —
    # can never serve a kernel wrapped for the wrong dispatch mode.
    shard_n = _shard_n(B)
    if shard_n > 1 and B % shard_n:
        shard_n = 1  # geometry was sized for a different mesh; stay local
    for m in ((shard_n, 1) if shard_n > 1 else (1,)):
        # Same build-observability pattern as
        # kernel_cache.device_keyed_cache: a miss is only known after
        # the call, so the span is retroactive.
        misses0 = _build_kernel_cached.cache_info().misses
        t0 = time.monotonic_ns()
        try:
            built = _build_kernel_cached(cfg, B, use_pallas, _n_devices(),
                                         _platform(), m, banded)
        except Exception as e:  # noqa: BLE001 — shard lattice edge
            if m <= 1:
                raise
            # sharded build failed: drop the partitioner to
            # single-device for the rest of the process and rebuild the
            # SAME tier locally (never a tier demotion, never fatal)
            from ..parallel.partitioner import get_partitioner

            if get_partitioner().demote(f"{type(e).__name__}: {e}"):
                rl.record_shard_demotion(None, kind, e)
            continue
        if _build_kernel_cached.cache_info().misses != misses0:
            args = dict(builder=f"poa.{kind}", B=B, shards=m,
                        max_nodes=cfg.max_nodes, depth=cfg.depth)
            if use_pallas:
                # the scoped-VMEM limit each of the geometry's programs
                # is compiled under, by width (0: the compiler's default)
                from .poa_pallas_ls import vmem_limit_bytes
                args["vmem_limit"] = {
                    f"u{u}": vmem_limit_bytes(cfg, u) or 0
                    for u in _group_widths(cfg, B // m)}
            obs.add_complete("kernel.build", t0, time.monotonic_ns(),
                             **args)
            obs.count(f"kernel.builds.poa.{kind}")
        return built


@functools.lru_cache(maxsize=64)
def _build_kernel_cached(cfg, B, use_pallas, n_dev, platform, shard_n=1,
                         banded=False):
    """Single- or multi-device kernel for a B-window batch: the XLA
    twin's one program, or the lockstep kernel's one a width
    (_LockstepPrograms).

    shard_n > 1: batch dim sharded over the partitioner's mesh (the
    production analogue of the reference's multi-GPU batch striping,
    src/cuda/cudapolisher.cpp:228-240, with no collectives) — shard_map
    around the per-shard pallas build, pjit sharding constraints around
    the XLA twin (which partitions transparently).

    Memoized on the full geometry key — including the device topology
    (n_dev, platform) and the shard count: the warm-up's compiled kernel
    IS the measured run's function object, so the in-process jit cache
    hits even when the persistent disk cache can't serve (observed: AOT
    entries compiled under different machine features fail to load and
    silently recompile — minutes per geometry on the CPU twin).
    """
    assert not (use_pallas and not _fits_vmem(cfg)), (
        "caller must check _fits_vmem before requesting the pallas kernel")
    if use_pallas:
        from .poa_pallas_ls import build_lockstep_poa_kernel
        interp = platform != "tpu"

        def program(groups):
            def build(b):
                return build_lockstep_poa_kernel(
                    cfg, interpret=interp, band=banded, groups=groups)(b)

            if shard_n <= 1:
                return build(B)
            from ..parallel.partitioner import get_partitioner
            n_in, n_out = (10, 7) if banded else (9, 6)
            sharded = get_partitioner().shard_build(build, B, n_in, n_out)
            # _device_batch divides B
            assert sharded is not None, (B, shard_n)
            return sharded

        return _LockstepPrograms(
            {u: program(u) for u in _group_widths(cfg, B // shard_n)})
    kernel = poa.build_poa_kernel(cfg)
    if shard_n <= 1:
        return kernel
    from ..parallel.partitioner import get_partitioner
    return get_partitioner().partition(
        kernel, in_axes=[("windows",)] * 9, out_axes=("windows",))


def admit_layers(lens, max_len: int) -> list:
    """Indices of the layers the device path can pack, in the export's
    order: 1 to `max_len` bases (the window class's geometry).  The
    first DEPTH_CAP of them are packed (_export_chunk)."""
    return [j for j in range(len(lens)) if 0 < lens[j] <= max_len]


def _export_chunk(pipeline, idxs, cfg, fallback, stats=None, report=None):
    """Export window bases for one chunk; apply per-layer admission.

    Returns [(window_idx, export, kept layer indices)] — windows the device
    can't represent go straight to the host fallback list, and an export
    failure (the `window.export` seam) quarantines just that window.
    """
    chunk = []
    # this chunk's admitted layers that DEPTH_CAP dropped, their bases,
    # the windows that lost one, and the bases of the layers that are
    # packed
    capped = capped_bases = capped_windows = bases = 0
    obs.count("native.calls.export_window", len(idxs))
    for i in idxs:
        try:
            wx = pipeline.export_window(i)
        except Exception as e:  # noqa: BLE001 — export seam
            fallback.append(i)
            if report is not None:
                report.record_quarantine(i, e)
            continue
        keep = admit_layers(wx.lens, cfg.max_len)
        # Per-class geometry admission (ADVICE.md): a layer longer than
        # THIS class's max_len is dropped here where the old dataset-max
        # geometry admitted it; counted (report.extra) so serving-mix
        # shifts on mixed-length datasets stay attributable.
        if stats is not None:
            stats["layers_dropped"] += int(
                sum(1 for ln in wx.lens[:DEPTH_CAP] if ln > cfg.max_len))
        if len(keep) < len(wx.lens[:DEPTH_CAP]) and len(keep) < 2:
            fallback.append(i)
            continue
        if len(keep) > DEPTH_CAP:
            # DEPTH_CAP's rule (a): the export's order is the host
            # engine's, so the first DEPTH_CAP are the ones that stay
            keep, dropped = keep[:DEPTH_CAP], keep[DEPTH_CAP:]
            wx.capped = len(dropped)
            capped += len(dropped)
            capped_bases += int(wx.lens[dropped].sum())
            capped_windows += 1
        bases += int(wx.lens[keep].sum())
        chunk.append((i, wx, keep))
    obs.count("poa.layers.capped", capped)
    obs.count("poa.layers.capped.bases", capped_bases)
    obs.count("poa.windows.capped", capped_windows)
    obs.count("poa.layers.bases", bases)
    if stats is not None:
        stats["layers_capped"] += capped
        stats["windows_capped"] += capped_windows
    return chunk


def _mesh_order(n_real: int, rows: int, shards: int):
    """Where a launch's rows sit in a batch of `rows` that `shards`
    shards split into contiguous runs: order[p] is the row of chunk item
    p for p < n_real, and the pad rows follow in ascending order, so
    `order` is a permutation of the batch.  The real rows are split
    evenly over the shards, each shard's share a contiguous run of the
    chunk's (depth, length) order at the head of the shard, its pad rows
    behind it: shares differ by at most one row, so no shard runs a
    wider or a longer program than it would packed real first, and none
    idles while another runs two (46 rows over 4 x 32: 12 / 12 / 11 /
    11, one program of sixteen a chip, where real first is 32 / 14 / 0 /
    0); a full batch comes out real first as it stands.  None on one
    shard, where real first is the layout.  The one place a row's slot
    is decided: _pack writes through it, _unpack reads back through it,
    and everything between and after (install, journal, parity sample,
    bisection, the band ladder) indexes by chunk position."""
    if shards <= 1:
        return None
    per_shard = rows // shards
    share, extra = divmod(n_real, shards)
    slot = np.arange(rows).reshape(shards, per_shard)
    real = (np.arange(per_shard)
            < (share + (np.arange(shards) < extra))[:, None])
    return np.concatenate([slot[real], slot[~real]])


def _pack(chunk, cfg, pad_to=None, band_widths=None, shards: int = 1):
    """The chunk's windows as one padded batch, chunk item p on row p or,
    where the batch will run over `shards` > 1 shards, on row
    _mesh_order(...)[p]."""
    B = pad_to if pad_to is not None else len(chunk)
    order = _mesh_order(len(chunk), B, shards)
    bb = np.zeros((B, cfg.max_backbone), dtype=np.uint8)
    bbw = np.zeros((B, cfg.max_backbone), dtype=np.int32)
    bb_len = np.ones(B, dtype=np.int32)   # padded windows: 1-base backbone
    n_layers = np.zeros(B, dtype=np.int32)
    seqs = np.zeros((B, cfg.depth, cfg.max_len), dtype=np.uint8)
    ws = np.zeros((B, cfg.depth, cfg.max_len), dtype=np.int32)
    lens = np.zeros((B, cfg.depth), dtype=np.int32)
    begins = np.zeros((B, cfg.depth), dtype=np.int32)
    ends = np.zeros((B, cfg.depth), dtype=np.int32)
    wband = np.zeros(B, dtype=np.int32)   # 0 = flat (padded rows stay 0)

    for p, (i, wx, keep) in enumerate(chunk):
        bi = p if order is None else order[p]
        if band_widths:
            wband[bi] = band_widths.get(i, 0)
        L = len(wx.backbone)
        bb[bi, :L] = encode(wx.backbone)
        bbw[bi, :L] = wx.backbone_weights
        bb_len[bi] = L
        K = len(keep)
        n_layers[bi] = K
        if K == 0:
            continue
        # Encode the window's whole layer blob ONCE, then contiguous
        # slice copies into flat row views — ~2x over the per-slice loop
        # with an encode() per layer at production layer sizes (and the
        # measured winner over a fancy-index gather/scatter, whose index
        # arrays cost more memory traffic than the bases themselves).
        # The reference fills batches in tight C++ under a mutex
        # (/root/reference/src/cuda/cudapolisher.cpp:83-145).
        enc = encode(wx.bases)
        w_all = wx.weights
        offsets = np.concatenate([[0], np.cumsum(wx.lens)]).astype(np.int64)
        kp = np.asarray(keep, dtype=np.int64)
        lens_k = wx.lens[kp].astype(np.int64)
        ML = cfg.max_len
        sflat = seqs[bi].reshape(-1)
        wflat = ws[bi].reshape(-1)
        for li in range(K):
            o = offsets[kp[li]]
            ll = lens_k[li]
            sflat[li * ML:li * ML + ll] = enc[o:o + ll]
            wflat[li * ML:li * ML + ll] = w_all[o:o + ll]
        lens[bi, :K] = lens_k
        begins[bi, :K] = wx.begins[kp]
        ends[bi, :K] = wx.ends[kp]
    return (bb, bbw, bb_len, n_layers, seqs, ws, lens, begins, ends, wband)


def _program_layers(n_layers, width: int) -> int:
    """The layers the lockstep programs of a launch run, summed over its
    programs: each runs its deepest window's count.  A shard's rows are
    contiguous and a multiple of the program's width, so programs are
    consecutive runs of `width` packed rows; on a mesh the pad rows (0
    layers) sit behind each shard's real rows, not at the end of the
    batch."""
    return int(np.asarray(n_layers).reshape(-1, width).max(axis=1).sum())


def _insert_slots_all(n_layers, groups: int, max_edges: int) -> int:
    """The in-edge slots the node-insertion blocks of a lockstep launch
    sweep where nothing bounds them: `max_edges` a sublane group a layer
    of each program.  The kernel reports what it swept under its bound
    (a group's largest in-edge count, read once a layer) in the same
    unit."""
    from .poa_pallas_ls import G

    return max_edges * groups * _program_layers(n_layers, groups * G)


def _count_launch(n_real, packed, groups: int = 0,
                  rung: str = NODE_RUNGS[0], shards: int = 1,
                  raised: bool = False, behind: bool = False) -> None:
    """One batch on its way to the device: `n_real` rows carry a
    window, the rest pad the batch to its compiled size (and to the
    shard multiple), packed for `shards` shards (_mesh_order), on the
    node rung `rung` (every rung's key at every
    launch, a zero too, so that a job the base rung served alone reads
    0 % upper and not nothing).  A launch whose every row is a window
    counts as full, and it goes out `behind` an earlier launch that is
    still dispatched and not waited for, or finds the device's queue
    empty (the aligner's pair, align_pallas._launch): all three keys
    at every launch, a zero too; the pair sums to poa.launches.
    `groups` is the lockstep kernel's
    group width for this launch (0: the XLA twin serves, which has no
    grid programs): its programs count as wide or narrow, both keys at
    every launch so that a job served by narrow programs alone reads
    0 % wide and not nothing, its real windows count under the one
    width that ran them (every width's key at every launch, for the
    same reason; they sum to poa.rows.real over the lockstep launches),
    and lock-step is billed what it costs: every window of a program
    runs the program's largest layer count.  A lockstep launch over a
    mesh also counts how evenly its real rows lie on the shards: the
    rows, and what the shards would hold if each were as full as the
    fullest (100 % of it where the split is even, 36 % for 46 rows
    packed real first into 4 x 32); nothing on one chip.  `raised`: the
    launch's programs were compiled under a scoped-VMEM limit of their
    own (vmem_limit_bytes), counted at every lockstep launch, a zero too,
    under a prefix of its own (poa_wide_program_share sums every
    counter under poa.programs.)."""
    from .poa_pallas_ls import G

    rows = len(packed[0])
    obs.count("poa.launches")
    obs.count("poa.launches.full", int(n_real == rows))
    obs.count("poa.queue.behind", int(behind))
    obs.count("poa.queue.empty", int(not behind))
    obs.count("poa.rows.real", n_real)
    obs.count("poa.rows.pad", rows - n_real)
    for name in NODE_RUNGS:
        obs.count(f"poa.windows.rung.{name}", n_real if name == rung else 0)
    n_layers = np.asarray(packed[3])
    obs.count("poa.layers.admitted", int(n_layers.sum()))
    width = groups * G                 # windows a grid program
    programs = rows // width if width else 0
    obs.count("poa.programs.wide", programs if groups > 1 else 0)
    obs.count("poa.programs.narrow", programs if groups == 1 else 0)
    if width:
        obs.count("poa.vmem.programs.raised", programs if raised else 0)
        for u in GROUP_WIDTHS:
            obs.count(f"poa.width.windows.u{u}", n_real if u == groups else 0)
        obs.count("poa.lockstep.layers.real", int(n_layers.sum()))
        obs.count("poa.lockstep.layers.slots",
                  width * _program_layers(n_layers, width))
        if shards > 1:
            held = np.bincount(
                _mesh_order(n_real, rows, shards)[:n_real]
                // (rows // shards), minlength=shards)
            obs.count("poa.mesh.rows.real", n_real)
            obs.count("poa.mesh.fullest.slots", shards * int(held.max()))


def _submit(kernel, packed, use_pallas, banded=False,
            rung: str = NODE_RUNGS[0]):
    """Dispatch one packed chunk; returns device futures (async).
    `packed` is _pack's 10-tuple (trailing per-window half-band row) or
    a legacy 9-tuple from flat-only callers (probes, the multichip
    worker) — the band row is only touched on banded dispatch."""
    bb, bbw, bb_len, n_layers, seqs, ws, lens, begins, ends = packed[:9]
    with obs.span("poa.dispatch", cat="launch", B=len(bb), rung=rung):
        if use_pallas:
            args = [bb_len[:, None], n_layers[:, None], lens, begins,
                    ends, bb.astype(np.int32), bbw, seqs.astype(np.int32),
                    ws]
            if banded:
                args.append(packed[9])
            return kernel(*args)
        return kernel(bb, bbw, bb_len, n_layers, seqs, ws, lens, begins,
                      ends)


class _Unpacked(tuple):
    """_unpack's host arrays, (cons_base, cons_cov, cons_len, failed[,
    band_hit]) as every caller takes them apart; `nodes`, the kernels'
    fifth output (each window's graph size at the end), rides beside
    them for _install's fill counters, and so does the lockstep kernel's
    last output summed over the launch's programs (None from the XLA
    twin): the in-edge slots its node insertions swept (`slots_swept`),
    beside what they would have swept unbounded (`slots_all`:
    _insert_slots_all, from the launch, which knows its programs), and
    the trips of its own loops (`steps`: poa_pallas_ls.STEP_COUNTERS by
    name, under the launch's _program_layers as `layers`)."""

    nodes = None
    slots_swept = None
    slots_all = None
    steps = None


def _unpack(outs, use_pallas, banded=False, rung: str = NODE_RUNGS[0],
            order=None, slots_all=None, layers=None):
    """Block on device futures; normalize to host arrays.  `failed` is 0
    for a served window, else the cause (poa.FAIL_CAUSES).  `order` is
    the batch's _mesh_order: row p of every array returned is chunk
    item p's, wherever _pack put it.  `slots_all` and `layers` are the
    launch's _insert_slots_all and _program_layers, handed on beside
    what the kernel swept and the steps its loops ran."""
    cb, cc, cl, fl = outs[0], outs[1], outs[2], outs[3]
    with obs.span("poa.wait", cat="launch", B=len(cb), rung=rung):
        cons_base = np.asarray(cb)
        cons_cov = np.asarray(cc)
        cons_len = np.asarray(cl)
        failed = np.asarray(fl)
        nodes = np.asarray(outs[4])
        band_hit = (np.asarray(outs[5])[:, 0]
                    if use_pallas and banded else None)
        counts = (np.asarray(outs[-1]).sum(axis=0).tolist()
                  if use_pallas else None)
    if use_pallas:
        cons_len, failed, nodes = cons_len[:, 0], failed[:, 0], nodes[:, 0]
    if order is not None:
        cons_base, cons_cov, cons_len, failed, nodes = (
            a[order] for a in (cons_base, cons_cov, cons_len, failed, nodes))
        if band_hit is not None:
            band_hit = band_hit[order]
    res = _Unpacked((cons_base, cons_cov, cons_len, failed)
                    + ((band_hit,) if use_pallas and banded else ()))
    res.nodes = nodes
    if counts is not None:
        from .poa_pallas_ls import PROGRAM_COUNTS

        steps = dict(zip(PROGRAM_COUNTS, counts))
        res.slots_swept = steps.pop("slots_swept")
        res.slots_all = slots_all
        if layers is not None:
            res.steps = {"layers": layers, **steps}
    return res


def _install(pipeline, chunk, results, trim, stats, fallback, report=None,
             tier=None, journal=None, band_states=None, band_cap=0,
             force_hit=False, depth_bucket=None):
    san = _sanitize()
    sanitizing = san.enabled()
    if sanitizing:
        # Concrete-side invariants (the kernel proxy skips traced calls):
        # in-range lengths/codes, boolean failed flags. The sanitize.nan
        # fault fires in here against a checker-only copy.
        san.check_consensus_outputs(results[:4], [i for i, _, _ in chunk],
                                    where=f"poa._install[{tier or 'device'}]")
    if len(results) == 5:
        cons_base, cons_cov, cons_len, failed, band_hit = results
    else:
        cons_base, cons_cov, cons_len, failed = results
        band_hit = None
    nodes = getattr(results, "nodes", None)
    n_served = nodes_used = backbone_bases = 0
    # DEPTH_CAP's counters: windows trimmed by the admitted count, capped
    # windows the kernel gave up, parity samples skipped for the cap
    n_trimmed = capped_redone = parity_skipped = 0
    overflow = dict.fromkeys(poa.FAIL_CAUSES, 0)   # cause -> windows
    retry = []
    for bi, (i, wx, keep) in enumerate(chunk):
        st = band_states.get(i) if band_states else None
        if st is not None and st.k:
            # banded dispatch: a kernel hit flag — or any failure, which
            # under a band may just mean the masked DP lost the path —
            # advances the verify-and-widen ladder instead of installing
            hit_bi = force_hit or (band_hit is not None
                                   and bool(band_hit[bi]))
            if hit_bi or failed[bi]:
                st.widen_width(band_cap, report, tier=tier or "device")
                if st.k and obs.enabled():
                    obs.count("poa.cells.banded",
                              len(keep) * (2 * st.k + 1))
                retry.append((i, wx, keep))
                continue
            st.pending = False
        if failed[bi]:
            fallback.append(i)
            stats["failed"] += 1
            cause = int(failed[bi])
            overflow[cause if cause in overflow else poa.FAIL_OTHER] += 1
            # the host redoes it from every layer, the dropped ones too
            capped_redone += bool(wx.capped)
            continue
        n_served += 1
        backbone_bases += len(wx.backbone)
        if nodes is not None:
            nodes_used += int(nodes[bi])
        cl = int(cons_len[bi])
        codes = cons_base[bi, :cl]
        cov = cons_cov[bi, :cl]
        out = np.asarray(codes)
        if wx.is_tgs and trim:
            # DEPTH_CAP's rule (b).  Threshold on the ADMITTED sequence
            # count (backbone + the layers this driver actually packed),
            # mirroring the reference accelerator's
            # seqs_added_per_window_ rule — it counts only sequences
            # successfully added to the GPU group
            # (src/cuda/cudabatch.cpp:139-163,233), not the window's full
            # layer count. Device coverage can only ever reach the
            # admitted count, so a full-window threshold (the CPU rule,
            # src/window.cpp:125-146) would over-trim between DEPTH_CAP
            # and 2*DEPTH_CAP layers and silently never trim above
            # 2*DEPTH_CAP. Host parity therefore holds exactly where the
            # two counts coincide: no layer dropped, by the cap or for
            # its length.
            n_admitted_seqs = len(keep) + 1
            kept_codes = tgs_trim(out, np.asarray(cov), n_admitted_seqs)
            n_trimmed += 1
        else:
            kept_codes = out
        payload = decode(kept_codes)
        if sanitizing and san.parity_due(stats["device"]):
            # Sampled host<->device parity. Host trim parity holds exactly
            # when no layers were dropped at admission (see the trim
            # comment above), so deeper windows are skipped. Recompute
            # BEFORE the install below so the device result is what
            # finally lands — an armed run stays byte-identical.
            n_seqs = pipeline.window_info(i)[0]
            if len(keep) + 1 == n_seqs:
                pipeline.consensus_cpu_one(i)
                san.check_parity(payload, pipeline.get_consensus(i), i,
                                 where=f"poa._install[{tier or 'device'}]")
            else:
                parity_skipped += bool(wx.capped)
        pipeline.set_consensus(i, payload, True)
        if journal is not None:
            journal.append_window(i, wx.target_id, wx.rank,
                                  tier or "device", payload, True)
        stats["device"] += 1
        if report is not None and tier is not None:
            report.record_served(tier)
    # once per launch: how full the served windows' graphs were, and why
    # the kernel gave the others up (every cause's key, a zero too)
    if nodes is not None:
        obs.count("poa.nodes.used", nodes_used)
        obs.count("poa.nodes.capacity", n_served * cons_base.shape[1])
        obs.count("poa.backbone.bases", backbone_bases)
    # how far the kernel's bound on the node-insertion sweep engaged
    swept = getattr(results, "slots_swept", None)
    slots_all = getattr(results, "slots_all", None)
    if swept is not None and slots_all is not None:
        obs.count("poa.insert.slots.swept", swept)
        obs.count("poa.insert.slots.all", slots_all)
    # what the kernel's own loops ran, over the layers of its programs
    # (every key at a lockstep launch, a zero too; none from the XLA twin)
    for name, trips in (getattr(results, "steps", None) or {}).items():
        obs.count(f"poa.ls.{name}", trips)
    for cause, name in poa.FAIL_CAUSES.items():
        obs.count(f"poa.windows.overflow.{name}", overflow[cause])
    obs.count("poa.windows.trim.admitted", n_trimmed)
    obs.count("poa.windows.capped.redone", capped_redone)
    if sanitizing:
        obs.count("sanitize.parity.skipped.capped", parity_skipped)
    # what the rung rule misjudged: graphs that outgrew the rung the
    # estimate chose, by the depth bucket they ran in (every bucket's key)
    for bucket in DEPTH_BUCKETS:
        obs.count(f"poa.windows.rung.miss.d{bucket}",
                  overflow[poa.FAIL_NODES] if bucket == depth_bucket else 0)
    return retry
