"""Benchmark: polished Mbp/sec on the device path vs the host oracle path.

Prints ONE JSON line:
  {"metric": "...", "value": N, "unit": "...", "vs_baseline": N,
   "device": {"platform": ..., "device_kind": ..., "count": N}}

A bench number is a device number: without a TPU every profile that
drives the device path exits non-zero (there is no host-path row), and a
kernel tier the lattice had to demote fails the bench.  The one other
mode is the rehearsal CI runs on a CPU asked for by name
(RACON_TPU_BENCH_FORCE_DEVICE=1), whose entries are marked forced.

Workload: a synthetic ONT-like polishing job (default 0.5 Mbp genome, 30x
reads at ~11% error, PAF overlaps from simulation truth, window=500 — the
shape of BASELINE.json's E. coli config, scaled to this machine; set
RACON_TPU_BENCH_MBP to change the size). value = polished megabases per
second of end-to-end wall time (parse -> polished FASTA) on the accelerated
path; vs_baseline = speedup over the host CPU path measured on the same
machine (the reference's comparison axis: accelerated backend vs its CPU
SPOA path).

RACON_TPU_BENCH_INPUT=sam switches the overlaps to SAM with ground-truth
CIGARs (the reference's SAM scenarios): no alignment phase, so the number
isolates the consensus engines. The recorded default stays PAF.
"""

import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from racon_tpu import config  # noqa: E402 — central knob registry

MBP = config.get_float("RACON_TPU_BENCH_MBP")
INPUT = config.get_str("RACON_TPU_BENCH_INPUT")
# 'ont' (default): ~8 kb reads at ~11% error — BASELINE config 2's shape.
# 'sr': 150 bp reads at ~1% error — the short-read (chr20-class,
# BASELINE config 4) regime: NGS-type windows (no trim), ~130 shallow
# layers per window instead of ~30 long ones.
PROFILE = config.get_str("RACON_TPU_BENCH_PROFILE")
PROFILES = {
    "ont": dict(mean_read=8000, sub=0.05, ins=0.03, dele=0.03),
    "sr": dict(mean_read=150, sub=0.008, ins=0.001, dele=0.001),
}
COVERAGE = 30
ARGS = dict(window_length=500, quality_threshold=10.0, error_threshold=0.3,
            match=5, mismatch=-4, gap=-8, num_threads=1)

if PROFILE not in PROFILES:
    raise SystemExit(f"RACON_TPU_BENCH_PROFILE must be one of "
                     f"{sorted(PROFILES)}, got {PROFILE!r}")
_WORKLOAD = ("synthetic ONT" if PROFILE == "ont"
             else "synthetic short-read")


def dataset(mbp: float = MBP):
    import hashlib
    import inspect
    import shutil

    from racon_tpu.tools import simulate

    # Cache keyed by size/coverage/profile (name AND parameter values —
    # tuning a PROFILES entry must not silently reuse a dataset generated
    # with the old parameters) plus the generator source, so simulator
    # changes invalidate stale data; built in a temp dir and renamed into
    # place so concurrent bench runs never see half-written files.
    src_tag = hashlib.sha256(
        (inspect.getsource(simulate) +
         repr(sorted(PROFILES[PROFILE].items()))).encode()).hexdigest()[:12]
    ptag = "" if PROFILE == "ont" else f"_{PROFILE}"
    outdir = f"/tmp/racon_tpu_bench_{mbp}mbp_{COVERAGE}x{ptag}_{src_tag}"
    if not os.path.isdir(outdir):
        tmpdir = outdir + f".tmp{os.getpid()}"
        shutil.rmtree(tmpdir, ignore_errors=True)
        paths = simulate.generate(tmpdir, mbp=mbp, coverage=COVERAGE,
                                  **PROFILES[PROFILE])
        try:
            os.rename(tmpdir, outdir)
        except OSError:
            shutil.rmtree(tmpdir, ignore_errors=True)  # another run won
    ovl = "overlaps.sam" if INPUT == "sam" else "overlaps.paf"
    return {k: os.path.join(outdir, f)
            for k, f in (("reads", "reads.fastq"),
                         ("overlaps", ovl),
                         ("draft", "draft.fasta"))}


def observed_window_lengths(draft_path: str, w: int) -> set:
    """Every window length the consensus phase will actually derive —
    now shared with the pipelined polisher's warm-up thread, so the one
    implementation lives next to warm_geometries (ops/poa_driver.py)."""
    from racon_tpu.ops.poa_driver import observed_window_lengths as owl

    return owl(draft_path, w)


def _forced_device() -> bool:
    """RACON_TPU_BENCH_FORCE_DEVICE=1: the rehearsal CI runs — the exact
    device-path flow on a CPU backend asked for by name.  Every entry it
    prints is marked forced and is never device evidence."""
    return config.get_bool("RACON_TPU_BENCH_FORCE_DEVICE")


def _require_chip(env=None) -> dict:
    """The device this bench will run on (platform, device_kind, count),
    read by a child that exits before anything else starts — the chip
    has one owner at a time, and the profiles that drive daemons or CLI
    children must themselves stay off JAX.  No chip is a failed bench,
    not a host-path row: exits non-zero unless the backend is a TPU or
    this is the forced rehearsal."""
    here = os.path.dirname(os.path.abspath(__file__))
    r = subprocess.run(
        [sys.executable, "-m", "racon_tpu.device"],
        capture_output=True, text=True, cwd=here, env=env)
    if r.returncode != 0:
        tail = (r.stderr.strip().splitlines() or ["no output"])[-1]
        raise SystemExit(f"[bench] no device: {tail}")
    ident = json.loads(r.stdout.strip().splitlines()[-1])
    if ident["platform"] != "tpu" and not _forced_device():
        raise SystemExit(
            f"[bench] no TPU ({ident['count']} {ident['platform']} "
            "device(s)): a bench number is a device number. "
            "RACON_TPU_BENCH_FORCE_DEVICE=1 runs the CPU rehearsal")
    return ident


def phase_wall(report_summary) -> dict:
    """Per-phase wall seconds (summed over serving tiers) from a
    RunReport.summary() dict — the bench's compact phase breakdown.
    Entries without per-tier walls (pre-observability writers) yield
    {}."""
    out = {}
    if isinstance(report_summary, dict):
        for phase, rep in report_summary.items():
            if isinstance(rep, dict) and isinstance(rep.get("wall_s"),
                                                    dict):
                out[phase] = round(sum(rep["wall_s"].values()), 4)
    return out


def pack_split(report_summary) -> dict:
    """Per-phase host-pack vs kernel wall split from a RunReport.summary()
    dict — the shared executor (racon_tpu/ops/batch_exec.py) stamps
    `pack_wall_s` / `kernel_wall_s` into each phase's extras.  The
    feeder criterion (pack time < kernel time) is checkable from this
    stamp alone.  Entries predating the executor yield {}."""
    out = {}
    if isinstance(report_summary, dict):
        for phase, rep in report_summary.items():
            ex = rep.get("extra") if isinstance(rep, dict) else None
            if isinstance(ex, dict) and ("pack_wall_s" in ex
                                         or "kernel_wall_s" in ex):
                out[phase] = {"pack_wall_s": ex.get("pack_wall_s"),
                              "kernel_wall_s": ex.get("kernel_wall_s")}
    return out


def serial_steps_stamp(cm) -> dict:
    """Top-level predicted per-phase serial DP step totals from the
    `cost_model` stamp — the one number the column-compression /
    row-packing work moves, lifted out of the nested stamp so trend
    readers can diff it across log generations.  None when the run
    recorded no cost model (metrics disarmed, serve/distrib lanes,
    pre-cost-model writers)."""
    if not isinstance(cm, dict):
        return None
    out = {ph: row["serial_steps"]
           for ph, row in cm.get("phases", {}).items()
           if isinstance(row, dict) and "serial_steps" in row}
    return out or None


def band_stamp(snap):
    """Banded-DP evidence from the measured run's counter snapshot:
    per-phase banded cell totals and the verify-and-widen hit rate
    (``band.hits / band.jobs``), as a ``(cells_banded, band_hit_rate)``
    pair.  Both None when banding never engaged — RACON_TPU_BAND off,
    metrics disarmed, or no job narrow enough to band — which is a
    different claim from a measured rate of 0.0 (banding on, every
    band verified first try)."""
    c = snap.get("counters") if isinstance(snap, dict) else None
    jobs = (c or {}).get("band.jobs", 0)
    if not jobs:
        return None, None
    cells = {ph: c[key] for ph, key in (("align", "align.cells.banded"),
                                        ("poa", "poa.cells.banded"))
             if c.get(key)}
    return cells or None, round(c.get("band.hits", 0) / jobs, 4)


def mem_stamp(report_summary):
    """``(peak_rss_mb, budget_mb)`` from a RunReport.summary()'s
    ``memory`` phase (the resilience/budget.py accounting stamp);
    ``(None, None)`` when the run carried no memory accounting —
    "not measured", a different claim from a measured 0."""
    if isinstance(report_summary, dict):
        m = report_summary.get("memory")
        ex = m.get("extra") if isinstance(m, dict) else None
        if isinstance(ex, dict):
            return ex.get("peak_rss_mb"), ex.get("budget_mb")
    return None, None


def normalize_entry(e: dict) -> dict:
    """Reader-side backfill for bench JSON entries.

    Backfills ``phase_wall`` (per-phase wall seconds) for entries whose
    embedded report already carried per-tier walls but predate the
    explicit stamp, and ``cost_model: null`` for entries written before
    the analytic cost model existed — "no prediction recorded" parses
    the same for every generation."""
    if not isinstance(e, dict):
        return e
    if "phase_wall" not in e:
        pw = phase_wall(e.get("report"))
        if pw:
            e = dict(e, phase_wall=pw)
    if "cost_model" not in e:
        e = dict(e, cost_model=None)
    if "pack_split" not in e:
        # old logs: recover the split from the embedded report when the
        # executor stamped it there, else explicit null ("not measured")
        e = dict(e, pack_split=pack_split(e.get("report")) or None)
    if "serial_steps" not in e:
        # old logs: recover per-phase predicted step totals from the
        # embedded cost-model stamp when it carried them, else explicit
        # null ("not predicted")
        e = dict(e, serial_steps=serial_steps_stamp(e.get("cost_model")))
    if "cells_banded" not in e or "band_hit_rate" not in e:
        # entries written before banded DP existed: explicit nulls ("not
        # measured"), same semantics as a fresh run with banding off
        e = dict(e)
        e.setdefault("cells_banded", None)
        e.setdefault("band_hit_rate", None)
    if ("serve" in e or "distrib" in e) and "fleet" not in e:
        # fleet-lane entries written before the telemetry stamp
        # (per-worker walls, queueing p95, heartbeat staleness):
        # explicit null — "not scraped", same as a run with obs off
        e = dict(e, fleet=None)
    if ("serve" in e or "distrib" in e) and "pool" not in e:
        # entries written before the elastic pool existed: explicit null
        # ("no pool-size timeline"), same as a run with the fleet off
        e = dict(e, pool=None)
    if ("serve" in e or "distrib" in e) and "ledger" not in e:
        # entries written before the per-job latency ledger existed:
        # explicit null ("no stage decomposition recorded")
        e = dict(e, ledger=None)
    if ("serve" in e or "distrib" in e) and "slo" not in e:
        # entries written before the per-tenant SLO engine existed:
        # explicit null ("no burn-rate snapshot scraped")
        e = dict(e, slo=None)
    if "peak_rss_mb" not in e or "budget_mb" not in e:
        # entries written before the memory budget existed: recover the
        # pair from the embedded report's memory phase when the run
        # stamped one, else explicit nulls ("not measured")
        peak, bud = mem_stamp(e.get("report"))
        e = dict(e)
        e.setdefault("peak_rss_mb", peak)
        e.setdefault("budget_mb", bud)
    return e


def run(backend: str, paths):
    import racon_tpu

    t0 = time.time()
    p = racon_tpu.create_polisher(paths["reads"], paths["overlaps"],
                                  paths["draft"], backend=backend, **ARGS)
    p.initialize()
    res = p.polish(True)
    dt = time.time() - t0
    polished_bp = sum(len(d) for _, d in res)
    # compact serving-mix report (who served what, fallback causes) —
    # attached to the bench JSON/log so a silently degraded tier can't
    # masquerade as a device measurement
    return polished_bp, dt, p.report.summary()


def main():
    if _forced_device():
        # rehearsal: the CPU backend, asked for by name, and the tier
        # the device path ships (interpret-mode pallas), not the XLA twin
        from __graft_entry__ import _force_cpu
        _force_cpu(1)
        os.environ.setdefault("RACON_TPU_PALLAS", "1")
    device = _require_chip()
    # Arm the in-process metrics registry (counters only — no trace file
    # unless RACON_TPU_TRACE is set) so the measured run counts the
    # per-bucket DP cells the analytic cost model predicts against
    # (racon_tpu/obs/costmodel.py).  setdefault: an explicit =0 wins.
    os.environ.setdefault("RACON_TPU_METRICS", "1")
    paths = dataset()

    # Warm the device path so compile time is not billed as throughput:
    # compile every consensus kernel geometry explicitly (one trivial
    # padded batch per depth bucket) at the window length the measured
    # dataset will actually derive, then run a small end-to-end pass for
    # everything else. The persistent compilation cache keeps both warm
    # across processes — a full-size warm-up pass would triple device wall
    # at multi-Mbp bench scales.
    from racon_tpu.ops import poa_driver
    warm_lens = observed_window_lengths(paths["draft"],
                                        ARGS["window_length"])
    poa_driver.warm_geometries(warm_lens, ARGS["match"],
                               ARGS["mismatch"], ARGS["gap"])
    run("tpu", dataset(mbp=min(MBP, 0.05)))

    bp_tpu, dt_tpu, rep_tpu = run("tpu", paths)
    demoted = {ph: r["degradations"] for ph, r in rep_tpu.items()
               if isinstance(r, dict) and r.get("degradations")}
    if demoted:
        raise SystemExit(
            f"[bench] a kernel tier failed and the lattice demoted it "
            f"({demoted}): that is a failed bench, not a bench of the "
            f"next tier down")
    # The measured run's obs state (cell counters + any trace file) is
    # the cost model's evidence; the CPU oracle run would reset the
    # registry and overwrite the trace, so snapshot now and mute tracing
    # for the oracle pass.
    from racon_tpu import obs
    snap_tpu = obs.snapshot()
    if config.get_raw("RACON_TPU_TRACE"):
        os.environ["RACON_TPU_TRACE"] = ""
    bp_cpu, dt_cpu, _ = run("cpu", paths)

    mbps_tpu = bp_tpu / dt_tpu / 1e6
    mbps_cpu = bp_cpu / dt_cpu / 1e6
    # what served is in the report; the tag only repeats the request
    kernel_tag = (" [XLA kernel: RACON_TPU_PALLAS=0]"
                  if config.get_raw("RACON_TPU_PALLAS") == "0"
                  else " [pallas ls]")
    if _forced_device():
        kernel_tag += " [FORCED DRY-RUN: not device evidence]"
    # numbers measured with the runtime sanitizer armed carry its
    # per-window checking overhead — stamp them so they are never
    # compared against clean-run baselines
    sanitized = config.get_bool("RACON_TPU_SANITIZE")
    # predicted-vs-measured per modeled phase on the run's machine
    # profile; None when metrics were explicitly disarmed
    from racon_tpu.obs import costmodel
    cm = costmodel.bench_cost_model(
        snap_tpu, phase_wall(rep_tpu),
        config.get_str("RACON_TPU_MACHINE_PROFILE") or "auto",
        platform=device["platform"], device_kind=device["device_kind"])
    cells_banded, band_hit_rate = band_stamp(snap_tpu)
    peak_rss_mb, budget_mb = mem_stamp(rep_tpu)
    print(json.dumps({
        "metric": f"polished Mbp/sec ({_WORKLOAD} {MBP} Mbp {COVERAGE}x, "
                  f"{INPUT.upper()}, w=500, end-to-end){kernel_tag}",
        "value": round(mbps_tpu, 4),
        "unit": "Mbp/s",
        "vs_baseline": round(mbps_tpu / mbps_cpu, 3),
        "device": device,
        "report": rep_tpu, "phase_wall": phase_wall(rep_tpu),
        "pack_split": pack_split(rep_tpu) or None,
        "cost_model": cm,
        "serial_steps": serial_steps_stamp(cm),
        "cells_banded": cells_banded, "band_hit_rate": band_hit_rate,
        "peak_rss_mb": peak_rss_mb, "budget_mb": budget_mb,
        **({"forced": True} if _forced_device() else {}),
        **({"sanitize": True} if sanitized else {}),
    }))
    print(f"[bench] tpu: {bp_tpu} bp in {dt_tpu:.1f}s | "
          f"cpu: {bp_cpu} bp in {dt_cpu:.1f}s", file=sys.stderr)


def serve_profile(jobs: int = 4, clients: int = 2) -> int:
    """`python bench.py serve`: benchmark the resident daemon path.

    Spawns a `racon-tpu serve` daemon (kernels warmed at startup),
    drives it with concurrent jobs over the standard bench dataset via
    the load-test harness (racon_tpu/serve/loadtest.py), and stamps a
    normalized entry — warm-path Mbp/s as the value, latency percentiles
    and the cold-vs-warm delta under "serve" — so the `obs bench`
    regression gate covers the daemon path.  The `profile:
    serve-<PROFILE>` field keeps it a separate trend series from the
    one-shot bench.  vs_baseline is null: the serve bench has no paired
    oracle run (the byte-identity claim is CI's cmp gate, not a
    throughput ratio)."""
    import tempfile

    from racon_tpu.serve import loadtest

    backend = "tpu"
    env = dict(os.environ)
    if _forced_device():
        # rehearsal: the daemon subprocess gets the forced-CPU env
        from __graft_entry__ import _force_cpu_env
        env.update(_force_cpu_env(env, 1))
    device = _require_chip(env)
    paths = dataset()
    # The rehearsal shrinks the window: at w=500 the XLA-twin consensus
    # runs minutes/window on a CPU backend (same reasoning as CI's
    # pipelined-polish gate), and forced entries are never device
    # evidence.  Device runs measure the production w=500.
    w = 100 if _forced_device() else ARGS["window_length"]
    state = tempfile.mkdtemp(prefix="racon_tpu_bench_serve.")
    proc = loadtest.spawn_daemon(
        state, backend, window_length=w,
        extra_args=["-m", str(ARGS["match"]), "-x", str(ARGS["mismatch"]),
                    "-g", str(ARGS["gap"])],
        env=env)
    with open(os.path.join(state, "serve.json")) as f:
        port = json.load(f)["port"]
    polish_args = {k: ARGS[k] for k in
                   ("quality_threshold", "error_threshold",
                    "match", "mismatch", "gap")}
    polish_args["window_length"] = w
    try:
        summary = loadtest.run_loadtest(port, paths, jobs, clients,
                                        polish_args=polish_args)
    finally:
        try:
            from racon_tpu.serve import ServeClient
            with ServeClient(port, timeout=10.0) as c:
                c.shutdown()
            proc.wait(timeout=30)
        except Exception:  # noqa: BLE001 — teardown must not mask results
            proc.kill()

    value = summary["warm_mbps"]
    if value is None:
        value = summary["throughput_mbps"]
    tag = (" [FORCED DRY-RUN: not device evidence]" if _forced_device()
           else "")
    serve_stats = {
        "jobs": summary["jobs"], "clients": summary["clients"],
        "throughput_mbps": summary["throughput_mbps"],
        "latency_s": summary["latency_s"],
        "service_s": summary["service_s"],
        "warm_kernel_builds": summary["warm_kernel_builds"],
    }
    entry = {
        "metric": f"serve: warm-path polished Mbp/sec ({_WORKLOAD} {MBP} "
                  f"Mbp {COVERAGE}x, {INPUT.upper()}, w={w}, {jobs} jobs/"
                  f"{clients} clients){tag}",
        "value": round(value, 4),
        "unit": "Mbp/s",
        # no paired oracle run in serve mode — explicit nulls keep
        # normalize_entry a fixed point on fresh entries
        "vs_baseline": None,
        "device": device,
        "cost_model": None,
        "pack_split": None,
        "serial_steps": None,
        "cells_banded": None,
        "band_hit_rate": None,
        "peak_rss_mb": None,
        "budget_mb": None,
        "serve": serve_stats,
        # scraped daemon telemetry (stats-op samples during the run)
        "fleet": summary.get("daemon_stats"),
        # elastic pool-size timeline (None: daemon ran without a plane)
        "pool": summary.get("pool"),
        # aggregated per-job latency ledger + end-of-run SLO snapshot
        # (None on daemons predating either; normalize_entry backfills
        # old logs to the same nulls)
        "ledger": summary.get("ledger"),
        "slo": summary.get("slo"),
        **({"forced": True} if _forced_device() else {}),
    }
    assert normalize_entry(dict(entry)) == entry, \
        "serve bench entry must be a normalize_entry fixed point"
    print(json.dumps(entry))
    print(f"[bench] serve: {summary['completed']}/{summary['jobs']} jobs, "
          f"makespan {summary['makespan_s']:.1f}s, errors: "
          f"{summary['errors'] or 'none'}", file=sys.stderr)
    return 0 if summary["completed"] == summary["jobs"] else 1


def distrib_profile(workers: int = 3) -> int:
    """`python bench.py distrib`: benchmark the multi-process chunk
    fleet (racon_tpu/distrib).

    Runs the standard bench dataset through a Coordinator driving
    `workers` localhost worker processes on the cpu backend (the chunk
    workers run the host-oracle path — the fleet's scaling axis is
    processes, not kernels; the device story is serve's), and stamps
    polished Mbp/s over the gathered output plus the fleet accounting —
    chunks, serving mix, re-dispatch / speculation / duplicate /
    journal-resume counts — under "distrib".  The `profile:
    distrib-<PROFILE>` field keeps it its own trend series for the
    `obs bench` regression gate.  vs_baseline is null: byte-identity to
    the serial CLI is CI's cmp gate, not a throughput ratio."""
    import tempfile

    from racon_tpu.distrib import Coordinator

    paths = dataset()
    workdir = tempfile.mkdtemp(prefix="racon_tpu_bench_distrib.")
    out_path = os.path.join(workdir, "polished.fasta")
    t0 = time.monotonic()
    coord = Coordinator(paths["reads"], paths["overlaps"], paths["draft"],
                        workdir, args=dict(ARGS), backend="cpu",
                        workers=workers)
    result = coord.run(out_path, timeout=1800)
    wall = time.monotonic() - t0
    polished_bp = 0
    with open(out_path) as f:
        for line in f:
            if not line.startswith(">"):
                polished_bp += len(line.strip())
    value = polished_bp / 1e6 / wall if wall > 0 else 0.0
    counters = result["counters"]
    from racon_tpu.obs import ledger as joblog
    dist_stage_s = joblog.stage_seconds(result.get("summary"))
    distrib_stats = {
        "workers": workers,
        "chunks": result["chunks"],
        "served": result["served"],
        "dispatches": counters.get("dispatches", 0),
        "redispatches": counters.get("redispatches", 0),
        "speculative": counters.get("speculative", 0),
        "duplicates": counters.get("duplicates", 0),
        "journal_replayed": counters.get("journal_replayed", 0),
        "workers_dead": counters.get("workers_dead", 0),
        "degradations": len(result["degradations"]),
    }
    entry = {
        "metric": f"distrib: polished Mbp/sec ({_WORKLOAD} {MBP} Mbp "
                  f"{COVERAGE}x, {INPUT.upper()}, "
                  f"w={ARGS['window_length']}, {workers} workers/"
                  f"{result['chunks']} chunks, end-to-end)",
        "value": round(value, 4),
        "unit": "Mbp/s",
        # no paired oracle run in distrib mode — explicit nulls keep
        # normalize_entry a fixed point on fresh entries
        "vs_baseline": None,
        # host-oracle workers by design: no device ran, and none is named
        "device": None,
        "cost_model": None,
        "pack_split": None,
        "serial_steps": None,
        "cells_banded": None,
        "band_hit_rate": None,
        "peak_rss_mb": None,
        "budget_mb": None,
        "distrib": distrib_stats,
        # fleet telemetry from the coordinator: per-worker chunk/kernel
        # walls, dispatch-queue wait p95, heartbeat staleness max
        "fleet": result.get("telemetry"),
        # elastic pool bounds + size timeline (fixed-size here: the
        # distrib bench pins min == max == workers)
        "pool": result.get("pool"),
        # per-stage compute seconds off the gathered run report (the
        # distrib lane has no per-job queueing stamps, and no daemon to
        # scrape an SLO snapshot from — slo stays an explicit null)
        "ledger": (({"stage_s": dist_stage_s} if dist_stage_s else None)),
        "slo": None,
    }
    assert normalize_entry(dict(entry)) == entry, \
        "distrib bench entry must be a normalize_entry fixed point"
    print(json.dumps(entry))
    served_total = sum(result["served"].values())
    print(f"[bench] distrib: {served_total}/{result['chunks']} chunks "
          f"({result['served']}), wall {wall:.1f}s, "
          f"redispatches {distrib_stats['redispatches']}, "
          f"replayed {distrib_stats['journal_replayed']}",
          file=sys.stderr)
    return 0 if served_total == result["chunks"] else 1


def stream_dataset(mbp: float, contigs: int):
    """Multi-contig dataset for the streaming bench, cached like
    dataset() (keyed by size/coverage/contigs + simulator source)."""
    import hashlib
    import inspect
    import shutil

    from racon_tpu.tools import simulate

    src_tag = hashlib.sha256(
        (inspect.getsource(simulate) +
         repr(sorted(PROFILES[PROFILE].items()))).encode()).hexdigest()[:12]
    outdir = (f"/tmp/racon_tpu_bench_stream_{mbp}mbp_{COVERAGE}x_"
              f"{contigs}c_{src_tag}")
    if not os.path.isdir(outdir):
        tmpdir = outdir + f".tmp{os.getpid()}"
        shutil.rmtree(tmpdir, ignore_errors=True)
        simulate.generate(tmpdir, mbp=mbp, coverage=COVERAGE,
                          contigs=contigs, **PROFILES[PROFILE])
        try:
            os.rename(tmpdir, outdir)
        except OSError:
            shutil.rmtree(tmpdir, ignore_errors=True)  # another run won
    ovl = "overlaps.sam" if INPUT == "sam" else "overlaps.paf"
    return {k: os.path.join(outdir, f)
            for k, f in (("reads", "reads.fastq"),
                         ("overlaps", ovl),
                         ("draft", "draft.fasta"))}


def stream_profile(contigs: int = 4) -> int:
    """`python bench.py stream`: the bounded-memory streaming path.

    Polishes a multi-contig draft through a CLI subprocess with the
    streaming input path armed under RACON_TPU_MEM_BUDGET_MB (default
    2048 MiB — override the knob for tighter drills), and stamps Mbp/s
    plus the run's memory accounting: ``peak_rss_mb`` (what the
    watchdog observed) against ``budget_mb``.  The `profile:
    stream-<PROFILE>` field keeps it its own trend series for the
    `obs bench` regression gate.  vs_baseline is null: byte-identity to
    the in-memory path is CI's cmp gate, not a throughput ratio.

    Genome-scale recipe (what the CI-sized default rehearses)::

        RACON_TPU_BENCH_MBP=3000 RACON_TPU_MEM_BUDGET_MB=8192 \\
            python bench.py stream

    — a 3 Gbp human-scale draft polished with peak RSS bounded by the
    chunk working set, not the genome (see docs/benchmarks.md)."""
    import tempfile

    env = dict(os.environ)
    env.pop("RACON_TPU_FAULT", None)
    on_device = not _forced_device()
    if not on_device:
        # the streaming bench measures memory behavior, not kernels: the
        # rehearsal runs the small-window XLA path — same reasoning as
        # serve_profile: the twin at w=500 runs minutes/window on a CPU —
        # and phase 1 on the host aligner
        env.update(JAX_PLATFORMS="cpu", RACON_TPU_PALLAS="0",
                   RACON_TPU_BATCH_WINDOWS="8",
                   RACON_TPU_DEVICE_ALIGNER="host")
    device = _require_chip(env)
    budget = config.get_int("RACON_TPU_MEM_BUDGET_MB") or 2048
    paths = stream_dataset(MBP, contigs)
    workdir = tempfile.mkdtemp(prefix="racon_tpu_bench_stream.")
    out_path = os.path.join(workdir, "polished.fasta")
    report_path = os.path.join(workdir, "report.json")
    w = ARGS["window_length"] if on_device else 100
    env["RACON_TPU_MEM_BUDGET_MB"] = str(budget)
    env["RACON_TPU_STREAM_INPUT"] = "1"
    cmd = [sys.executable, "-m", "racon_tpu.cli", "--tpu",
           "-w", str(w), "--report", report_path,
           paths["reads"], paths["overlaps"], paths["draft"]]
    t0 = time.monotonic()
    with open(out_path, "w") as out_f, \
            open(os.path.join(workdir, "stderr.log"), "w") as err_f:
        rc = subprocess.call(cmd, stdout=out_f, stderr=err_f, env=env)
    wall = time.monotonic() - t0
    if rc != 0:
        tail = ""
        try:
            with open(os.path.join(workdir, "stderr.log")) as f:
                tail = f.read()[-500:]
        except OSError:
            pass
        print(f"[bench] stream: CLI exited {rc}: {tail}", file=sys.stderr)
        return 1
    polished_bp = 0
    with open(out_path) as f:
        for line in f:
            if not line.startswith(">"):
                polished_bp += len(line.strip())
    value = polished_bp / 1e6 / wall if wall > 0 else 0.0
    try:
        with open(report_path) as f:
            rep = json.load(f).get("phases", {})
    except (OSError, ValueError):
        rep = {}
    peak_rss_mb, budget_mb = mem_stamp(rep)
    mem = rep.get("memory", {}) if isinstance(rep, dict) else {}
    extra = mem.get("extra", {}) if isinstance(mem, dict) else {}
    stream_stats = {
        "contigs": contigs,
        "streamed": extra.get("streamed"),
        "pressure_level": extra.get("pressure_level"),
        "quarantined": len(mem.get("quarantined", [])
                           if isinstance(mem, dict) else []),
        "degradations": sum(len(p.get("degradations", []))
                            for p in rep.values()
                            if isinstance(p, dict)),
    }
    tag = (" [FORCED DRY-RUN: not device evidence]" if _forced_device()
           else "")
    entry = {
        "metric": f"stream: polished Mbp/sec ({_WORKLOAD} {MBP} Mbp "
                  f"{COVERAGE}x, {INPUT.upper()}, w={w}, {contigs} "
                  f"contigs, budget {budget} MiB, end-to-end){tag}",
        "value": round(value, 4),
        "unit": "Mbp/s",
        # no paired oracle run here — byte-identity is CI's cmp gate;
        # explicit nulls keep normalize_entry a fixed point
        "vs_baseline": None,
        "device": device,
        "cost_model": None,
        "pack_split": None,
        "serial_steps": None,
        "cells_banded": None,
        "band_hit_rate": None,
        "peak_rss_mb": peak_rss_mb,
        "budget_mb": budget_mb,
        "stream": stream_stats,
        **({"forced": True} if _forced_device() else {}),
    }
    assert normalize_entry(dict(entry)) == entry, \
        "stream bench entry must be a normalize_entry fixed point"
    print(json.dumps(entry))
    print(f"[bench] stream: {polished_bp} bp in {wall:.1f}s, peak RSS "
          f"{peak_rss_mb} MiB / budget {budget_mb} MiB "
          f"(pressure {stream_stats['pressure_level']})", file=sys.stderr)
    return 0


def multichip_profile(counts=(1, 2, 4, 8), repeats: int = 3) -> int:
    """`python bench.py multichip`: the device-count scaling sweep as a
    bench series.

    Runs tools/multichip.py's sweep (one bounded subprocess per mesh
    width; the partitioner under-subscribes the visible devices via
    RACON_TPU_MESH_SHAPE) and stamps windows/s at the widest mesh as the
    value, with every per-count measurement under "multichip" — so the
    `obs bench` regression gate trends the sharded dispatch path.  The
    `profile: multichip-<PROFILE>` field keeps it its own series.  The
    forced rehearsal sweeps virtual CPU devices, which share the host's
    cores: its entry is marked `forced`, never device evidence.
    vs_baseline is null: scaling vs the 1-device row IS the metric, not
    a ratio against the CPU oracle."""
    from racon_tpu.tools import multichip as mc

    real = not _forced_device()
    device = _require_chip() if real else None
    results = mc.sweep(sorted(set(counts)), repeats=repeats, real=real)
    ok = {n: e for n, e in results.items() if e.get("ok")
          and e.get("windows_per_s")}
    if not ok:
        print("[bench] multichip: every sweep count failed", file=sys.stderr)
        print(json.dumps(results, indent=2), file=sys.stderr)
        return 1
    top = max(ok, key=int)
    value = ok[top]["windows_per_s"]
    tier = ok[top]["tier"]
    tag = "" if real else " [FORCED DRY-RUN: not device evidence]"
    mc_stats = {
        "counts": results,
        "scaling_vs_1": (round(value / ok["1"]["windows_per_s"], 3)
                         if ok.get("1") and ok["1"]["windows_per_s"]
                         else None),
    }
    entry = {
        "metric": f"multichip: sharded consensus windows/sec at {top} "
                  f"device(s) (counts {sorted(map(int, results))}, "
                  f"tier {tier}, batch {ok[top]['batch']}){tag}",
        "value": round(value, 2),
        "unit": "windows/s",
        # no paired oracle run in the sweep — explicit nulls keep
        # normalize_entry a fixed point on fresh entries
        "vs_baseline": None,
        # forced sweeps run on virtual CPU devices the workers make
        "device": device,
        "cost_model": None,
        "pack_split": None,
        "serial_steps": None,
        "cells_banded": None,
        "band_hit_rate": None,
        "peak_rss_mb": None,
        "budget_mb": None,
        "multichip": mc_stats,
        **({"forced": True} if not real else {}),
    }
    assert normalize_entry(dict(entry)) == entry, \
        "multichip bench entry must be a normalize_entry fixed point"
    print(json.dumps(entry))
    print(f"[bench] multichip: {len(ok)}/{len(results)} counts measured, "
          f"{top}-device {value:.1f} windows/s "
          f"(x{mc_stats['scaling_vs_1']} vs 1 device)", file=sys.stderr)
    return 0 if len(ok) == len(results) else 1


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "serve":
        sys.exit(serve_profile())
    if len(sys.argv) > 1 and sys.argv[1] == "distrib":
        sys.exit(distrib_profile())
    if len(sys.argv) > 1 and sys.argv[1] == "multichip":
        sys.exit(multichip_profile())
    if len(sys.argv) > 1 and sys.argv[1] == "stream":
        sys.exit(stream_profile())
    main()
