"""Alignment-phase driver: batches CIGAR-less overlaps onto the device
banded global aligner, installs CIGARs, and lets the host finish whatever the
device rejects (too long / too divergent), mirroring the reference's
cudaaligner orchestration (/root/reference/src/cuda/cudapolisher.cpp:74-214,
rejection statuses src/cuda/cudaaligner.cpp:63-71).
"""

from __future__ import annotations

import sys
import time

from .. import config, obs


def _on_tpu() -> bool:
    import jax
    return jax.devices()[0].platform == "tpu"


def _engine() -> str:
    """Which aligner serves phase 1.

    Default 'auto': the Hirschberg engine (Pallas distance kernels +
    host-orchestrated splitting, O(band) memory — covers full-length
    reads) on a TPU backend, the host Myers aligner elsewhere — the same
    device-on-TPU posture as the consensus path (_use_pallas) and the
    reference, whose accelerator serves phase 1 whenever CUDA devices
    exist (/root/reference/src/cuda/cudapolisher.cpp:74-214). Explicit
    overrides: '0'/'host', 'hirschberg'. A device-engine failure
    degrades to the host aligner for the remaining jobs (see
    run_alignment_phase).
    """
    env = config.get_str("RACON_TPU_DEVICE_ALIGNER")
    if env in ("auto", ""):
        return "hirschberg" if _on_tpu() else "host"
    if env in ("0", "host"):
        return "host"
    if env == "hirschberg":
        return "hirschberg"
    print(f"[racon_tpu::align] WARNING: unknown RACON_TPU_DEVICE_ALIGNER="
          f"{env!r}; using the host aligner "
          f"(valid: auto, 0/host, hirschberg)", file=sys.stderr)
    return "host"


def run_alignment_phase(pipeline, progress: bool = False,
                        journal=None) -> dict:
    """Device alignment for every eligible CIGAR-less overlap; host for
    the rest.  Device failures run through the degradation lattice inside
    the engine's run_jobs (per-cohort retry, bisection-quarantine, engine
    death -> host for the remainder); already-installed CIGARs are kept
    and the served count survives a mid-phase engine failure.

    With `journal` armed, device-served CIGARs journaled by a previous
    run are replayed (and excluded from device batching — the native
    host pass skips any job whose CIGAR is already set), and fresh
    device results are journaled through a CigarTap as the engine
    installs them.  Host-computed CIGARs are not journaled: the native
    engine recomputes them deterministically on resume.

    Returns stats {device:…, host:…, report: PhaseReport} — the report's
    per-tier served counts sum to the job count, clean or
    fault-injected."""
    from ..analysis import sanitize
    from ..resilience import faults
    from ..resilience import lattice as rl
    from ..resilience.journal import CigarTap, replay_cigars
    from ..resilience.report import PhaseReport

    report = PhaseReport("alignment", rl.ALIGN_TIERS + ("journal",))
    # guard_stats is a no-op passthrough unless RACON_TPU_SANITIZE=1.
    stats = sanitize.guard_stats({"device": 0, "host": 0, "report": report},
                                 "align_driver.run_alignment_phase")
    n = pipeline.num_align_jobs()
    report.total = n
    # Bulk-FFI lengths array, fetched ONCE and threaded through the cells
    # counter, the engine's eligibility rule and its own bucketing.
    lengths = (pipeline.align_job_lengths()
               if n and hasattr(pipeline, "align_job_lengths") else None)
    if lengths is not None and obs.enabled():
        # Total need-band DP cells over ALL phase-1 jobs (host share
        # included) for the cost model (obs/costmodel.py): per pair,
        # max(n, m) rows x the 10%-rule band the aligner actually needs.
        import numpy as np

        L = np.asarray(lengths, dtype=np.int64)[:n]
        if L.size:
            mx = L.max(axis=1)
            need = np.abs(L[:, 1] - L[:, 0]) + mx // 10 + 2
            obs.count("align.cells.total", int((mx * need).sum()))
    replayed = replay_cigars(pipeline, journal, n, report)
    if n:
        # engine resolution inside the guard AND the try: with no align
        # jobs (SAM input) phase 1 must not touch the JAX backend at all,
        # and a backend-init failure under 'auto' must degrade to host,
        # not abort the polish.
        engine = "auto"
        try:
            engine = _engine()
            if engine != "host":
                # what served: compiled or interpreted kernels, the
                # cohort size and the mesh width they dispatch over
                from ..parallel.partitioner import get_partitioner
                from . import align_pallas

                report.extra["kernels"] = {
                    "engine": engine,
                    "interpreted": not _on_tpu(),
                    "batch": align_pallas.cohort_size(),
                    "shards": get_partitioner().batch_axis_size}
                faults.check("align.compile")
                # duck-typed pipelines without the lengths table raise
                # AttributeError here -> outer catch -> host degrade
                ln = (lengths if lengths is not None
                      else pipeline.align_job_lengths())
                jobs = [i for i in range(n) if i not in replayed
                        and align_pallas.band_for(int(ln[i, 0]),
                                                  int(ln[i, 1])) > 0]
                if jobs:
                    sink = (CigarTap(pipeline, journal, "hirschberg")
                            if journal is not None else pipeline)
                    # stats["device"] accumulates INSIDE run_jobs, per
                    # installed CIGAR: an exception escaping run_jobs
                    # after partial installs (kernel build, sanitizer,
                    # install failure) must not zero the device count —
                    # the host-served figure below is derived from it.
                    align_pallas.run_jobs(sink, jobs, report=report,
                                          stats=stats, lengths=ln)
        except Exception as e:  # noqa: BLE001 — engine/backend init
            print(f"[racon_tpu::align] WARNING: device aligner "
                  f"'{engine}' failed ({type(e).__name__}: {e}); "
                  f"finishing the alignment phase on the host",
                  file=sys.stderr)
            report.record_failure(engine, e)
            report.record_degrade(engine, "host", e)
    # Host finishes everything still CIGAR-less (device-rejected or
    # ineligible).
    t0 = time.perf_counter()
    with obs.span("align.host") as sp:
        pipeline.align_jobs_cpu()
        sp.set(jobs=n - stats["device"] - len(replayed))
    report.add_wall("host", time.perf_counter() - t0)
    stats["host"] = n - stats["device"] - len(replayed)
    report.record_served("host", stats["host"])
    return stats
