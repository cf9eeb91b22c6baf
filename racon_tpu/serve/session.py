"""Resident polishing session: one process, many polish jobs.

A ``PolishSession`` owns what is expensive to build and safe to share —
the process-global kernel caches (``ops/kernel_cache.device_keyed_cache``
and the poa_driver geometry lru are keyed by topology, not by run, so
every compiled kernel outlives the polisher that built it) — and builds
what must be per-request fresh through the normal
``polisher.create_polisher`` seam: journal, run report, trace, fault
schedule (``polisher.reset_run_state``).  ``warm()`` pre-compiles the
consensus geometries once at startup via ``poa_driver.warm_geometries``,
so even the first job pays no kernel builds.

Because the per-run state the constructors reset is module-global,
in-process jobs must not overlap; ``run_job`` holds a lock and the
scheduler (scheduler.py) provides the concurrency by queueing.  Each
job runs inside its own directory (``<workdir>/jobs/<job_id>/``) holding
its journal, trace, report, and polished output — concurrent jobs can
never clobber each other's artifacts because the job id namespaces every
path.
"""

from __future__ import annotations

import json
import os
import threading
import time
from dataclasses import dataclass, field
from typing import List, Optional

from .. import config, fingerprint, obs
from ..obs import context, flight, ledger
from ..polisher import create_polisher

#: Polish parameters a job may override, with the CLI defaults — the
#: same contract as `racon_tpu.cli` flags, so a serve job and a CLI run
#: with equal parameters produce byte-identical output.
POLISH_ARG_DEFAULTS = {
    "window_length": 500,
    "quality_threshold": 10.0,
    "error_threshold": 0.3,
    "trim": True,
    "fragment_correction": False,
    "match": 3,
    "mismatch": -5,
    "gap": -4,
    "num_threads": 1,
}

BACKENDS = ("cpu", "tpu")


class JobCancelled(RuntimeError):
    """Raised inside run_job when the job's cancel event is set."""


@dataclass
class JobSpec:
    """One polish request: input paths + polish parameters.

    ``args`` overrides ``POLISH_ARG_DEFAULTS`` (unknown keys are a
    submit-time error, not a mid-run crash).  ``job_id`` is assigned by
    the scheduler when empty.  ``window_budget`` overrides the daemon's
    ``RACON_TPU_SERVE_WINDOW_BUDGET`` for this job (0 = daemon default).
    """

    sequences: str
    overlaps: str
    target: str
    args: dict = field(default_factory=dict)
    include_unpolished: bool = False
    backend: str = ""
    job_id: str = ""
    submitter: str = "local"
    window_budget: int = 0
    #: Priority lane (higher serves first; fairness still rotates
    #: tenants within a lane — fleet/queues.py).
    priority: int = 0
    #: Optional trace context ({"trace_id", "parent"}) from the
    #: submitter, so the job's spans parent under the caller's timeline
    #: when the traces are merged (obs/context.py).
    trace: Optional[dict] = None

    def validate(self) -> None:
        unknown = sorted(set(self.args) - set(POLISH_ARG_DEFAULTS))
        if unknown:
            raise ValueError(
                f"unknown polish arg(s) {', '.join(unknown)}; allowed: "
                f"{', '.join(sorted(POLISH_ARG_DEFAULTS))}")
        if self.backend and self.backend not in BACKENDS:
            raise ValueError(f"unknown backend {self.backend!r}; allowed: "
                             f"{', '.join(BACKENDS)}")
        for label, path in (("sequences", self.sequences),
                            ("overlaps", self.overlaps),
                            ("target", self.target)):
            if not path or not os.path.isfile(path):
                raise ValueError(f"{label} file not found: {path!r}")
        if self.job_id and ("/" in self.job_id or self.job_id.startswith(".")):
            raise ValueError(f"invalid job id {self.job_id!r}")

    def polish_args(self) -> dict:
        """The full kwargs for create_polisher: defaults + overrides."""
        merged = dict(POLISH_ARG_DEFAULTS)
        merged.update(self.args)
        return merged

    def as_dict(self) -> dict:
        return {
            "sequences": self.sequences,
            "overlaps": self.overlaps,
            "target": self.target,
            "args": dict(self.args),
            "include_unpolished": self.include_unpolished,
            "backend": self.backend,
            "job_id": self.job_id,
            "submitter": self.submitter,
            "window_budget": self.window_budget,
            "priority": self.priority,
            "trace": dict(self.trace) if self.trace else None,
        }

    @classmethod
    def from_dict(cls, d: dict) -> "JobSpec":
        unknown = sorted(set(d) - {
            "sequences", "overlaps", "target", "args", "include_unpolished",
            "backend", "job_id", "submitter", "window_budget", "priority",
            "trace"})
        if unknown:
            raise ValueError(f"unknown job field(s): {', '.join(unknown)}")
        for key in ("sequences", "overlaps", "target"):
            if not isinstance(d.get(key), str) or not d.get(key):
                raise ValueError(f"job field {key!r} must be a non-empty "
                                 f"path string")
        args = d.get("args") or {}
        if not isinstance(args, dict):
            raise ValueError("job field 'args' must be an object")
        return cls(
            sequences=d["sequences"],
            overlaps=d["overlaps"],
            target=d["target"],
            args=dict(args),
            include_unpolished=bool(d.get("include_unpolished", False)),
            backend=str(d.get("backend") or ""),
            job_id=str(d.get("job_id") or ""),
            submitter=str(d.get("submitter") or "local"),
            window_budget=int(d.get("window_budget") or 0),
            priority=int(d.get("priority") or 0),
            trace=(dict(d.get("trace"))
                   if isinstance(d.get("trace"), dict) else None),
        )


def _journal_replayed(report) -> int:
    """Units the journal replayed across all phases of a resumed run."""
    return sum(rep.served.get("journal", 0)
               for rep in report.phases.values())


class PolishSession:
    """Resident session.  Thread-safe: ``run_job`` serializes in-process
    jobs (the per-run runtime state the polisher constructors reset is
    module-global); the kernel caches are shared across jobs and across
    sessions in the same process — that sharing IS the hot path."""

    def __init__(self, workdir: str, backend: str = "tpu"):
        if backend not in BACKENDS:
            raise ValueError(f"unknown backend {backend!r}")
        self.workdir = workdir
        self.backend = backend
        self.jobs_run = 0
        self.warmed: List[int] = []
        self.warm_wall_s = 0.0
        #: (job id, start, end) of the last job's second trace write on
        #: the monotonic clock: the one part of a job's close its own
        #: trace file cannot hold, handed to the next job's tracer
        self._release_prev = None
        self._lock = threading.Lock()
        os.makedirs(os.path.join(workdir, "jobs"), exist_ok=True)

    # -- layout ------------------------------------------------------------

    def job_dir(self, job_id: str) -> str:
        # the `serve_job_dir` site of the unified fingerprint registry
        return fingerprint.serve_job_paths(self.workdir, job_id)["dir"]

    # -- startup warm-up ---------------------------------------------------

    def warm(self, window_lengths=(500,), match: int = 3,
             mismatch: int = -5, gap: int = -4) -> float:
        """Pre-compile (or load from the persistent XLA cache) every
        consensus kernel geometry for these window lengths, so the first
        job's consensus phase finds everything hot.  Device backend
        only; returns the wall seconds spent.  Same mechanism as the
        phase pipeline's warm-up thread (polisher.py) and bench.py's
        prewarm — ``poa_driver.warm_geometries``.

        Every geometry of the base node rung, that is: what every job
        needs.  The upper rung's program (windows of more than ~55
        layers, ``poa_driver.NODE_RUNGS``) is built by the first job
        deep enough to ask for it, which pays its ~10 s of trace, lower
        and compile once per process; building it here would charge
        every process, deep jobs or none, one more program per window
        class."""
        if self.backend != "tpu":
            return 0.0
        from ..ops import poa_driver

        lens = sorted({int(w) for w in window_lengths})
        t0 = time.monotonic()
        poa_driver.warm_geometries(lens, match, mismatch, gap)
        self.warm_wall_s = round(time.monotonic() - t0, 4)
        self.warmed = lens
        return self.warm_wall_s

    def warm_for_target(self, target_path: str, window_length: int = 500,
                        match: int = 3, mismatch: int = -5,
                        gap: int = -4) -> float:
        """Warm every geometry a specific draft will derive (full chunks
        plus per-contig tail remainders — ``observed_window_lengths``)."""
        if self.backend != "tpu":
            return 0.0
        from ..ops import poa_driver

        lens = poa_driver.observed_window_lengths(target_path,
                                                  int(window_length))
        return self.warm(sorted(lens), match, mismatch, gap)

    # -- job execution -----------------------------------------------------

    def run_job(self, spec: JobSpec,
                cancel_event: Optional[threading.Event] = None) -> dict:
        """Run one polish job to completion inside its job directory.

        Serialized: only one in-process job runs at a time (the
        scheduler queues the rest).  The job's journal is always armed
        with resume semantics — a re-submitted job whose previous run
        was preempted replays the journaled prefix instead of
        recomputing, and still produces byte-identical output."""
        with self._lock:
            return self._run_job_locked(spec, cancel_event)

    def _run_job_locked(self, spec: JobSpec, cancel) -> dict:
        job_id = spec.job_id or f"job{self.jobs_run:04d}"
        backend = spec.backend or self.backend
        paths = fingerprint.serve_job_paths(self.workdir, job_id, backend)
        jd = paths["dir"]
        os.makedirs(jd, exist_ok=True)
        out_path = paths["output"]
        trace_path = paths["trace"]
        journal_path = paths["journal"]
        report_path = paths["report"]

        cold = self.jobs_run == 0
        t0 = time.monotonic()
        if cancel is not None and cancel.is_set():
            raise JobCancelled(job_id)
        # trace-context propagation: a submitter's {trace_id, parent}
        # pair (JobSpec.trace) is activated before create_polisher so
        # the job's fresh tracer stamps it; a flight dump from this job
        # lands in the job directory
        context.activate(spec.trace)
        flight.set_dir(jd)
        try:
            polisher = create_polisher(
                spec.sequences, spec.overlaps, spec.target, backend=backend,
                journal_path=journal_path, resume_journal=True,
                trace_path=trace_path, **spec.polish_args())
            # The constructor armed this request's tracer and opened its
            # root span `job` (ended below).  Every span in the file
            # belongs to this job (the trace itself is per-request; the
            # instant event tags it with the job id), except the second
            # trace write of the job before, which that job's own file
            # could not hold.
            if self._release_prev is not None:
                prev_id, r0, r1 = self._release_prev
                obs.add_complete("job.release.prev", r0, r1, parent_id=None,
                                 job=prev_id, t0_mono_ns=r0)
            obs.event("serve.job", job=job_id, backend=backend, cold=cold,
                      submitter=spec.submitter)
            polisher.initialize()
            if cancel is not None and cancel.is_set():
                # Phase boundary: alignment is done and journaled; the
                # consensus phase has not started.  The journal makes the
                # cancellation cheap to undo — a re-run resumes from here.
                raise JobCancelled(job_id)
            # polish() left `job.close` open after its own part (journal,
            # report, first trace write); the rest of the close is here
            out = polisher.polish(not spec.include_unpolished)
            kernel_builds = obs.counter_total("kernel.builds.")

            with obs.span("job.close.output"):
                with open(out_path, "w") as f:
                    for name, data in out:
                        f.write(f">{name}\n{data}\n")
            with obs.span("job.close.report"):
                summary = polisher.report.summary()
                # compute-side latency-ledger fragment: per-stage seconds
                # from this run's own report plus the build/replay
                # overlays, persisted with the report and shipped in the
                # result for the scheduler's job ledger
                stage_s = ledger.stage_seconds(summary)
                stage_s.update(ledger.overlay_seconds(obs.snapshot()))
                polisher.report.ledger = {"job": job_id, "stage_s": stage_s}
                report_doc = dict(polisher.report.as_dict())
                report_doc["job_id"] = job_id
                # where the job's spans are: the one artifact whose path
                # a reader of the report cannot derive from the report's
                report_doc["trace"] = trace_path
                with open(report_path, "w") as f:
                    json.dump(report_doc, f, indent=1)
                    f.write("\n")

            with obs.span("job.close.ship"):
                self.jobs_run += 1
                obs.telemetry_tick(jobs_run=self.jobs_run, job=job_id)
                # bounded span shipment: rides inside the result payload
                # so a tracing submitter can absorb this job's spans into
                # its own merged timeline
                ship = obs.shipment()
            result = {
                "job_id": job_id,
                "backend": backend,
                "cold": cold,
                "wall_s": round(time.monotonic() - t0, 4),
                "records": len(out),
                "polished_bp": sum(len(data) for _, data in out),
                "kernel_builds": kernel_builds,
                "journal_replayed": _journal_replayed(polisher.report),
                "output": out_path,
                "report": report_path,
                "trace": trace_path,
                "obs": ship,
                "summary": summary,
                "ledger": {"stage_s": dict(stage_s)},
            }
            obs.end("job.close")
            obs.end("job", job=job_id, backend=backend, cold=cold)
        except JobCancelled:
            raise
        except Exception as e:  # noqa: BLE001 — post-mortem breadcrumb;
            # the scheduler owns the failure handling
            flight.dump("job_error", job=job_id,
                        error=f"{type(e).__name__}: {e}")
            raise
        finally:
            # scoped teardown: re-write the (now complete) per-job trace
            # and disarm, so the next job — or a bare polisher in the
            # same process — can never append into this job's file
            r0 = time.monotonic_ns()
            obs.release(write=True)
            self._release_prev = (job_id, r0, time.monotonic_ns())
            context.clear()
        # the second write cannot be in the file it writes: timed here,
        # and stamped into the next job's tracer as `job.release.prev`
        result["release_s"] = (self._release_prev[2] - r0) / 1e9
        return result

    def stats(self) -> dict:
        return {
            "backend": self.backend,
            "jobs_run": self.jobs_run,
            "warmed_window_lengths": list(self.warmed),
            "warm_wall_s": self.warm_wall_s,
            "workdir": self.workdir,
        }


#: Serve knob accessors (registered in racon_tpu/config.py; README has
#: the docs rows).  Centralized here so scheduler/server share defaults.

def serve_port() -> int:
    return config.get_int("RACON_TPU_SERVE_PORT")


def serve_queue_depth() -> int:
    return config.get_int("RACON_TPU_SERVE_QUEUE_DEPTH")


def serve_max_jobs() -> int:
    return config.get_int("RACON_TPU_SERVE_MAX_JOBS")


def serve_warmup_enabled() -> bool:
    return config.get_bool("RACON_TPU_SERVE_WARMUP")


def serve_window_budget() -> int:
    return config.get_int("RACON_TPU_SERVE_WINDOW_BUDGET")
