"""FleetPlane: the autoscaling, multi-job chunk-level control plane.

Where the serve scheduler multiplexes whole jobs onto one resident
session and the distrib coordinator farms chunks of a *single* job to a
*static* worker list, the plane does both at once: every admitted job
is split into contig chunks (``polisher._split_fasta`` — the same
base-balanced split the phase pipeline uses, so chunked output
concatenates byte-identically), all chunks share one dispatch queue,
and an ``ElasticPool`` of `racon_tpu.distrib.worker` processes grows
and shrinks from live signals.  Workers are completely agnostic: the
plane speaks the exact distrib wire protocol (serve/protocol.py), so
the same worker binary serves a fixed coordinator or an elastic plane.

Robustness model, layered on the shared lease core (fleet/leases.py):

* **Affinity + work-stealing.**  A worker prefers chunks of the job it
  last served (hot inputs, hot kernel geometries).  When its job has no
  eligible chunk but others do, it *steals* — tenant-fair rotation,
  highest job priority first — guarded by the deterministic
  ``pool.steal`` fault point and counted/traced (``fleet.steal``).
  ``RACON_TPU_FLEET_STEAL=0`` pins workers to their job instead.
* **Autoscaling.**  The monitor grows the pool one worker per tick when
  a backlog is pending and the recent chunk queueing p95 exceeds
  ``RACON_TPU_FLEET_SCALE_P95_MS`` (or the backlog dwarfs capacity, or
  no worker is active), and drains one worker per idle second above the
  floor.  Both transitions carry fault points (``pool.scale_up`` /
  ``pool.scale_down``); scale-down is drain-based, so a resize can
  never cut a lease or orphan a canonical journal.
* **Leases, speculation, reclaim.**  Exactly the distrib discipline:
  TTL leases with heartbeat renewal, EOF as the fast death signal,
  speculative duplicates for stragglers, exponential backoff on
  re-dispatch, and ``lease.reclaim``-guarded reclaim that releases a
  dead holder's canonical journals so the re-run resumes.
* **Host floor.**  A chunk that exhausts its retry budget — or every
  chunk, when the fleet collapses and cannot respawn — runs in the
  plane through the host-oracle CLI, recorded as a ``fleet -> local``
  degradation in the RunReport.  Output stays byte-identical on every
  path.

Tracing: when armed, dispatches emit ``distrib.dispatch`` events with
fresh child span ids and workers parent their ``distrib.chunk`` spans
under them, so ``python -m racon_tpu.obs fleet`` validates the merged
plane trace exactly like a coordinator trace — with ``fleet.scale_up``
/ ``fleet.scale_down`` / ``fleet.steal`` instant events interleaved.
"""

from __future__ import annotations

import os
import socket
import statistics
import subprocess
import sys
import threading
import time
from typing import Callable, Dict, List, Optional

from .. import obs
from ..obs import context, flight, slo
from ..polisher import _split_fasta
from ..resilience.report import PhaseReport, RunReport
from ..serve.protocol import read_message, write_message
from ..distrib.common import (SCOPED_KNOBS, distrib_fault_worker,
                              distrib_heartbeat, distrib_lease_ttl,
                              distrib_max_retries, distrib_retry_base,
                              distrib_speculate)
from . import (fleet_max_workers, fleet_min_workers, fleet_scale_p95_ms,
               fleet_steal_enabled)
from .leases import (Chunk, Lease, fire_reclaim_fault,
                     release_worker_leases)
from .pool import ElasticPool

#: Lattice tiers of the plane phase (same naming as distrib: the fleet
#: is the device-analogue, local is the in-controller oracle floor).
TIERS = ("fleet", "local")

JOB_TERMINAL = ("done", "failed", "cancelled")


class FleetJob:
    """One admitted job: its inputs, its chunks, and its lifecycle
    (running -> done | failed | cancelled)."""

    def __init__(self, job_id: str, tenant: str, priority: int,
                 sequences: str, overlaps: str, target: str, args: dict,
                 include_unpolished: bool, backend: str, workdir: str,
                 on_done: Optional[Callable] = None):
        self.id = job_id
        self.tenant = tenant
        self.priority = priority
        self.sequences = sequences
        self.overlaps = overlaps
        self.target = target
        self.args = args
        self.include_unpolished = include_unpolished
        self.backend = backend
        self.workdir = workdir
        self.on_done = on_done     # (state, result, error) after terminal
        self.state = "running"
        self.error: Optional[str] = None
        self.result: Optional[dict] = None
        self.chunks: List[Chunk] = []
        self.done = threading.Event()
        self.t_submit = time.monotonic()
        self.t_end: Optional[float] = None
        # ledger stage_s fragment (obs/ledger.py): per-stage seconds
        # accumulated across this job's chunks — plane queue waits plus
        # the workers' report-derived compute stages.  Chunks run in
        # parallel, so these are resource-seconds, not wall slices.
        self.stage_s: Dict[str, float] = {}

    def add_stage(self, stage: str, seconds) -> None:
        # call with the plane's _cv held
        try:
            s = float(seconds)
        except (TypeError, ValueError):
            return
        if s >= 0:
            self.stage_s[stage] = self.stage_s.get(stage, 0.0) + s

    def unfinished(self) -> int:
        return sum(1 for c in self.chunks if c.state != "done")


class FleetPlane:
    def __init__(self, workdir: str,
                 min_workers: Optional[int] = None,
                 max_workers: Optional[int] = None,
                 lease_ttl: Optional[float] = None,
                 max_retries: Optional[int] = None,
                 backend: str = "cpu",
                 trace_path: Optional[str] = None,
                 report_path: Optional[str] = None):
        self.workdir = workdir
        self.min_workers = (fleet_min_workers() if min_workers is None
                            else min_workers)
        self.max_workers = max(self.min_workers, 1 if max_workers is None
                               else max_workers)
        if max_workers is None:
            self.max_workers = max(self.min_workers, fleet_max_workers())
        self.lease_ttl = (distrib_lease_ttl() if lease_ttl is None
                          else lease_ttl)
        self.max_retries = (distrib_max_retries() if max_retries is None
                            else max_retries)
        self.backend = backend
        if backend == "tpu":
            from ..device import check_device_workers
            check_device_workers(self.max_workers, "fleet plane")
        self.trace_path = trace_path
        self.report_path = report_path

        self.jobs: Dict[str, FleetJob] = {}
        self.chunks: List[Chunk] = []          # global chunk table
        self.counters: Dict[str, int] = {}
        self.completed_walls: List[float] = []
        self.queue_waits: List[float] = []     # eligible->dispatch, s
        self.worker_stats: Dict[int, dict] = {}
        self._staleness_max = 0.0
        self._affinity: Dict[int, str] = {}    # worker -> last job id
        self._tenant_rr: List[str] = []        # steal-order rotation
        self._ctx: Optional[dict] = None
        self._last_tick = 0.0
        self._last_scale = 0.0
        self._idle_ticks = 0
        self._respawn_failures = 0
        self._degraded = False
        self.report = RunReport()
        self.phase = PhaseReport("fleet", TIERS)
        self.report.attach(self.phase)
        self._cv = threading.Condition()
        self._stopping = False
        self._dead_workers = set()
        self._sock: Optional[socket.socket] = None
        self._monitor_thread: Optional[threading.Thread] = None
        self.port = 0
        self.pool = ElasticPool(
            logs_dir=os.path.join(workdir, "workers"),
            min_workers=self.min_workers, max_workers=self.max_workers,
            env_fn=self._worker_env,
            on_spawn=lambda i, pid: obs.event("fleet.spawn", worker=i,
                                              pid=pid),
            on_spawn_failure=self._on_spawn_failure)

    # -- counters -----------------------------------------------------------

    def _count(self, name: str, n: int = 1) -> None:
        # Condition wraps an RLock, so this is safe (and cheap) from
        # call sites that already hold self._cv.
        with self._cv:
            self.counters[name] = self.counters.get(name, 0) + n
        obs.count(f"fleet.{name}", n)

    def _on_spawn_failure(self, index: int, exc: BaseException) -> None:
        self.phase.record_failure("fleet", exc)  # concurrency: PhaseReport counters are guarded by the pool caller's _cv (monitor/start paths)
        obs.event("fleet.spawn_failed", worker=index,
                  error=f"{type(exc).__name__}: {exc}")

    # -- lifecycle ----------------------------------------------------------

    def start(self) -> None:
        """Arm tracing/flight, bind the dispatch socket, fill the pool
        to its floor, start the monitor.  The plane owns the process
        tracer for its lifetime (with the plane on, device jobs run in
        workers, not in-process, so nothing else arms it)."""
        obs.reset()
        obs.set_role("fleet")
        context.activate(context.fresh())
        obs.configure(trace_path=self.trace_path)
        self._ctx = context.current() if obs.enabled() else None
        os.makedirs(self.workdir, exist_ok=True)
        flight.set_dir(self.workdir)
        self._sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._sock.bind(("127.0.0.1", 0))
        self.port = self._sock.getsockname()[1]
        self._sock.listen(16)
        t = threading.Thread(target=self._accept_loop,
                             name="fleet-accept", daemon=True)
        t.start()
        with self._cv:
            self.pool.port = self.port
            self.pool.start()
        self._monitor_thread = threading.Thread(
            target=self._monitor, name="fleet-monitor", daemon=True)
        self._monitor_thread.start()

    def stop(self, timeout: float = 10.0) -> None:
        """Graceful shutdown: stop dispatching (every fetch drains),
        wait the workers out, kill leftovers, write report + trace."""
        with self._cv:
            if self._stopping:
                return
            self._stopping = True
            self._cv.notify_all()
        if self._monitor_thread is not None:
            self._monitor_thread.join(timeout)
        self.pool.shutdown(timeout=max(1.0, timeout / 2))
        if self._sock is not None:
            try:
                self._sock.close()
            except OSError:
                pass
        self.report.finalize()
        self.report.flight = flight.scan(self.workdir)
        if self.report.flight:
            self._count("flight_dumps", len(self.report.flight))
        with self._cv:
            self.phase.extra.update(self.counters)
            self.phase.extra.update(self.pool.counters)
        if self.report_path:
            self.report.write(self.report_path)
        obs.release(write=True)
        context.clear()

    def _worker_env(self, index: int) -> dict:
        env = dict(os.environ)
        for k in SCOPED_KNOBS:
            env.pop(k, None)
        # fault scoping: exactly one worker inherits RACON_TPU_FAULT, so
        # a chaos run kills a known worker instead of the whole fleet
        if "RACON_TPU_FAULT" in env and index != distrib_fault_worker():
            env.pop("RACON_TPU_FAULT", None)
        return env

    # -- submission ---------------------------------------------------------

    def submit_job(self, job_id: str, sequences: str, overlaps: str,
                   target: str, args: dict, include_unpolished: bool,
                   backend: str, workdir: str, tenant: str = "local",
                   priority: int = 0,
                   on_done: Optional[Callable] = None) -> FleetJob:
        """Admit one job: split it into chunks and make them eligible.
        Returns immediately; ``on_done(state, result, error)`` fires
        (off the submitter's thread) when the job is terminal."""
        chunks_dir = os.path.join(workdir, "chunks")
        os.makedirs(chunks_dir, exist_ok=True)
        # the split is deterministic in (target, hint): a restarted
        # daemon re-splits identically and chunk journals line up
        paths = _split_fasta(target, max(2, 2 * self.max_workers),
                             chunks_dir)
        if paths is None:
            paths = [target]
        job = FleetJob(job_id, tenant, priority, sequences, overlaps,
                       target, args, include_unpolished,
                       backend or self.backend, workdir, on_done)
        with self._cv:
            if self._stopping:
                raise RuntimeError("fleet plane is stopping")
            if job_id in self.jobs and \
                    self.jobs[job_id].state not in JOB_TERMINAL:
                raise RuntimeError(f"job {job_id!r} is already "
                                   f"{self.jobs[job_id].state}")
            base = len(self.chunks)
            for i, p in enumerate(paths):
                cd = os.path.join(chunks_dir, f"chunk{i:03d}")
                os.makedirs(cd, exist_ok=True)
                c = Chunk(base + i, p, cd)
                c.job = job           # backrefs for multi-job dispatch
                c.pos = i             # position inside the job's gather
                job.chunks.append(c)
                self.chunks.append(c)
            self.jobs[job_id] = job
            self.phase.total += len(job.chunks)
            if tenant not in self._tenant_rr:
                self._tenant_rr.append(tenant)
            self._count("jobs_admitted")
            self._cv.notify_all()
        return job

    def cancel_job(self, job_id: str) -> bool:
        """Cancel a job: pending chunks never dispatch again, running
        attempts are told to stop renewing on their next heartbeat and
        their late results are discarded.  True if the job was live."""
        with self._cv:
            job = self.jobs.get(job_id)
            if job is None or job.state in JOB_TERMINAL:
                return False
            job.state = "cancelled"
            job.error = "cancelled"
            job.t_end = time.monotonic()
            self._count("jobs_cancelled")
            self._cv.notify_all()
        self._finish_job(job, "cancelled", error="cancelled mid-run")
        return True

    # -- connection handling ------------------------------------------------

    def _accept_loop(self) -> None:
        while True:
            try:
                conn, _ = self._sock.accept()
            except OSError:
                return   # socket closed during shutdown
            t = threading.Thread(target=self._serve_conn, args=(conn,),
                                 name="fleet-conn", daemon=True)
            t.start()

    def _serve_conn(self, conn: socket.socket) -> None:
        worker = -1
        try:
            f = conn.makefile("rwb")
            while True:
                try:
                    req = read_message(f)
                    if req is None:
                        break
                    if "worker" in req:
                        worker = int(req["worker"])
                    resp = self._dispatch(req)
                except (ValueError, KeyError, TypeError) as e:
                    resp = {"ok": False, "error": f"{e}"}
                except Exception as e:  # noqa: BLE001 — one bad request
                    # must not take down the plane
                    resp = {"ok": False,
                            "error": f"{type(e).__name__}: {e}"}
                write_message(f, resp)
        except (OSError, BrokenPipeError, ConnectionResetError):
            pass
        finally:
            try:
                conn.close()
            except OSError:
                pass
            # EOF on any of a worker's connections: a clean drain is a
            # completed scale-down; anything else is the fast death
            # signal and reclaims the worker's leases right now
            if worker >= 0:
                if self.pool.is_draining(worker):
                    self._count("workers_drained")
                else:
                    self._worker_dead(worker, "connection lost")

    def _dispatch(self, req: dict) -> dict:
        op = req.get("op")
        if op == "hello":
            return {"ok": True, "lease_ttl": self.lease_ttl,
                    "heartbeat": distrib_heartbeat(self.lease_ttl)}
        if op == "fetch":
            return self._fetch(int(req["worker"]))
        if op == "heartbeat":
            return self._heartbeat(int(req["worker"]), int(req["chunk"]),
                                   int(req["attempt"]))
        if op == "result":
            return self._result(req)
        if op == "error":
            return self._chunk_error(req)
        if op == "stats":
            return self._stats()
        raise ValueError(f"unknown op {op!r}")

    # -- assignment ---------------------------------------------------------

    def _eligible(self, now: float) -> List[Chunk]:
        """Dispatchable chunks (call with the lock held)."""
        return [c for c in self.chunks
                if c.state == "pending" and not c.local
                and c.next_eligible <= now
                and c.job.state == "running"]

    def _fetch(self, worker: int) -> dict:
        with self._cv:
            if self._stopping or self.pool.is_draining(worker):
                # a worker only fetches between chunks, so a drain
                # answer here is graceful by construction: it holds no
                # lease and owns no canonical journal
                return {"ok": True, "drain": True}
            now = time.monotonic()
            eligible = self._eligible(now)
            aff = self.jobs.get(self._affinity.get(worker, ""))
            if aff is not None and aff.state == "running":
                own = [c for c in eligible if c.job is aff]
                if own:
                    chunk = min(own, key=lambda c: (worker in c.tried,
                                                    c.index))
                    return self._assign(chunk, worker, speculative=False)
                if eligible:
                    # the worker's job is live but starved here: take a
                    # chunk from another job (tenant-fair, priority
                    # first) — the cross-job steal
                    if not fleet_steal_enabled():
                        return {"ok": True, "wait": True, "poll_s": 0.2}
                    try:
                        from ..resilience import faults
                        faults.check("pool.steal")
                    except Exception:  # noqa: BLE001 — absorbed: a
                        # faulted steal skips this fetch; the chunk
                        # stays eligible for the next one
                        self._count("steal_faults")
                        return {"ok": True, "wait": True, "poll_s": 0.2}
                    chunk = self._pick_fair(eligible, worker)
                    self._count("steals")
                    obs.event("fleet.steal", chunk=chunk.index,
                              worker=worker, job=chunk.job.id,
                              victim_tenant=chunk.job.tenant,
                              from_job=aff.id)
                    return self._assign(chunk, worker, speculative=False)
            elif eligible:
                chunk = self._pick_fair(eligible, worker)
                return self._assign(chunk, worker, speculative=False)
            chunk = self._straggler(worker, now)
            if chunk is not None:
                self._count("speculative")
                return self._assign(chunk, worker, speculative=True)
            return {"ok": True, "wait": True, "poll_s": 0.2}

    def _pick_fair(self, eligible: List[Chunk], worker: int) -> Chunk:
        """Tenant-fair pick: the first tenant in the rotation with an
        eligible chunk is served and rotates to the back; within a
        tenant, highest job priority first, then a chunk this worker
        has not tried, then global order (call with the lock held)."""
        by_tenant: Dict[str, List[Chunk]] = {}
        for c in eligible:
            by_tenant.setdefault(c.job.tenant, []).append(c)
        for t in by_tenant:
            if t not in self._tenant_rr:
                self._tenant_rr.append(t)
        for i, t in enumerate(self._tenant_rr):
            cs = by_tenant.get(t)
            if cs:
                self._tenant_rr.append(self._tenant_rr.pop(i))
                return min(cs, key=lambda c: (-c.job.priority,
                                              worker in c.tried, c.index))
        return min(eligible, key=lambda c: c.index)

    def _straggler(self, worker: int, now: float) -> Optional[Chunk]:
        """The longest-running chunk past the speculation threshold
        that `worker` could duplicate (call with the lock held)."""
        factor = distrib_speculate()
        if factor <= 0 or not self.completed_walls:
            return None
        median = statistics.median(self.completed_walls)
        best, best_elapsed = None, 0.0
        for c in self.chunks:
            if (c.state != "running" or c.local or worker in c.tried
                    or len(c.leases) >= 2 or not c.leases
                    or c.job.state != "running"):
                continue
            elapsed = now - min(ls.t_start for ls in c.leases.values())
            if elapsed > factor * median and elapsed > best_elapsed:
                best, best_elapsed = c, elapsed
        return best

    def _assign(self, c: Chunk, worker: int, speculative: bool) -> dict:  # concurrency: caller holds this plane's _cv; a Chunk is owned by exactly one plane, so the coordinator's _cv never guards the same instance
        c.attempts += 1
        attempt = c.attempts
        c.state = "running"
        c.tried.add(worker)
        canonical = not c.journal_held
        if canonical:
            c.journal_held = True
            journal = c.journal
        else:
            journal = os.path.join(c.dir, f"journal.a{attempt}.jsonl")
        c.leases[attempt] = Lease(worker, attempt, self.lease_ttl,
                                  canonical)
        self._affinity[worker] = c.job.id
        wait = max(0.0, time.monotonic() - max(c.t_pending,
                                               c.next_eligible))
        self.queue_waits.append(wait)
        # plane-side queueing rides the job ledger's dispatch stage:
        # with a plane attached the scheduler's own dispatch is instant
        # and the real wait happens here, per chunk
        c.job.add_stage("dispatch", wait)
        self._count("dispatches")
        if attempt > 1 and not speculative:
            self._count("redispatches")
        # same dispatch/span contract as the distrib coordinator: the
        # worker stamps this span id as its distrib.chunk parent, so
        # `obs fleet` parents the merged plane trace identically; the
        # job id lets `obs critpath` group chunk spans per job
        ctx = context.child(self._ctx)
        obs.event("distrib.dispatch", chunk=c.index, worker=worker,
                  attempt=attempt, speculative=speculative,
                  canonical_journal=canonical, job=c.job.id,
                  tenant=c.job.tenant,
                  trace_id=(ctx or {}).get("trace_id"),
                  span_id=(ctx or {}).get("parent"))
        return {"ok": True, "chunk": {
            "index": c.index, "attempt": attempt,
            "sequences": c.job.sequences, "overlaps": c.job.overlaps,
            "target": c.target, "args": c.job.args,
            "include_unpolished": c.job.include_unpolished,
            "backend": c.job.backend, "journal": journal,
            "output": os.path.join(c.dir, f"out.a{attempt}.fasta"),
            "trace": ctx,
        }}

    # -- worker messages ----------------------------------------------------

    def _heartbeat(self, worker: int, index: int, attempt: int) -> dict:
        with self._cv:
            c = self.chunks[index]
            lease = c.leases.get(attempt)
            if (lease is None or c.state == "done"
                    or c.job.state != "running"):
                return {"ok": True, "cancel": True}
            now = time.monotonic()
            self._staleness_max = max(self._staleness_max,
                                      now - lease.last_beat)
            lease.last_beat = now
            lease.deadline = now + self.lease_ttl
            self._count("heartbeats")
            return {"ok": True, "cancel": False}

    def _result(self, req: dict) -> dict:
        index = int(req["chunk"])
        attempt = int(req["attempt"])
        stats = req.get("stats") or {}
        finished: Optional[FleetJob] = None
        with self._cv:
            c = self.chunks[index]
            lease = c.leases.pop(attempt, None)
            if c.state == "done" or c.job.state != "running":
                self._count("duplicates")
                obs.event("fleet.duplicate", chunk=index,
                          worker=int(req["worker"]), attempt=attempt)
                return {"ok": True, "accepted": False}
            c.state = "done"
            c.served_by = "fleet"
            c.output = str(req["output"])
            c.stats = stats
            self.phase.record_served("fleet")
            if lease is not None:
                wall = time.monotonic() - lease.t_start
                self.completed_walls.append(wall)
                self.phase.add_wall("fleet", wall)
            replayed = int(stats.get("journal_replayed") or 0)
            if replayed:
                self._count("journal_replayed", replayed)
            self._count("chunks_fleet")
            # fold the worker's report-derived stage durations into the
            # job's ledger fragment (shipped onward in _gather)
            frag = stats.get("stage_s")
            if isinstance(frag, dict):
                for stage, s in frag.items():
                    if isinstance(stage, str):
                        c.job.add_stage(stage, s)
            ws = self.worker_stats.setdefault(
                int(req["worker"]),
                {"chunks": 0, "wall_s": 0.0, "kernel_wall_s": 0.0,
                 "rss_mb": 0.0})
            ws["chunks"] += 1
            ws["wall_s"] = round(
                ws["wall_s"] + float(stats.get("wall_s") or 0.0), 4)
            ws["kernel_wall_s"] = round(
                ws["kernel_wall_s"]
                + float(stats.get("kernel_wall_s") or 0.0), 4)
            # peak RSS per worker: the memory dimension of
            # fleet_telemetry() the admission ladder reads
            ws["rss_mb"] = max(ws.get("rss_mb", 0.0),
                               float(stats.get("rss_mb") or 0.0))
            obs.event("fleet.chunk_done", chunk=index, job=c.job.id,
                      worker=int(req["worker"]), attempt=attempt,
                      replayed=replayed)
            absorbed = obs.absorb(req.get("obs"))
            if absorbed:
                self._count("obs_events_absorbed", absorbed)
            if c.job.unfinished() == 0:
                finished = c.job
            self._cv.notify_all()
        if finished is not None:
            self._finish_job(finished, "done")
        return {"ok": True, "accepted": True}

    def _chunk_error(self, req: dict) -> dict:
        index = int(req["chunk"])
        attempt = int(req["attempt"])
        err = str(req.get("error", "worker error"))
        with self._cv:
            c = self.chunks[index]
            lease = c.leases.pop(attempt, None)
            if lease is not None and lease.canonical:
                # the worker survived to report, so its journal writer
                # is closed: the canonical journal is safe to hand on
                c.journal_held = False
            if c.state != "done" and c.job.state == "running":
                self._fail_chunk(c, RuntimeError(err))
            obs.event("fleet.chunk_error", chunk=index,
                      worker=int(req["worker"]), attempt=attempt,
                      error=err)
            return {"ok": True}

    def _stats(self) -> dict:
        with self._cv:
            now = time.monotonic()
            states = {"pending": 0, "running": 0, "done": 0}
            for c in self.chunks:
                states[c.state] = states.get(c.state, 0) + 1
            leases = sum(len(c.leases) for c in self.chunks)
            staleness = 0.0
            for c in self.chunks:
                for ls in c.leases.values():
                    staleness = max(staleness, now - ls.last_beat)
            self._staleness_max = max(self._staleness_max, staleness)
            return {"ok": True,
                    "chunks": states,
                    "leases": leases,
                    "workers": {"live": self.pool.live(),
                                "dead": len(self._dead_workers)},
                    "served": dict(self.phase.served),
                    "staleness_s": round(staleness, 3),
                    "counters": dict(self.counters),
                    "telemetry": obs.telemetry(last=8)}

    # -- failure paths (call with the lock held) ----------------------------

    def _fail_chunk(self, c: Chunk, exc: BaseException) -> None:  # concurrency: caller holds this plane's _cv; a Chunk is owned by exactly one plane
        c.failures += 1
        self.phase.record_failure("fleet", exc)
        self.phase.retries += 1
        if not c.leases and c.state != "done":
            c.state = "pending"
            backoff = distrib_retry_base() * (2 ** (c.failures - 1))
            c.next_eligible = time.monotonic() + backoff
            self._cv.notify_all()

    def _worker_dead(self, worker: int, why: str) -> None:
        with self._cv:
            if worker in self._dead_workers or self._stopping:
                return
            self._dead_workers.add(worker)
            self._count("workers_dead")
            obs.event("fleet.worker_dead", worker=worker, cause=why)
            # the reclaim transition is a named fault point: kill=1
            # crashes the controller mid-reclaim, a raise is absorbed
            # and counted — reclaim itself always proceeds
            if fire_reclaim_fault():
                self._count("reclaim_faults")
            for c in self.chunks:
                popped = release_worker_leases(c, worker)
                if popped:
                    self._count("lease_reclaimed", len(popped))
                    if c.state != "done" and c.job.state == "running":
                        self._fail_chunk(
                            c, RuntimeError(f"worker {worker} died "
                                            f"({why}) holding chunk "
                                            f"{c.index}"))

    def _expire_leases(self) -> None:
        now = time.monotonic()
        with self._cv:
            for c in self.chunks:
                expired = [a for a, ls in c.leases.items()
                           if ls.deadline < now]
                for a in expired:
                    lease = c.leases.pop(a)
                    # NOT releasing the canonical journal: an
                    # unresponsive-but-alive holder may still be writing
                    self._count("lease_expired")
                    obs.event("fleet.lease_expired", chunk=c.index,
                              worker=lease.worker, attempt=a)
                    if c.state != "done" and c.job.state == "running":
                        self._fail_chunk(
                            c, TimeoutError(
                                f"lease on chunk {c.index} expired "
                                f"(worker {lease.worker}, attempt {a})"))

    # -- autoscaling monitor ------------------------------------------------

    def _monitor(self) -> None:
        while True:
            with self._cv:
                if self._stopping:
                    return
            for index, rc, was_draining in self._reap():
                if not was_draining:
                    self._worker_dead(index, f"exited {rc}")
            self._expire_leases()
            now = time.monotonic()
            if now - self._last_scale >= 0.25:
                self._last_scale = now
                self._autoscale(now)
            if now - self._last_tick >= 1.0:
                self._last_tick = now
                self._telemetry_tick(now)
            local_work = []
            with self._cv:
                for c in self.chunks:
                    if (c.failures > self.max_retries and not c.leases
                            and c.state == "pending" and not c.local
                            and c.job.state == "running"):
                        c.local = True
                        self._degrade(f"chunk {c.index} exhausted its "
                                      f"retry budget ({c.failures} "
                                      f"failures > {self.max_retries})")
                local_work = [c for c in self.chunks
                              if c.local and c.state == "pending"
                              and c.job.state == "running"]
            for c in local_work:
                self._run_local(c)
            with self._cv:
                self._cv.wait(0.05)

    def _reap(self):
        with self._cv:
            return self.pool.reap()

    def _autoscale(self, now: float) -> None:
        """One scaling decision per call: grow when a backlog queues
        past the p95 trigger (or capacity is gone), drain when idle
        above the floor.  At most one worker per direction per tick, so
        the pool walks, never thrashes."""
        with self._cv:
            backlog = len(self._eligible(now))
            active = self.pool.active()
            live = self.pool.live()
            leases = sum(len(c.leases) for c in self.chunks)
            recent = self.queue_waits[-50:]
            p95_ms = 0.0
            if recent:
                waits = sorted(recent)
                p95_ms = 1000.0 * waits[min(len(waits) - 1,
                                            int(0.95 * len(waits)))]
            if backlog > 0:
                self._idle_ticks = 0
                # SLO burn is a first-class scale trigger: a multi-window
                # burn-rate alert grows the pool even before the queueing
                # p95 trips (obs/slo.py; the cause string makes the
                # slo-driven growth visible in counters and the trace)
                slo_burn = slo.engine().alerting("")
                if slo_burn:
                    self._count("slo_alert_ticks")
                if active == 0 or p95_ms > fleet_scale_p95_ms() \
                        or backlog >= 4 * active \
                        or (slo_burn and live < self.pool.max_workers):
                    cause = (f"backlog {backlog}, active {active}, "
                             f"queueing p95 {p95_ms:.0f}ms")
                    if slo_burn:
                        cause = f"slo_burn: {cause}"
                    spawned = self.pool.scale_up(1, cause=cause)
                    if slo_burn and spawned:
                        self._count("scale_up_slo")
                    if active == 0 and spawned == 0 and live == 0:
                        self._respawn_failures += 1
                        if self._respawn_failures >= 3:
                            # fleet collapse and the pool cannot come
                            # back: every eligible chunk falls to the
                            # local oracle floor
                            for c in self._eligible(now):
                                c.local = True
                            self._degrade("fleet collapse: no live "
                                          "workers and respawn failing")
                    else:
                        self._respawn_failures = 0
            elif leases == 0 and active > self.pool.min_workers:
                self._idle_ticks += 1
                if self._idle_ticks >= 4:
                    self._idle_ticks = 0
                    self.pool.scale_down(1, cause="idle above floor")
            else:
                self._idle_ticks = 0

    def _telemetry_tick(self, now: float) -> None:
        with self._cv:
            staleness = max(
                (now - ls.last_beat for c in self.chunks
                 for ls in c.leases.values()), default=0.0)
            self._staleness_max = max(self._staleness_max, staleness)
            obs.telemetry_tick(
                queue_depth=sum(1 for c in self.chunks
                                if c.state == "pending"
                                and c.job.state == "running"),
                leases=sum(len(c.leases) for c in self.chunks),
                workers_live=self.pool.live(),
                workers_active=self.pool.active(),
                jobs_running=sum(1 for j in self.jobs.values()
                                 if j.state == "running"),
                staleness_s=round(staleness, 3))

    def _degrade(self, cause: str) -> None:
        """Record the fleet→local lattice step (once per plane life)."""
        if not self._degraded:
            self._degraded = True
            self.phase.record_degrade("fleet", "local",
                                      RuntimeError(cause))

    # -- local (host-oracle) floor ------------------------------------------

    def _run_local(self, c: Chunk) -> None:  # concurrency: chunk-state writes happen under this plane's _cv; a Chunk is owned by exactly one plane
        """Execute one chunk in the plane through the host-oracle CLI —
        the same demotion target as the serve host lane, byte-identical
        output.  A free canonical journal (cpu fingerprint only) is
        resumed; otherwise a fresh local journal."""
        job = c.job
        with self._cv:
            if c.state == "done" or job.state != "running":
                return
            c.state = "running"
            resume = (not c.journal_held) and job.backend == "cpu"
        journal = c.journal if resume else os.path.join(
            c.dir, "journal.local.jsonl")
        out_path = os.path.join(c.dir, "out.local.fasta")
        part = out_path + ".part"
        a = job.args
        cmd = [sys.executable, "-m", "racon_tpu.cli",
               "-w", str(a["window_length"]),
               "-q", str(a["quality_threshold"]),
               "-e", str(a["error_threshold"]),
               "-m", str(a["match"]), "-x", str(a["mismatch"]),
               "-g", str(a["gap"]), "-t", str(a["num_threads"]),
               "--resume-journal", journal]
        if not a["trim"]:
            cmd.append("--no-trimming")
        if a["fragment_correction"]:
            cmd.append("-f")
        if job.include_unpolished:
            cmd.append("-u")
        cmd += [job.sequences, job.overlaps, c.target]
        env = dict(os.environ)
        for k in SCOPED_KNOBS:
            env.pop(k, None)
        t0 = time.monotonic()
        with open(part, "w") as out_f, \
                open(os.path.join(c.dir, "local.stderr.log"), "w") as err_f:
            rc = subprocess.call(cmd, stdout=out_f, stderr=err_f, env=env)
        finished: Optional[FleetJob] = None
        failed = False
        with self._cv:
            if c.state == "done" or job.state != "running":
                self._count("duplicates")   # a late fleet result won
                return
            if rc != 0:
                # the local rung is the floor: a failure here fails the
                # JOB (not the plane) — the scheduler's host lane is
                # the next rung up and re-runs the whole job there
                self.phase.record_failure(
                    "local", RuntimeError(f"local chunk {c.index} "
                                          f"exited {rc}"))
                failed = True
            else:
                os.replace(part, out_path)
                c.state = "done"
                c.served_by = "local"
                c.output = out_path
                self.phase.record_served("local")
                self.phase.add_wall("local", time.monotonic() - t0)
                self._count("chunks_local")
                obs.event("fleet.chunk_local", chunk=c.index, job=job.id)
                if job.unfinished() == 0:
                    finished = job
                self._cv.notify_all()
        if failed:
            with self._cv:
                if job.state == "running":
                    job.state = "failed"
                    job.error = (f"chunk {c.index} failed on the local "
                                 f"rung (exit {rc}; see "
                                 f"{c.dir}/local.stderr.log)")
                    job.t_end = time.monotonic()
                    self._count("jobs_failed")
            self._finish_job(job, "failed", error=job.error)
        elif finished is not None:
            self._finish_job(finished, "done")

    # -- job completion -----------------------------------------------------

    def _finish_job(self, job: FleetJob, state: str,
                    error: Optional[str] = None) -> None:
        """Gather (on done), mark terminal, fire the callback.  Runs
        outside the lock: the gather is file I/O and the callback
        re-enters the scheduler's own lock — holding ours across either
        would order fleet._cv before scheduler._cv."""
        result = None
        if state == "done":
            try:
                result = self._gather(job)
            except Exception as e:  # noqa: BLE001 — a torn gather fails
                # the job, not the plane
                state, error = "failed", f"gather: {type(e).__name__}: {e}"
        with self._cv:
            if job.state == "running" or job.state == "cancelled":
                job.state = state if job.state != "cancelled" \
                    else "cancelled"
            job.result = result
            if error and not job.error:
                job.error = error
            if job.t_end is None:
                job.t_end = time.monotonic()
            if state == "done":
                self._count("jobs_done")
            elif state == "failed":
                self._count("jobs_failed")
            obs.event("fleet.job_done", job=job.id, state=job.state,
                      chunks=len(job.chunks))
            job.done.set()
            self._cv.notify_all()
        if job.on_done is not None:
            job.on_done(job.state, result, job.error)

    def _gather(self, job: FleetJob) -> dict:
        """Ordered gather: chunk outputs concatenate in position order,
        so the polished FASTA is byte-identical to a single-process
        run."""
        out_path = os.path.join(job.workdir, "polished.fasta")
        part = out_path + ".part"
        with open(part, "wb") as out:
            for c in sorted(job.chunks, key=lambda c: c.pos):
                assert c.state == "done" and c.output, c.index
                with open(c.output, "rb") as f:
                    out.write(f.read())
        os.replace(part, out_path)
        records = polished_bp = 0
        with open(out_path) as f:
            for line in f:
                if line.startswith(">"):
                    records += 1
                else:
                    polished_bp += len(line.strip())
        replayed = sum(int(c.stats.get("journal_replayed") or 0)
                       for c in job.chunks)
        served: Dict[str, int] = {}
        for c in job.chunks:
            served[c.served_by or "?"] = served.get(c.served_by or "?",
                                                    0) + 1
        return {
            "job_id": job.id,
            "backend": job.backend,
            "cold": False,
            "wall_s": round(time.monotonic() - job.t_submit, 4),
            "records": records,
            "polished_bp": polished_bp,
            "kernel_builds": 0,
            "journal_replayed": replayed,
            "output": out_path,
            "report": None,
            "trace": None,
            "summary": None,
            "fleet": {"chunks": len(job.chunks), "served": served},
            "ledger": {"stage_s": {k: round(v, 6) for k, v in
                                   sorted(job.stage_s.items())}},
        }

    # -- telemetry ----------------------------------------------------------

    def _queueing_p95(self) -> Optional[float]:
        waits = sorted(self.queue_waits)
        if not waits:
            return None
        return round(waits[min(len(waits) - 1,
                               int(0.95 * len(waits)))], 4)

    def fleet_telemetry(self) -> dict:
        """The per-run fleet telemetry summary stamped into serve stats
        and bench entries."""
        with self._cv:
            return {
                "workers": {str(w): dict(s)
                            for w, s in sorted(self.worker_stats.items())},
                "queueing_p95_s": self._queueing_p95(),
                "staleness_max_s": round(self._staleness_max, 3),
            }

    def snapshot(self) -> dict:
        """Live control-plane snapshot for the serve ``stats`` verb and
        the load-test poller: pool size/limits, counters, timeline."""
        with self._cv:
            jobs: Dict[str, int] = {}
            for j in self.jobs.values():
                jobs[j.state] = jobs.get(j.state, 0) + 1
            counters = dict(self.counters)
            counters.update(self.pool.counters)
            return {
                "workers": {"live": self.pool.live(),
                            "active": self.pool.active(),
                            "dead": len(self._dead_workers)},
                "min_workers": self.pool.min_workers,
                "max_workers": self.pool.max_workers,
                "jobs": jobs,
                "chunks_pending": sum(1 for c in self.chunks
                                      if c.state == "pending"),
                "counters": counters,
                "queueing_p95_s": self._queueing_p95(),
                "staleness_max_s": round(self._staleness_max, 3),
                "timeline": [list(s) for s in
                             self.pool.size_timeline[-64:]],
            }
