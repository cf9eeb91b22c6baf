"""The `racon-tpu distrib` coordinator: chunk fleet with leases.

The coordinator splits the target FASTA into contiguous contig chunks
(``polisher._split_fasta`` — the same base-balanced split the phase
pipeline uses, so chunked output concatenates byte-identically) and
farms them out to a fleet of worker processes over the serve wire
format (newline-JSON over localhost TCP, serve/protocol.py).  Workers
are clients: they connect, say ``hello``, then loop ``fetch`` →
polish → ``result``; a background thread per in-flight chunk sends
``heartbeat`` renewals on a second connection.

Robustness model (the headline, not an afterthought):

* **Leases.**  Every assignment carries a TTL lease.  A heartbeat renews
  it; a lease that outlives its TTL expires and the chunk re-queues with
  exponential backoff (``RACON_TPU_DISTRIB_RETRY_BASE * 2^n``).  A
  worker connection EOF (crash, SIGKILL) expires all of its leases
  immediately — death is detected at socket speed, not TTL speed.
* **Re-dispatch.**  An expired/failed chunk prefers a worker that has
  not attempted it.  The per-chunk journal lives on the shared
  filesystem, so when the previous holder is *known dead* the re-run
  resumes the journaled prefix instead of recomputing
  (resilience/journal.py); a holder that is merely unresponsive keeps
  journal ownership and the re-run writes a fresh side journal — two
  live writers never share a journal file.
* **Speculation.**  An idle worker with no pending work duplicates the
  longest-running chunk once it exceeds ``RACON_TPU_DISTRIB_SPECULATE``
  × the median completed-chunk wall.  The first result to arrive wins;
  later duplicates are discarded deterministically (the chunk is already
  ``done``) and counted.
* **Fleet → local.**  The degradation lattice's next rung up: a chunk
  that exhausts its retry budget — or every chunk, when the fleet
  shrinks to zero — is executed by the coordinator itself through the
  host-oracle CLI (the same demotion target as the serve host lane),
  recorded as a ``fleet → local`` degradation in the run report.

Ordered gather: results install per chunk index and concatenate in
order, so the polished FASTA is byte-identical to a single-process run
(pinned by tests/test_distrib.py and the CI chaos job's ``cmp`` gate).

The lease/chunk lifecycle and the worker-process pool live in
racon_tpu/fleet (leases.py, pool.py) — the shared core this coordinator
and the elastic multi-job FleetPlane both run on.  The coordinator uses
the pool at a fixed size (min == max == ``--workers``); reclaim of a
dead worker's leases passes through the ``lease.reclaim`` fault point.
"""

from __future__ import annotations

import os
import socket
import statistics
import subprocess
import sys
import threading
import time
from typing import Dict, List, Optional

from .. import obs
from ..fleet.leases import (Chunk, Lease,  # noqa: F401 — re-exported;
                            # the classes moved to the shared fleet core
                            fire_reclaim_fault, release_worker_leases)
from ..fleet.pool import ElasticPool
from ..obs import context, flight
from ..polisher import _split_fasta
from ..resilience import faults
from ..resilience.report import PhaseReport, RunReport
from ..serve.protocol import read_message, write_message
from ..serve.session import POLISH_ARG_DEFAULTS
from .common import (SCOPED_KNOBS, distrib_fault_worker,
                     distrib_heartbeat, distrib_lease_ttl,
                     distrib_max_retries, distrib_retry_base,
                     distrib_speculate, distrib_workers)

#: Fleet tiers, lattice order (fleet is the device-analogue; local is
#: the coordinator-run oracle floor).
TIERS = ("fleet", "local")


class Coordinator:
    def __init__(self, sequences: str, overlaps: str, target: str,
                 workdir: str, args: Optional[dict] = None,
                 include_unpolished: bool = False, backend: str = "cpu",
                 workers: Optional[int] = None,
                 chunks_hint: Optional[int] = None,
                 lease_ttl: Optional[float] = None,
                 max_retries: Optional[int] = None,
                 trace_path: Optional[str] = None,
                 report_path: Optional[str] = None):
        self.sequences = sequences
        self.overlaps = overlaps
        self.target = target
        self.workdir = workdir
        self.args = dict(POLISH_ARG_DEFAULTS)
        self.args.update(args or {})
        self.include_unpolished = include_unpolished
        self.backend = backend
        self.n_workers = distrib_workers() if workers is None else workers
        if backend == "tpu":
            from ..device import check_device_workers
            check_device_workers(self.n_workers, "distrib")
        self.chunks_hint = chunks_hint
        self.lease_ttl = (distrib_lease_ttl() if lease_ttl is None
                          else lease_ttl)
        self.max_retries = (distrib_max_retries() if max_retries is None
                            else max_retries)
        self.trace_path = trace_path
        self.report_path = report_path

        self.chunks: List[Chunk] = []
        self.counters: Dict[str, int] = {}
        self.completed_walls: List[float] = []
        self.queue_waits: List[float] = []      # eligible→dispatch, s
        self.worker_stats: Dict[int, dict] = {} # per-worker aggregates
        self._staleness_max = 0.0               # worst heartbeat gap, s
        self._ctx: Optional[dict] = None        # fleet trace context
        self._last_tick = 0.0
        self.report = RunReport()
        self.phase = PhaseReport("distrib", TIERS)
        self.report.attach(self.phase)
        self._cv = threading.Condition()
        self._stopping = False
        self._degraded = False
        self._dead_workers = set()
        self._sock: Optional[socket.socket] = None
        self.port = 0
        # fixed-size use of the shared elastic pool: min == max, filled
        # once by start(); spawn failures shrink it, nothing regrows it
        self.pool = ElasticPool(
            logs_dir=os.path.join(workdir, "workers"),
            min_workers=self.n_workers, max_workers=self.n_workers,
            env_fn=self._worker_env,
            on_spawn=lambda i, pid: obs.event("distrib.spawn",
                                              worker=i, pid=pid),
            on_spawn_failure=self._on_spawn_failure)

    # -- counters (mirrored into obs so the coordinator trace carries
    # -- distrib.* series even though the python dict is the source of
    # -- truth when tracing is disarmed) -----------------------------------

    def _count(self, name: str, n: int = 1) -> None:
        # Condition wraps an RLock, so this is safe (and cheap) from
        # call sites that already hold self._cv.
        with self._cv:
            self.counters[name] = self.counters.get(name, 0) + n
        obs.count(f"distrib.{name}", n)

    # -- setup -------------------------------------------------------------

    def _layout(self) -> None:
        chunks_dir = os.path.join(self.workdir, "chunks")
        os.makedirs(chunks_dir, exist_ok=True)
        paths = _split_fasta(self.target, self.chunks_hint or
                             max(2, 2 * self.n_workers), chunks_dir)
        if paths is None:
            # single contig / non-FASTA: one chunk, the whole target
            paths = [self.target]
        for i, p in enumerate(paths):
            cd = os.path.join(chunks_dir, f"chunk{i:03d}")
            os.makedirs(cd, exist_ok=True)
            self.chunks.append(Chunk(i, p, cd))
        self.phase.total = len(self.chunks)

    def _listen(self) -> None:
        self._sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._sock.bind(("127.0.0.1", 0))
        self.port = self._sock.getsockname()[1]
        self._sock.listen(16)
        t = threading.Thread(target=self._accept_loop,
                             name="distrib-accept", daemon=True)
        t.start()

    def _worker_env(self, index: int) -> dict:
        env = dict(os.environ)
        for k in SCOPED_KNOBS:
            env.pop(k, None)
        # fault scoping: exactly one worker inherits RACON_TPU_FAULT, so
        # a chaos run kills a known worker instead of the whole fleet
        if "RACON_TPU_FAULT" in env and index != distrib_fault_worker():
            env.pop("RACON_TPU_FAULT", None)
        return env

    def _on_spawn_failure(self, index: int, exc: BaseException) -> None:
        # a spawn failure (injected or real) shrinks the fleet; it must
        # not kill the run, which can still finish on fewer workers or
        # degrade to local.  The pool counts spawn_failures.
        self.phase.record_failure("fleet", exc)  # concurrency: invoked from pool.start() before any worker thread exists
        obs.event("distrib.spawn_failed", worker=index,
                  error=f"{type(exc).__name__}: {exc}")

    def _spawn_fleet(self) -> None:
        with self._cv:
            self.pool.port = self.port
            spawned = self.pool.start()
        if spawned:
            self._count("workers_spawned", spawned)

    # -- connection handling ------------------------------------------------

    def _accept_loop(self) -> None:
        while True:
            try:
                conn, _ = self._sock.accept()
            except OSError:
                return   # socket closed during shutdown
            t = threading.Thread(target=self._serve_conn, args=(conn,),
                                 name="distrib-conn", daemon=True)
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
                    # must not take down the coordinator
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
            # EOF on any of a worker's connections is the fast death
            # signal: a SIGKILLed worker's kernel-closed sockets get its
            # leases expired right now, not a TTL from now
            if worker >= 0:
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

    def _fetch(self, worker: int) -> dict:
        with self._cv:
            if self._stopping or all(c.state == "done"
                                     for c in self.chunks):
                return {"ok": True, "drain": True}
            now = time.monotonic()
            eligible = [c for c in self.chunks
                        if c.state == "pending" and not c.local
                        and c.next_eligible <= now]
            if eligible:
                # prefer a chunk this worker has not attempted (the
                # "retry on a different worker" rule), then chunk order
                chunk = min(eligible,
                            key=lambda c: (worker in c.tried, c.index))
                return self._assign(chunk, worker, speculative=False)
            chunk = self._straggler(worker, now)
            if chunk is not None:
                return self._assign(chunk, worker, speculative=True)
            return {"ok": True, "wait": True, "poll_s": 0.2}

    def _straggler(self, worker: int, now: float) -> Optional[Chunk]:
        """The longest-running chunk past the speculation threshold that
        `worker` could duplicate (call with the lock held)."""
        factor = distrib_speculate()
        if factor <= 0 or not self.completed_walls:
            return None
        median = statistics.median(self.completed_walls)
        best, best_elapsed = None, 0.0
        for c in self.chunks:
            if (c.state != "running" or c.local or worker in c.tried
                    or len(c.leases) >= 2 or not c.leases):
                continue
            elapsed = now - min(ls.t_start for ls in c.leases.values())
            if elapsed > factor * median and elapsed > best_elapsed:
                best, best_elapsed = c, elapsed
        return best

    def _assign(self, c: Chunk, worker: int, speculative: bool) -> dict:
        c.attempts += 1
        attempt = c.attempts
        c.state = "running"
        c.tried.add(worker)
        # journal ownership: the canonical per-chunk journal resumes a
        # re-dispatch, but only one live writer may ever hold it — a
        # merely-unresponsive holder keeps it and the new attempt gets a
        # fresh side journal
        canonical = not c.journal_held
        if canonical:
            c.journal_held = True
            journal = c.journal
        else:
            journal = os.path.join(c.dir, f"journal.a{attempt}.jsonl")
        c.leases[attempt] = Lease(worker, attempt, self.lease_ttl,
                                  canonical)
        self.queue_waits.append(max(
            0.0, time.monotonic() - max(c.t_pending, c.next_eligible)))
        self._count("dispatches")
        if speculative:
            self._count("speculative")
        if attempt > 1 and not speculative:
            self._count("redispatches")
        # trace-context propagation: each dispatch gets a fresh span id;
        # the worker stamps it as `parent` on its distrib.chunk span, so
        # the merged timeline parents worker spans under this event
        ctx = context.child(self._ctx)
        obs.event("distrib.dispatch", chunk=c.index, worker=worker,
                  attempt=attempt, speculative=speculative,
                  canonical_journal=canonical,
                  trace_id=(ctx or {}).get("trace_id"),
                  span_id=(ctx or {}).get("parent"))
        return {"ok": True, "chunk": {
            "index": c.index, "attempt": attempt,
            "sequences": self.sequences, "overlaps": self.overlaps,
            "target": c.target, "args": self.args,
            "include_unpolished": self.include_unpolished,
            "backend": self.backend, "journal": journal,
            "output": os.path.join(c.dir, f"out.a{attempt}.fasta"),
            "trace": ctx,
        }}

    # -- worker messages ----------------------------------------------------

    def _heartbeat(self, worker: int, index: int, attempt: int) -> dict:
        with self._cv:
            c = self.chunks[index]
            lease = c.leases.get(attempt)
            if lease is None or c.state == "done":
                # the attempt was superseded (lease expired and the
                # chunk re-dispatched, or another attempt won)
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
        with self._cv:
            c = self.chunks[index]
            lease = c.leases.pop(attempt, None)
            if c.state == "done":
                # first result won already; this duplicate is discarded
                # deterministically (its per-attempt output file is
                # never installed)
                self._count("duplicates")
                obs.event("distrib.duplicate", chunk=index,
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
            ws["rss_mb"] = max(ws.get("rss_mb", 0.0),
                               float(stats.get("rss_mb") or 0.0))
            obs.event("distrib.chunk_done", chunk=index,
                      worker=int(req["worker"]), attempt=attempt,
                      replayed=replayed)
            # fold the worker's shipped span buffer + metrics into the
            # coordinator's tracer: the written trace IS the merged
            # multi-process fleet timeline
            absorbed = obs.absorb(req.get("obs"))
            if absorbed:
                self._count("obs_events_absorbed", absorbed)
            self._cv.notify_all()
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
            if c.state != "done":
                self._fail_chunk(c, RuntimeError(err))
            obs.event("distrib.chunk_error", chunk=index,
                      worker=int(req["worker"]), attempt=attempt,
                      error=err)
            return {"ok": True}

    def _stats(self) -> dict:
        """The deepened 'stats' wire verb: live fleet telemetry for a
        poller (queue depth, in-flight leases, per-tier served,
        heartbeat staleness) plus the recent telemetry ring."""
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
                    "workers": {"live": self._live_workers(),
                                "dead": len(self._dead_workers)},
                    "served": dict(self.phase.served),
                    "staleness_s": round(staleness, 3),
                    "counters": dict(self.counters),
                    "telemetry": obs.telemetry(last=8)}

    def _queueing_p95(self) -> Optional[float]:
        """p95 of the eligible→dispatch queue waits (None before the
        first dispatch) — the bench telemetry stamp."""
        waits = sorted(self.queue_waits)
        if not waits:
            return None
        return round(waits[min(len(waits) - 1,
                               int(0.95 * len(waits)))], 4)

    def fleet_telemetry(self) -> dict:
        """The per-run fleet telemetry summary stamped into the run
        result and bench entries."""
        return {
            "workers": {str(w): dict(s)
                        for w, s in sorted(self.worker_stats.items())},
            "queueing_p95_s": self._queueing_p95(),
            "staleness_max_s": round(self._staleness_max, 3),
        }

    # -- failure paths (call with the lock held) ----------------------------

    def _fail_chunk(self, c: Chunk, exc: BaseException) -> None:
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
            if worker in self._dead_workers:
                return
            if self._stopping or all(c.state == "done"
                                     for c in self.chunks):
                return   # clean drain-and-exit, not a death
            self._dead_workers.add(worker)
            self._count("workers_dead")
            obs.event("distrib.worker_dead", worker=worker, cause=why)
            # the reclaim transition is a named fault point: kill=1
            # crashes the coordinator mid-reclaim, a raise is absorbed
            # and counted — the reclaim itself always proceeds
            if fire_reclaim_fault():
                self._count("reclaim_faults")
            for c in self.chunks:
                # a known-dead writer releases the canonical journal so
                # the re-dispatch resumes it
                popped = release_worker_leases(c, worker)
                if popped:
                    self._count("lease_expired", len(popped))
                    if c.state != "done":
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
                    # NOT releasing the canonical journal here: an
                    # unresponsive-but-alive holder may still be writing
                    self._count("lease_expired")
                    obs.event("distrib.lease_expired", chunk=c.index,
                              worker=lease.worker, attempt=a)
                    if c.state != "done":
                        self._fail_chunk(
                            c, TimeoutError(
                                f"lease on chunk {c.index} expired "
                                f"(worker {lease.worker}, attempt {a})"))

    # -- fleet -> local degradation -----------------------------------------

    def _live_workers(self) -> int:
        return sum(1 for i in self.pool.alive_indices()
                   if i not in self._dead_workers)

    def _degrade(self, cause: str) -> None:
        """Record the fleet→local lattice step (once per run)."""
        if not self._degraded:
            self._degraded = True
            self.phase.record_degrade("fleet", "local",
                                      RuntimeError(cause))

    def _run_local(self, c: Chunk) -> None:
        """Execute one chunk in the coordinator through the host-oracle
        CLI — the same demotion target as the serve host lane, so the
        output stays byte-identical.  A free canonical journal (cpu
        fingerprint only) is resumed; otherwise a fresh local journal."""
        with self._cv:
            if c.state == "done":
                return
            c.state = "running"
            resume = (not c.journal_held) and self.backend == "cpu"
        journal = c.journal if resume else os.path.join(
            c.dir, "journal.local.jsonl")
        out_path = os.path.join(c.dir, "out.local.fasta")
        part = out_path + ".part"
        a = self.args
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
        if self.include_unpolished:
            cmd.append("-u")
        cmd += [self.sequences, self.overlaps, c.target]
        env = dict(os.environ)
        for k in SCOPED_KNOBS:
            env.pop(k, None)
        t0 = time.monotonic()
        with open(part, "w") as out_f, \
                open(os.path.join(c.dir, "local.stderr.log"), "w") as err_f:
            rc = subprocess.call(cmd, stdout=out_f, stderr=err_f, env=env)
        with self._cv:
            if c.state == "done":
                self._count("duplicates")   # a late fleet result won
                return
            if rc != 0:
                # the local rung is the floor: a failure here fails the
                # run (reported by run())
                c.state = "pending"
                c.local = True
                self.phase.record_failure(
                    "local", RuntimeError(f"local chunk {c.index} "
                                          f"exited {rc}"))
                raise RuntimeError(
                    f"chunk {c.index} failed on the local rung "
                    f"(exit {rc}; see {c.dir}/local.stderr.log)")
            os.replace(part, out_path)
            c.state = "done"
            c.served_by = "local"
            c.output = out_path
            self.phase.record_served("local")
            self.phase.add_wall("local", time.monotonic() - t0)
            self._count("chunks_local")
            obs.event("distrib.chunk_local", chunk=c.index)
            self._cv.notify_all()

    # -- main loop ----------------------------------------------------------

    def run(self, output_path: str,
            timeout: Optional[float] = None) -> dict:
        obs.reset()
        obs.set_role("coordinator")
        # fleet trace context: minted fresh per run, activated before
        # configure so the tracer stamps it into the file's provenance;
        # _assign derives one child context per dispatch from it
        context.activate(context.fresh())
        obs.configure(trace_path=self.trace_path)
        self._ctx = context.current() if obs.enabled() else None
        faults.reset()
        os.makedirs(self.workdir, exist_ok=True)
        flight.set_dir(self.workdir)
        deadline = (None if not timeout
                    else time.monotonic() + timeout)
        try:
            with obs.span("distrib.run", workers=self.n_workers,
                          backend=self.backend):
                self._layout()
                self._listen()
                self._spawn_fleet()
                try:
                    self._monitor(deadline)
                finally:
                    self._shutdown_fleet()
                self._gather(output_path)
            self.report.finalize()
            # post-mortem sweep: any flight.<pid>.json a crashed/killed
            # worker left in a chunk dir is referenced from the report
            self.report.flight = flight.scan(self.workdir)
            if self.report.flight:
                self._count("flight_dumps", len(self.report.flight))
            # pool counters (spawn_failures, scale_* fault absorbs)
            # merge under the coordinator's own, which win on overlap
            counters = dict(self.pool.counters)
            counters.update(self.counters)
            self.phase.extra.update(counters)
            if self.report_path:
                self.report.write(self.report_path)
            self.report.write_env()
            replayed = self.counters.get("journal_replayed", 0)
            return {
                "output": output_path,
                "chunks": len(self.chunks),
                "workers": self.n_workers,
                "served": dict(self.phase.served),
                "degradations": list(self.phase.degradations),
                "counters": counters,
                "journal_replayed": replayed,
                "report": self.report_path,
                "trace": self.trace_path,
                "telemetry": self.fleet_telemetry(),
                "pool": {"min": self.pool.min_workers,
                         "max": self.pool.max_workers,
                         "timeline": [list(s) for s in
                                      self.pool.size_timeline]},
                "flight": [d.get("path") for d in self.report.flight],
                "summary": self.report.summary(),
            }
        finally:
            # scoped teardown: write the merged trace, then disarm the
            # process-global tracer and trace context so a second
            # in-process run can never append into this run's file
            obs.release(write=True)
            context.clear()

    def _monitor(self, deadline: Optional[float]) -> None:
        while True:
            with self._cv:
                if all(c.state == "done" for c in self.chunks):
                    return
            if deadline is not None and time.monotonic() > deadline:
                raise TimeoutError(
                    f"distrib run exceeded its deadline with "
                    f"{sum(1 for c in self.chunks if c.state != 'done')} "
                    f"chunk(s) unfinished")
            # reap dead worker processes (second death signal, for a
            # worker that died before ever connecting)
            with self._cv:
                reaped = self.pool.reap()
            for i, rc, _was_draining in reaped:
                self._worker_dead(i, f"exited {rc}")
            self._expire_leases()
            now = time.monotonic()
            if now - self._last_tick >= 1.0:
                self._last_tick = now
                with self._cv:
                    staleness = max(
                        (now - ls.last_beat for c in self.chunks
                         for ls in c.leases.values()), default=0.0)
                    self._staleness_max = max(self._staleness_max,
                                              staleness)
                    obs.telemetry_tick(
                        queue_depth=sum(1 for c in self.chunks
                                        if c.state == "pending"),
                        leases=sum(len(c.leases) for c in self.chunks),
                        workers_live=self._live_workers(),
                        staleness_s=round(staleness, 3))
            local_work = []
            with self._cv:
                live = self._live_workers()
                undone = [c for c in self.chunks if c.state != "done"]
                for c in undone:
                    if (c.failures > self.max_retries and not c.leases
                            and c.state == "pending" and not c.local):
                        c.local = True
                        self._degrade(f"chunk {c.index} exhausted its "
                                      f"retry budget ({c.failures} "
                                      f"failures > {self.max_retries})")
                if live == 0 and undone:
                    # fleet collapse: every remaining chunk falls to the
                    # local rung (leases of dead workers are already
                    # expired by _worker_dead)
                    for c in undone:
                        if c.state == "pending" and not c.local:
                            c.local = True
                    if any(c.local for c in undone):
                        self._degrade("fleet collapse: no live workers")
                local_work = [c for c in self.chunks
                              if c.local and c.state == "pending"]
            for c in local_work:
                self._run_local(c)
            with self._cv:
                self._cv.wait(0.05)

    def _shutdown_fleet(self) -> None:
        with self._cv:
            self._stopping = True
        self.pool.shutdown(timeout=5.0)
        if self._sock is not None:
            try:
                self._sock.close()
            except OSError:
                pass

    def _gather(self, output_path: str) -> None:
        """Ordered gather: chunk outputs concatenate in chunk order, so
        the result is byte-identical to an unchunked run."""
        part = output_path + ".part"
        with open(part, "wb") as out:
            for c in self.chunks:
                assert c.state == "done" and c.output, c.index
                with open(c.output, "rb") as f:
                    out.write(f.read())
        os.replace(part, output_path)
