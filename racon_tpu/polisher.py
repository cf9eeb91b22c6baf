"""Polisher front-ends: CPU oracle path and the TPU-backed path.

Mirrors the reference's factory seam (racon::createPolisher returning either
the base Polisher or the CUDA subclass, /root/reference/src/polisher.cpp:
137-163): `create_polisher(..., backend=...)` returns a polisher whose two hot
phases run either on the host oracle or on the TPU batch kernels with host
fallback for rejected work (the reference's graceful-degradation lattice,
src/cuda/cudapolisher.cpp:204-213,354-378).

Preemption tolerance: pass `journal_path` (CLI `--journal` /
`--resume-journal`, or the `RACON_TPU_JOURNAL` knob) and every served
window/CIGAR is appended to a crash-safe journal
(resilience/journal.py) as it is installed; a resumed run replays the
journal, recomputes only what is missing, and produces byte-identical
output.
"""

from __future__ import annotations

import os
import sys
import time
from typing import List, Optional, Tuple

from . import config, obs
from .pipeline import Pipeline
from .resilience import budget, faults, watchdog
from .resilience.journal import (Journal, input_fingerprint,
                                 replay_windows)
from .resilience.report import PhaseReport, RunReport

#: Handoff-queue sentinel: the alignment worker is done.
_DONE = object()


class _WorkerFailure:
    """An exception captured on the alignment worker thread, re-raised on
    the consumer so a pipelined polish fails exactly like a sequential
    one (instead of hanging on the queue)."""

    __slots__ = ("exc",)

    def __init__(self, exc: BaseException):
        self.exc = exc


def _split_fasta(target_path: str, n_chunks_hint: int, outdir: str):
    """Split a multi-contig FASTA into up to `n_chunks_hint` contiguous,
    roughly base-balanced chunk files (record text copied verbatim, so
    each chunk parses to byte-identical contigs).  Returns the chunk
    paths, or None when the target is not splittable (single contig,
    non-FASTA content) — the caller falls back to sequential phases.

    Two consumers depend on the contiguous/verbatim contract: the phase
    pipeline (below) overlaps alignment and consensus across chunks in
    one process, and the distrib coordinator (racon_tpu/distrib) farms
    chunks out to a worker fleet — both re-concatenate per-chunk output
    in chunk order and rely on it being byte-identical to the unchunked
    run."""
    import gzip
    import os

    opener = gzip.open if target_path.lower().endswith(".gz") else open
    records = []   # [bases, [raw lines]]
    cur = None
    try:
        with opener(target_path, "rt") as f:
            for line in f:
                if line.startswith(">"):
                    cur = [0, [line]]
                    records.append(cur)
                elif cur is None:
                    return None   # leading non-FASTA content
                else:
                    cur[0] += len(line.strip())
                    cur[1].append(line)
    except (OSError, UnicodeDecodeError):
        return None
    if len(records) < 2:
        return None
    k = min(len(records), max(2, n_chunks_hint))
    per_chunk = sum(r[0] for r in records) / k
    paths = []
    idx = 0
    for ci in range(k):
        must_leave = k - ci - 1   # later chunks each need >= 1 contig
        group = [records[idx]]
        acc = records[idx][0]
        idx += 1
        while (len(records) - idx > must_leave
               and (ci == k - 1 or acc + records[idx][0] <= per_chunk)):
            group.append(records[idx])
            acc += records[idx][0]
            idx += 1
        path = os.path.join(outdir, f"chunk{ci:03d}.fasta")
        with open(path, "w") as f:
            for _, lines in group:
                f.writelines(lines)
        paths.append(path)
    return paths


def reset_run_state(trace_path: Optional[str]) -> None:
    """Per-run reset of the module-global runtime state, shared by both
    polisher constructors: the deterministic fault schedule, watchdog
    wedge streaks, sanitizer findings, and obs arming all start fresh.

    This is the seam the serving layer leans on (racon_tpu/serve): a
    resident process runs many polishes, so every construction must
    re-arm per-request state — while everything deliberately *not* reset
    here (the topology-keyed kernel cache, the XLA compile cache) stays
    hot across jobs.  It also means in-process polishes cannot overlap;
    the serve scheduler serializes device-lane jobs for exactly this
    reason.

    The armed tracer's clock starts where this reset does, and two spans
    open there for other functions to end: ``job``, the root that every
    span of the run hangs under (the serve session ends it; a bare
    polisher leaves it open, and the trace written at the end of
    ``polish()`` holds it up to that write), and ``job.open`` under it,
    which ``initialize()`` ends where ``phase.parse`` begins."""
    t0 = time.monotonic_ns()
    faults.reset()     # per-run firing schedule (deterministic)
    watchdog.reset()   # per-run wedge streaks
    budget.configure()  # fresh memory watermarks + RSS watchdog
    from .analysis import sanitize
    sanitize.reset()   # per-run sanitizer findings
    obs.reset()        # per-run trace/metrics (disarmed unless armed
    obs.configure(trace_path=trace_path,  # by --trace / the knobs)
                  epoch_ns=t0)
    obs.begin("job", t0, root=True)
    obs.begin("job.open", t0)


def _open_journal(paths: Tuple[str, str, str], backend: str,
                  journal_path: Optional[str], resume: bool,
                  params: dict) -> Optional[Journal]:
    """Resolve this run's journal.  An explicit path (the CLI flags) wins
    and a fingerprint mismatch on explicit resume is an error; the
    `RACON_TPU_JOURNAL` knob auto-resumes and falls back to a fresh
    journal when the fingerprint says the inputs changed."""
    on_mismatch = "error"
    if journal_path is None:
        journal_path = config.get_str("RACON_TPU_JOURNAL") or None
        resume, on_mismatch = True, "fresh"
    if journal_path is None:
        return None
    with obs.span("job.open.journal"):
        fp = input_fingerprint(paths, params, backend)
        return Journal(journal_path, fp, resume=resume,
                       on_mismatch=on_mismatch)


def _open_pipeline(paths: Tuple[str, str, str], kwargs: dict) -> Pipeline:
    """The run's native pipeline (format sniffing, parsers opened, the
    thread pool and its aligners): the last seam of ``job.open``."""
    with obs.span("job.open.pipeline"):
        return Pipeline(*paths, **kwargs)


def _close_job(journal: Optional[Journal], report: RunReport,
               before_report=None) -> None:
    """What ``polish()`` does after ``phase.stitch``, as the first part
    of ``job.close`` (the serve session goes on under the same span and
    ends it): the journal's close, the report, the trace file.  The
    write is stamped after it has ended, so that the file a later write
    leaves holds it."""
    obs.begin("job.close")
    if journal is not None:
        with obs.span("job.close.journal"):
            journal.close()
    with obs.span("job.close.report"):
        if before_report is not None:
            before_report()
        # the process's peak so far, once a job: a gauge kept as a count
        obs.count("job.rss.peak_mb", round(budget.peak_rss_mb()))
        report.finalize().write_env()
    obs.maybe_stop_device_trace()
    t0 = time.monotonic_ns()
    if obs.write_trace() is not None:
        obs.add_complete("job.close.trace", t0, time.monotonic_ns())


class CpuPolisher:
    """Pure-host polishing (the correctness oracle)."""

    def __init__(self, sequences_path: str, overlaps_path: str,
                 target_path: str, journal_path: Optional[str] = None,
                 resume_journal: bool = False,
                 trace_path: Optional[str] = None, **kwargs):
        reset_run_state(trace_path)
        self._journal = _open_journal(
            (sequences_path, overlaps_path, target_path), "cpu",
            journal_path, resume_journal, kwargs)
        self._pipeline = _open_pipeline(
            (sequences_path, overlaps_path, target_path), kwargs)
        self.report = RunReport()

    def initialize(self) -> None:
        # The native initialize fuses parse + host alignment + window
        # building in one ABI call (deliberately not decomposed: the
        # split Python calls carry extra fault-injection points that
        # would shift deterministic fault schedules); the host path's
        # phase attribution is therefore one span.
        obs.end("job.open")
        with obs.span("phase.parse", fused="parse+align+window_assign"):
            self._pipeline.initialize()

    def polish(self, drop_unpolished: bool = True) -> List[Tuple[str, str]]:
        with obs.span("phase.poa", tier="host"):
            if self._journal is None:
                self._polish_unjournaled()
            else:
                self._polish_journaled(self._journal)
        with obs.span("phase.stitch"):
            out = self._pipeline.stitch(drop_unpolished)
        _close_job(self._journal, self.report)
        return out

    def _polish_unjournaled(self) -> None:
        pipeline = self._pipeline
        rep = PhaseReport("consensus", ("host",))
        rep.total = pipeline.num_windows()
        t0 = time.perf_counter()
        pipeline.consensus_cpu_all()
        rep.add_wall("host", time.perf_counter() - t0)
        rep.record_served("host", rep.total)
        self.report.attach(rep)

    def _polish_journaled(self, jr: Journal) -> None:
        # Window-at-a-time host consensus so every result is durable the
        # moment it exists (consensus_cpu_all's thread pool computes the
        # whole run before Python sees anything to journal); sequential
        # serving is the durability price on the host path.
        pipeline = self._pipeline
        n = pipeline.num_windows()
        rep = PhaseReport("consensus", ("journal", "host"))
        rep.total = n
        replayed = replay_windows(pipeline, jr, n, rep)
        t0 = time.perf_counter()
        for i in range(n):
            if i in replayed:
                continue
            polished = pipeline.consensus_cpu_one(i)
            _, _, rank, _, _, tid = pipeline.window_info(i)
            jr.append_window(i, tid, rank, "host",
                             pipeline.get_consensus(i), polished)
            rep.record_served("host")
        rep.add_wall("host", time.perf_counter() - t0)
        self.report.attach(rep)


class TpuPolisher:
    """TPU-backed polishing: batched banded alignment + batched POA on
    device, host fallback for work outside device limits.

    After polish(), `self.report` (a resilience.report.RunReport) holds
    the per-phase serving/fallback accounting — who served what, why
    anything fell back, retries/bisections, quarantined windows, wall
    time per tier, and (on a resumed run) how many units the journal
    replayed vs how many were served fresh."""

    def __init__(self, sequences_path: str, overlaps_path: str,
                 target_path: str, journal_path: Optional[str] = None,
                 resume_journal: bool = False,
                 trace_path: Optional[str] = None, **kwargs):
        from . import device

        # before anything else: no TPU (and no JAX_PLATFORMS=cpu asked
        # for by name) is an error here, not a quieter tier later
        ident = device.require_tpu()
        reset_run_state(trace_path)
        self._kwargs = dict(kwargs)
        self._paths = (sequences_path, overlaps_path, target_path)
        self._journal = _open_journal(
            self._paths, "tpu", journal_path, resume_journal, kwargs)
        # Cross-phase pipelining (RACON_TPU_PIPELINE_PHASES=1): POA for
        # early target chunks runs while late alignment cohorts are still
        # in flight on a worker thread.  The journal records windows by
        # run-global index; a chunked run would journal chunk-local
        # indices, so journaled runs stay sequential.
        self._pipelined = config.get_bool("RACON_TPU_PIPELINE_PHASES")
        if self._pipelined and self._journal is not None:
            print("[racon_tpu::polisher] NOTE: RACON_TPU_PIPELINE_PHASES "
                  "ignored — the window journal needs run-global indices; "
                  "running the phases sequentially", file=sys.stderr)
            self._pipelined = False
        # Streaming input (RACON_TPU_STREAM_INPUT=1, auto-armed by a
        # memory budget): each target chunk's pipeline parses a
        # byte-range subset of the reads/overlaps files instead of the
        # whole inputs, so peak RSS is O(chunk) — see streamio.py.
        # Like pipelining, it chunks the target, so journaled runs
        # (run-global window indices) stay on the unchunked path.
        self._stream = (config.get_bool("RACON_TPU_STREAM_INPUT")
                        or budget.budget_mb() > 0)
        if self._stream and self._journal is not None:
            print("[racon_tpu::polisher] NOTE: streaming input ignored — "
                  "the window journal needs run-global indices; parsing "
                  "the full inputs", file=sys.stderr)
            self._stream = False
        # Chunked modes parse per target chunk; the full-target
        # Pipeline is only built when we end up sequential.
        self._pipeline = (None if (self._pipelined or self._stream) else
                          _open_pipeline(self._paths, kwargs))
        self._queue = None
        self._worker = None
        self._warm = None
        self._tmpdir = None
        self._chunks = None
        self._stream_index = None
        self._collapsed = False
        # pressure/streaming accounting: torn-chunk quarantines and the
        # memory lattice edges land here, peak RSS is stamped in extra
        self._mem_rep = PhaseReport("memory", ())
        self.report = RunReport()
        self.report.stamp_device(ident)

    def initialize(self) -> None:
        try:
            from .ops.align_driver import run_alignment_phase
        except ImportError as e:
            raise RuntimeError(
                "TPU backend unavailable (racon_tpu.ops failed to import); "
                "run without --tpu for the host path") from e

        obs.maybe_start_device_trace()
        if self._pipelined or self._stream:
            chunks = self._split_target()
            if chunks is not None:
                self._chunks = chunks
                if self._stream:
                    self._arm_streaming(chunks)
                obs.end("job.open")     # the chunks' phases start here
                if self._pipelined:
                    self._start_phase_pipeline(chunks, run_alignment_phase)
                # streaming without pipelining defers the per-chunk
                # polish loop to polish()
                return
            self._pipelined = False
            self._stream = False
        if self._pipeline is None:
            self._pipeline = _open_pipeline(self._paths, self._kwargs)
        obs.end("job.open")
        with obs.span("phase.parse"):
            self._pipeline.prepare()
        with obs.span("phase.align") as sp:
            stats = run_alignment_phase(self._pipeline,
                                        journal=self._journal)
            sp.set(device=stats.get("device"), host=stats.get("host"))
        self.report.attach(stats.get("report"))
        with obs.span("phase.window_assign"):
            self._pipeline.build_windows()

    # -- phase pipelining --------------------------------------------------
    def _split_target(self):
        """Chunk the target FASTA for the phase pipeline / streaming
        loop; None (with a note) when the input is not splittable —
        sequential full-input fallback."""
        import tempfile

        target = self._paths[2]
        if not target.lower().endswith((".fa", ".fasta",
                                        ".fa.gz", ".fasta.gz")):
            print("[racon_tpu::polisher] NOTE: chunked polishing needs a "
                  "FASTA target; running the phases sequentially",
                  file=sys.stderr)
            return None
        depth = max(1, config.get_int("RACON_TPU_HANDOFF_DEPTH"))
        self._tmpdir = tempfile.mkdtemp(prefix="racon_tpu_chunks.")
        chunks = _split_fasta(target, depth + 2, self._tmpdir)
        if chunks is None:
            import shutil

            shutil.rmtree(self._tmpdir, ignore_errors=True)
            self._tmpdir = None
            print("[racon_tpu::polisher] NOTE: target has fewer than two "
                  "contigs; running the phases sequentially",
                  file=sys.stderr)
        return chunks

    # -- streaming working sets -------------------------------------------
    def _arm_streaming(self, chunks) -> None:
        """Build the per-chunk byte-range index (one streaming pass over
        each input).  Unsupported formats (MHAP's ordinal read ids) and
        unreadable inputs fall back to full-file chunk pipelines with a
        NOTE — never an error here; the native parser renders the final
        verdict on the full files."""
        from .streamio import TORN_ERRORS, StreamIndex, StreamUnsupported

        try:
            self._stream_index = StreamIndex(
                self._paths[0], self._paths[1], chunks, self._tmpdir)
        except StreamUnsupported as e:
            print(f"[racon_tpu::polisher] NOTE: streaming input disabled "
                  f"({e}); chunk pipelines parse the full inputs",
                  file=sys.stderr)
            self._stream_index = None
        except TORN_ERRORS as e:
            print(f"[racon_tpu::polisher] NOTE: streaming index failed "
                  f"({type(e).__name__}: {e}); chunk pipelines parse the "
                  f"full inputs", file=sys.stderr)
            self._stream_index = None

    def _chunk_inputs(self, ci: int):
        """(sequences, overlaps, subset_paths) for chunk ci's pipeline:
        the streamed working-set subset when streaming is armed, the
        full inputs otherwise.  This is the synchronous per-chunk
        budget poll (the deterministic ``mem.pressure`` seam); under
        soft-or-worse pressure the working set round-trips through the
        disk spill file before realization.  A torn chunk is
        quarantined — recorded in the RunReport, the run continues —
        and polishes from whatever working set the index recovered
        before the tear."""
        level = budget.poll()
        idx = self._stream_index
        if idx is None:
            return self._paths[0], self._paths[1], None
        torn = idx.torn(ci)
        try:
            ws = idx.materialize(ci)
            if budget.at_least(level, "soft"):
                ws.park(budget.spill_dir(self._tmpdir))
            paths = ws.realize(self._tmpdir)
        except Exception as e:  # noqa: BLE001 — degrade, never die
            self._quarantine_chunk(ci, torn or e)
            return self._paths[0], self._paths[1], None
        if torn is not None:
            self._quarantine_chunk(ci, torn)
        return paths[0], paths[1], paths

    def _quarantine_chunk(self, ci: int, exc: BaseException) -> None:
        print(f"[racon_tpu::polisher] WARNING: chunk {ci} working set "
              f"degraded ({type(exc).__name__}: {exc}); quarantining the "
              f"chunk", file=sys.stderr)
        self._mem_rep.record_quarantine(ci, exc)

    @staticmethod
    def _release_ws(ws_paths) -> None:
        """Delete a chunk's realized subset files (the native pipeline
        has fully parsed them by the end of prepare())."""
        if ws_paths:
            for p in ws_paths:
                try:
                    os.unlink(p)
                except OSError:
                    pass

    def _maybe_collapse(self) -> bool:
        """Hard-watermark latch for the pipelined path: once crossed,
        the alignment worker stops running ahead of POA (the phase
        pipeline collapses to sequential consumption) and the pressure
        lattice edge is recorded once."""
        if not budget.hard_latched():
            return False
        if not self._collapsed:
            self._collapsed = True
            self._mem_rep.record_degrade(
                "pipelined", "sequential",
                RuntimeError("hard memory watermark"))
        return True

    def _start_phase_pipeline(self, chunks, run_alignment_phase) -> None:
        """Arm the bounded handoff queue, the kernel prewarm thread (its
        compiles overlap the alignment phase instead of serializing
        before POA), and the single alignment worker.  One worker + FIFO
        queue = chunks arrive at POA in target order, so the stitched
        output is byte-identical to a sequential run."""
        import queue
        import threading

        from .ops import poa_driver

        kwargs = self._kwargs
        target = self._paths[2]

        def warm():
            try:
                w = int(kwargs.get("window_length", 500))
                lens = poa_driver.observed_window_lengths(target, w)
                poa_driver.warm_geometries(lens, kwargs.get("match", 3),
                                           kwargs.get("mismatch", -5),
                                           kwargs.get("gap", -4))
            except Exception as e:  # noqa: BLE001 — prewarm is best-effort
                print(f"[racon_tpu::polisher] WARNING: consensus prewarm "
                      f"failed ({type(e).__name__}: {e}); kernels compile "
                      f"on first use", file=sys.stderr)

        self._warm = threading.Thread(target=warm, name="poa-warm",
                                      daemon=True)
        self._warm.start()

        depth = max(1, config.get_int("RACON_TPU_HANDOFF_DEPTH"))
        self._queue = q = queue.Queue(maxsize=depth)

        def worker():
            try:
                for ci, chunk_path in enumerate(chunks):
                    # memory backpressure: under soft-or-worse pressure
                    # stop running ahead of POA until the consumer
                    # drains the handoff queue; a hard breach collapses
                    # the pipeline for the rest of the run
                    # (pipelined -> sequential, recorded once)
                    while ((self._maybe_collapse()
                            or budget.at_least(budget.level(), "soft"))
                           and not q.empty()):
                        time.sleep(0.02)
                    seqs_i, ovls_i, ws_paths = self._chunk_inputs(ci)
                    with obs.span("phase.parse", chunk=ci):
                        pl = Pipeline(seqs_i, ovls_i, chunk_path, **kwargs)
                        pl.prepare()
                    self._release_ws(ws_paths)
                    with obs.span("phase.align", chunk=ci) as sp:
                        stats = run_alignment_phase(pl, journal=None)
                        sp.set(device=stats.get("device"),
                               host=stats.get("host"))
                    with obs.span("phase.window_assign", chunk=ci):
                        pl.build_windows()
                    q.put((ci, pl, stats))
                q.put(_DONE)
            except BaseException as e:  # noqa: BLE001 — re-raised on main
                q.put(_WorkerFailure(e))

        self._worker = threading.Thread(target=worker, name="align-worker",
                                        daemon=True)
        self._worker.start()

    def _polish_pipelined(self, drop_unpolished: bool):
        from .ops.poa_driver import run_consensus_phase

        align_rep = None
        cons_rep = None
        out: List[Tuple[str, str]] = []
        try:
            # The prewarm compiles overlapped the alignment phase; POA
            # must not start until the geometries (and _WARM_DEAD) are
            # settled.
            if self._warm is not None:
                self._warm.join()
            while True:
                item = self._queue.get()
                if item is _DONE:
                    break
                if isinstance(item, _WorkerFailure):
                    raise item.exc
                ci, pl, stats = item
                rep = stats.get("report")
                if rep is not None:
                    if align_rep is None:
                        align_rep = rep
                    else:
                        align_rep.merge(rep)
                with obs.span("phase.poa", chunk=ci):
                    cstats = run_consensus_phase(
                        pl,
                        match=self._kwargs.get("match", 3),
                        mismatch=self._kwargs.get("mismatch", -5),
                        gap=self._kwargs.get("gap", -4),
                        trim=self._kwargs.get("trim", True),
                        journal=None)
                crep = cstats.get("report")
                if crep is not None:
                    if cons_rep is None:
                        cons_rep = crep
                    else:
                        cons_rep.merge(crep)
                with obs.span("phase.stitch", chunk=ci):
                    out.extend(pl.stitch(drop_unpolished))
        finally:
            if self._tmpdir is not None:
                import shutil

                shutil.rmtree(self._tmpdir, ignore_errors=True)
                self._tmpdir = None
        self.report.attach(align_rep)
        self.report.attach(cons_rep)
        return out

    def _polish_stream_sequential(self, drop_unpolished: bool):
        """Streaming without phase pipelining: one chunk at a time —
        materialize the working set, polish, release — so peak RSS is
        O(chunk), not O(genome)."""
        from .ops.align_driver import run_alignment_phase
        from .ops.poa_driver import run_consensus_phase

        align_rep = None
        cons_rep = None
        out: List[Tuple[str, str]] = []
        try:
            for ci, chunk_path in enumerate(self._chunks):
                seqs_i, ovls_i, ws_paths = self._chunk_inputs(ci)
                with obs.span("phase.parse", chunk=ci):
                    pl = Pipeline(seqs_i, ovls_i, chunk_path,
                                  **self._kwargs)
                    pl.prepare()
                self._release_ws(ws_paths)
                with obs.span("phase.align", chunk=ci) as sp:
                    stats = run_alignment_phase(pl, journal=None)
                    sp.set(device=stats.get("device"),
                           host=stats.get("host"))
                with obs.span("phase.window_assign", chunk=ci):
                    pl.build_windows()
                rep = stats.get("report")
                if rep is not None:
                    if align_rep is None:
                        align_rep = rep
                    else:
                        align_rep.merge(rep)
                with obs.span("phase.poa", chunk=ci):
                    cstats = run_consensus_phase(
                        pl,
                        match=self._kwargs.get("match", 3),
                        mismatch=self._kwargs.get("mismatch", -5),
                        gap=self._kwargs.get("gap", -4),
                        trim=self._kwargs.get("trim", True),
                        journal=None)
                crep = cstats.get("report")
                if crep is not None:
                    if cons_rep is None:
                        cons_rep = crep
                    else:
                        cons_rep.merge(crep)
                with obs.span("phase.stitch", chunk=ci):
                    out.extend(pl.stitch(drop_unpolished))
                del pl   # release the chunk's native working set
        finally:
            if self._tmpdir is not None:
                import shutil

                shutil.rmtree(self._tmpdir, ignore_errors=True)
                self._tmpdir = None
        self.report.attach(align_rep)
        self.report.attach(cons_rep)
        return out

    def _stamp_memory(self) -> None:
        """Attach the memory PhaseReport (peak RSS, budget, pressure
        verdicts) when a budget/streaming was armed or anything was
        recorded on it."""
        b = budget.active()
        armed = (b is not None and b.enabled) or self._stream
        if not (armed or self._mem_rep.degradations
                or self._mem_rep.quarantined):
            return
        self._mem_rep.extra.update({
            "peak_rss_mb": round(budget.peak_rss_mb(), 1),
            "budget_mb": b.budget_mb if b is not None else 0,
            "streamed": self._stream_index is not None,
            "pressure_level": b.level() if b is not None else "ok",
        })
        self.report.attach(self._mem_rep)

    def polish(self, drop_unpolished: bool = True) -> List[Tuple[str, str]]:
        from .ops.poa_driver import run_consensus_phase

        if self._pipelined:
            out = self._polish_pipelined(drop_unpolished)
        elif self._chunks is not None:
            out = self._polish_stream_sequential(drop_unpolished)
        else:
            with obs.span("phase.poa"):
                stats = run_consensus_phase(
                    self._pipeline,
                    match=self._kwargs.get("match", 3),
                    mismatch=self._kwargs.get("mismatch", -5),
                    gap=self._kwargs.get("gap", -4),
                    trim=self._kwargs.get("trim", True),
                    journal=self._journal)
            self.report.attach(stats.get("report"))
            with obs.span("phase.stitch"):
                out = self._pipeline.stitch(drop_unpolished)
        _close_job(self._journal, self.report, self._stamp_memory)
        return out


def create_polisher(sequences_path: str, overlaps_path: str, target_path: str,
                    backend: str = "cpu", **kwargs):
    """Factory. backend: 'cpu' (host oracle) or 'tpu' (device batched).
    `journal_path=`/`resume_journal=` arm the crash-safe result journal
    (see resilience/journal.py); `trace_path=` arms the span tracer and
    writes a Chrome-trace JSON at the end of polish() (see
    racon_tpu/obs, CLI `--trace`, `RACON_TPU_TRACE`)."""
    if backend == "cpu":
        return CpuPolisher(sequences_path, overlaps_path, target_path,
                           **kwargs)
    if backend == "tpu":
        return TpuPolisher(sequences_path, overlaps_path, target_path,
                           **kwargs)
    raise ValueError(f"unknown backend: {backend!r}")
