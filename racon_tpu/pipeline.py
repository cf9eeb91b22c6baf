"""Python handle over the native polishing pipeline.

Wraps the C ABI in rt_capi.cpp. The pipeline object exposes the two
accelerator seams (overlap-alignment jobs and window-consensus jobs) as numpy
arrays ready for device batching; everything else (parsing, filtering,
windowing, stitching) runs natively.
"""

from __future__ import annotations

import ctypes
from dataclasses import dataclass
from typing import List, Tuple

import numpy as np

from . import native, obs
from .resilience import faults

#: The native engine's stage marks (``rt::Stage`` in rt_pipeline.hpp),
#: by stage id: the child span each becomes, and what its second count
#: (after ``items``) is called there, if it has one.
_STAGES = (
    ("native.prepare.targets", "bytes"),
    ("native.prepare.reads", "bytes"),
    ("native.prepare.overlaps", "kept"),
    ("native.prepare.transmute", "tasks"),
    ("native.initialize.align", None),
    ("native.build_windows.breaks", "tasks"),
    ("native.build_windows.create", None),
    ("native.build_windows.layers", None),
    ("native.stitch.join", "bytes"),
)


@dataclass
class WindowExport:
    """One window's POA problem in packed form (layers sorted by begin)."""

    index: int
    rank: int
    target_id: int
    is_tgs: bool
    backbone: np.ndarray       # uint8 ASCII bases [L]
    backbone_weights: np.ndarray  # uint8 (PHRED-33, dummy backbone = 0) [L]
    lens: np.ndarray           # uint32 [K]
    begins: np.ndarray         # uint32 [K]
    ends: np.ndarray           # uint32 [K] (inclusive backbone positions)
    bases: np.ndarray          # uint8 concatenated layer bases
    weights: np.ndarray        # uint8 concatenated layer weights
    # admitted layers the consensus driver's depth cap dropped when it
    # packed this export (poa_driver._export_chunk sets it)
    capped: int = 0


class Pipeline:
    """One polishing run (sequences + overlaps + targets -> polished FASTA)."""

    def __init__(self, sequences_path: str, overlaps_path: str,
                 target_path: str, *, fragment_correction: bool = False,
                 window_length: int = 500, quality_threshold: float = 10.0,
                 error_threshold: float = 0.3, trim: bool = True,
                 match: int = 3, mismatch: int = -5, gap: int = -4,
                 num_threads: int = 1):
        self._lib = native.load()
        self._h = self._lib.rt_pipeline_create(
            sequences_path.encode(), overlaps_path.encode(),
            target_path.encode(), 1 if fragment_correction else 0,
            window_length, quality_threshold, error_threshold,
            1 if trim else 0, match, mismatch, gap, num_threads)
        if not self._h:
            native.check_error(self._lib)
            raise native.NativeError("pipeline creation failed")

    def __del__(self):
        if getattr(self, "_h", None):
            self._lib.rt_pipeline_destroy(self._h)
            self._h = None

    # -- phase 1 ----------------------------------------------------------
    # Coarse native calls carry `native.*` spans (racon_tpu/obs) so a
    # trace separates time inside the C++ engine from device batching;
    # per-window calls (export_window, consensus_cpu_one) are counted in
    # the drivers instead — a span per window would swamp the buffer.
    # What happens inside one comes back as stage marks, stamped by the
    # engine on the spans' clock and laid under the call's span once
    # that has closed (reading and stamping them is the tracing's own
    # time, not the call's).
    def prepare(self) -> None:
        with obs.span("native.prepare") as sp:
            self._lib.rt_pipeline_prepare(self._h)
            native.check_error(self._lib)
        self._stamp_stages(sp)
        self._count_prepared()

    def stage_marks(self) -> List[Tuple[int, int, int, int, int]]:
        """(stage id, start ns, end ns, items, bytes) for each stage of
        the last coarse call, on ``time.monotonic_ns()``'s clock, in one
        ABI crossing."""
        cap = len(_STAGES)
        out = (ctypes.c_uint64 * (5 * cap))()
        n = min(self._lib.rt_pipeline_stage_marks(self._h, out, cap), cap)
        return [tuple(int(v) for v in out[5 * i:5 * i + 5])
                for i in range(n)]

    def _stamp_stages(self, call_span) -> None:
        """The last coarse call's stages as child spans of the span that
        was open around it, and what its blocked pool loops took as
        ``native.pool.items`` over ``native.pool.tasks``, counted once a
        call (nothing crosses the ABI when disarmed)."""
        if not obs.enabled():
            return
        parent = getattr(call_span, "id", None)
        pool_items = pool_tasks = 0
        for stage, t0, t1, items, extra in self.stage_marks():
            name, second = _STAGES[stage]
            args = {"items": items}
            if second is not None:
                args[second] = extra
            if second == "tasks":   # a blocked loop on the native pool
                pool_items += items
                pool_tasks += extra
            obs.add_complete(name, t0, t1, parent_id=parent, **args)
        if pool_tasks:
            obs.count("native.pool.items", pool_items)
            obs.count("native.pool.tasks", pool_tasks)

    def _prepare_counts(self) -> Tuple[int, int, int]:
        """(targets, overlap records parsed, overlaps the filters kept):
        what the native prepare saw, in one ABI crossing."""
        out = (ctypes.c_uint64 * 3)()
        self._lib.rt_pipeline_prepare_counts(self._h, out)
        return int(out[0]), int(out[1]), int(out[2])

    def filter_counts(self) -> Tuple[int, int, int, int]:
        """(overlaps the error threshold dropped, window layers offered,
        dropped as under 2 % of a window, dropped by mean quality): what
        the native filters did so far, in one ABI crossing."""
        out = (ctypes.c_uint64 * 4)()
        self._lib.rt_pipeline_filter_counts(self._h, out)
        return int(out[0]), int(out[1]), int(out[2]), int(out[3])

    def _count_prepared(self) -> None:
        targets, parsed, kept = self._prepare_counts()
        obs.count("polish.targets", targets)
        obs.count("overlaps.parsed", parsed)
        obs.count("overlaps.kept", kept)

    def _count_filtered(self) -> None:
        """What the native filters dropped, once the windows are built
        (one crossing for both filters; nothing when disarmed)."""
        if not obs.enabled():
            return
        dropped_error, offered, short, quality = self.filter_counts()
        obs.count("overlaps.dropped.error", dropped_error)
        obs.count("layers.offered", offered)
        obs.count("layers.dropped.short", short)
        obs.count("layers.dropped.quality", quality)

    def num_align_jobs(self) -> int:
        return self._lib.rt_pipeline_num_align_jobs(self._h)

    def align_job(self, job: int) -> Tuple[np.ndarray, np.ndarray]:
        """Query/target byte arrays for alignment job `job`."""
        q = ctypes.c_char_p()
        t = ctypes.c_char_p()
        ql = ctypes.c_uint32()
        tl = ctypes.c_uint32()
        self._lib.rt_pipeline_align_job(
            self._h, job, ctypes.byref(q), ctypes.byref(ql), ctypes.byref(t),
            ctypes.byref(tl))
        qa = np.frombuffer(ctypes.string_at(q, ql.value), dtype=np.uint8)
        ta = np.frombuffer(ctypes.string_at(t, tl.value), dtype=np.uint8)
        return qa, ta

    def align_job_lengths(self) -> np.ndarray:
        """(q_len, t_len) per job without copying the bytes — one bulk
        ABI crossing (the per-job loop survives as `_align_job_lengths_loop`,
        the parity oracle)."""
        n = self.num_align_jobs()
        out = np.zeros((n, 2), dtype=np.uint32)
        if n:
            self._lib.rt_pipeline_align_job_lengths(
                self._h, out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)))
            native.check_error(self._lib)
        return out

    def _align_job_lengths_loop(self) -> np.ndarray:
        """Per-job ctypes loop — the pre-bulk implementation, kept as the
        differential-test oracle for rt_pipeline_align_job_lengths."""
        n = self.num_align_jobs()
        out = np.zeros((n, 2), dtype=np.uint32)
        q = ctypes.c_char_p()
        t = ctypes.c_char_p()
        ql = ctypes.c_uint32()
        tl = ctypes.c_uint32()
        for i in range(n):
            self._lib.rt_pipeline_align_job(
                self._h, i, ctypes.byref(q), ctypes.byref(ql),
                ctypes.byref(t), ctypes.byref(tl))
            out[i, 0] = ql.value
            out[i, 1] = tl.value
        return out

    def set_job_cigar(self, job: int, cigar: str) -> None:
        self._lib.rt_pipeline_set_job_cigar(self._h, job, cigar.encode())

    def align_jobs_cpu(self) -> None:
        faults.check("native.call")
        with obs.span("native.align_jobs_cpu"):
            self._lib.rt_pipeline_align_jobs_cpu(self._h)
            native.check_error(self._lib)

    def build_windows(self) -> None:
        with obs.span("native.build_windows") as sp:
            self._lib.rt_pipeline_build_windows(self._h)
            native.check_error(self._lib)
        self._stamp_stages(sp)
        self._count_filtered()

    def initialize(self) -> None:
        with obs.span("native.initialize") as sp:
            self._lib.rt_pipeline_initialize(self._h)
            native.check_error(self._lib)
        self._stamp_stages(sp)
        self._count_prepared()
        self._count_filtered()

    # -- phase 2 ----------------------------------------------------------
    def num_windows(self) -> int:
        return self._lib.rt_pipeline_num_windows(self._h)

    def window_growth(self) -> np.ndarray:
        """(windows, 3) uint64 in one ABI crossing: the layer bases each
        window's alignments put off its backbone (another base, or
        inserted: what makes a graph grow), the nodes the host engine's
        graph held and the most in-edges one of them held (both 0 where
        it did not run)."""
        out = np.zeros((self.num_windows(), 3), dtype=np.uint64)
        if len(out):
            self._lib.rt_pipeline_window_growth(
                self._h, out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)))
            native.check_error(self._lib)
        return out

    def window_info(self, i: int) -> Tuple[int, int, int, bool, int, int]:
        out = (ctypes.c_uint64 * 6)()
        self._lib.rt_pipeline_window_info(self._h, i, out)
        return (int(out[0]), int(out[1]), int(out[2]), bool(out[3]),
                int(out[4]), int(out[5]))

    def export_window(self, i: int) -> WindowExport:
        faults.check("window.export", (i,))
        (n_seqs, bb_len, rank, is_tgs, layer_bytes,
         target_id) = self.window_info(i)
        k = n_seqs - 1
        bb = np.zeros(bb_len, dtype=np.uint8)
        bbw = np.zeros(bb_len, dtype=np.uint8)
        lens = np.zeros(k, dtype=np.uint32)
        begins = np.zeros(k, dtype=np.uint32)
        ends = np.zeros(k, dtype=np.uint32)
        bases = np.zeros(layer_bytes, dtype=np.uint8)
        weights = np.zeros(layer_bytes, dtype=np.uint8)
        u8p = ctypes.POINTER(ctypes.c_uint8)
        u32p = ctypes.POINTER(ctypes.c_uint32)
        self._lib.rt_pipeline_window_export(
            self._h, i,
            bb.ctypes.data_as(u8p), bbw.ctypes.data_as(u8p),
            lens.ctypes.data_as(u32p), begins.ctypes.data_as(u32p),
            ends.ctypes.data_as(u32p), bases.ctypes.data_as(u8p),
            weights.ctypes.data_as(u8p))
        return WindowExport(index=i, rank=rank, target_id=target_id,
                            is_tgs=is_tgs, backbone=bb, backbone_weights=bbw,
                            lens=lens, begins=begins, ends=ends, bases=bases,
                            weights=weights)

    def consensus_cpu_one(self, i: int) -> bool:
        faults.check("native.call", (i,))
        r = self._lib.rt_pipeline_consensus_cpu_one(self._h, i)
        if r < 0:
            native.check_error(self._lib)
            raise native.NativeError(f"consensus failed for window {i}")
        return bool(r)

    def consensus_cpu_submit(self, i: int) -> None:
        """Hand window i's host consensus to a native pool worker and
        return at once (each worker owns its aligner slot; this thread
        may go on exporting and installing *other* windows)."""
        faults.check("native.call", (i,))
        self._lib.rt_pipeline_consensus_cpu_submit(self._h, i)
        native.check_error(self._lib)

    def consensus_cpu_join(self, windows) -> Tuple[List[bool], int]:
        """Wait for every submitted window.  Returns (polished flag per
        window of `windows`, how many had already finished when the wait
        began); a window that failed raises here, after all have ended."""
        idx = (ctypes.c_uint64 * len(windows))(*windows)
        polished = (ctypes.c_uint8 * len(windows))()
        done = self._lib.rt_pipeline_consensus_cpu_join(
            self._h, idx, len(windows), polished)
        if done < 0:
            native.check_error(self._lib)
            raise native.NativeError("host consensus failed")
        return [bool(p) for p in polished], int(done)

    def consensus_cpu_all(self) -> None:
        faults.check("native.call")
        with obs.span("native.consensus_cpu_all"):
            self._lib.rt_pipeline_consensus_cpu_all(self._h)
            native.check_error(self._lib)

    def get_consensus(self, i: int) -> bytes:
        """Window i's stored consensus (host- or device-produced)."""
        ln = ctypes.c_uint64()
        p = self._lib.rt_pipeline_get_consensus(self._h, i, ctypes.byref(ln))
        return ctypes.string_at(p, ln.value)

    def set_consensus(self, i: int, consensus: bytes, polished: bool) -> None:
        self._lib.rt_pipeline_set_consensus(
            self._h, i, consensus, len(consensus), 1 if polished else 0)

    def stitch(self, drop_unpolished: bool = True) -> List[Tuple[str, str]]:
        with obs.span("native.stitch") as sp:
            n = self._lib.rt_pipeline_stitch(
                self._h, 1 if drop_unpolished else 0)
            native.check_error(self._lib)
        self._stamp_stages(sp)
        # targets the stitch left out: no window of theirs was polished
        obs.count("polish.targets.dropped", self._prepare_counts()[0] - n)
        out = []
        ln = ctypes.c_uint64()
        with obs.span("native.stitch.copy", records=n):
            for i in range(n):
                p = self._lib.rt_pipeline_result_name(self._h, i,
                                                      ctypes.byref(ln))
                name = ctypes.string_at(p, ln.value).decode()
                p = self._lib.rt_pipeline_result_data(self._h, i,
                                                      ctypes.byref(ln))
                data = ctypes.string_at(p, ln.value).decode()
                out.append((name, data))
        return out
