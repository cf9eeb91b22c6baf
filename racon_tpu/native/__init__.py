"""ctypes binding to the racon-tpu native host runtime (libracon_host.so).

The native library implements the host side of the framework: parsers for
FASTA/FASTQ/MHAP/PAF/SAM (+gzip), the sequence/overlap/window data model,
overlap filtering, the banded global aligner and POA consensus oracle, the
thread pool, and the stitching pipeline — the parity surface of the
reference's first-party C++ layer (/root/reference/src/) and its vendored
native dependencies (bioparser, spoa, edlib, thread_pool).

The Python side orchestrates the TPU phases and claims work through the job
export/import seam (see rt_pipeline.hpp).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
from typing import List, Optional

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_LIB_PATH = os.path.join(_DIR, "build", "libracon_host.so")

_lib: Optional[ctypes.CDLL] = None


def _newer_than_lib(path: str) -> bool:
    try:
        lib_mtime = os.path.getmtime(_LIB_PATH)
    except OSError:
        return True
    return os.path.getmtime(path) > lib_mtime


def ensure_built() -> str:
    """Build libracon_host.so if missing or stale. Returns its path."""
    src_dir = os.path.join(_DIR, "src")
    inputs = [os.path.join(src_dir, f) for f in os.listdir(src_dir)
              if f.endswith((".cpp", ".hpp"))]
    inputs.append(os.path.join(_DIR, "Makefile"))
    stale = not os.path.exists(_LIB_PATH) or any(
        _newer_than_lib(p) for p in inputs)
    if stale:
        proc = subprocess.run(
            ["make", "-j", str(os.cpu_count() or 4)],
            cwd=_DIR,
            capture_output=True,
            text=True,
        )
        if proc.returncode != 0:
            raise RuntimeError(
                f"native build failed (make exited {proc.returncode}):\n"
                f"{proc.stdout}\n{proc.stderr}")
    return _LIB_PATH


def load() -> ctypes.CDLL:
    """Load (building if necessary) the native library, configured."""
    global _lib
    if _lib is not None:
        return _lib
    lib = ctypes.CDLL(ensure_built())

    u8p = ctypes.POINTER(ctypes.c_uint8)
    u32p = ctypes.POINTER(ctypes.c_uint32)
    u64p = ctypes.POINTER(ctypes.c_uint64)

    lib.rt_last_error.restype = ctypes.c_char_p
    lib.rt_last_error.argtypes = []

    lib.rt_edit_distance.restype = ctypes.c_int64
    lib.rt_edit_distance.argtypes = [
        ctypes.c_char_p, ctypes.c_uint32, ctypes.c_char_p, ctypes.c_uint32]

    lib.rt_align_cigar.restype = ctypes.c_void_p
    lib.rt_align_cigar.argtypes = [
        ctypes.c_char_p, ctypes.c_uint32, ctypes.c_char_p, ctypes.c_uint32]

    lib.rt_free.restype = None
    lib.rt_free.argtypes = [ctypes.c_void_p]

    lib.rt_window_consensus.restype = ctypes.c_void_p
    lib.rt_window_consensus.argtypes = [
        ctypes.c_char_p, ctypes.c_uint32, ctypes.c_char_p, ctypes.c_char_p,
        ctypes.c_char_p, u32p, u32p, u32p, ctypes.c_uint32, ctypes.c_int,
        ctypes.c_int, ctypes.c_int, ctypes.c_int8, ctypes.c_int8,
        ctypes.c_int8, ctypes.POINTER(ctypes.c_int)]

    lib.rt_pipeline_create.restype = ctypes.c_void_p
    lib.rt_pipeline_create.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_int,
        ctypes.c_uint32, ctypes.c_double, ctypes.c_double, ctypes.c_int,
        ctypes.c_int8, ctypes.c_int8, ctypes.c_int8, ctypes.c_uint32]

    lib.rt_pipeline_destroy.restype = None
    lib.rt_pipeline_destroy.argtypes = [ctypes.c_void_p]

    for name in ("rt_pipeline_prepare", "rt_pipeline_align_jobs_cpu",
                 "rt_pipeline_build_windows", "rt_pipeline_initialize",
                 "rt_pipeline_consensus_cpu_all"):
        fn = getattr(lib, name)
        fn.restype = None
        fn.argtypes = [ctypes.c_void_p]

    lib.rt_pipeline_prepare_counts.restype = None
    lib.rt_pipeline_prepare_counts.argtypes = [ctypes.c_void_p, u64p]
    lib.rt_pipeline_window_growth.restype = None
    lib.rt_pipeline_window_growth.argtypes = [ctypes.c_void_p, u64p]
    lib.rt_pipeline_filter_counts.restype = None
    lib.rt_pipeline_filter_counts.argtypes = [ctypes.c_void_p, u64p]

    lib.rt_pipeline_stage_marks.restype = ctypes.c_uint64
    lib.rt_pipeline_stage_marks.argtypes = [ctypes.c_void_p, u64p,
                                            ctypes.c_uint64]
    lib.rt_steady_clock_ns.restype = ctypes.c_int64
    lib.rt_steady_clock_ns.argtypes = []
    lib.rt_pool_parallel_for_probe.restype = ctypes.c_uint32
    lib.rt_pool_parallel_for_probe.argtypes = [
        ctypes.c_uint32, ctypes.c_uint64, ctypes.c_uint64,
        ctypes.POINTER(ctypes.c_uint32)]

    lib.rt_pipeline_num_align_jobs.restype = ctypes.c_uint64
    lib.rt_pipeline_num_align_jobs.argtypes = [ctypes.c_void_p]

    lib.rt_pipeline_align_job.restype = None
    lib.rt_pipeline_align_job.argtypes = [
        ctypes.c_void_p, ctypes.c_uint64,
        ctypes.POINTER(ctypes.c_char_p), u32p,
        ctypes.POINTER(ctypes.c_char_p), u32p]

    lib.rt_pipeline_align_job_lengths.restype = None
    lib.rt_pipeline_align_job_lengths.argtypes = [ctypes.c_void_p, u32p]

    lib.rt_pipeline_set_job_cigar.restype = None
    lib.rt_pipeline_set_job_cigar.argtypes = [
        ctypes.c_void_p, ctypes.c_uint64, ctypes.c_char_p]

    lib.rt_pipeline_num_windows.restype = ctypes.c_uint64
    lib.rt_pipeline_num_windows.argtypes = [ctypes.c_void_p]

    lib.rt_pipeline_window_info.restype = None
    lib.rt_pipeline_window_info.argtypes = [
        ctypes.c_void_p, ctypes.c_uint64, u64p]

    lib.rt_pipeline_window_export.restype = None
    lib.rt_pipeline_window_export.argtypes = [
        ctypes.c_void_p, ctypes.c_uint64, u8p, u8p, u32p, u32p, u32p, u8p, u8p]

    lib.rt_pipeline_consensus_cpu_one.restype = ctypes.c_int
    lib.rt_pipeline_consensus_cpu_one.argtypes = [
        ctypes.c_void_p, ctypes.c_uint64]

    lib.rt_pipeline_consensus_cpu_submit.restype = None
    lib.rt_pipeline_consensus_cpu_submit.argtypes = [
        ctypes.c_void_p, ctypes.c_uint64]

    lib.rt_pipeline_consensus_cpu_join.restype = ctypes.c_int64
    lib.rt_pipeline_consensus_cpu_join.argtypes = [
        ctypes.c_void_p, u64p, ctypes.c_uint64, u8p]

    lib.rt_pipeline_set_consensus.restype = None
    lib.rt_pipeline_set_consensus.argtypes = [
        ctypes.c_void_p, ctypes.c_uint64, ctypes.c_char_p, ctypes.c_uint32,
        ctypes.c_int]

    lib.rt_pipeline_stitch.restype = ctypes.c_uint64
    lib.rt_pipeline_stitch.argtypes = [ctypes.c_void_p, ctypes.c_int]

    lib.rt_pipeline_result_name.restype = ctypes.c_void_p
    lib.rt_pipeline_result_name.argtypes = [
        ctypes.c_void_p, ctypes.c_uint64, u64p]

    lib.rt_pipeline_result_data.restype = ctypes.c_void_p
    lib.rt_pipeline_result_data.argtypes = [
        ctypes.c_void_p, ctypes.c_uint64, u64p]

    lib.rt_pipeline_get_consensus.restype = ctypes.c_void_p
    lib.rt_pipeline_get_consensus.argtypes = [ctypes.c_void_p,
                                              ctypes.c_uint64, u64p]

    lib.rt_pipeline_window_type.restype = ctypes.c_int
    lib.rt_pipeline_window_type.argtypes = [ctypes.c_void_p]

    # rt_hirschberg.hpp: numpy buffers go in by address (`_addr`)
    vp = ctypes.c_void_p
    lib.rt_hirschberg_pack.restype = ctypes.c_int64
    lib.rt_hirschberg_pack.argtypes = [
        vp, vp, ctypes.c_uint64, ctypes.c_int32, ctypes.c_int32,
        ctypes.c_int, ctypes.c_uint32, vp, vp, vp]

    lib.rt_hirschberg_select.restype = None
    lib.rt_hirschberg_select.argtypes = [
        vp, vp, ctypes.c_uint32, vp, vp, vp, ctypes.c_uint64, vp, vp]

    lib.rt_hirschberg_gather.restype = None
    lib.rt_hirschberg_gather.argtypes = [
        vp, vp, ctypes.c_uint64, ctypes.c_int, vp]

    lib.rt_ops_to_cigars.restype = ctypes.c_int64
    lib.rt_ops_to_cigars.argtypes = [vp, vp, ctypes.c_uint64, vp, vp]

    _lib = lib
    return lib


class NativeError(RuntimeError):
    """Raised when the native runtime reports an error (it never exits the
    process when used as a library; the CLI binary exits 1 instead)."""


def check_error(lib: ctypes.CDLL) -> None:
    msg = lib.rt_last_error()
    if msg:
        raise NativeError(msg.decode().strip())


def edit_distance(q: bytes, t: bytes) -> int:
    """Global (NW) edit distance — the accuracy metric of the test suite
    (reference analogue: test/racon_test.cpp:14-23)."""
    lib = load()
    return lib.rt_edit_distance(q, len(q), t, len(t))


def align_cigar(q: bytes, t: bytes) -> str:
    """Global alignment CIGAR (host banded NW)."""
    lib = load()
    ptr = lib.rt_align_cigar(q, len(q), t, len(t))
    if not ptr:
        check_error(lib)
        raise NativeError("alignment failed")
    try:
        return ctypes.string_at(ptr).decode()
    finally:
        lib.rt_free(ptr)


def window_consensus(backbone: bytes, layers, *, backbone_qual: bytes = None,
                     quals=None, begins=None, ends=None, tgs: bool = True,
                     trim: bool = True, match: int = 5, mismatch: int = -4,
                     gap: int = -8):
    """One-shot host POA window consensus (unit/differential test hook).

    layers: list of bytes. begins/ends: per-layer backbone positions
    (default: full span). quals: list of bytes or None.
    Returns (consensus: bytes, polished: bool).
    """
    lib = load()
    n = len(layers)
    bb_len = len(backbone)
    lens = (ctypes.c_uint32 * n)(*[len(s) for s in layers])
    begins_a = (ctypes.c_uint32 * n)(
        *(begins if begins is not None else [0] * n))
    ends_a = (ctypes.c_uint32 * n)(
        *(ends if ends is not None else [bb_len - 1] * n))
    bases = b"".join(layers)
    has_qual = quals is not None
    qual_cat = b"".join(quals) if has_qual else None
    polished = ctypes.c_int(0)
    ptr = lib.rt_window_consensus(
        backbone, bb_len, backbone_qual, bases, qual_cat, lens, begins_a,
        ends_a, n, 1 if has_qual else 0, 1 if tgs else 0, 1 if trim else 0,
        match, mismatch, gap, ctypes.byref(polished))
    if not ptr:
        check_error(lib)
        raise NativeError("window consensus failed")
    try:
        return ctypes.string_at(ptr), bool(polished.value)
    finally:
        lib.rt_free(ptr)


# --------------------------------------------------------------------------
# host bookkeeping of the device's Hirschberg aligner (rt_hirschberg.hpp)
# --------------------------------------------------------------------------

def _addr(a: np.ndarray, dtype) -> int:
    """Address of a C-contiguous array of `dtype`, checked before native
    code reads it as one."""
    if a.dtype != dtype or not a.flags.c_contiguous:
        raise TypeError(f"want a C-contiguous {np.dtype(dtype).name} array, "
                        f"got {a.dtype.name} {a.shape}")
    return a.ctypes.data


#: columns of the per-pair table `hirschberg_pack` reads (int64): address
#: of the pair's int32 query codes, of its target codes, n, m, gdmin
PAIR_COLS = 5
#: columns of a task table (int32): pair (-1 = pad slot), ia, ib, ja, jb
TASK_COLS = 5


def hirschberg_pack(pairs: np.ndarray, tasks: np.ndarray, rcap: int, K: int,
                    backward: bool, q_words: int):
    """Stage one launch: (scal [B, 4], qs [B, q_words], ts [B, rcap + K])
    for the B slots of `tasks`, from the code arrays `pairs` points at
    (the caller keeps them alive)."""
    B = len(tasks)
    if pairs.shape[1:] != (PAIR_COLS,) or tasks.shape[1:] != (TASK_COLS,):
        raise ValueError((pairs.shape, tasks.shape))
    if B and int(tasks[:, 0].max()) >= len(pairs):
        raise IndexError("task of a pair outside the table")
    scal = np.empty((B, 4), np.int32)
    qs = np.empty((B, q_words), np.int32)
    ts = np.empty((B, rcap + K), np.int32)
    bad = load().rt_hirschberg_pack(
        _addr(pairs, np.int64), _addr(tasks, np.int32), B, rcap, K,
        1 if backward else 0, q_words, scal.ctypes.data, qs.ctypes.data,
        ts.ctypes.data)
    if bad >= 0:
        raise ValueError(f"slot {bad}: task {tasks[bad].tolist()} does not "
                         f"fit its pair or rcap={rcap}, K={K}")
    return scal, qs, ts


def hirschberg_select(F: np.ndarray, Bv: np.ndarray, rows: np.ndarray,
                      lo: np.ndarray, hi: np.ndarray):
    """(lane, tot) per task: the first lane of row `rows[i]` within
    [lo[i], hi[i]] that minimises F + Bv; lane -1 where the range misses
    the row."""
    n = len(rows)
    if F.shape != Bv.shape or F.ndim != 2 or not len(lo) == len(hi) == n:
        raise ValueError((F.shape, Bv.shape, n, len(lo), len(hi)))
    if n and not 0 <= int(rows.min()) <= int(rows.max()) < len(F):
        raise IndexError("row outside the launch")
    lane = np.empty(n, np.int32)
    tot = np.empty(n, np.int32)
    load().rt_hirschberg_select(
        _addr(F, np.int32), _addr(Bv, np.int32), F.shape[1],
        _addr(rows, np.int32), _addr(lo, np.int32), _addr(hi, np.int32), n,
        lane.ctypes.data, tot.ctypes.data)
    return lane, tot


def hirschberg_gather(src: np.ndarray, cnt: np.ndarray,
                      reverse: bool) -> np.ndarray:
    """The int32 codes of the segments laid back to back: segment s is
    cnt[s] codes read at address src[s] (the caller vouches for it and
    keeps it alive), back to front if `reverse`."""
    n = len(src)
    if len(cnt) != n or (n and int(cnt.min()) < 0):
        raise ValueError((n, len(cnt)))
    out = np.empty(int(cnt.sum()), np.int32)
    load().rt_hirschberg_gather(
        _addr(src, np.int64), _addr(cnt, np.int32), n, 1 if reverse else 0,
        out.ctypes.data)
    return out


def ops_to_cigars(ops: np.ndarray, off: np.ndarray) -> List[str]:
    """CIGAR strings of the pairs whose forward op codes (0=M, 1=I, 2=D)
    lie back to back in `ops`, pair p at ops[off[p]:off[p + 1]]."""
    n = len(off) - 1
    if n < 0 or off[0] != 0 or int(off[-1]) != len(ops) \
            or (np.diff(off.astype(np.int64)) < 0).any():
        raise ValueError("offsets do not tile the op codes")
    out = np.empty(2 * len(ops), np.uint8)
    ends = np.empty(n + 1, np.uint64)
    if load().rt_ops_to_cigars(
            _addr(ops, np.int32), _addr(off, np.uint64), n, out.ctypes.data,
            ends.ctypes.data) < 0:
        raise ValueError("op code outside 0..2")
    text = out[:int(ends[-1])].tobytes().decode("ascii")
    return [text[a:b] for a, b in zip(ends[:-1].tolist(), ends[1:].tolist())]
