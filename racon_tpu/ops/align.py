"""Batched banded global (NW) alignment on device.

TPU-native replacement for the reference's edlib call on CIGAR-less overlaps
(/root/reference/src/overlap.cpp:205-224) and its CUDA batch analogue
(/root/reference/src/cuda/cudaaligner.cpp). Unit costs, static band per
size bucket (the reference GPU path also aligns banded: auto band = 10% of
mean overlap length, src/cuda/cudapolisher.cpp:159-163).

Formulation: rows i over the query, each row a K-lane vector over band
offsets o, with cell (i, o) <-> target column j = i + dmin + o. The
horizontal (target-gap) dependency is resolved with the affine-transform
cummin: D[i][o] = o + cummin(V[i][o] - o). A 2-bit move per cell (stored as
u8) supports an exact in-band traceback; ops are RLE'd to a CIGAR on host.

In-band paths are valid alignments but may be suboptimal if the true path
leaves the band — same approximation contract as the reference's banded CUDA
aligner, with accuracy pinned by the golden tests.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .. import native
from .encoding import encode
from ..device import named
from .kernel_cache import device_keyed_cache

INF = jnp.int32(1 << 28)

# (max sequence length, band width) buckets; larger pairs go to the host.
BUCKETS = ((1024, 256), (2048, 512), (4096, 1024), (8192, 2048))
MAX_DEVICE_LEN = BUCKETS[-1][0]

#: Declared compile budget for the aligner: one jit signature per
#: (cap, band) bucket at the nominal batch.  A deliberate literal (see
#: POA_RECOMPILE_BUDGET in poa_driver.py): adding a bucket without
#: revisiting this number fails the jaxpr audit.
ALIGN_RECOMPILE_BUDGET = 4


def device_eligible(q_len: int, t_len: int) -> bool:
    n, m = int(q_len), int(t_len)
    if n == 0 or m == 0:
        return False
    size = max(n, m)
    for cap, band in BUCKETS:
        if size <= cap:
            return abs(m - n) + 2 <= band
    return False


def _bucket_for(size: int):
    for cap, band in BUCKETS:
        if size <= cap:
            return cap, band
    raise ValueError(size)


@device_keyed_cache(maxsize=16)
def build_align_kernel(cap: int, band: int, shard_n: int = 1):
    """jit kernel over a batch: returns (moves-free) ops + lengths.

    shard_n > 1 constrains every input/output to shard its leading
    (``query``) batch dim over the partitioner's mesh — the pjit path;
    the vmapped XLA program partitions transparently, no per-shard
    rebuild needed.  Callers pad cohorts to a shard_n multiple (the
    executor's pad seam) before dispatching on the sharded kernel."""
    K = band
    PAD = K + 2

    def one(q, t, n, m):
        # q, t: u8 codes padded to cap; n, m actual lengths.
        diff = m - n
        slack = (K - 1 - jnp.abs(diff)) // 2
        dmin = jnp.minimum(0, diff) - slack

        t_pad = jnp.full(cap + 2 * PAD, 255, dtype=jnp.uint8)
        t_pad = jax.lax.dynamic_update_slice(t_pad, t, (PAD,))

        o_vec = jnp.arange(K, dtype=jnp.int32)

        row0_j = dmin + o_vec
        row0 = jnp.where((row0_j >= 0) & (row0_j <= m), row0_j, INF)

        def row_fn(prev_row, xs):
            qc, i = xs  # i = 1..cap
            j_vec = i + dmin + o_vec
            tsl = jax.lax.dynamic_slice(t_pad, (i + dmin - 1 + PAD,), (K,))
            sub = prev_row + jnp.where(tsl == qc, 0, 1)
            up = jnp.concatenate([prev_row[1:], jnp.array([INF])]) + 1
            V = jnp.minimum(sub, up)
            mv = jnp.where(V == sub, jnp.uint8(0), jnp.uint8(1))
            # boundary column j == 0: only vertical moves
            V = jnp.where(j_vec == 0, i, V)
            mv = jnp.where(j_vec == 0, jnp.uint8(1), mv)
            V = jnp.where((j_vec < 0) | (j_vec > m), INF, V)
            # horizontal pass
            row = jax.lax.cummin(V - o_vec) + o_vec
            mv = jnp.where(row < V, jnp.uint8(2), mv)
            row = jnp.where((j_vec < 0) | (j_vec > m), INF, row)
            return row, mv

        ii = jnp.arange(1, cap + 1, dtype=jnp.int32)
        _, moves = jax.lax.scan(row_fn, row0, (q.astype(jnp.uint8), ii))
        # moves[i-1] is row i

        # Traceback from (n, j=m).
        OPS = 2 * cap

        def cond(c):
            i, j, _, cnt, _ = c
            return ((i > 0) | (j > 0)) & (cnt < OPS)

        def body(c):
            i, j, ops, cnt, ok = c
            o = j - i - dmin
            in_band = (o >= 0) & (o < K)
            mv = jnp.where(i > 0,
                           jnp.where(in_band,
                                     moves[jnp.maximum(i - 1, 0),
                                           jnp.clip(o, 0, K - 1)],
                                     jnp.uint8(3)),
                           jnp.uint8(2))  # row 0: consume target
            ok = ok & (mv != 3)
            # 0=M (diag), 1=I (query), 2=D (target)
            ops = ops.at[cnt].set(mv)
            i = jnp.where(mv == 2, i, i - 1)
            j = jnp.where(mv == 1, j, j - 1)
            return (i, j, ops, cnt + 1, ok)

        ops0 = jnp.zeros(OPS, dtype=jnp.uint8)
        i, j, ops, cnt, ok = jax.lax.while_loop(
            cond, body, (n, m, ops0, jnp.int32(0), jnp.bool_(True)))
        ok = ok & (i == 0) & (j == 0)
        return ops, cnt, ok

    batched = named("racon_align_xla")(jax.vmap(one))
    if shard_n > 1:
        from ..parallel.partitioner import get_partitioner

        return get_partitioner().partition(
            batched, in_axes=[("query",)] * 4, out_axes=("query",))
    return jax.jit(batched)


class _XlaAlignOps:
    """Executor hooks (ops/batch_exec.py) for the moves-matrix aligner.

    The jit kernel call is a JAX async dispatch, so the shared executor
    keeps depth-Q chunks in flight: the host packs chunk N+1 while chunk
    N executes.  Packing is single-copy — each job's bases land once in
    the chunk's padded buffers; lattice retries and bisection probes
    gather rows from the per-job views instead of re-materializing."""

    span_name = "align.cohort"
    pack_span = "align.export"
    install_span = "align.install"

    def __init__(self, pipeline, report, stats, state):
        self.pipeline = pipeline
        self.report = report
        self.stats = stats
        self.state = state        # {"served": int}
        self.rows = {}            # job -> (q_row, t_row, n, m)
        self.dead = False

    def live_tier(self, ctx, kind):
        return "host" if self.dead else "xla"

    def export(self, ctx, chunk):
        return list(chunk)

    def pack(self, ctx, chunk):
        cap = ctx["cap"]
        B = len(chunk)
        q = np.zeros((B, cap), dtype=np.uint8)
        t = np.zeros((B, cap), dtype=np.uint8)
        n = np.zeros(B, dtype=np.int32)
        m = np.zeros(B, dtype=np.int32)
        for bi, job in enumerate(chunk):
            qa, ta = self.pipeline.align_job(job)
            q[bi, :len(qa)] = encode(qa)
            t[bi, :len(ta)] = encode(ta)
            n[bi] = len(qa)
            m[bi] = len(ta)
            self.rows[job] = (q[bi], t[bi], n[bi], m[bi])
        return q, t, n, m

    def dispatch(self, ctx, kind, packed, chunk):
        from ..resilience import faults

        faults.check("align.run", chunk)
        kern = ctx["skernel"] if ctx.get("use_shard") else ctx["kernel"]
        return kern(*packed)

    def attempt(self, ctx, kind, sub):
        from ..resilience import faults

        faults.check("align.run", sub)
        q = np.stack([self.rows[j][0] for j in sub])
        t = np.stack([self.rows[j][1] for j in sub])
        n = np.asarray([self.rows[j][2] for j in sub], dtype=np.int32)
        m = np.asarray([self.rows[j][3] for j in sub], dtype=np.int32)
        return tuple(np.asarray(x) for x in ctx["kernel"](q, t, n, m))

    def unpack(self, ctx, kind, outs):
        return tuple(np.asarray(x) for x in outs)

    def span_args(self, ctx, chunk, pipelined):
        return {"cap": ctx["cap"], "jobs": len(chunk)}

    def install(self, ctx, kind, sub, results):
        from ..analysis import sanitize
        from ..resilience import faults

        ops, cnt, ok = results
        if sanitize.enabled():
            sanitize.check_align_outputs(ops, cnt, ok,
                                         where="align.run_jobs")
        for bi, job in enumerate(sub):
            if not ok[bi]:
                continue  # host will align it
            faults.check("align.install", (job,))
            cigar = ops_to_cigar(ops[bi, :cnt[bi]][::-1])
            self.pipeline.set_job_cigar(job, cigar)
            self.state["served"] += 1
            if self.stats is not None:
                self.stats["device"] = self.stats.get("device", 0) + 1
            if self.report is not None:
                self.report.record_served("xla")

    def surrender(self, ctx, items, exported):
        pass  # CIGAR-less jobs fall to the native host pass

    def quarantine(self, ctx, job, exc):
        if self.report is not None:
            self.report.record_quarantine(job, exc)

    def demote(self, ctx, kind, cause):
        import sys

        self.dead = True
        print(f"[racon_tpu::align] WARNING: xla aligner failed "
              f"({type(cause).__name__}: {cause}); remaining jobs "
              f"fall back to the host aligner", file=sys.stderr)
        if self.report is not None:
            self.report.record_degrade("xla", "host", cause)
        return "host"

    def done(self, ctx, chunk):
        # keep host memory O(depth x batch): rows die with the chunk
        for job in chunk:
            self.rows.pop(job, None)

    # -- sharded dispatch (optional executor hooks) ------------------------
    def shard_multiple(self, ctx, chunk):
        # Decided per cohort: the executor pads the packed buffers to
        # the returned multiple, then dispatch() (same submit call)
        # routes to the sharded kernel.  Tail cohorts below the
        # will_shard floor go single-device unpadded.  install() indexes
        # results by real-row position, so the trailing pad rows
        # (repeats of the last job) are computed and dropped.
        ctx["use_shard"] = False
        m = ctx.get("shard_n", 1)
        if m <= 1 or ctx.get("skernel") is None:
            return 1
        from ..parallel.partitioner import get_partitioner

        if not get_partitioner().will_shard(len(chunk)):
            return 1
        ctx["use_shard"] = True
        return m

    def demote_shard(self, ctx, kind, cause):
        if not ctx.get("use_shard"):
            return False
        ctx["use_shard"] = False
        ctx["shard_n"] = 1
        from ..parallel.partitioner import get_partitioner
        from ..resilience import lattice as rl

        if get_partitioner().demote(f"{type(cause).__name__}: {cause}"):
            rl.record_shard_demotion(self.report, kind, cause)
        return True


def run_jobs(pipeline, jobs, batch: int = 16, report=None,
             stats=None, lengths=None) -> int:
    """Align the given pipeline jobs on device; install CIGARs.
    Returns how many alignments the device served.

    Jobs bucket by padded length (lengths only — bases are packed once
    per chunk into padded buffers at dispatch time), and every chunk runs
    through the degradation lattice via the shared executor
    (ops/batch_exec.py): depth-Q async dispatch, bounded retry, then
    bisection so a poisoned job is quarantined to the host while the rest
    of the chunk stays on the device.  A chunk-independent failure stops
    the engine; the served count stays accurate for whatever was already
    installed.

    `lengths` is the bulk job-lengths array (the driver fetches it once
    and threads it through); without it, one bulk fetch happens here.

    ``stats`` (the driver's accounting dict) has its ``'device'`` entry
    incremented per installed CIGAR, so even an exception that escapes
    this function entirely — a kernel build for a later bucket, a
    sanitizer trip, an install failure — cannot zero out work already
    installed (which the driver's host count is derived from)."""
    import sys

    from ..resilience import lattice as rl
    from .. import obs
    from .batch_exec import BatchExecutor

    if lengths is None and hasattr(pipeline, "align_job_lengths"):
        lengths = pipeline.align_job_lengths()
    if lengths is not None:
        maxlen = {j: int(max(lengths[j, 0], lengths[j, 1])) for j in jobs}
    else:  # duck-typed pipelines without the lengths table
        maxlen = {}
        for job in jobs:
            qa, ta = pipeline.align_job(job)
            maxlen[job] = max(len(qa), len(ta))
    # Group by bucket (lengths only, no bases copied yet).
    grouped = {}
    for job in jobs:
        cap, band = _bucket_for(maxlen[job])
        grouped.setdefault((cap, band), []).append(job)

    state = {"served": 0}
    ops_obj = _XlaAlignOps(pipeline, report, stats, state)
    executor = BatchExecutor(ops_obj, report=report)
    try:
        from ..parallel.partitioner import get_partitioner

        part = get_partitioner()
        shard_n = part.batch_axis_size if part.will_shard(batch) else 1
        for (cap, band), items in sorted(grouped.items()):
            kernel = build_align_kernel(cap, band)
            skernel = None
            if shard_n > 1:
                try:
                    skernel = build_align_kernel(cap, band, shard_n)
                except Exception as e:  # noqa: BLE001 — shard edge
                    # sharded wrap failed to build: single-device for
                    # the rest of the process, same tier (never fatal)
                    if part.demote(f"{type(e).__name__}: {e}"):
                        rl.record_shard_demotion(report, "xla", e)
                    shard_n = 1
            obs.count(f"align.bucket.c{cap}", len(items))
            # Measured-cell counter for the cost model (obs/costmodel.py):
            # every job in a bucket pays the full padded cap x band DP.
            obs.count(f"align.cells.c{cap}", len(items) * cap * band)
            ctx = {"cap": cap, "band": band, "kernel": kernel,
                   "skernel": skernel, "shard_n": shard_n}
            for off in range(0, len(items), batch):
                executor.submit(ctx, items[off:off + batch])
            # drain before the next bucket's kernel build so in-flight
            # futures never outlive their geometry's packed buffers
            executor.flush()
    except Exception as e:  # noqa: BLE001 — lattice boundary
        cause = e.cause if isinstance(e, rl.TierDead) else e
        print(f"[racon_tpu::align] WARNING: xla aligner failed "
              f"({type(cause).__name__}: {cause}); remaining jobs "
              f"fall back to the host aligner", file=sys.stderr)
        if report is not None:
            report.record_degrade("xla", "host", cause)
    if report is not None:
        executor.stamp_walls(report)
    return state["served"]


def ops_to_cigars(ops_list) -> list:
    """CIGAR strings of forward-ordered op code arrays (0=M, 1=I, 2=D):
    one native run-length pass over them all."""
    off = np.zeros(len(ops_list) + 1, np.uint64)
    np.cumsum([len(ops) for ops in ops_list], out=off[1:])
    flat = np.concatenate(ops_list) if len(ops_list) else ()
    return native.ops_to_cigars(np.ascontiguousarray(flat, np.int32), off)


def ops_to_cigar(ops: np.ndarray) -> str:
    """Run-length encode forward-ordered op codes (0=M,1=I,2=D)."""
    return ops_to_cigars([ops])[0]
