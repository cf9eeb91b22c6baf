"""Shared bucketed-batch executor: the one feeder both device drivers run on.

Extracted from poa_driver.run_consensus_phase's chunk loop so the consensus
and alignment paths share a single serving seam:

* **single-copy packing** — the driver's `pack` hook copies each unit's
  bytes exactly once into preallocated padded buffers; lattice retries and
  bisection probes reuse the packed views instead of re-materializing;
* **depth-Q async dispatch** — a kernel call is a JAX async dispatch,
  so up to `depth` packed chunks stay in flight and the host packs
  chunk N+1 while chunk N executes — the analogue of the reference's
  continuous batch fill running concurrently with kernel execution
  (/root/reference/src/cuda/cudapolisher.cpp:83-145).  An engine that
  orchestrates many launches per chunk on the host (Hirschberg)
  dispatches a generator advanced to its first wait, and its `unpack`
  and `install` give the chunk behind it a step whenever that chunk's
  launches are back, while they resolve their own;
* **one resilience seam** — the degradation lattice
  (resilience/lattice.py: bounded retry, batch bisection-quarantine,
  tier demotion down to the host floor), the journal taps, the runtime
  sanitizer hooks, and the obs span/counter emission all live in the
  driver-supplied hooks called from exactly one place, so every engine
  inherits identical failure semantics;
* **pack/kernel wall split** — `pack_ns` (host export+pack) vs
  `kernel_ns` (host wall blocked in the lattice serve) accumulate per
  executor and surface as `report.extra["pack_wall_s"/"kernel_wall_s"]`
  in the drivers.  `kernel_wall_s` is NOT kernel time: for the
  Hirschberg engine the serve is a cohort's `align_steps` from its
  first wait on — task arrays, padding, the per-task midpoint loop, the
  traceback — and steps of the next cohort's at its yields; for a
  one-launch engine it is the blocking copy back.  The launch-level
  spans tell those apart: the executor emits
  the ops object's `pack_span` (exactly what `pack_ns` sums) and
  `install_span`, the drivers emit `*.dispatch` / `*.wait` per launch
  (category `"launch"`).

The driver supplies an *ops* object (duck-typed; no registration):

    span_name: str            # per-chunk obs span name ("poa.chunk", …)
    pack_span: str            # launch span over export + pack + shard
                              # pad ("poa.pack", "align.export"): one
                              # name per seam, none shared with a span
                              # the driver emits itself
    install_span: str         # launch span over the install loop
    live_tier(ctx, kind)      # best live tier at/below `kind` (None =
                              # the bucket's entry tier); may stash the
                              # kernel handle on ctx
    export(ctx, idxs)         # -> chunk items ([] = nothing to serve)
    pack(ctx, chunk)          # -> packed buffers (single-copy)
    dispatch(ctx, kind, packed, chunk)  # async kernel call -> futures;
                              # owns the pre-dispatch faults.check
    attempt(ctx, kind, sub)   # lattice retry/bisect probe over packed
                              # views; owns its faults.check
    unpack(ctx, kind, outs)   # block on dispatched futures -> results
    span_args(ctx, chunk, pipelined)   # extra span args (dict)
    install(ctx, kind, sub, results)   # journal/sanitize/report seam
    surrender(ctx, items, exported)    # route items to the host floor
    quarantine(ctx, item, exc)         # one poisoned item -> host
    demote(ctx, kind, cause)  # tier died: record + return next tier
    done(ctx, chunk)          # optional: chunk fully resolved — release
                              # any per-chunk packed state
    widen(ctx, kind)          # optional (banded DP): items of the chunk
                              # whose band verify failed and that should
                              # be re-attempted with widened params
                              # ([] = ladder drained).  The executor
                              # loops attempt+install over them reusing
                              # the packed batch — the verify-and-widen
                              # re-dispatch seam (ops/band.py)

Sharded dispatch (optional hooks; engines without them are untouched):

    shard_multiple(ctx, chunk)  # mesh batch-axis size this chunk will
                              # dispatch over (1 = single device).  When
                              # >1 the executor pads the packed buffers
                              # to that multiple HERE — the one place
                              # pad-to-multiple math runs — and counts
                              # the padding + per-device shard rows in
                              # obs (`shard.pad_rows`, `shard.rows.d<i>`)
    demote_shard(ctx, kind, cause)  # a sharded serve died: drop to
                              # single-device dispatch and return True to
                              # retry the SAME tier (the lattice's
                              # `sharded -> single-device` edge); False =
                              # not sharded, demote the tier as usual
"""

from __future__ import annotations

import time
from collections import deque

import numpy as np

from .. import config, obs
from ..resilience import budget
from ..resilience import lattice as rl


def pipeline_depth() -> int:
    """How many packed chunks may be in flight on the device at once."""
    return max(1, config.get_int("RACON_TPU_PIPELINE_DEPTH"))


def pad_to_multiple(packed, m):
    """Pad every packed array's leading dim up to a multiple of `m` by
    repeating the final row — valid rows recomputed and discarded, never
    sentinel garbage, so padded lanes can't poison a kernel.  Returns
    (padded tuple, rows added).  The round-UP replacement for the old
    round-DOWN `parallel.mesh.divisible_batch` remainder spill; every
    sharded engine pads through this one helper."""
    rows = int(np.asarray(packed[0]).shape[0])
    pad = (m - rows % m) % m
    if pad <= 0:
        return tuple(packed), 0
    out = []
    for a in packed:
        a = np.asarray(a)
        out.append(np.concatenate([a, np.repeat(a[-1:], pad, axis=0)],
                                  axis=0))
    return tuple(out), pad


def count_shard_rows(n_real, rows, m) -> int:
    """Shard-size observability for one sharded dispatch of `rows` rows
    (`n_real` of them real work) over `m` mesh shards: padded-row total
    plus one counter per device position, so shard balance ('within one
    batch per device') is checkable from any trace snapshot.  Returns
    the pad-row count.  Shared by the executor's pad seam and the
    host-orchestrated Hirschberg rounds (align_pallas), which pad their
    own pow2 batches."""
    pad = max(0, rows - n_real)
    if pad > 0:
        obs.count("shard.pad_rows", pad)
    obs.count("shard.chunks")
    per_dev = rows // m
    for i in range(m):
        obs.count(f"shard.rows.d{i}", per_dev)
    return pad


class BatchExecutor:
    """Depth-Q pipelined chunk server over a driver-supplied ops seam."""

    def __init__(self, ops, *, depth=None, report=None):
        self.ops = ops
        self.report = report
        self.depth = pipeline_depth() if depth is None else max(1, depth)
        # In-flight chunks: (ctx, chunk, outs, kind). JAX dispatch is
        # async, so with depth Q the host packs/exports chunks N+1..N+Q
        # while chunk N executes. Depth >= 2 keeps the device busy across
        # the host's pack gap even when pack time fluctuates; more mostly
        # adds host memory (Q packed batches).
        self._pending = deque()
        self.pack_ns = 0     # host wall: export + single-copy pack
        self.kernel_ns = 0   # host wall blocked inside the lattice serve
        self.shard_pad_rows = 0  # rows added padding batches to a
        #                          device multiple (sharded mode only)

    def _check_pressure(self) -> None:
        """Hard-watermark reaction at the pack seam: every queued packed
        chunk is host memory, so once the memory budget's hard watermark
        latches the executor stops queuing — depth drops to 1 and each
        pack resolves inline (batched -> stream-sequential, recorded
        once per executor).  Byte-identical: depth only changes when
        results are waited on, never what computes."""
        if self.depth <= 1 or not budget.hard_latched():
            return
        self.depth = 1
        if self.report is not None:
            self.report.record_degrade(
                "batched", "stream-sequential",
                RuntimeError("hard memory watermark"))
        self.flush()

    # -- feeding -----------------------------------------------------------
    def submit(self, ctx, idxs) -> None:
        """Export, pack, and dispatch one chunk; drain at depth Q."""
        self._check_pressure()
        ops = self.ops
        kind = ops.live_tier(ctx, None)
        if kind == "host":
            ops.surrender(ctx, idxs, exported=False)
            return
        t0 = time.monotonic_ns()
        with obs.span(ops.pack_span, cat="launch", units=len(idxs)):
            chunk = ops.export(ctx, idxs)
            packed = ops.pack(ctx, chunk) if chunk else None
            shard_m = getattr(ops, "shard_multiple", None)
            if packed is not None and shard_m is not None:
                m = shard_m(ctx, chunk)
                if m > 1:
                    packed, _ = pad_to_multiple(packed, m)
                    self._count_shard(len(chunk), packed, m)
        self.pack_ns += time.monotonic_ns() - t0
        if not chunk:
            return
        try:
            outs = ops.dispatch(ctx, kind, packed, chunk)
        except Exception as e:  # noqa: BLE001 — lattice edge
            # synchronous dispatch failure: resolve this chunk through
            # the lattice right now (retry/bisect/demote)
            if self.report is not None:
                self.report.record_failure(kind, e)
                self.report.retries += 1
            self._resolve(ctx, chunk, None, kind)
            return
        self._pending.append((ctx, chunk, outs, kind))
        if len(self._pending) >= self.depth:
            self._resolve(*self._pending.popleft())

    def in_flight(self) -> int:
        """Chunks dispatched and not yet resolved: what a launch going
        out now finds queued on the device ahead of it."""
        return len(self._pending)

    def flush(self) -> None:
        """Block on every in-flight chunk and install its results."""
        while self._pending:
            self._resolve(*self._pending.popleft())

    # -- resolution --------------------------------------------------------
    def _resolve(self, ctx, chunk, outs, kind) -> None:
        """Fully serve one exported chunk through the lattice, starting at
        `kind` with optionally already-dispatched device futures `outs`.

        Per tier: bounded retry, then batch bisection (a poisoned item is
        quarantined to the host while the rest of the batch stays on the
        device); a batch-independent failure (TierDead) demotes one tier,
        down to the host floor.
        """
        ops = self.ops
        submitted_kind = kind
        while True:
            kind = ops.live_tier(ctx, kind)
            if kind == "host":
                ops.surrender(ctx, chunk, exported=True)
                self._done(ctx, chunk)
                return

            def attempt(sub, _kind=kind):
                return ops.attempt(ctx, _kind, sub)

            # the pipelined futures are only valid for the tier they were
            # dispatched on; a demotion in between invalidates them
            cached = None
            if outs is not None and kind == submitted_kind:
                cached = (lambda _o=outs, _k=kind: ops.unpack(ctx, _k, _o))
            t0 = time.monotonic_ns()
            try:
                with obs.span(ops.span_name, tier=kind,
                              **ops.span_args(ctx, chunk,
                                              cached is not None)):
                    pairs, quarantined = rl.serve_with_bisect(
                        chunk, attempt, tier=kind, report=self.report,
                        cached=cached)
            except rl.TierDead as td:
                self.kernel_ns += time.monotonic_ns() - t0
                outs = None
                # sharded -> single-device is a lattice edge ABOVE tier
                # demotion: a sharded compile failure / device loss drops
                # to single-device dispatch and retries the SAME tier
                # (byte-identical; sharding never changes what computes)
                demote_shard = getattr(ops, "demote_shard", None)
                if demote_shard is not None and demote_shard(ctx, kind,
                                                             td.cause):
                    continue
                kind = ops.demote(ctx, kind, td.cause)
                continue
            self.kernel_ns += time.monotonic_ns() - t0
            with obs.span(ops.install_span, cat="launch",
                          units=len(chunk)):
                for sub, results in pairs:
                    ops.install(ctx, kind, sub, results)
                for item, exc in quarantined:
                    ops.quarantine(ctx, item, exc)
            self._widen(ctx, kind, attempt)
            self._done(ctx, chunk)
            return

    def _widen(self, ctx, kind, attempt) -> None:
        """Drain the ops' verify-and-widen ladder (banded DP): re-serve
        the chunk's band-hit items with widened params until the ladder
        is empty.  Re-dispatches reuse the packed batch views (install
        advanced each item's band state; attempt reads it), so a retry
        costs zero re-packing.  The ladder is bounded
        (RACON_TPU_BAND_MAX_WIDENINGS doublings, then the flat kernel),
        so this loop terminates."""
        ops = self.ops
        widen = getattr(ops, "widen", None)
        if widen is None:
            return
        while True:
            retry = widen(ctx, kind)
            if not retry:
                return
            t0 = time.monotonic_ns()
            try:
                with obs.span(ops.span_name, tier=kind,
                              band_retry=len(retry)):
                    pairs, quarantined = rl.serve_with_bisect(
                        retry, attempt, tier=kind, report=self.report,
                        cached=None)
            except rl.TierDead as td:
                self.kernel_ns += time.monotonic_ns() - t0
                # the tier died mid-ladder: surrender the pending
                # band retries to the host floor (the oracle) rather
                # than re-serving the already-installed chunk
                ops.demote(ctx, kind, td.cause)
                ops.surrender(ctx, retry, exported=True)
                return
            self.kernel_ns += time.monotonic_ns() - t0
            for sub, results in pairs:
                ops.install(ctx, kind, sub, results)
            for item, exc in quarantined:
                ops.quarantine(ctx, item, exc)

    def _done(self, ctx, chunk) -> None:
        done = getattr(self.ops, "done", None)
        if done is not None:
            done(ctx, chunk)

    def _count_shard(self, n_real, packed, m) -> None:
        rows = int(np.asarray(packed[0]).shape[0])
        self.shard_pad_rows += count_shard_rows(n_real, rows, m)

    # -- accounting --------------------------------------------------------
    def stamp_walls(self, report) -> None:
        """Fold the pack/kernel wall split into a PhaseReport's extras
        (accumulating: the alignment phase may run several engines)."""
        if report is None:
            return
        report.extra["pack_wall_s"] = round(
            report.extra.get("pack_wall_s", 0.0) + self.pack_ns / 1e9, 6)
        report.extra["kernel_wall_s"] = round(
            report.extra.get("kernel_wall_s", 0.0) + self.kernel_ns / 1e9, 6)
        if self.shard_pad_rows:
            report.extra["shard_pad_rows"] = (
                report.extra.get("shard_pad_rows", 0) + self.shard_pad_rows)
