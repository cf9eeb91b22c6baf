"""Deterministic fault injection at the device/host seams.

Every place the drivers hand work to (or take results back from) an
accelerator kernel or the native host engine is a named *injection
point*.  The `RACON_TPU_FAULT` environment variable arms one or more of
them:

    RACON_TPU_FAULT="poa.run.ls:raise=MosaicError"
    RACON_TPU_FAULT="poa.run.xla:window=5"
    RACON_TPU_FAULT="align.run:batch=1:count=1,poa.run.xla:hang=2"

Spec grammar (comma-separated specs; colon-separated fields):

    <point>[:batch=N][:window=I][:count=N][:hang=SECONDS][:raise=NAME]
           [:kill=1]

* `point`   — one of KNOWN_POINTS below.  The first field.
* `batch=N` — fire only on the Nth invocation of the point (0-based,
  counted per point per run).  Retries re-invoke the point, so a
  `batch=0:count=1` fault fails the first attempt and lets the retry
  succeed — the deterministic transient fault.
* `window=I`— fire only when window/job index I is in the submitted
  batch (run points pass the batch's indices).  Batch bisection narrows
  such a fault down to the poisoned window, which is quarantined to the
  host while the rest of the batch stays on the device.
* `count=N` — fire at most N times (default: unlimited — the point is
  permanently broken, which is how a whole tier is killed).
* `hang=S`  — sleep S seconds instead of raising (exercises the
  per-device-call watchdog; combine with `RACON_TPU_DEVICE_TIMEOUT`).
* `raise=NAME` — exception class to raise (default `MosaicError`, the
  synthetic stand-in for a Mosaic compile/runtime failure).
* `kill=1`  — SIGKILL the whole process instead of raising: the
  deterministic mid-run crash (no handlers, no flushing — exactly what
  a preemption does).  Combine with `batch=N` on `journal.append` to
  die after exactly N journaled results; the crash-resume tests are
  built on it.

Specs are validated eagerly: a malformed spec raises `ValueError` with a
single-line message (the CLI surfaces it as exit 1, reference-style).
Counters are per-run — `reset()` is called by the polisher constructors
so consecutive runs in one process see identical firing schedules.
"""

from __future__ import annotations

import os
import signal
import threading
import time
from dataclasses import dataclass, field
from typing import Optional, Sequence

from .. import config

ENV = "RACON_TPU_FAULT"

#: Every injection point the drivers expose.  Compile points fire when a
#: kernel for that tier is (re)built; run points fire on every batch
#: submitted to that tier; the host seams fire per native call / window
#: export.
KNOWN_POINTS = frozenset({
    "align.compile",     # phase-1 device engine kernel build
    "align.run",         # phase-1 device engine, per cohort
    "align.install",     # phase-1 CIGAR install, per job (after the
                         # lattice: an escape mid-install must not erase
                         # the device-served count — see align_driver)
    "band.hit",          # banded DP verify (ops/band.py): an armed
                         # fault (raise=MosaicError/InjectedFault)
                         # classifies every banded job of the attempt as
                         # a band hit instead of raising — the
                         # deterministic widening-exhaustion drill that
                         # drives the ladder to its flat floor
    "poa.compile.ls",    # lockstep consensus kernel build
    "poa.compile.xla",   # XLA-twin consensus kernel build
    "poa.run.ls",        # lockstep consensus, per submitted batch
    "poa.run.xla",       # XLA-twin consensus, per submitted batch
    "native.call",       # host (native) engine calls — the lattice floor
    "window.export",     # per-window export from the native pipeline
    "journal.append",    # durable-journal record write (resilience/journal)
    "journal.replay",    # journal replay on --resume-journal
    "watchdog.call",     # device-dispatch entry under the watchdog
    "sanitize.nan",      # sanitizer: poison the checker's COPY of one
                         # consensus buffer (polish output untouched)
    "sanitize.stats",    # sanitizer: one real cross-thread stats-dict
                         # mutation through the guard
    # distributed seams (racon_tpu/distrib): the coordinator checks
    # worker.spawn before launching each fleet process; a worker checks
    # worker.heartbeat before every lease renewal and worker.result
    # before delivering a finished chunk.  kill=1 on the worker points is
    # a real SIGKILL of that worker mid-chunk — the chaos suite's
    # deterministic worker loss.  Scope the env to one worker with
    # RACON_TPU_DISTRIB_FAULT_WORKER.
    "worker.spawn",      # coordinator, per worker process launched
    "worker.heartbeat",  # worker, before each heartbeat send
    "worker.result",     # worker, before delivering a chunk result
    # elastic control plane seams (racon_tpu/fleet): the pool checks
    # pool.scale_up / pool.scale_down before growing / draining the
    # worker fleet, the plane checks pool.steal before handing a chunk
    # of job A to a worker whose affinity is job B, and every lease
    # reclaim (worker death or drain) checks lease.reclaim before
    # releasing the dead holder's leases.  A raise on these points is
    # absorbed as a modeled control-plane failure (the transition is
    # skipped or proceeds degraded, and counted); kill=1 is the
    # deterministic controller crash mid-transition — the recover()
    # interplay tests are built on pool.scale_up:kill=1.
    "pool.scale_up",     # elastic pool, before spawning a growth worker
    "pool.scale_down",   # elastic pool, before draining a worker
    "pool.steal",        # fleet plane, before a cross-job work steal
    "lease.reclaim",     # lease layer, before reclaiming a dead
                         # holder's leases
    # memory-budget seams (racon_tpu/resilience/budget.py): the budget
    # checks mem.pressure on every synchronous poll — a raise there is
    # absorbed as a forced hard-watermark breach (the deterministic
    # memory-pressure drill: backpressure, spill, and the pressure
    # lattice edges all fire without needing real RSS growth).
    # mem.spill fires before a chunk working set is parked to the spill
    # file — a raise aborts that park and the working set stays in
    # memory (absorbed + counted).  mem.oom fires in the distrib worker
    # before polishing a fetched chunk; kill=1 there is a real
    # OOM-style SIGKILL of that worker mid-chunk (scope with
    # RACON_TPU_DISTRIB_FAULT_WORKER) — the journal/lease machinery
    # resumes the chunk byte-identically.
    "mem.pressure",      # budget poll: forced hard-watermark breach
    "mem.spill",         # before parking a working set to the spill file
    "mem.oom",           # distrib worker, before polishing a chunk
    # SLO seam (racon_tpu/obs/slo.py): the burn-rate engine checks
    # slo.burn on every evaluation — a raise is absorbed as a forced
    # burn (both windows report at least the alert threshold for one
    # fast window, counted as burn_faults).  This is the deterministic
    # injected-slowdown drill: the alert -> autoscale path fires
    # without a real latency regression.
    "slo.burn",          # SLO engine, forced burn-rate breach
})


class InjectedFault(Exception):
    """Base class for synthetic injected failures."""


class MosaicError(InjectedFault):
    """Synthetic stand-in for a Mosaic compile/runtime failure."""


#: Exception classes a spec may name.  Builtins are included so the
#: lattice's broad-Exception handling is exercised with realistic types.
EXCEPTIONS = {
    "MosaicError": MosaicError,
    "InjectedFault": InjectedFault,
    "RuntimeError": RuntimeError,
    "ValueError": ValueError,
    "TimeoutError": TimeoutError,
    "OSError": OSError,
}

_UNLIMITED = -1


@dataclass
class FaultSpec:
    point: str
    batch: Optional[int] = None
    window: Optional[int] = None
    count: int = _UNLIMITED
    hang: float = 0.0
    kill: bool = False
    raise_name: str = "MosaicError"
    fired: int = field(default=0, compare=False)

    def spent(self) -> bool:
        return self.count != _UNLIMITED and self.fired >= self.count

    def describe(self) -> str:
        sel = []
        if self.batch is not None:
            sel.append(f"batch={self.batch}")
        if self.window is not None:
            sel.append(f"window={self.window}")
        return ":".join([self.point, *sel]) or self.point


def parse_spec(text: str) -> list:
    """Parse a RACON_TPU_FAULT value; raises ValueError on any malformed
    field (unknown point, unknown key, non-integer selector, unknown
    exception name) with a single-line message."""
    specs = []
    for part in filter(None, (p.strip() for p in text.split(","))):
        fields = part.split(":")
        point = fields[0]
        if point not in KNOWN_POINTS:
            raise ValueError(
                f"{ENV}: unknown injection point {point!r} "
                f"(valid: {', '.join(sorted(KNOWN_POINTS))})")
        spec = FaultSpec(point)
        for f in fields[1:]:
            key, sep, val = f.partition("=")
            if not sep:
                raise ValueError(f"{ENV}: expected key=value, got {f!r}")
            try:
                if key == "batch":
                    spec.batch = int(val)
                elif key == "window":
                    spec.window = int(val)
                elif key == "count":
                    spec.count = int(val)
                elif key == "hang":
                    spec.hang = float(val)
                elif key == "kill":
                    spec.kill = int(val) != 0
                elif key == "raise":
                    if val not in EXCEPTIONS:
                        raise ValueError(
                            f"{ENV}: unknown exception {val!r} "
                            f"(valid: {', '.join(sorted(EXCEPTIONS))})")
                    spec.raise_name = val
                else:
                    raise ValueError(f"{ENV}: unknown key {key!r} "
                                     f"(valid: batch, window, count, hang, "
                                     f"kill, raise)")
            except ValueError as e:
                if str(e).startswith(ENV):
                    raise
                raise ValueError(
                    f"{ENV}: bad value {val!r} for {key!r}") from None
        specs.append(spec)
    return specs


class FaultPlan:
    """Parsed specs plus per-point invocation counters for one run.

    The plan is process-global shared state: checks come from the main
    thread, serve/distrib/fleet connection handlers and the fleet
    monitor, so invocation counting and spec selection happen under
    ``_LOCK`` — a racing pair of checks must burn two distinct
    invocation indices, or ``batch=N`` selectors stop being
    deterministic.  The *action* (sleep/raise/SIGKILL) runs outside the
    lock so a ``hang=S`` spec stalls only its own thread.
    """

    def __init__(self, specs):
        self.specs = specs
        self.calls = {}

    def check(self, point: str,
              windows: Optional[Sequence[int]] = None) -> None:
        with _LOCK:
            n = self.calls.get(point, 0)
            self.calls[point] = n + 1
            fire = None
            for spec in self.specs:
                if spec.point != point or spec.spent():
                    continue
                if spec.batch is not None and spec.batch != n:
                    continue
                if spec.window is not None:
                    if windows is None or spec.window not in windows:
                        continue
                spec.fired += 1
                fire = spec
                break
        if fire is None:
            return
        from ..obs import flight
        flight.record("fault.fired", point=point, invocation=n,
                      spec=fire.describe())
        if fire.kill:
            # the flight dump is the ONLY artifact this process
            # leaves: it must land before the uncatchable signal
            flight.dump("fault_kill", point=point, invocation=n)
            # the deterministic preemption: no cleanup, no flush —
            # the process is gone mid-append, exactly like a real
            # SIGKILL/OOM/eviction
            os.kill(os.getpid(), signal.SIGKILL)
        if fire.hang:
            time.sleep(fire.hang)
            return
        raise EXCEPTIONS[fire.raise_name](
            f"injected fault at {fire.describe()} (invocation {n})")


# Guards the plan cache and every FaultPlan counter (see
# FaultPlan.check).  Nothing is called while holding it, so it nests
# safely under any control-plane lock (scheduler/coordinator/plane _cv).
_LOCK = threading.Lock()

# cache keyed on the raw env string so monkeypatched environments take
# effect immediately; counters persist while the string is unchanged
# (reset() re-arms them at the start of each polisher run)
_cached_env: Optional[str] = None
_cached_plan: Optional[FaultPlan] = None


def _plan() -> Optional[FaultPlan]:
    global _cached_env, _cached_plan
    env = config.get_str(ENV)
    with _LOCK:
        if env != _cached_env:
            _cached_env = env
            _cached_plan = FaultPlan(parse_spec(env)) if env else None
        return _cached_plan


def active_spec() -> str:
    """The armed spec string ('' when fault injection is off)."""
    return config.get_str(ENV)


def check(point: str, windows: Optional[Sequence[int]] = None) -> None:
    """Fire any armed fault for `point`.  `windows`: the window/job
    indices in the batch being submitted (run points only).  No-op when
    RACON_TPU_FAULT is unset; raises ValueError on a malformed spec."""
    assert point in KNOWN_POINTS, point
    plan = _plan()
    if plan is not None:
        plan.check(point, windows)


def reset() -> None:
    """Re-arm the plan (fresh counters).  Called by the polisher
    constructors so consecutive runs fire deterministically."""
    global _cached_env, _cached_plan
    with _LOCK:
        _cached_env = None
        _cached_plan = None


def validate_env() -> None:
    """Eagerly parse RACON_TPU_FAULT; raises ValueError when malformed.
    The CLI calls this up front so a bad spec is a single-line error."""
    env = config.get_str(ENV)
    if env:
        parse_spec(env)
