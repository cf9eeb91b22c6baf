"""The unified degradation lattice.

Ordered tiers, per-tier bounded retry, a per-device-call watchdog, and
batch bisection — the shared machinery both drivers run their device
calls through.  The reference implements the same posture ad hoc: failed
CUDA batches are re-polished on the host
(/root/reference/src/cuda/cudapolisher.cpp:354-378); here every edge is
explicit and deterministically testable via `resilience.faults`.

Tier orders (best first; a tier's failure demotes to the next):

    consensus:  ls -> xla -> host
    alignment:  hirschberg -> host
                (RACON_TPU_DEVICE_ALIGNER says whether phase 1 enters at
                the device engine or at the host Myers aligner)

Failure taxonomy the drivers map onto this module:

* transient batch failure  -> bounded retry at the same tier
  (`RACON_TPU_TIER_RETRIES`, default 1 extra attempt)
* hung device call         -> watchdog timeout surfaces it as an error
  (`RACON_TPU_DEVICE_TIMEOUT` seconds; 0/unset = disabled)
* wedged tier              -> `RACON_TPU_WEDGE_LIMIT` consecutive
  watchdog timeouts classify the tier as wedged (`TierWedged`, a
  TierDead subtype): demote immediately instead of burning one full
  deadline per retry (see resilience/watchdog.py)
* window-correlated failure-> batch bisection: the failing batch is
  split, halves are probed, and the poisoned window is quarantined to
  the host while the rest of the batch stays on the device
* tier-wide failure        -> `TierDead` (both halves of a bisection
  fail); the caller demotes the whole geometry one tier
"""

from __future__ import annotations

import time
from typing import Callable, List, Optional, Sequence, Tuple

from .. import config, obs
# the watchdog moved to its own module (resilience/watchdog.py); the
# names stay importable from here — every caller and test uses the
# lattice as the façade
from .watchdog import (WatchdogTimeout, call_with_watchdog,  # noqa: F401
                       device_timeout, tracker)

#: Consensus kernel tiers, best first.  "host" is the floor: windows are
#: re-polished one-by-one by the native SPOA-equivalent engine.
CONSENSUS_TIERS = ("ls", "xla", "host")

#: Alignment tiers, best first: the one device engine
#: (ops/align_pallas.py) degrades straight to the host Myers aligner.
ALIGN_TIERS = ("hirschberg", "host")


class TierDead(Exception):
    """The current tier fails batch-independently; demote the geometry."""

    def __init__(self, cause: BaseException):
        super().__init__(str(cause))
        self.cause = cause
        # a dead tier is a crash-adjacent event: dump the flight ring at
        # raise time (covers TierWedged too) so the post-mortem exists
        # even if a caller turns this into a process exit
        from ..obs import flight
        flight.record("lattice.tier_dead", kind="event",
                      error=f"{type(cause).__name__}: {cause}",
                      wedged=isinstance(self, TierWedged))
        flight.dump("tier_dead",
                    error=f"{type(cause).__name__}: {cause}")


class TierWedged(TierDead):
    """The tier kept timing out (RACON_TPU_WEDGE_LIMIT consecutive
    watchdog expiries): a device call that hangs instead of raising.
    A TierDead subtype — callers demote exactly as for any
    dead tier — but distinguishable in reports, and raised *instead of
    retrying* so a wedged tier stops costing one full watchdog deadline
    per attempt."""


def tier_retries() -> int:
    """Extra attempts per tier before bisecting/demoting (default 1)."""
    return max(0, config.get_int("RACON_TPU_TIER_RETRIES"))


def serve_with_bisect(items: Sequence, attempt: Callable,
                      *, tier: str, report=None,
                      retries: Optional[int] = None,
                      cached: Optional[Callable] = None
                      ) -> Tuple[List[Tuple[list, object]],
                                 List[Tuple[object, BaseException]]]:
    """Serve one batch at a fixed tier with bounded retry and bisection.

    items    — one opaque work unit per real window/job in the batch.
    attempt  — attempt(sub_items) -> tier result for that sub-batch
               (pack + submit + block); called under the watchdog.
    cached   — optional zero-arg callable returning the full batch's
               already-dispatched result (the async-pipelined outs);
               tried as attempt #0 so the happy path stays pipelined.

    Returns (pairs, quarantined):
      pairs       — [(sub_items, result)] covering every served unit
      quarantined — [(item, exception)] poisoned units for the host

    Raises TierDead when failures are batch-independent (both halves of
    a bisection fail), i.e. the tier itself is broken for this geometry
    and the caller should demote.  Two poisoned windows landing in
    opposite halves are indistinguishable from a dead tier and demote
    conservatively — correctness is preserved either way (the next tier,
    ultimately the host, serves them).
    """
    n_retries = tier_retries() if retries is None else retries
    if tracker().is_wedged(tier):
        # the tier wedged earlier in this run — do not feed it at all
        raise TierWedged(WatchdogTimeout(
            f"tier {tier!r} is wedged ({tracker().streak(tier)} "
            f"consecutive watchdog timeouts)", tier=tier))

    def timed(fn):
        t0 = time.perf_counter()
        try:
            return call_with_watchdog(fn, tier=tier)
        finally:
            if report is not None:
                report.add_wall(tier, time.perf_counter() - t0)

    def attempts(sub, use_cached):
        last = None
        for a in range(n_retries + 1):
            try:
                if a == 0 and use_cached:
                    return timed(cached)
                return timed(lambda: attempt(sub))
            except Exception as e:  # noqa: BLE001 — lattice boundary
                last = e
                if report is not None:
                    report.record_failure(tier, e)
                    if a < n_retries:
                        report.retries += 1
                if a < n_retries:
                    obs.event("lattice.retry", tier=tier, attempt=a + 1,
                              error=type(e).__name__)
                    obs.count(f"retries.{tier}")
                if (isinstance(e, WatchdogTimeout)
                        and tracker().is_wedged(tier)):
                    # repeated expiry = wedged jit call; each further
                    # attempt would burn a full deadline, so classify
                    # and demote instead of retrying/bisecting
                    raise TierWedged(e) from e
        raise last

    def serve(sub, use_cached):
        try:
            return [(list(sub), attempts(sub, use_cached))], []
        except TierDead:
            raise               # wedge classification — not bisectable
        except Exception as e:  # noqa: BLE001 — lattice boundary
            if len(sub) <= 1:
                return [], [(sub[0], e)]
            if report is not None:
                report.bisections += 1
            obs.event("lattice.bisect", tier=tier, size=len(sub),
                      error=type(e).__name__)
            obs.count(f"bisections.{tier}")
            mid = len(sub) // 2
            probes = []
            for half in (sub[:mid], sub[mid:]):
                try:
                    probes.append((half, timed(lambda h=half: attempt(h))))
                except Exception as he:  # noqa: BLE001
                    if report is not None:
                        report.record_failure(tier, he)
                    probes.append((half, he))
            if all(isinstance(r, BaseException) for _, r in probes):
                raise TierDead(e) from e
            pairs, quarantined = [], []
            for half, r in probes:
                if isinstance(r, BaseException):
                    p, q = serve(half, False)  # TierDead propagates
                    pairs.extend(p)
                    quarantined.extend(q)
                else:
                    pairs.append((list(half), r))
            return pairs, quarantined

    return serve(list(items), cached is not None)


def next_consensus_tier(kind: str) -> str:
    """The tier below `kind` in the consensus lattice ('host' floor)."""
    i = CONSENSUS_TIERS.index(kind)
    return CONSENSUS_TIERS[min(i + 1, len(CONSENSUS_TIERS) - 1)]


def record_band_fallback(report, tier: str, cause=None) -> None:
    """The `banded -> flat` lattice edge, recorded once per job.

    Orthogonal to tier demotion (like the sharded -> single-device
    edge): the job stays at `tier`, only the DP band is dropped — the
    flat kernel is the byte-identity oracle, so the floor of the
    verify-and-widen ladder can never change output.  Shows up in the
    report's degradation list as `<tier>+banded -> <tier>` and in the
    metrics as `band.fallbacks`, so a band that keeps getting hit is
    visible in any trace or run report."""
    exc = cause if isinstance(cause, BaseException) else None
    if report is not None:
        report.record_degrade(f"{tier}+banded", tier, exc)
    obs.count("band.fallbacks")


def record_shard_demotion(report, tier: str, cause) -> None:
    """The `sharded -> single-device` lattice edge, recorded once.

    Orthogonal to tier demotion: the kernel stays at `tier`, only the
    mesh dispatch is dropped (sharding changes where rows compute, never
    what — output stays byte-identical).  Shows up in the report's
    degradation list as `<tier>+sharded -> <tier>` and in the metrics as
    `shard.demotions`, so a silent fallback to one device is visible in
    any trace or run report."""
    exc = cause if isinstance(cause, BaseException) else None
    if report is not None:
        report.record_degrade(f"{tier}+sharded", tier, exc)
    obs.count("shard.demotions")
    import sys
    print(f"[racon-tpu] sharded dispatch failed at tier {tier!r} "
          f"({cause}); demoting to single-device dispatch",
          file=sys.stderr)
