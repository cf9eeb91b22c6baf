"""Hung-step watchdog: deadline-scoped timing around device dispatch.

A device call can fail by silence instead of an exception: a `jit` call
that never returns (a hung device or runtime).  This module owns the two
halves of turning that silence into a routable fault:

* `call_with_watchdog` runs one device call on a daemon thread with a
  deadline (`RACON_TPU_DEVICE_TIMEOUT`); expiry raises
  `WatchdogTimeout`.  A truly hung device op cannot be cancelled from
  Python — the abandoned call keeps its thread, and the caller's job is
  to stop feeding the dead tier.
* `WedgeTracker` classifies *repeated* timeouts: one timeout is a
  transient (the lattice retries at the same tier), but
  `RACON_TPU_WEDGE_LIMIT` consecutive timeouts on one tier mean the tier
  is wedged, and the lattice converts the next failure into
  `TierWedged` (a `TierDead` subtype) so the geometry demotes instead of
  burning a full watchdog deadline per retry forever.

The tracker is process-global per-run state exactly like the fault
plan's counters: `reset()` is called by the polisher constructors so
consecutive runs classify identically.
"""

from __future__ import annotations

import threading
from typing import Callable, Dict, Optional

from .. import config, obs
from . import faults


class WatchdogTimeout(Exception):
    """A device call exceeded the RACON_TPU_DEVICE_TIMEOUT watchdog."""

    def __init__(self, message: str, tier: Optional[str] = None,
                 elapsed: float = 0.0):
        super().__init__(message)
        self.tier = tier
        self.elapsed = elapsed


def device_timeout() -> float:
    """Per-device-call watchdog in seconds; 0 (default) disables it."""
    try:
        return config.get_float("RACON_TPU_DEVICE_TIMEOUT")
    except ValueError:
        return 0.0


def wedge_limit() -> int:
    """Consecutive same-tier watchdog timeouts before the tier is
    declared wedged (default 3; 0 disables wedge classification so every
    timeout stays an ordinary retryable failure)."""
    try:
        return max(0, config.get_int("RACON_TPU_WEDGE_LIMIT"))
    except ValueError:
        return 3


class WedgeTracker:
    """Consecutive-timeout counter per tier.

    A success at a tier clears its streak — a tier that times out, then
    serves, is slow-but-alive, not wedged.  The counter is keyed by tier
    name only (not geometry): a hung device hangs every geometry, and
    demoting them all at once is the behavior that stops the bleeding.
    """

    def __init__(self):
        self._streak: Dict[str, int] = {}

    def record_timeout(self, tier: str) -> int:
        n = self._streak.get(tier, 0) + 1
        self._streak[tier] = n
        return n

    def record_success(self, tier: str) -> None:
        self._streak.pop(tier, None)

    def streak(self, tier: str) -> int:
        return self._streak.get(tier, 0)

    def is_wedged(self, tier: str) -> bool:
        limit = wedge_limit()
        return limit > 0 and self._streak.get(tier, 0) >= limit

    def reset(self) -> None:
        self._streak.clear()


_TRACKER = WedgeTracker()


def tracker() -> WedgeTracker:
    """The process-wide per-run wedge tracker."""
    return _TRACKER


def reset() -> None:
    """Clear wedge streaks; called by the polisher constructors next to
    `faults.reset()` so consecutive runs classify identically."""
    _TRACKER.reset()


def call_with_watchdog(fn: Callable, timeout: Optional[float] = None,
                       tier: Optional[str] = None):
    """Run fn() under the watchdog.  With no timeout configured this is a
    direct call (no thread).  On expiry raises WatchdogTimeout — and,
    when `tier` is given, feeds the wedge tracker so the lattice can
    distinguish a transient stall from a wedged tier."""
    faults.check("watchdog.call")
    t = device_timeout() if timeout is None else timeout
    if not t or t <= 0:
        return fn()
    box = {}

    def runner():
        try:
            box["result"] = fn()
        except BaseException as e:  # noqa: BLE001 — relayed to caller
            box["error"] = e

    th = threading.Thread(target=runner, daemon=True,
                          name="racon-tpu-watchdog-call")
    th.start()
    th.join(t)
    if th.is_alive():
        if tier is not None:
            _TRACKER.record_timeout(tier)
        obs.event("watchdog.timeout", tier=tier, deadline_s=t,
                  streak=_TRACKER.streak(tier) if tier is not None else 0)
        obs.count(f"watchdog_timeouts.{tier or 'unknown'}")
        raise WatchdogTimeout(
            f"device call exceeded the {t:.3g}s watchdog", tier=tier,
            elapsed=t)
    if "error" in box:
        raise box["error"]
    if tier is not None:
        _TRACKER.record_success(tier)
    return box["result"]
