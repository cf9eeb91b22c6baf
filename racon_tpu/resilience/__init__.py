"""Resilience layer: deterministic fault injection, the unified
degradation lattice, and the machine-readable run report.

The reference racon degrades gracefully when the accelerator rejects work
— failed CUDA batches are re-polished on the host
(/root/reference/src/cuda/cudapolisher.cpp:354-378). This package makes
that posture a tested subsystem instead of scattered try/except blocks:

* `faults`  — named injection points at every device/host seam, driven by
  the `RACON_TPU_FAULT` env spec, so any lattice edge can be triggered
  deterministically on the CPU backend in CI.
* `lattice` — the ordered degradation tiers (ls -> xla -> host for
  consensus; hirschberg -> host for alignment) plus the shared
  retry / watchdog / batch-bisection machinery the drivers run through.
* `watchdog`— the deadline-scoped timer around device dispatch and the
  wedge tracker that classifies repeated timeouts as a wedged tier
  (`TierWedged`) so a hung jit call demotes instead of hanging the run.
* `journal` — the crash-safe, append-only window-result journal behind
  `--journal` / `--resume-journal` / `RACON_TPU_JOURNAL`: a SIGKILLed
  run resumes and reproduces byte-identical output.
* `report`  — per-phase serving/fallback accounting surfaced through
  `Polisher.polish()`, the `--report` CLI flag, `RACON_TPU_REPORT`, and
  `bench.py`.
"""

from . import faults, journal, lattice, report, watchdog  # noqa: F401
from .faults import InjectedFault, MosaicError, check, parse_spec, reset
from .journal import CigarTap, Journal, JournalError, input_fingerprint
from .lattice import (ALIGN_TIERS, CONSENSUS_TIERS, TierDead, TierWedged,
                      WatchdogTimeout, call_with_watchdog, device_timeout,
                      serve_with_bisect, tier_retries)
from .report import PhaseReport, RunReport
from .watchdog import WedgeTracker, wedge_limit

__all__ = [
    "faults", "journal", "lattice", "report", "watchdog",
    "InjectedFault", "MosaicError", "check", "parse_spec", "reset",
    "CigarTap", "Journal", "JournalError", "input_fingerprint",
    "ALIGN_TIERS", "CONSENSUS_TIERS", "TierDead", "TierWedged",
    "WatchdogTimeout", "call_with_watchdog", "device_timeout",
    "serve_with_bisect", "tier_retries",
    "PhaseReport", "RunReport",
    "WedgeTracker", "wedge_limit",
]
