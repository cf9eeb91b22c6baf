"""Machine-readable run report: who served what, and why it fell back.

Each polishing phase produces a `PhaseReport` (per-tier served counts,
fallback causes, retries, bisections, quarantined window indices, wall
time per tier); the polisher aggregates them into a `RunReport` surfaced
through `TpuPolisher.report`, the CLI `--report PATH` flag, the
`RACON_TPU_REPORT` env var (written at the end of `polish()`), and the
one-line bench JSON.

Invariant (regression-tested): a phase's per-tier served counts sum to
its total job/window count, clean or fault-injected.
"""

from __future__ import annotations

import json
import sys
import time
from typing import List, Optional, Tuple

from .. import config, obs

ENV_REPORT = "RACON_TPU_REPORT"

#: Cap per-tier recorded cause strings / quarantined indices so a
#: pathological run cannot balloon the report.
_MAX_CAUSES = 20
_MAX_QUARANTINED = 1000


class PhaseReport:  # concurrency: single-writer accumulator; the coordinator serializes its cross-thread instance under Coordinator._cv
    """Serving/fallback accounting for one phase (alignment/consensus)."""

    def __init__(self, phase: str, tiers: Tuple[str, ...]):
        self.phase = phase
        self.tiers = tuple(tiers)
        self.total = 0
        self.served = {t: 0 for t in self.tiers}
        self.retries = 0
        self.bisections = 0
        self.quarantined: List[int] = []
        self.degradations: List[dict] = []
        self.causes = {}      # tier -> [error strings]
        self.wall_s = {}      # tier -> accumulated seconds
        self.extra = {}       # phase-specific counters (layers_dropped, …)

    # -- recording --------------------------------------------------------
    # The obs hooks below feed the metrics registry from the same calls
    # that mutate the report, so the served-sum invariant between the
    # two (obs.served_sum_check) holds by construction unless some path
    # serves work while bypassing the report — which is the drift the
    # cross-check exists to expose.
    def record_served(self, tier: str, n: int = 1) -> None:
        self.served[tier] = self.served.get(tier, 0) + n
        obs.count(f"served.{self.phase}.{tier}", n)

    def record_failure(self, tier: str, exc: BaseException) -> None:
        lst = self.causes.setdefault(tier, [])
        if len(lst) < _MAX_CAUSES:
            lst.append(f"{type(exc).__name__}: {exc}")
        obs.count(f"failures.{self.phase}.{tier}")

    def record_degrade(self, frm: str, to: str,
                       exc: Optional[BaseException] = None) -> None:
        self.degradations.append({
            "from": frm, "to": to,
            "error": f"{type(exc).__name__}: {exc}" if exc else None})
        obs.event("lattice.demote", phase=self.phase, frm=frm, to=to,
                  error=type(exc).__name__ if exc else None)
        obs.count(f"demotions.{self.phase}.{frm}")

    def record_quarantine(self, index: int,
                          exc: Optional[BaseException] = None) -> None:
        if len(self.quarantined) < _MAX_QUARANTINED:
            self.quarantined.append(int(index))
        if exc is not None:
            self.record_failure("quarantine", exc)
        obs.event("lattice.quarantine", phase=self.phase, index=int(index))
        obs.count(f"quarantined.{self.phase}")

    def add_wall(self, tier: str, seconds: float) -> None:
        self.wall_s[tier] = self.wall_s.get(tier, 0.0) + seconds

    def merge(self, other: "PhaseReport") -> None:
        """Fold another report for the same phase into this one (the
        pipelined polisher runs one report per target chunk and merges).

        Pure accounting — the obs counters were already fed at record
        time on `other`, so merging does NOT re-feed them; the served-sum
        cross-check stays valid against the merged counts."""
        self.total += other.total
        for t, c in other.served.items():
            self.served[t] = self.served.get(t, 0) + c
        self.retries += other.retries
        self.bisections += other.bisections
        room = _MAX_QUARANTINED - len(self.quarantined)
        if room > 0:
            self.quarantined.extend(other.quarantined[:room])
        self.degradations.extend(other.degradations)
        for t, msgs in other.causes.items():
            lst = self.causes.setdefault(t, [])
            lst.extend(msgs[:max(0, _MAX_CAUSES - len(lst))])
        for t, s in other.wall_s.items():
            self.wall_s[t] = self.wall_s.get(t, 0.0) + s
        for k, v in other.extra.items():
            cur = self.extra.get(k)
            if isinstance(cur, (int, float)) and isinstance(v, (int, float)):
                self.extra[k] = round(cur + v, 6)
            else:
                self.extra[k] = v

    # -- views ------------------------------------------------------------
    def served_total(self) -> int:
        return sum(self.served.values())

    def as_dict(self) -> dict:
        return {
            "phase": self.phase,
            "total": self.total,
            "served": dict(self.served),
            "retries": self.retries,
            "bisections": self.bisections,
            "quarantined": list(self.quarantined),
            "degradations": list(self.degradations),
            "causes": {k: list(v) for k, v in self.causes.items()},
            "wall_s": {k: round(v, 4) for k, v in self.wall_s.items()},
            **({"extra": dict(self.extra)} if self.extra else {}),
        }


class RunReport:
    """Aggregated per-run report (all phases + the armed fault spec)."""

    def __init__(self):
        self.phases = {}
        # monotonic: a wall-clock (time.time) duration goes negative or
        # balloons across an NTP step; the wall-clock lint rule
        # (analysis/rules/clock.py) enforces this repo-wide
        self._t0 = time.monotonic()
        self.wall_s = None
        # flight-recorder dumps swept from the workdir after the run
        # (obs/flight.py `scan` docs) — each entry is one post-mortem
        self.flight: List[dict] = []
        # per-job latency-ledger fragment (obs/ledger.py): the serve
        # session stamps the compute side's stage_s decomposition here
        # so the persisted report carries it; None outside serving
        self.ledger: Optional[dict] = None
        # the device the 'tpu' backend ran on (racon_tpu/device.py:
        # platform, device_kind, count) and the persistent-cache traffic
        # at construction; None on the host path, which never
        # initialises a backend
        self.device: Optional[dict] = None
        self._cache0: Optional[dict] = None

    def stamp_device(self, ident: dict) -> None:
        from .. import device

        self.device = dict(ident)
        self._cache0 = device.cache_traffic()

    def attach(self, phase_report: Optional[PhaseReport]) -> None:
        if phase_report is not None:
            self.phases[phase_report.phase] = phase_report

    def finalize(self) -> "RunReport":
        self.wall_s = time.monotonic() - self._t0
        return self

    def as_dict(self) -> dict:
        from ..analysis import sanitize
        from .faults import active_spec

        return {
            "phases": {k: v.as_dict() for k, v in self.phases.items()},
            "device": self.device,
            **self._cache_dict(),
            "fault_spec": active_spec(),
            # stale-knob check: RACON_TPU_* vars set in the environment
            # but unknown to the config registry — a typo'd knob surfaces
            # here instead of being silently ignored
            "unknown_knobs": config.unknown_env_knobs(),
            # runtime-sanitizer verdict: armed flag + structured findings
            # (rendered by `python -m racon_tpu.analysis
            # --sanitize-report REPORT.json`)
            "sanitize": {"armed": sanitize.enabled(),
                         "findings": sanitize.as_dicts()},
            # observability snapshot: metrics registry + the served-sum
            # cross-check against the per-phase counts above (racon_tpu/obs)
            "obs": {"armed": obs.enabled(),
                    **({"metrics": obs.snapshot(),
                        "served_sum": obs.served_sum_check(self.phases)}
                       if obs.enabled() else {})},
            # post-mortem references: one compact entry per flight dump
            # found after the run (the dump file holds the full ring)
            "flight": [{"path": d.get("path"), "pid": d.get("pid"),
                        "role": d.get("role"), "reason": d.get("reason"),
                        "events": len(d.get("events") or [])}
                       for d in self.flight],
            "wall_s": round(self.wall_s if self.wall_s is not None
                            else time.monotonic() - self._t0, 3),
            # latency-ledger fragment, present only when serving stamped
            # one (obs/ledger.py) — keys absent rather than null so
            # non-serve reports stay byte-for-byte what they were
            **({"ledger": dict(self.ledger)} if self.ledger else {}),
        }

    def _cache_dict(self) -> dict:
        """Persistent compilation cache: where it lives and this run's
        share of the process's traffic (a warm run shows hits and no
        misses).  Absent on the host path."""
        if self._cache0 is None:
            return {}
        import jax

        from .. import device

        now = device.cache_traffic()
        return {"jax_cache": {
            "dir": jax.config.jax_compilation_cache_dir,
            **{k: round(v - self._cache0[k], 3) for k, v in now.items()
               if k != "by_fun"}}}

    def summary(self) -> dict:
        """Compact serving-mix view for logs and the bench JSON line."""
        out = {
            phase: {"total": r.total, "served": dict(r.served),
                    "retries": r.retries, "bisections": r.bisections,
                    "quarantined": len(r.quarantined),
                    "degradations": len(r.degradations),
                    "wall_s": {t: round(s, 4)
                               for t, s in r.wall_s.items()},
                    # pack/kernel wall split and other phase extras ride
                    # along so bench.py can stamp them into log entries
                    **({"extra": dict(r.extra)} if r.extra else {})}
            for phase, r in self.phases.items()
        }
        stale = config.unknown_env_knobs()
        if stale:
            out["unknown_knobs"] = stale
        return out

    def write(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.as_dict(), f, indent=2, sort_keys=True)
            f.write("\n")

    def write_env(self) -> None:
        """Write to $RACON_TPU_REPORT when set; a write failure warns,
        it never fails the polish."""
        path = config.get_raw(ENV_REPORT)
        if not path:
            return
        try:
            self.write(path)
        except OSError as e:
            print(f"[racon_tpu::report] WARNING: cannot write {path}: {e}",
                  file=sys.stderr)
