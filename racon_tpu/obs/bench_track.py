"""Bench-history tracker: trend deltas + regression gate over a bench
trajectory.

History is whatever ``BENCH_r<N>.json`` round wrappers ({n, cmd, rc,
tail, parsed}; ``parsed`` holds the bench.py one-line JSON entry) sit in
the root, plus entry files named on the command line.  The checkout
commits none — the driver's ``PERF_LEDGER.jsonl`` is the record of chip
runs — so an empty history is a clean one.

Every entry passes through ``bench.normalize_entry`` (the reader-side
backfill) so older generations parse identically: ``phase_wall`` is
derived from the embedded report when the explicit stamp is missing, and
``cost_model`` backfills ``null``.  Entries are then grouped into
comparable series (same workload shape + kernel tier), and the newest
entry in each series is gated against its predecessor:

* headline throughput (``value``) dropping more than ``threshold``;
* ``vs_baseline`` dropping more than ``threshold``;
* any per-phase wall (``phase_wall``) growing more than ``threshold``
  (and more than ``min_delta_s``, to filter noise on tiny runs).

Exit codes mirror the trace-diff CLI: 0 clean, 2 unreadable history,
3 regression.  Stdlib-only except for the ``bench`` import, which is
optional (a vendored fallback keeps the module usable when the repo-root
script is absent, e.g. installed layouts).
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys
from typing import Dict, List, Optional, Tuple

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

_BENCH_ROUND = re.compile(r"BENCH_r(\d+)\.json$")


def _normalize(e: dict) -> dict:
    """bench.normalize_entry when the repo-root script is importable,
    else a minimal vendored equivalent (same semantics for the fields
    this tracker reads)."""
    try:
        if _REPO_ROOT not in sys.path:
            sys.path.insert(0, _REPO_ROOT)
        import bench
        return bench.normalize_entry(e)
    except Exception:  # noqa: BLE001 — installed layout without bench.py
        if not isinstance(e, dict):
            return e
        if "cost_model" not in e:
            e = dict(e, cost_model=None)
        if "serial_steps" not in e:
            cm = e.get("cost_model")
            ss = ({ph: row["serial_steps"]
                   for ph, row in cm.get("phases", {}).items()
                   if isinstance(row, dict) and "serial_steps" in row}
                  if isinstance(cm, dict) else None)
            e = dict(e, serial_steps=ss or None)
        return e


def load_history(root: str = _REPO_ROOT,
                 extra_paths: Optional[List[str]] = None
                 ) -> Tuple[List[dict], List[str]]:
    """All throughput entries, oldest first, normalized.  Returns
    (entries, problems); a malformed file is a *problem* (exit-2
    material).  No files is no entries and no problem."""
    entries: List[dict] = []
    problems: List[str] = []

    rounds = sorted(glob.glob(os.path.join(root, "BENCH_r*.json")),
                    key=lambda p: int(_BENCH_ROUND.search(p).group(1))
                    if _BENCH_ROUND.search(p) else 0)
    for path in rounds:
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError) as e:
            problems.append(f"{path}: {e}")
            continue
        parsed = doc.get("parsed") if isinstance(doc, dict) else None
        if isinstance(parsed, dict) and "value" in parsed:
            entries.append(dict(_normalize(parsed),
                                _source=os.path.basename(path)))

    for path in extra_paths or []:
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError) as e:
            problems.append(f"{path}: {e}")
            continue
        if isinstance(doc, dict) and isinstance(doc.get("parsed"), dict):
            doc = doc["parsed"]   # BENCH_r-style wrapper accepted too
        if isinstance(doc, dict) and "value" in doc:
            entries.append(dict(_normalize(doc),
                                _source=os.path.basename(path)))
        else:
            problems.append(f"{path}: no 'value' field — not a bench entry")
    return entries, problems


def series_key(e: dict) -> str:
    """Comparable-series key: workload shape + how it was served.  A
    forced CPU rehearsal and a device measurement are different
    experiments — the gate must never diff one against the other."""
    status = "forced" if e.get("forced") else "device"
    return "|".join(str(e.get(k, "?")) for k in
                    ("unit", "mbp", "input", "profile")) + \
        f"|{status}|{e.get('kernel', '?')}" + \
        ("|sanitize" if e.get("sanitize") else "")


def _pct(new: float, old: float) -> float:
    return 100.0 * (new - old) / old if old else float("inf")


def trend(entries: List[dict], threshold: float = 0.25,
          min_delta_s: float = 0.05) -> dict:
    """Group into series, compute consecutive deltas, gate the newest
    entry of each series against its predecessor."""
    series: Dict[str, List[dict]] = {}
    for e in entries:
        series.setdefault(series_key(e), []).append(e)

    out = {"series": [], "regressions": []}
    for key, ents in series.items():
        deltas = []
        for prev, cur in zip(ents, ents[1:]):
            d = {"from": prev.get("_source"), "to": cur.get("_source"),
                 "value": [prev.get("value"), cur.get("value")],
                 "value_pct": None, "phase_pct": {}}
            pv, cv = prev.get("value"), cur.get("value")
            if isinstance(pv, (int, float)) and pv \
                    and isinstance(cv, (int, float)):
                d["value_pct"] = round(_pct(cv, pv), 1)
            ppw, cpw = prev.get("phase_wall"), cur.get("phase_wall")
            if isinstance(ppw, dict) and isinstance(cpw, dict):
                for phase in sorted(set(ppw) | set(cpw)):
                    o, n = ppw.get(phase), cpw.get(phase)
                    if isinstance(o, (int, float)) and o \
                            and isinstance(n, (int, float)):
                        d["phase_pct"][phase] = round(_pct(n, o), 1)
            deltas.append(d)
        out["series"].append({"key": key, "n": len(ents),
                              "sources": [e.get("_source") for e in ents],
                              "values": [e.get("value") for e in ents],
                              "deltas": deltas})
        if len(ents) < 2:
            continue
        prev, cur = ents[-2], ents[-1]
        src = f"{prev.get('_source')} -> {cur.get('_source')}"
        pv, cv = prev.get("value"), cur.get("value")
        if isinstance(pv, (int, float)) and pv > 0 \
                and isinstance(cv, (int, float)) \
                and cv < pv * (1.0 - threshold):
            out["regressions"].append(
                f"[{key}] value: {pv} -> {cv} Mbp/s "
                f"({_pct(cv, pv):+.0f}%, threshold "
                f"-{threshold * 100:.0f}%) {src}")
        pb, cb = prev.get("vs_baseline"), cur.get("vs_baseline")
        if isinstance(pb, (int, float)) and pb > 0 \
                and isinstance(cb, (int, float)) \
                and cb < pb * (1.0 - threshold):
            out["regressions"].append(
                f"[{key}] vs_baseline: {pb} -> {cb} "
                f"({_pct(cb, pb):+.0f}%) {src}")
        ppw, cpw = prev.get("phase_wall"), cur.get("phase_wall")
        if isinstance(ppw, dict) and isinstance(cpw, dict):
            for phase in sorted(set(ppw) & set(cpw)):
                o, n = ppw[phase], cpw[phase]
                if isinstance(o, (int, float)) and o > 0 \
                        and isinstance(n, (int, float)) \
                        and n > o * (1.0 + threshold) \
                        and (n - o) > min_delta_s:
                    out["regressions"].append(
                        f"[{key}] phase_wall.{phase}: {o}s -> {n}s "
                        f"({_pct(n, o):+.0f}%) {src}")
    return out


def render(result: dict) -> str:
    lines = []
    for s in result["series"]:
        vals = " -> ".join("?" if v is None else f"{v:g}"
                           for v in s["values"])
        lines.append(f"series [{s['key']}]  n={s['n']}")
        lines.append(f"  value: {vals}")
        for d in s["deltas"]:
            pcts = "" if d["value_pct"] is None else f"{d['value_pct']:+g}%"
            ph = "  ".join(f"{k}:{v:+g}%" for k, v in d["phase_pct"].items())
            lines.append(f"    {d['from']} -> {d['to']}: {pcts}"
                         f"{('  phases: ' + ph) if ph else ''}")
    if result["regressions"]:
        for r in result["regressions"]:
            lines.append(f"REGRESSION: {r}")
    else:
        lines.append("no regression in any series")
    return "\n".join(lines)
