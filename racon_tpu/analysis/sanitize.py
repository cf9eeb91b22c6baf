"""Opt-in runtime sanitizer for the device drivers (dynamic analysis).

``RACON_TPU_SANITIZE=1`` arms three families of runtime checks — the
dynamic counterpart to this package's static lint + jaxpr audit:

* **kernel-output invariants** — every builder decorated with
  ``ops.kernel_cache.device_keyed_cache`` gets its built kernel wrapped
  in a checking proxy: float device outputs must be finite.  Checks are
  skipped while the proxied kernel is being re-traced (``shard_map`` /
  ``jit`` hand it tracers, not arrays); the concrete arrays are covered
  at the driver seams below.
* **driver-seam invariants** — the consensus install path
  (``poa_driver._install``) asserts in-range consensus codes/lengths,
  and on a sampled fraction of device-served windows
  (``RACON_TPU_SANITIZE_PARITY``, default every 8th) recomputes the
  window on the host and compares byte-for-byte *before* the device
  result is installed, so an armed run stays byte-identical to an
  unarmed one.  (The aligner's install seam, ``align_pallas.run_jobs``,
  needs no armed check: its native run-length pass refuses an op code
  outside M/I/D on every run.)
* **shared-state guards** — the drivers' stats dicts are wrapped so a
  mutation from any thread other than the owning driver thread is
  recorded as a ``racy-stats`` finding.

Violations never raise and never alter polish output: they are recorded
as structured findings, surfaced in ``RunReport.as_dict()["sanitize"]``
and rendered by ``python -m racon_tpu.analysis --sanitize-report``.

Fault hooks (the ``RACON_TPU_FAULT`` grammar, default ``raise=``):
``sanitize.nan`` poisons the checker's *copy* of one device buffer (the
installed consensus is untouched) and ``sanitize.stats`` performs one
real cross-thread stats mutation — both prove the detectors fire
end-to-end without corrupting a run.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import Dict, List, Tuple

import numpy as np

from .. import config

KNOB = "RACON_TPU_SANITIZE"
PARITY_KNOB = "RACON_TPU_SANITIZE_PARITY"

#: Distinct (kind, where) findings kept; later hits only bump counters.
_MAX_FINDINGS = 100


@dataclass
class Finding:
    """One sanitizer violation class, aggregated across occurrences."""

    kind: str    # nonfinite | consensus-range | parity | racy-stats
    where: str   # kernel builder / driver seam that caught it
    detail: str  # first occurrence's specifics
    count: int = 1


_lock = threading.Lock()
_findings: Dict[Tuple[str, str], Finding] = {}


def enabled() -> bool:
    """Whether the runtime sanitizer is armed."""
    return config.get_bool(KNOB)


def reset() -> None:
    """Clear collected findings (per-run; polisher ctors call this)."""
    with _lock:
        _findings.clear()


def record(kind: str, where: str, detail: str) -> None:
    """Record one violation (thread-safe; capped, never raises)."""
    with _lock:
        f = _findings.get((kind, where))
        if f is not None:
            f.count += 1
        elif len(_findings) < _MAX_FINDINGS:
            _findings[(kind, where)] = Finding(kind, where, detail)


def findings() -> List[Finding]:
    with _lock:
        return list(_findings.values())


def as_dicts() -> List[dict]:
    """JSON-ready findings (the RunReport / --sanitize-report schema)."""
    return [{"kind": f.kind, "where": f.where, "detail": f.detail,
             "count": f.count} for f in findings()]


# --------------------------------------------------------------------------
# kernel-output proxy (hooked in by ops.kernel_cache.device_keyed_cache)
# --------------------------------------------------------------------------

def wrap_kernel(name: str, built):
    """Checking proxy around a built kernel (or kernel factory).

    Factories — builders whose return value is itself a callable that
    produces the kernel (the Pallas POA builders) — are wrapped
    transitively so the eventual kernel is proxied.  Outputs pass
    through unchanged; only a check rides along."""
    if not callable(built):
        return built

    def proxied(*args, **kwargs):
        out = built(*args, **kwargs)
        if callable(out):
            return wrap_kernel(name, out)
        check_kernel_outputs(name, out)
        return out

    return proxied


def check_kernel_outputs(name: str, out) -> None:
    """Generic invariant on concrete kernel outputs: float arrays are
    finite.  Tracers (a proxied kernel re-traced inside shard_map/jit)
    are skipped wholesale — the driver seams check the concrete side."""
    arrays = out if isinstance(out, (tuple, list)) else (out,)
    import jax

    for a in arrays:
        if isinstance(a, jax.core.Tracer):
            return
    for k, a in enumerate(arrays):
        try:
            arr = np.asarray(a)
        except Exception:  # not array-like (config tuples, scalars…)
            continue
        if arr.dtype.kind == "f" and not np.isfinite(arr).all():
            record("nonfinite", f"{name}[out {k}]",
                   f"non-finite values in float output {k} "
                   f"(shape {arr.shape})")


# --------------------------------------------------------------------------
# driver-seam checks (called from ops/poa_driver.py)
# --------------------------------------------------------------------------

def check_consensus_outputs(results, idxs, where: str) -> None:
    """Consensus chunk invariants at the install seam, where the arrays
    are concrete: cons_len within the padded capacity, base codes
    decodable (0..4) within each served length, failed values 0 or one
    of the kernels' causes (ops/poa.py FAIL_CAUSES).

    The ``sanitize.nan`` fault poisons a float COPY for the checker only
    — the arrays the driver installs are never touched, so a
    fault-injected run still polishes byte-identically."""
    from ..ops.poa import FAIL_CAUSES

    cons_base, _cons_cov, cons_len, failed = (np.asarray(x)
                                              for x in results)
    cons_len = cons_len.reshape(-1)
    failed = failed.reshape(-1)

    check_view = cons_base.astype(np.float32, copy=True)
    from ..resilience import faults
    try:
        faults.check("sanitize.nan", idxs)
    except faults.InjectedFault:
        if check_view.size:
            check_view.reshape(-1)[0] = np.nan
    if not np.isfinite(check_view).all():
        record("nonfinite", where,
               f"non-finite consensus values (chunk windows {idxs[:4]}…)")

    cap = cons_base.shape[1] if cons_base.ndim >= 2 else cons_base.size
    for bi in range(len(cons_len)):
        if int(failed[bi]) != 0 and int(failed[bi]) not in FAIL_CAUSES:
            record("consensus-range", where,
                   f"failed value {failed[bi]!r} is no cause (row {bi})")
        if int(failed[bi]):
            continue
        cl = int(cons_len[bi])
        if cl < 0 or cl > cap:
            record("consensus-range", where,
                   f"cons_len {cl} outside [0, {cap}] (row {bi})")
            continue
        row = cons_base[bi, :cl] if cons_base.ndim >= 2 else cons_base[:cl]
        if row.size and (int(row.min()) < 0 or int(row.max()) > 4):
            record("consensus-range", where,
                   f"base code outside 0..4 (row {bi}, "
                   f"min {int(row.min())}, max {int(row.max())})")


# --------------------------------------------------------------------------
# sampled host<->device parity
# --------------------------------------------------------------------------

def parity_stride() -> int:
    """Every Nth device-served window is host-recomputed and compared
    (0 = parity probe off)."""
    try:
        return max(0, config.get_int(PARITY_KNOB))
    except ValueError:
        return 0


def parity_due(n_installed: int) -> bool:
    s = parity_stride()
    return s > 0 and n_installed % s == 0


def check_parity(device_payload, host_payload, window: int,
                 where: str) -> None:
    """Byte-compare a device consensus against the host recompute of the
    same window (the caller recomputes BEFORE installing the device
    result, so the final pipeline state is untouched either way)."""
    d = (device_payload.encode() if isinstance(device_payload, str)
         else bytes(device_payload))
    h = (host_payload.encode() if isinstance(host_payload, str)
         else bytes(host_payload))
    if d != h:
        record("parity", where,
               f"window {window}: device consensus ({len(d)}b) != "
               f"host recompute ({len(h)}b)")


# --------------------------------------------------------------------------
# shared-state guard (driver stats dicts)
# --------------------------------------------------------------------------

class GuardedStats(dict):
    """Dict guard recording a ``racy-stats`` finding when any thread
    other than the creating (driver) thread mutates it.  The write still
    happens — the guard observes, it does not serialize."""

    def __init__(self, initial: dict, where: str):
        super().__init__(initial)
        self._owner = threading.get_ident()
        self._where = where

    def __setitem__(self, key, value):
        tid = threading.get_ident()
        if tid != self._owner:
            record("racy-stats", self._where,
                   f"key {key!r} written from thread {tid} "
                   f"(owner {self._owner})")
        super().__setitem__(key, value)


def guard_stats(stats: dict, where: str) -> dict:
    """Wrap a driver stats dict when the sanitizer is armed (passthrough
    otherwise).  The ``sanitize.stats`` fault performs one real
    cross-thread mutation through the guard — detector path exercised
    end-to-end, stats content left unchanged."""
    if not enabled():
        return stats
    g = GuardedStats(stats, where)
    from ..resilience import faults
    try:
        faults.check("sanitize.stats")
    except faults.InjectedFault:
        t = threading.Thread(target=g.__setitem__,
                             args=("_sanitize_stats_probe", 1),
                             name="sanitize-stats-probe", daemon=True)
        t.start()
        t.join()
        g.pop("_sanitize_stats_probe", None)
    return g
