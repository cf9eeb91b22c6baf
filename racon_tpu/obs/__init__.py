"""Unified observability: span tracing + metrics for the polish pipeline.

One module-level armed/disarmed switch feeds two sinks:

* a **span tracer** (tracer.Tracer) producing Chrome-trace/Perfetto JSON
  — nested phase spans, per-bucket POA batches, align cohorts, journal
  replays, kernel builds, plus instant events for lattice retries /
  demotions / quarantines and watchdog timeouts;
* a **metrics registry** (metrics.Metrics) — counters and histograms
  keyed by phase, serving tier, and bucket class.  ``served.*`` counters
  are incremented inside ``PhaseReport.record_served`` itself, so the
  served-sum invariant between the metrics and the run report is checked
  (``served_sum_check``), not assumed.

Arming: ``obs.configure(trace_path=...)`` (the polisher constructors call
it after ``obs.reset()``), the CLI ``--trace`` flag, or the
``RACON_TPU_TRACE`` / ``RACON_TPU_METRICS`` knobs.  Disarmed, every hook
is a no-op: ``span()`` returns a shared null singleton and ``count()`` /
``event()`` are a None-check — polish output stays byte-identical and no
trace file is written (regression-tested in tests/test_obs.py).

Imports stay stdlib + config so this module is loadable from anywhere in
the stack (kernel_cache, resilience, tools) without cycles or a jax
dependency; the optional ``jax.profiler`` device capture imports jax
lazily and only when armed on a TPU backend.
"""

from __future__ import annotations

import collections
import sys
import threading
import time
from typing import Optional

from .. import config
from . import context, flight
from .metrics import Metrics
from .tracer import NULL_SPAN, Span, Tracer

ENV_TRACE = "RACON_TPU_TRACE"
ENV_METRICS = "RACON_TPU_METRICS"
ENV_TRACE_DEVICE = "RACON_TPU_TRACE_DEVICE"
ENV_SHIP_EVENTS = "RACON_TPU_OBS_SHIP_EVENTS"
ENV_TELEMETRY_RING = "RACON_TPU_TELEMETRY_RING"

#: The five pipeline phases every polish decomposes into, in execution
#: order.  Span names are ``phase.<name>``; the CLI breakdown and the
#: CI trace validation key off this tuple.
PHASES = ("parse", "align", "window_assign", "poa", "stitch")

_lock = threading.Lock()
_tracer: Optional[Tracer] = None
_metrics: Optional[Metrics] = None
_trace_path: Optional[str] = None
_device_tracing = False

# Process role ("coordinator", "worker0", "serve", …) for the merged
# fleet timeline.  Survives reset() on purpose: a process keeps its
# identity across every run it hosts, exactly like its pid.
_role: Optional[str] = None

# Live-telemetry ring: periodic gauge snapshots (queue depth, in-flight
# leases, …) scraped through the serve/distrib 'stats' wire verb.
# Survives reset() — it is per-process history, not per-run state.
_telemetry_lock = threading.Lock()
_telemetry = None


# -- arming ----------------------------------------------------------------

def reset() -> None:
    """Disarm and drop all collected state (called per run by the
    polisher constructors, before ``configure``).  A device trace left
    running by a crashed run is stopped first.  The flight recorder,
    process role, trace context, and telemetry ring survive — they are
    process identity/history, not per-run trace state."""
    global _tracer, _metrics, _trace_path
    maybe_stop_device_trace()
    with _lock:
        _tracer = None
        _metrics = None
        _trace_path = None


def configure(trace_path: Optional[str] = None,
              metrics: Optional[bool] = None,
              epoch_ns: Optional[int] = None) -> None:
    """Arm for one run.  Explicit arguments (the CLI flags) win; ``None``
    falls back to the ``RACON_TPU_TRACE`` / ``RACON_TPU_METRICS`` knobs.
    Tracing implies metrics (the snapshot rides inside the trace file);
    ``RACON_TPU_METRICS=1`` alone collects spans + counters in memory for
    the ``RunReport["obs"]`` snapshot without writing a trace file.

    Idempotent per destination: re-arming with the trace path already
    armed keeps the collected spans (the serve session re-enters
    ``reset``/``configure`` per job; the distrib coordinator arms once
    per ``run()``).  Arming a *different* path swaps in a fresh tracer,
    so a second in-process run can never append spans into the previous
    run's file — the scoped teardown (``release()``) plus this check is
    the regression surface tests/test_obs.py pins.

    ``epoch_ns`` (a ``time.monotonic_ns()`` stamp) is where a fresh
    tracer's ts=0 lies: the start of the state reset that ends in this
    arming, so that the job's root span begins at 0."""
    global _tracer, _metrics, _trace_path
    if trace_path is None:
        trace_path = config.get_str(ENV_TRACE) or None
    if metrics is None:
        metrics = config.get_bool(ENV_METRICS)
    if not trace_path and not metrics:
        return
    with _lock:
        if _tracer is not None and _trace_path == trace_path:
            return
        _trace_path = trace_path
        _tracer = Tracer(t0_ns=epoch_ns)
        _metrics = Metrics()
        _tracer.role = _role
        ctx = context.current()
        if ctx is not None:
            _tracer.trace_id = ctx.get("trace_id")
            _tracer.parent_span = ctx.get("parent")
        # every finished span also lands in a span_us.<name> log2
        # histogram, so the CLI breakdown gets p50/p99 per span name
        # even when the bounded event buffer truncated the timeline —
        # and in the flight-recorder ring, so a crash dump carries the
        # span tail too
        m = _metrics
        fl = flight.recorder()
        def _on_complete(name, dur_us, _m=m, _fl=fl):
            _m.observe(f"span_us.{name}", dur_us)
            _fl.span(name, dur_us)
        _tracer.on_complete = _on_complete


def release(write: bool = True) -> Optional[str]:
    """Scoped teardown of one armed run: optionally write the trace,
    then disarm.  The multi-run surfaces (distrib coordinator, serve
    scheduler) call this in a ``finally`` so the process-global tracer
    never outlives the run that armed it."""
    path = write_trace() if write else None
    reset()
    return path


def set_role(role: Optional[str]) -> None:
    """Name this process's track in merged fleet timelines and flight
    dumps ("coordinator", "worker0", "serve", …).  Sticky across
    ``reset()``."""
    global _role
    _role = role
    flight.set_role(role)
    t = _tracer
    if t is not None:
        t.role = role


def role() -> Optional[str]:
    return _role


def enabled() -> bool:
    return _tracer is not None


def tracer() -> Optional[Tracer]:
    """The armed tracer, or None — read-only introspection for tests
    and tools; mutation goes through the hooks below."""
    return _tracer


def trace_path() -> Optional[str]:
    return _trace_path


# -- recording hooks (each a cheap no-op when disarmed) --------------------

def span(name: str, cat: str = "span", **args):
    """Context manager timing a region; returns the shared null span
    when disarmed so the call site costs one identity return.  ``cat``
    is the event's category: ``"launch"`` for the drivers' per-launch
    spans, which a bounded shipment drops first (``Tracer.export``)."""
    t = _tracer
    if t is None:
        return NULL_SPAN
    return Span(t, name, args, cat)


def event(name: str, **args) -> None:
    """Instant event (lattice demotion, watchdog timeout, …).  Always
    breadcrumbed into the flight recorder — instant events are exactly
    the rare, high-signal moments a post-mortem needs — and additionally
    recorded on the tracer timeline when armed."""
    flight.record(name, **args)
    t = _tracer
    if t is not None:
        t.add_instant(name, **args)


def add_complete(name: str, t0_ns: int, t1_ns: int, cat: str = "span",
                 **args) -> None:
    """Retroactive span from raw monotonic_ns stamps (kernel-cache miss
    detection times the call first, then learns it was a compile).  Its
    parent is the caller's innermost open span unless ``parent_id=``
    says otherwise (``Tracer.add_complete``)."""
    t = _tracer
    if t is not None:
        t.add_complete(name, t0_ns, t1_ns, cat=cat, **args)


def begin(name: str, t0_ns: Optional[int] = None, root: bool = False,
          **args) -> None:
    """Open a span that another function ends (``Tracer.begin``): the
    job's root span ``job`` and the two halves of its boundary,
    ``job.open`` and ``job.close``, which reach from a polisher's
    constructor into ``initialize()`` and from ``polish()`` into the
    serve session."""
    t = _tracer
    if t is not None:
        t.begin(name, t0_ns, root, **args)


def end(name: str, **args) -> None:
    """Close what ``begin`` opened under this name; a no-op when
    disarmed or when no such span is open."""
    t = _tracer
    if t is not None:
        t.end(name, **args)


def count(name: str, n: int = 1) -> None:
    m = _metrics
    if m is not None:
        m.count(name, n)


def observe(name: str, value: float) -> None:
    m = _metrics
    if m is not None:
        m.observe(name, value)


# -- cross-process span shipping -------------------------------------------

def shipment(max_events: Optional[int] = None) -> Optional[dict]:
    """Bounded, JSON-ready export of this process's span buffer +
    metrics snapshot, shipped with a distrib chunk / serve job result so
    the coordinator can fold it into the merged fleet trace.  None when
    disarmed — a disarmed worker ships nothing and the wire field stays
    absent."""
    t = _tracer
    if t is None:
        return None
    if max_events is None:
        max_events = max(1, config.get_int(ENV_SHIP_EVENTS))
    return t.export(max_events=max_events, metrics=snapshot())


def absorb(ship) -> int:
    """Fold a peer process's ``shipment()`` into this process's armed
    tracer (timestamps re-based, pid tracks preserved).  No-op when
    disarmed or the shipment is absent/malformed; returns the number of
    events absorbed."""
    t = _tracer
    if t is None or not isinstance(ship, dict):
        return 0
    return t.ingest(ship)


# -- live telemetry ----------------------------------------------------------

def telemetry_tick(**gauges) -> dict:
    """Append one gauge snapshot (queue depth, in-flight leases, …) to
    the process's bounded telemetry ring and return it.  Armed or not —
    telemetry is scrape-state for the 'stats' wire verb, not trace
    output — but when metrics are armed the per-phase served totals ride
    along so a poller watches serving progress live."""
    global _telemetry
    entry = {"t_mono_ns": time.monotonic_ns()}
    entry.update(gauges)
    # every tick carries the process RSS: memory is the gauge that
    # matters when the budget watchdog (resilience/budget.py) is the
    # thing a poller wants to see approaching its watermarks
    from ..resilience import budget as _budget
    entry["mem.rss_mb"] = round(_budget.rss_mb(), 1)
    m = _metrics
    if m is not None:
        entry["served_total"] = m.prefix_sum("served.")
    with _telemetry_lock:
        if _telemetry is None:
            _telemetry = collections.deque(
                maxlen=max(1, config.get_int(ENV_TELEMETRY_RING)))
        _telemetry.append(entry)
    return entry


def telemetry(last: Optional[int] = None) -> list:
    """The telemetry ring, oldest first (optionally just the last N)."""
    with _telemetry_lock:
        items = [] if _telemetry is None else list(_telemetry)
    return items[-last:] if last else items


# -- snapshots & invariants ------------------------------------------------

def snapshot() -> Optional[dict]:
    """JSON-ready metrics snapshot, or None when disarmed."""
    m = _metrics
    return None if m is None else m.snapshot()


def counter_total(prefix: str) -> int:
    """Sum of every counter whose name starts with ``prefix`` (0 when
    metrics are disarmed).  The serve session reads
    ``counter_total("kernel.builds.")`` after each job to prove the
    hot-kernel invariant: jobs 2..N on a resident process build nothing."""
    m = _metrics
    return 0 if m is None else m.prefix_sum(prefix)


def served_sum_check(phases) -> dict:
    """Cross-check the ``served.<phase>.<tier>`` counters against each
    ``PhaseReport``'s served totals.  The counters are fed from
    ``record_served`` itself, so a mismatch means some code path served
    work while bypassing the report (or vice versa) — exactly the drift
    this layer exists to catch.

    ``phases`` is the ``RunReport.phases`` mapping; returns
    ``{phase: {"report": n, "metrics": n, "ok": bool}}``."""
    m = _metrics
    if m is None:
        return {}
    out = {}
    for name, rep in phases.items():
        counted = m.prefix_sum(f"served.{name}.")
        total = rep.served_total()
        out[name] = {"report": total, "metrics": counted,
                     "ok": counted == total}
    return out


# -- export ----------------------------------------------------------------

def _device() -> Optional[dict]:
    """Backend platform / device_kind / device_count for the trace
    provenance stamp.  Reads jax only when the run already imported it
    (a traced device polish always has) — this module must stay
    importable, and write_trace callable, without a jax dependency."""
    if "jax" not in sys.modules:
        return None
    from .. import device

    try:
        ident = device.identity()
    except Exception:  # noqa: BLE001 — provenance only, never fail a write
        return None
    return {"platform": ident["platform"],
            "device_kind": ident["device_kind"],
            "device_count": ident["count"]}


def write_trace() -> Optional[str]:
    """Write the Chrome-trace JSON (metrics snapshot embedded) to the
    configured path.  Returns the path written, or None when tracing is
    disarmed or armed metrics-only.  A write failure warns — a full disk
    must not fail the polish that just finished."""
    t, path = _tracer, _trace_path
    if t is None or not path:
        return None
    try:
        t.write(path, metrics=snapshot(), device=_device())
    except OSError as e:
        print(f"[racon_tpu::obs] WARNING: cannot write trace {path}: {e}",
              file=sys.stderr)
        return None
    return path


# -- optional jax.profiler device capture ----------------------------------

def maybe_start_device_trace() -> bool:
    """Best-effort ``jax.profiler`` device trace next to the host trace
    (``<trace_path>.device/``), gated on ``RACON_TPU_TRACE_DEVICE=1`` and
    an actual TPU backend — on CPU/GPU the host spans already tell the
    whole story.  Any failure degrades to host-only tracing."""
    global _device_tracing
    if _trace_path is None or _device_tracing:
        return False
    if not config.get_bool(ENV_TRACE_DEVICE):
        return False
    try:
        import jax

        if jax.devices()[0].platform != "tpu":
            return False
        jax.profiler.start_trace(f"{_trace_path}.device")
    except Exception as e:  # noqa: BLE001 — never fail a polish for this
        print(f"[racon_tpu::obs] WARNING: device trace unavailable "
              f"({type(e).__name__}: {e}); continuing host-only",
              file=sys.stderr)
        return False
    _device_tracing = True
    return True


def maybe_stop_device_trace() -> None:
    global _device_tracing
    if not _device_tracing:
        return
    _device_tracing = False
    try:
        import jax

        jax.profiler.stop_trace()
    except Exception as e:  # noqa: BLE001
        print(f"[racon_tpu::obs] WARNING: device trace stop failed "
              f"({type(e).__name__}: {e})", file=sys.stderr)
