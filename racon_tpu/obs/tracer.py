"""Thread-safe span tracer emitting Chrome-trace ("Trace Event Format")
JSON, loadable in Perfetto / chrome://tracing.

Design constraints (mirrored by tests/test_obs.py):

* **Monotonic clock only.**  Span math uses ``time.monotonic_ns()``;
  a wall-clock (``time.time``) span goes negative across an NTP step.
  The ``wall-clock`` lint rule (analysis/rules/clock.py) scopes this
  package, so a regression is a lint failure, not a code review hope.
* **Bounded memory.**  The event buffer is capped; past the cap events
  are counted as dropped (surfaced in the written trace) instead of
  growing without bound on pathological runs.
* **No data dependence.**  The tracer observes timing only — it never
  touches sequences, CIGARs, or consensus bytes, which is what makes
  the armed-vs-disarmed byte-identity guarantee trivial to keep.
"""

from __future__ import annotations

import itertools
import json
import os
import sys
import threading
import time
from typing import List, Optional

#: ``add_complete``'s default parent: the caller's innermost open span.
_CALLER = object()


class Span:
    """One timed region, used as a context manager.

    Records a Chrome-trace complete ("ph":"X") event on exit; ``set()``
    attaches key/value args that show up in the Perfetto detail pane.
    An exception escaping the body is recorded as an ``error`` arg so a
    trace of a degraded run shows *where* the lattice demoted.

    One clock: when the process has imported JAX the span also enters a
    ``jax.profiler.TraceAnnotation`` of the same name and args, so under
    a running ``jax.profiler`` trace it sits on the ``/host:CPU`` plane
    of the same ``.xplane.pb`` as the device ops.  With the profiler off
    a ``TraceMe`` is a flag check; without JAX nothing is imported.

    A span knows what caused it: ``id`` counts up per tracer, ``parent``
    is the id of the span that was open on the same thread when this one
    began (the tracer's per-thread stack), or the job's root span for
    the first span of a worker thread."""

    __slots__ = ("_tracer", "name", "cat", "args", "_t0", "_annotation",
                 "id", "parent")

    def __init__(self, tracer: "Tracer", name: str, args: dict,
                 cat: str = "span"):
        self._tracer = tracer
        self.name = name
        self.cat = cat
        self.args = args
        self._t0 = 0
        self._annotation = None
        self.id = 0
        self.parent = None

    def set(self, **attrs) -> "Span":
        self.args.update(attrs)
        if self._annotation is not None:
            self._annotation.set_metadata(**attrs)
        return self

    def __enter__(self) -> "Span":
        # getattr: another thread may be half-way through importing jax
        profiler = getattr(sys.modules.get("jax"), "profiler", None)
        if profiler is not None:
            self._annotation = profiler.TraceAnnotation(self.name,
                                                        **self.args)
            self._annotation.__enter__()
        self.id, self.parent = self._tracer._push()
        self._t0 = time.monotonic_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        t1 = time.monotonic_ns()
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
        if exc_type is not None:
            self.args.setdefault("error", exc_type.__name__)
        self._tracer._pop(self.id)
        self._tracer._complete(self.name, self._t0, t1, self.cat, self.args,
                               self.id, self.parent)
        return False


class _NullSpan:
    """The disarmed span: a shared, allocation-free no-op so tracing-off
    call sites cost one attribute load + identity return."""

    __slots__ = ()

    def set(self, **attrs) -> "_NullSpan":
        return self

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        return False


#: Singleton handed out by ``obs.span()`` when tracing is disarmed.
NULL_SPAN = _NullSpan()


class Tracer:
    """In-memory trace-event buffer.  All mutation happens under one
    lock, so spans opened from watchdog threads, the native callback
    thread, or test thread pools interleave safely."""

    def __init__(self, max_events: int = 200_000,
                 t0_ns: Optional[int] = None):
        self._lock = threading.Lock()
        self._events: List[dict] = []
        self._thread_names = {}   # tid -> python thread name ("M" events)
        self.dropped = 0
        self._max = max_events
        #: Optional ``(name, dur_us) -> None`` callback fired for every
        #: complete event — even past the buffer cap, so the span_us.*
        #: duration histograms stay exact when the timeline is truncated.
        self.on_complete = None
        # Event timestamps are offsets from tracer creation so traces
        # start near ts=0 regardless of the monotonic clock's epoch.
        # ``t0_ns`` moves the epoch back to where the armed run began
        # (the state reset that ends in arming), so that the job's root
        # span starts at ts=0 and not before it.
        self._t0 = time.monotonic_ns() if t0_ns is None else t0_ns
        self.pid = os.getpid()
        # Causes: ids count up from 1; each thread keeps the ids of its
        # open spans; ``root_id`` is the job's root span, the parent of
        # a span that a thread with nothing open begins.
        self._ids = itertools.count(1)
        self._open = threading.local()
        self.root_id: Optional[int] = None
        self._held = {}           # name -> (Span begun, its thread)
        #: Cross-process provenance, stamped by ``obs.configure`` from
        #: ``obs.context`` / ``obs.set_role``.  ``role`` names this
        #: process's track in a merged timeline ("coordinator",
        #: "worker0", …); trace_id/parent_span tie its spans to the
        #: fleet-wide trace context.
        self.role: Optional[str] = None
        self.trace_id: Optional[str] = None
        self.parent_span: Optional[str] = None
        # Events absorbed from other processes' shipments (already
        # re-based onto this tracer's clock) + their metadata events.
        self._foreign: List[dict] = []
        self._foreign_meta: List[dict] = []

    @property
    def t0_ns(self) -> int:
        """Monotonic epoch of this tracer's ts=0 — CLOCK_MONOTONIC is
        system-wide on Linux, so two same-host tracers re-base each
        other's events via the difference of their epochs."""
        return self._t0

    def _ts_us(self, t_ns: int) -> int:
        # Clamp at the epoch: a span on a concurrent thread (e.g. an rpc
        # handler) may have *started* before this tracer was re-armed for
        # the current trace file, so its start predates t0.  Pinning it
        # to ts=0 keeps every emitted event schema-valid (ts >= 0).
        return max(0, (t_ns - self._t0) // 1000)

    def _stack(self) -> list:
        try:
            return self._open.stack
        except AttributeError:
            stack = self._open.stack = []
            return stack

    def _innermost(self) -> Optional[int]:
        """The id of the span open innermost on this thread; the job's
        root span where the thread has none open."""
        stack = self._stack()
        return stack[-1] if stack else self.root_id

    def _push(self) -> tuple:
        """(a fresh id, its parent's id or None), the id now innermost
        on this thread."""
        parent = self._innermost()
        sid = next(self._ids)
        self._stack().append(sid)
        return sid, parent

    def _pop(self, sid: int) -> None:
        stack = self._stack()
        if stack and stack[-1] == sid:
            stack.pop()
        elif sid in stack:        # ended out of order
            stack.remove(sid)

    def _append(self, ev: dict, tid: Optional[int] = None) -> None:
        if tid is None:
            tid = threading.get_ident()
        ev["pid"] = self.pid
        ev["tid"] = tid
        with self._lock:
            if tid not in self._thread_names:
                self._thread_names[tid] = threading.current_thread().name
            if len(self._events) >= self._max:
                self.dropped += 1
                return
            self._events.append(ev)

    def _complete(self, name: str, t0_ns: int, t1_ns: int, cat: str,
                  args: dict, sid: int, parent: Optional[int],
                  tid: Optional[int] = None) -> None:
        dur = max(0, (t1_ns - t0_ns) // 1000)
        self._append({"name": name, "cat": cat, "ph": "X",
                      "ts": self._ts_us(t0_ns), "dur": dur,
                      "id": sid, "parent": parent, "args": args}, tid)
        cb = self.on_complete
        if cb is not None:
            cb(name, dur)

    def add_complete(self, name: str, t0_ns: int, t1_ns: int,
                     cat: str = "span", parent_id=_CALLER, **args) -> None:
        """Record a finished region [t0_ns, t1_ns] (monotonic_ns stamps).
        Exposed directly (not only via Span) so call sites that detect an
        interesting region *after the fact* — e.g. a kernel-cache miss —
        can stamp it retroactively.  Its parent is the span innermost on
        the caller's thread now (the job's root span where none is open)
        unless ``parent_id`` names another, or None for no parent."""
        if parent_id is _CALLER:
            parent_id = self._innermost()
        self._complete(name, t0_ns, t1_ns, cat, args, next(self._ids),
                       parent_id)

    def begin(self, name: str, t0_ns: Optional[int] = None,
              root: bool = False, cat: str = "span", **args) -> None:
        """Open a span that another function will ``end``: one open span
        per name.  It is innermost on this thread until then, and with
        ``root`` the parent of whatever a thread with nothing open
        begins.  Still open when the trace is written or shipped, it is
        in the file up to that moment, with the arg ``open``.  No
        ``TraceAnnotation``: that needs a ``with`` block."""
        self.end(name)            # one open span per name
        sp = Span(self, name, args, cat)
        sp.id, sp.parent = self._push()
        sp._t0 = time.monotonic_ns() if t0_ns is None else t0_ns
        with self._lock:
            self._held[name] = (sp, threading.get_ident())
        if root:
            self.root_id = sp.id

    def end(self, name: str, **args) -> None:
        """Close the span ``begin`` opened under this name (a no-op when
        none is open), on the thread that began it."""
        t1 = time.monotonic_ns()
        with self._lock:
            sp, tid = self._held.pop(name, (None, None))
        if sp is None:
            return
        self._pop(sp.id)
        sp.args.update(args)
        self._complete(sp.name, sp._t0, t1, sp.cat, sp.args, sp.id,
                       sp.parent, tid)

    def _still_open(self) -> List[dict]:
        """The spans begun and not ended, as events up to now (caller
        holds the lock)."""
        now = time.monotonic_ns()
        return [{"name": sp.name, "cat": sp.cat, "ph": "X",
                 "ts": self._ts_us(sp._t0),
                 "dur": max(0, (now - sp._t0) // 1000), "id": sp.id,
                 "parent": sp.parent, "args": dict(sp.args, open=True),
                 "pid": self.pid, "tid": tid}
                for sp, tid in self._held.values()]

    def add_instant(self, name: str, cat: str = "event", **args) -> None:
        """Record a point event (lattice demotion, watchdog timeout, …)."""
        self._append({"name": name, "cat": cat, "ph": "i", "s": "t",
                      "ts": self._ts_us(time.monotonic_ns()),
                      "args": args})

    def events(self) -> List[dict]:
        with self._lock:
            return list(self._events)

    # -- cross-process shipping -------------------------------------------
    def export(self, max_events: Optional[int] = None,
               metrics: Optional[dict] = None) -> dict:
        """A JSON-ready shipment of this process's span buffer: the last
        ``max_events`` events (newest win — the tail is where the crash
        or the result lives), thread names, and the clock epoch a peer
        needs to re-base them.  Bounded so a shipment always fits the
        wire's one-line message limit.  The cap is filled with every
        other category first and the drivers' ``"launch"`` spans last:
        a job's tail is all launches, and a shipment of nothing else
        would drop the ``phase.*`` spans from every merged trace."""
        with self._lock:
            events = list(self._events) + self._still_open()
            names = dict(self._thread_names)
            dropped = self.dropped
        if max_events is not None and len(events) > max_events:
            dropped += len(events) - max_events
            launches, rest = [], []
            for i, ev in enumerate(events):
                (launches if ev.get("cat") == "launch" else rest).append(i)
            keep = rest[-max_events:]
            if len(keep) < max_events:
                keep += launches[len(keep) - max_events:]
            events = [events[i] for i in sorted(keep)]
        ship = {
            "pid": self.pid,
            "t0_mono_ns": self._t0,
            "role": self.role,
            "trace_id": self.trace_id,
            "dropped": dropped,
            "thread_names": {str(t): n for t, n in names.items()},
            "events": events,
        }
        if metrics is not None:
            ship["metrics"] = metrics
        return ship

    def ingest(self, ship: dict) -> int:
        """Absorb a peer process's ``export()``: re-base its timestamps
        onto this tracer's clock (same-host monotonic epochs) and keep
        its pid/tid stamps so the merged file renders one track per
        process.  Malformed shipments are dropped whole — a worker's
        trace must never corrupt the coordinator's.  Returns the number
        of events absorbed."""
        if not isinstance(ship, dict):
            return 0
        events = ship.get("events")
        if not isinstance(events, list):
            return 0
        try:
            dt_us = (int(ship["t0_mono_ns"]) - self._t0) // 1000
            pid = int(ship["pid"])
        except (KeyError, TypeError, ValueError):
            return 0
        absorbed = []
        for ev in events:
            if not isinstance(ev, dict) or "ts" not in ev:
                continue
            ev = dict(ev)
            try:
                ev["ts"] = max(0, int(ev["ts"]) + dt_us)
                ev["pid"] = int(ev.get("pid", pid))
                ev["tid"] = int(ev.get("tid", 0))
            except (TypeError, ValueError):
                continue
            absorbed.append(ev)
        meta = []
        role = ship.get("role")
        meta.append({"name": "process_name", "ph": "M", "pid": pid,
                     "tid": 0, "args": {"name": role or f"pid{pid}"}})
        tnames = ship.get("thread_names")
        if isinstance(tnames, dict):
            for t, n in sorted(tnames.items()):
                try:
                    meta.append({"name": "thread_name", "ph": "M",
                                 "pid": pid, "tid": int(t),
                                 "args": {"name": str(n)}})
                except (TypeError, ValueError):
                    continue
        try:
            foreign_dropped = int(ship.get("dropped", 0))
        except (TypeError, ValueError):
            foreign_dropped = 0
        with self._lock:
            self._foreign.extend(absorbed)
            self._foreign_meta.extend(meta)
            self.dropped += foreign_dropped
        return len(absorbed)

    def to_dict(self, metrics: Optional[dict] = None,
                device: Optional[dict] = None) -> dict:
        """The full Chrome-trace JSON object.  Extra top-level keys are
        ignored by Perfetto, so the metrics snapshot and provenance ride
        along in the same file the timeline lives in."""
        with self._lock:
            events = (list(self._events) + self._still_open()
                      + list(self._foreign))
            names = dict(self._thread_names)
            meta = list(self._foreign_meta)
            dropped = self.dropped
        events.append({"name": "process_name", "ph": "M", "pid": self.pid,
                       "tid": 0,
                       "args": {"name": self.role or "racon-tpu"}})
        for tid, tname in sorted(names.items()):
            events.append({"name": "thread_name", "ph": "M", "pid": self.pid,
                           "tid": tid, "args": {"name": tname}})
        events.extend(meta)
        doc = {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": {"tool": "racon_tpu.obs", "clock": "monotonic",
                          "dropped_events": dropped,
                          "pid": self.pid,
                          "t0_monotonic_ns": self._t0},
        }
        if self.role:
            doc["otherData"]["role"] = self.role
        if self.trace_id:
            doc["otherData"]["trace_id"] = self.trace_id
            if self.parent_span:
                doc["otherData"]["parent_span"] = self.parent_span
        if device:
            # platform + device_kind let `obs validate --profile auto`
            # pick the machine profile without re-importing the backend
            doc["otherData"].update(device)
        if metrics is not None:
            doc["racon_tpu"] = {"metrics": metrics}
        return doc

    def write(self, path: str, metrics: Optional[dict] = None,
              device: Optional[dict] = None) -> None:
        tmp = f"{path}.tmp.{self.pid}"
        with open(tmp, "w") as f:
            json.dump(self.to_dict(metrics, device=device), f)
            f.write("\n")
        os.replace(tmp, path)
