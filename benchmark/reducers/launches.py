"""Readers of the program's launch-level spans and counters and of its
jit-stage clock (``racon_tpu/device.py``): device wait per unit, the
share of a span no named span inside it covers, counters per unit, and
set-up's share of trace + lower.

A program that predates these spans and counters has nothing for them to
read: every reader then returns ``None`` and the line leaves the metric
out.
"""

from __future__ import annotations

from .. import xplane
from .spans import _median, _span_s


def _served(job: dict, phase: str, tier: str):
    return (job["phases"].get(phase) or {}).get("served", {}).get(tier)


def _named(job: dict, names) -> list:
    """The job's span names a list asks for; an entry that ends in ``*``
    asks for every name with that prefix (``jit.*``)."""
    exact = {n for n in names if not n.endswith("*")}
    prefixes = tuple(n[:-1] for n in names if n.endswith("*"))
    return sorted(n for n in job["spans"]
                  if n in exact or (prefixes and n.startswith(prefixes)))


def span_per_unit_ms(run, spans, phase, tier):
    """Milliseconds in the named spans per unit ``tier`` served in
    ``phase``, median over the window's jobs."""
    def one(job):
        served = _served(job, phase, tier)
        if not served or not any(job["spans"].get(n) for n in spans):
            return None
        return 1e3 * _span_s(job, spans) / served
    return _median(one(j) for j in run["jobs"])


def _covered_ns(outer: list, inner: list) -> float:
    """Nanoseconds of the ``outer`` intervals that the union of the
    ``inner`` ones covers.  Intervals are ``(start, dur)``; the inner
    ones may nest, overlap, come from other threads or reach outside."""
    events = [xplane.Event("", s, d) for s, d in inner]
    return sum(xplane.busy_ns(events, lo, lo + dur) for lo, dur in outer)


def uncovered_share(run, span, inside):
    """Percent of a span's time that the union of the named spans inside
    it does not cover, median over the window's jobs.  With the launches
    as ``inside`` it is the host's share of a cohort; with every named
    part of a phase it is what the spans still cannot see.  A job in
    which none of the ``inside`` spans occurs has nothing to read."""
    note = {}

    def one(job):
        outer = job["spans"].get(span) or []
        names = _named(job, inside)
        total = sum(d for _, d in outer)
        if total <= 0 or not names:
            return None
        inner = [iv for n in names for iv in job["spans"][n]]
        share = 100.0 * (1 - _covered_ns(outer, inner) / total)
        note.update(
            span_s=total / 1e9, uncovered_s=share / 100 * total / 1e9,
            inside_s={n: _covered_ns(outer, job["spans"][n]) / 1e9
                      for n in names},
            trace_events=sum(len(v) for v in job["spans"].values())
            + len(job.get("events") or ()))
        return share

    value = _median(one(j) for j in run["jobs"])
    if note:
        # of the last window job: where the span's time is, by name
        # (nested names count twice here, never in the share), and how
        # many events the job's trace file holds
        run["notes"][f"uncovered_share:{span}"] = note
    return value


def counter_per_unit(run, counters, phase, tier):
    """Sum of the named counters per unit ``tier`` served in ``phase``,
    median over the window's jobs."""
    def one(job):
        served = _served(job, phase, tier)
        if not served or not any(c in job["counters"] for c in counters):
            return None
        return sum(job["counters"].get(c, 0) for c in counters) / served
    return _median(one(j) for j in run["jobs"])


def counter_sum(run, counters, witness):
    """Sum of the named counters over the window's jobs.  A counter that
    never fired is absent from a job, which reads as 0 only when the job
    has a counter of the ``witness`` prefix: the program counts launches
    at all."""
    jobs = [j for j in run["jobs"]
            if any(k.startswith(witness) for k in j["counters"])]
    if not jobs:
        return None
    return sum(j["counters"].get(c, 0) for j in jobs for c in counters)


def setup_trace_lower_s(run):
    """Seconds this process spent tracing and lowering (the program's
    ``jax.monitoring`` listener, process lifetime) less the window jobs'
    own ``jit.trace`` + ``jit.lower`` spans: set-up's share.  The note
    lists the functions that cost most, by the name they were jitted
    under."""
    from racon_tpu import device

    traffic = device.cache_traffic()
    if "trace_s" not in traffic or "lower_s" not in traffic:
        return None
    in_window = sum(_span_s(j, ["jit.trace", "jit.lower"])
                    for j in run["jobs"])
    by_fun = sorted(traffic.get("by_fun", {}).items(),
                    key=lambda kv: -(kv[1]["trace_s"] + kv[1]["lower_s"]))
    run["notes"]["trace_lower_s"] = {
        "trace_s": traffic["trace_s"], "lower_s": traffic["lower_s"],
        "traces": traffic["traces"], "lowerings": traffic["lowerings"],
        "in_window_s": in_window,
        "by_fun": {f: {k: round(v, 3) for k, v in row.items()}
                   for f, row in by_fun[:12]}}
    return traffic["trace_s"] + traffic["lower_s"] - in_window


REDUCERS = {
    "span_per_unit_ms": span_per_unit_ms,
    "uncovered_share": uncovered_share,
    "counter_per_unit": counter_per_unit,
    "counter_sum": counter_sum,
    "setup_trace_lower_s": setup_trace_lower_s,
}
