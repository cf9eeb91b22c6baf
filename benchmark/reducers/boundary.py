"""Readers of the job boundary on the device's timeline: how long the
chip idles at the two ends of a job, and under which of the program's
spans.

A traced job is the benchmark's ``bench.job`` annotation.  Its **head**
is the idle gap from the annotation's start to the first device
operation inside it, its **tail** the gap from the last one to the
annotation's end, both on the chip that was busy least.  Each gap is
shared out **by overlap**: every nanosecond goes to the innermost span
that was open on the driver thread (the thread that opened the job's root
span ``job``), which is that span's self time inside the gap.  For that
the reader opens the job's own trace file, where each span carries its
thread (``tid``), its ``id`` and its ``parent``; ``run["jobs"][i]["spans"]``
keeps names and intervals only.  The file's path is in the job's report
(``trace``), or in its result where the harness keeps that.

A program whose spans carry no ids, or whose report names no trace, has
nothing to share a gap out by: the seconds still read, the unnamed share
returns ``None``.
"""

from __future__ import annotations

import json

from .. import xplane

#: what the driver thread was inside when nothing more specific was open
NO_SPAN = "(no span)"


def _gaps(trace, chip):
    """Per annotated job: (job id, head (lo, hi), tail (lo, hi)) on the
    profiler's clock; a job without a device operation is all head."""
    out = []
    for job_id, ev in trace.jobs:
        idle = xplane.gaps(trace.ops[chip], ev.start, ev.end)
        head = idle[0] if idle and idle[0][0] == ev.start else None
        tail = (idle[-1] if idle and idle[-1][1] == ev.end
                and idle[-1] != head else None)
        out.append((job_id, head or (ev.start, ev.start),
                    tail or (ev.end, ev.end)))
    return out


def _trace_path(job: dict):
    return ((job.get("result") or {}).get("trace")
            or (job.get("report") or {}).get("trace"))


def _driver_spans(job: dict, after: dict = None):
    """The spans of the job's driver thread as (start, end, depth, name)
    on the profiler's clock, from the job's trace file; ``None`` where
    the file, the ids or the root span are missing.  ``after`` is the
    job that ran next: its file holds this job's second trace write as
    ``job.release.prev``, with the start it really had."""
    def events(j):
        path = _trace_path(j)
        if not path:
            return None, []
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            return None, []
        return (doc.get("otherData", {}).get("t0_monotonic_ns"),
                [e for e in doc.get("traceEvents", [])
                 if e.get("ph") == "X" and isinstance(e.get("id"), int)])

    offset = job.get("clock_offset_ns")
    t0, spans = events(job)
    root = next((e for e in spans if e["name"] == "job"), None)
    if offset is None or t0 is None or root is None:
        return None
    by_id = {e["id"]: e for e in spans}

    def depth(e):
        n, seen = 0, set()
        while e is not None and e["id"] not in seen:
            seen.add(e["id"])
            e = by_id.get(e.get("parent"))
            n += 1
        return n

    out = []
    for e in spans:
        if e.get("tid") == root["tid"] and e["name"] != "job.release.prev":
            lo = t0 + e["ts"] * 1000 - offset
            out.append((lo, lo + e["dur"] * 1000, depth(e), e["name"]))
    if after is not None:
        for e in events(after)[1]:
            args = e.get("args") or {}
            if (e["name"] == "job.release.prev"
                    and args.get("job") == job["id"]
                    and isinstance(args.get("t0_mono_ns"), int)):
                lo = args["t0_mono_ns"] - offset
                out.append((lo, lo + e["dur"] * 1000, 1, e["name"]))
    return out


def _share_out(gap, spans) -> dict:
    """Seconds of the gap (lo, hi) per span name: each stretch between
    two span edges goes to the deepest span that covers it (the latest
    begun among equals), or to ``NO_SPAN``."""
    lo, hi = gap
    inside = [s for s in spans if s[1] > lo and s[0] < hi]
    edges = sorted({lo, hi} | {min(max(t, lo), hi)
                               for s in inside for t in s[:2]})
    out = {}
    for a, b in zip(edges, edges[1:]):
        cover = [s for s in inside if s[0] <= a and s[1] >= b]
        name = (max(cover, key=lambda s: (s[2], s[0]))[3] if cover
                else NO_SPAN)
        out[name] = out.get(name, 0.0) + (b - a) / 1e9
    return out


def _boundary(run):
    """The note both readers share, made once a run: seconds of head and
    tail over the traced jobs, and where the spans allow it each shared
    out by span name."""
    note = run["notes"].get("boundary_idle")
    if note is not None:
        return note
    trace, reduced = run.get("trace"), run.get("device") or {}
    chip = reduced.get("worst_chip")
    if trace is None or chip not in trace.ops or not trace.jobs:
        return None
    by_id = {j["id"]: (j, run["jobs"][i + 1] if i + 1 < len(run["jobs"])
                       else None) for i, j in enumerate(run["jobs"])}
    note = {"chip": chip, "traced_jobs": len(trace.jobs),
            "head_s": 0.0, "tail_s": 0.0, "head_by_span_s": {},
            "tail_by_span_s": {}, "jobs_shared_out": 0}
    for job_id, head, tail in _gaps(trace, chip):
        note["head_s"] += (head[1] - head[0]) / 1e9
        note["tail_s"] += (tail[1] - tail[0]) / 1e9
        job, after = by_id.get(job_id, (None, None))
        spans = _driver_spans(job, after) if job is not None else None
        if spans is None:
            continue
        note["jobs_shared_out"] += 1
        for key, gap in (("head_by_span_s", head), ("tail_by_span_s", tail)):
            for name, s in _share_out(gap, spans).items():
                note[key][name] = note[key].get(name, 0.0) + s
    run["notes"]["boundary_idle"] = note
    return note


def boundary_idle_s(run):
    """Seconds a traced job leaves the least busy chip idle at its two
    ends (annotation start to first device operation, last one to
    annotation end), mean over the traced jobs."""
    note = _boundary(run)
    if note is None:
        return None
    return (note["head_s"] + note["tail_s"]) / note["traced_jobs"]


def boundary_idle_unnamed_share(run, unnamed_prefixes):
    """Percent of that idle during which the driver thread's innermost
    open span was the root, one of the ``unnamed_prefixes`` (``phase.``:
    a whole phase names no seam) or none at all: what the spans still
    cannot see at the boundary.  Only over jobs whose trace file allowed
    the sharing out; ``None`` where none did."""
    note = _boundary(run)
    if note is None or not note["jobs_shared_out"]:
        return None
    total = unnamed = 0.0
    for key in ("head_by_span_s", "tail_by_span_s"):
        for name, s in note[key].items():
            total += s
            if (name in (NO_SPAN, "job")
                    or name.startswith(tuple(unnamed_prefixes))):
                unnamed += s
    return 100.0 * unnamed / total if total else None


REDUCERS = {
    "boundary_idle_s": boundary_idle_s,
    "boundary_idle_unnamed_share": boundary_idle_unnamed_share,
}
