"""Reduces a ``jax.profiler`` trace (``.xplane.pb``) to the benchmark's
device numbers: busy and idle time per chip, time per device operation,
idle gaps labelled by what the host was doing.

What is a device plane, which of its lines hold operations and how the
benchmark's own annotations are named is data (``trace_layout.json``).
``benchmark/tests`` checks the arithmetic on a small trace kept beside
them.  Timestamps are nanoseconds on the profiler's clock, which starts
near 0 at ``start_trace``.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field


@dataclass
class Event:
    name: str
    start: float
    dur: float

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclass
class DeviceTrace:
    """Operations per chip and the benchmark's job annotations."""

    ops: dict = field(default_factory=dict)       # chip -> [Event]
    op_line: dict = field(default_factory=dict)   # chip -> line name used
    jobs: list = field(default_factory=list)      # [(job id, Event)]
    planes: dict = field(default_factory=dict)    # plane -> {line: count}


def newest_xplane(trace_dir: str):
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def read(path: str, layout: dict, text_proto: bool = False) -> DeviceTrace:
    from jax.profiler import ProfileData

    if text_proto:
        with open(path) as f:
            data = ProfileData.from_text_proto(f.read())
    else:
        data = ProfileData.from_file(path)
    dev_re = re.compile(layout["device_plane"])
    host_re = re.compile(layout["host_plane"])
    out = DeviceTrace()
    for plane in data.planes:
        lines = {}
        for line in plane.lines:
            lines.setdefault(line.name, []).append(line)
        out.planes[plane.name] = {}
        m = dev_re.match(plane.name)
        if m:
            chip = int(m.group(1))
            wanted = next((names for names in (layout["op_lines"],
                                               layout["fallback_lines"])
                           if any(n in lines for n in names)), [])
            out.op_line[chip] = [n for n in wanted if n in lines]
            out.ops[chip] = sorted(
                (Event(e.name, e.start_ns, e.duration_ns)
                 for n in out.op_line[chip] for ln in lines[n]
                 for e in ln.events), key=lambda e: (e.start, -e.dur))
        for name, lns in lines.items():
            n = 0
            for ln in lns:
                for e in ln.events:
                    n += 1
                    if (host_re.match(plane.name)
                            and e.name == layout["annotation"]):
                        stats = dict(e.stats)
                        out.jobs.append((
                            str(stats.get(layout["annotation_stat"], "")),
                            Event(e.name, e.start_ns, e.duration_ns)))
            out.planes[plane.name][name] = n
    out.jobs.sort(key=lambda j: j[1].start)
    return out


# -- interval arithmetic ---------------------------------------------------

def merged(events, lo: float, hi: float) -> list:
    """Union of the events' intervals, clipped to [lo, hi], as a sorted
    list of disjoint (start, end)."""
    out = []
    for e in sorted(events, key=lambda e: e.start):
        s, t = max(e.start, lo), min(e.end, hi)
        if t <= s:
            continue
        if out and s <= out[-1][1]:
            if t > out[-1][1]:
                out[-1] = (out[-1][0], t)
        else:
            out.append((s, t))
    return out


def busy_ns(events, lo: float, hi: float) -> float:
    return sum(t - s for s, t in merged(events, lo, hi))


def gaps(events, lo: float, hi: float) -> list:
    """The idle intervals of [lo, hi]: its complement of the union."""
    out, at = [], lo
    for s, t in merged(events, lo, hi):
        if s > at:
            out.append((at, s))
        at = t
    if hi > at:
        out.append((at, hi))
    return out


_HLO = re.compile(r"^(%\S+) = .*? ([\w\-]+)\((.*)$", re.DOTALL)
_SHAPE = re.compile(r"\b[a-z]+\d*\[([\d,]*)\]")


def short_name(name: str, limit: int = 120) -> str:
    """A device op's name in the trace is its whole HLO text; keep the
    result name, the opcode and the operands' dimensions (what tells two
    geometries of one kernel apart), run-length encoded."""
    m = _HLO.match(name)
    if not m:
        return name[:limit]
    dims, runs = _SHAPE.findall(m.group(3).split("), ")[0]), []
    for d in dims:
        if runs and runs[-1][0] == d:
            runs[-1][1] += 1
        else:
            runs.append([d, 1])
    shapes = " ".join(f"[{d}]" + (f"x{n}" if n > 1 else "")
                      for d, n in runs)
    return f"{m.group(1)} {m.group(2)} {shapes}"[:limit].rstrip()


def self_times(events) -> dict:
    """Seconds per operation name, each event counted without the time
    of the events nested inside it (a ``while`` holds its body's ops on
    the same line), so that the names add up to the busy time."""
    total, stack = {}, []          # stack of [event, child time]

    def close(upto: float) -> None:
        while stack and stack[-1][0].end <= upto:
            ev, child = stack.pop()
            total[ev.name] = total.get(ev.name, 0.0) + max(
                ev.dur - child, 0.0)
            if stack:
                stack[-1][1] += ev.dur

    for e in events:                  # sorted by (start, -dur)
        close(e.start)
        stack.append([e, 0.0])
    close(float("inf"))
    return {k: v / 1e9 for k, v in total.items()}


# -- the reduction ---------------------------------------------------------

def window(trace: DeviceTrace):
    """[start of the first annotated job, end of the last] on the
    profiler's clock, or None without annotations."""
    if not trace.jobs:
        return None
    return (trace.jobs[0][1].start, max(ev.end for _, ev in trace.jobs))


def reduce(trace: DeviceTrace, label=None, top: int = 10) -> dict:
    """Device numbers of the traced window.

    ``label(t_ns)`` names what the host was doing at a time on the
    profiler's clock (the harness builds it from the program's spans);
    without it gaps are labelled by the job annotation they fall in.
    """
    win = window(trace)
    if win is None or not trace.ops:
        return {}
    lo, hi = win
    per_chip = {c: busy_ns(evs, lo, hi) for c, evs in trace.ops.items()}
    if not any(per_chip.values()):
        return {"window_s": (hi - lo) / 1e9, "busy_s": 0.0,
                "chips": len(per_chip)}
    worst = min(per_chip, key=lambda c: per_chip[c])
    win_ns = hi - lo

    names = {}
    for evs in trace.ops.values():
        inside = [e for e in evs if e.end > lo and e.start < hi]
        for k, v in self_times(inside).items():
            k = short_name(k)
            names[k] = names.get(k, 0.0) + v / len(trace.ops)
    device_ops = sorted(names.items(), key=lambda kv: -kv[1])[:top]

    def job_label(t: float) -> str:
        for job, ev in trace.jobs:
            if ev.start <= t < ev.end:
                return f"in job {job}"
        return "between jobs"

    by_label = {}
    for s, t in gaps(trace.ops[worst], lo, hi):
        mid = (s + t) / 2
        what = (label(mid) if label else None) or job_label(mid)
        by_label[what] = by_label.get(what, 0.0) + (t - s) / 1e9
    idle_gaps = sorted(by_label.items(), key=lambda kv: -kv[1])[:top]

    return {
        "window_s": win_ns / 1e9,
        "busy_s": sum(per_chip.values()) / len(per_chip) / 1e9,
        "busy_s_per_chip": {str(c): v / 1e9
                            for c, v in sorted(per_chip.items())},
        "idle_share_worst_chip": 100.0 * (1 - per_chip[worst] / win_ns),
        "worst_chip": worst,
        "chips": len(per_chip),
        "op_lines": {str(c): v for c, v in trace.op_line.items()},
        "device_ops": [[k, v] for k, v in device_ops],
        "idle_gaps": [[k, v] for k, v in idle_gaps],
    }


def kernel_seconds(trace: DeviceTrace, patterns: list,
                   intervals: list) -> float:
    """Device seconds, averaged over chips, of the operations whose name
    matches one of ``patterns`` (case-insensitive regexes) and that start
    inside one of ``intervals`` (profiler-clock (lo, hi) pairs)."""
    regs = [re.compile(p, re.IGNORECASE) for p in patterns]
    matches = {}                  # names are whole HLO texts: match once

    def is_kernel(name: str) -> bool:
        if name not in matches:
            matches[name] = any(r.search(name) for r in regs)
        return matches[name]

    total = 0.0
    for evs in trace.ops.values():
        hit = [e for e in evs if is_kernel(e.name)
               and any(lo <= e.start < hi for lo, hi in intervals)]
        total += sum(t - s for s, t in merged(
            hit, float("-inf"), float("inf")))
    return total / max(len(trace.ops), 1) / 1e9
