"""Readers of the program's spans, report extras and counters, and of
the harness's own set-up facts."""

from __future__ import annotations

import statistics

from .. import judge


def _median(values):
    values = [v for v in values if v is not None]
    return statistics.median(values) if values else None


def _span_s(job: dict, names) -> float:
    return sum(dur for n in names for _, dur in job["spans"].get(n, ())) / 1e9


def fact(run, key):
    """A number ``run.py`` took itself (set-up, device, host oracle)."""
    return run["facts"].get(key)


def first_job_excess(run):
    """Warm-up job wall minus the median window job wall: what the first
    job of a process pays on top of a job (trace + lower, cache load)."""
    first = run["facts"].get("first_job_wall_s")
    med = _median(j["wall_s"] for j in run["jobs"])
    return None if first is None or med is None else first - med


def span_s_per_mbp(run, spans):
    """Seconds in the named spans per polished Mbp, median over the
    window's jobs; durations from each job's trace file."""
    return _median(_span_s(j, spans) / (j["polished_bp"] / 1e6)
                   for j in run["jobs"] if j["polished_bp"])


def _extra(job, phase, key):
    return ((job["phases"].get(phase) or {}).get("extra") or {}).get(key, 0.0)


def overhead_share(run, span, phase, walls):
    """Percent of a phase's span not inside the phase's blocking kernel
    and pack calls: 1 - sum(extra[walls]) / span, median over jobs."""
    def one(job):
        total = _span_s(job, [span])
        if total <= 0:
            return None
        return 100.0 * (1 - sum(_extra(job, phase, w) for w in walls) / total)
    return _median(one(j) for j in run["jobs"])


def wall_per_unit_ms(run, phase, wall, tier):
    """A phase's ``extra[wall]`` over the units ``tier`` served, in ms,
    median over the window's jobs (never the warm-up job, whose
    ``kernel_wall_s`` holds trace + lower)."""
    def one(job):
        served = (job["phases"].get(phase) or {}).get("served", {}).get(tier)
        return 1e3 * _extra(job, phase, wall) / served if served else None
    return _median(one(j) for j in run["jobs"])


def counter_ratio(run, numerator, denominator_prefix):
    """Percent: a counter over the sum of the counters with a prefix,
    over the window's jobs."""
    num = sum(j["counters"].get(numerator, 0) for j in run["jobs"])
    den = sum(v for j in run["jobs"] for k, v in j["counters"].items()
              if k.startswith(denominator_prefix))
    return 100.0 * num / den if den else None


def served_share(run):
    return judge.device_served_share([j["phases"] for j in run["jobs"]])


def residual(run, which):
    edits = run["edits"].get(which)
    if edits is None:
        return None
    return judge.residual_err_per_100kb(edits, run["data"]["truth_bp"])


REDUCERS = {
    "fact": fact,
    "first_job_excess": first_job_excess,
    "span_s_per_mbp": span_s_per_mbp,
    "overhead_share": overhead_share,
    "wall_per_unit_ms": wall_per_unit_ms,
    "counter_ratio": counter_ratio,
    "served_share": served_share,
    "residual": residual,
}
