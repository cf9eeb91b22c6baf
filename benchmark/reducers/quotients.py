"""Readers that divide one thing the program counted by another: a
counter over a counter or over a family of counters, a span's time over
a counter.  A program without either (one that predates the counters)
has nothing to read: ``None``, and the line leaves the metric out."""

from __future__ import annotations

from .spans import _median, _span_s


def counter_quotient(run, numerator, denominator):
    """One counter over another, median over the window's jobs."""
    def one(job):
        num, den = (job["counters"].get(k) for k in (numerator, denominator))
        return None if num is None or not den else num / den
    return _median(one(j) for j in run["jobs"])


def span_per_counter(run, spans, counter, scale):
    """Seconds in the named spans times ``scale`` (1e6: microseconds)
    per unit of a counter, median over the window's jobs."""
    def one(job):
        units = job["counters"].get(counter)
        if not units or not any(job["spans"].get(n) for n in spans):
            return None
        return scale * _span_s(job, spans) / units
    return _median(one(j) for j in run["jobs"])


def counter_share(run, numerator, denominator_prefix):
    """Percent: a counter over the sum of the counters with a prefix,
    over the window's jobs (``spans.counter_ratio``), but nothing where
    no job has the numerator: a program that does not count it."""
    jobs = [j for j in run["jobs"] if numerator in j["counters"]]
    den = sum(v for j in jobs for k, v in j["counters"].items()
              if k.startswith(denominator_prefix))
    if not den:
        return None
    return 100.0 * sum(j["counters"][numerator] for j in jobs) / den


REDUCERS = {"counter_quotient": counter_quotient,
            "span_per_counter": span_per_counter,
            "counter_share": counter_share}
