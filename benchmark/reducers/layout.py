"""Reader for the raw-layout cell that the older ones do not serve: a
span's share of another span.  The cell's other ``raw_*`` metrics read
the program's counters through ``quotients.py`` / ``deep.py`` and the
device trace through ``device.py`` / ``deep.py``.

A program without the spans (every one before PR 39 has no root span
``job``) has nothing to read: ``None``, and the line leaves the metric
out.
"""

from __future__ import annotations

from .spans import _median, _span_s


def span_share(run, spans, of):
    """Percent of the span ``of`` that the named spans take, median over
    the window's jobs.  The named spans are taken whole (they do not
    nest in one another where a metric's file lists them)."""
    def one(job):
        whole = _span_s(job, [of])
        if whole <= 0 or not any(job["spans"].get(n) for n in spans):
            return None
        return 100.0 * _span_s(job, spans) / whole
    return _median(one(j) for j in run["jobs"])


REDUCERS = {"span_share": span_share}
