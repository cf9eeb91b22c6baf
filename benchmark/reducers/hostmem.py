"""Reader of the host's memory: the process's peak resident set as the
program counts it when a job closes (``job.rss.peak_mb``, MiB, once a
job: ``racon_tpu/polisher._close_job``).  A program without the counter
(every one before PR 50) has nothing to read: ``None``, and the line
leaves the metric out."""

from __future__ import annotations

MIB = 1 << 20


def peak_rss_gb(run):
    """The largest peak RSS any window job closed with, in GB: what the
    serving process held at most (data parsed, windows, two packed
    launches in flight, the journal), which is a deployment's host
    memory.  The peak is the process's, so it never falls from one job
    to the next."""
    peaks = [j["counters"]["job.rss.peak_mb"] for j in run["jobs"]
             if "job.rss.peak_mb" in j["counters"]]
    return max(peaks) * MIB / 1e9 if peaks else None


REDUCERS = {"peak_rss_gb": peak_rss_gb}
