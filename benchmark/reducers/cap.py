"""Reader for the depth-cap cell: one counter over the sum of counters
named one by one.

``quotients.counter_share`` sums every counter with a prefix, which is
too wide where a family has grown members that are not parts of the
whole: ``poa.layers.`` holds the layers' bases beside their counts, and
``poa.windows.rung.`` the rule's misses beside the rungs.  The program
counts, once a chunk (``racon_tpu/ops/poa_driver.py``):
``poa.layers.capped`` (layers ``DEPTH_CAP`` dropped),
``poa.layers.capped.bases``, ``poa.windows.capped``; once a launch:
``poa.layers.admitted``, ``poa.windows.rung.<rung>``,
``poa.windows.trim.admitted`` / ``.full``, ``poa.windows.capped.redone``.
A program without the numerator has nothing to read: ``None``, and the
line leaves the metric out.
"""

from __future__ import annotations


def counter_over_sum(run, numerator, counters):
    """Percent: one counter over the sum of the named ones, over the
    window's jobs that have the numerator."""
    jobs = [j for j in run["jobs"] if numerator in j["counters"]]
    den = sum(j["counters"].get(c, 0) for j in jobs for c in counters)
    if not den:
        return None
    return 100.0 * sum(j["counters"][numerator] for j in jobs) / den


REDUCERS = {"counter_over_sum": counter_over_sum}
