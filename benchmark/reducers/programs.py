"""Readers of the program cache (``racon_tpu/ops/kernel_cache.Program``,
PR 42): how many of the process's kernel programs were loaded from
``<compile cache dir>/programs/`` and how many it had to trace, lower
and write.  A program without the cache (its ``cache_traffic()`` has no
such counts) has nothing to read: ``None``, and the line leaves the
metric out."""

from __future__ import annotations


def program_cache_hit_share(run):
    """Percent of the process's kernel programs that came from the
    program cache: hits over hits + misses (``device.cache_traffic()``,
    process lifetime, so ``warm_for_target`` is covered).  0 on a tree's
    first run, 100 on every later one.  The note carries the counts and
    the seconds of read + deserialize."""
    from racon_tpu import device

    traffic = device.cache_traffic()
    hits, misses = (traffic.get(k) for k in ("program_hits",
                                             "program_misses"))
    if hits is None or misses is None or not hits + misses:
        return None
    run["notes"]["program_cache"] = {
        "hits": hits, "misses": misses,
        "skipped": traffic.get("program_skipped", 0),
        "program_load_s": traffic.get("program_load_s", 0.0)}
    return 100.0 * hits / (hits + misses)


REDUCERS = {"program_cache_hit_share": program_cache_hit_share}
