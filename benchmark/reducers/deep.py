"""Readers for the deep-coverage cell: what the node rungs did, and a
roofline of the consensus kernel whose operations come from the graphs
the run really built.

The program counts, once per launch (``racon_tpu/ops/poa_driver.py``):
``poa.windows.rung.<rung>`` (windows dispatched on each node rung),
``poa.nodes.used`` / ``poa.nodes.capacity`` (the kernel's node count and
the rung's slots, over the windows the device served),
``poa.windows.overflow.<cause>`` (windows the kernel gave up, by cause),
``poa.layers.admitted`` / ``.bases`` / ``.capped``.  A program without
them (every one before PR 35) has nothing to read: ``None``, and the
line leaves the metric out.
"""

from __future__ import annotations

import re

from .. import costs, xplane

_POA_WINDOWS = re.compile(r"^poa\.windows\.d(\d+)\.c(\d+)$")


def counter_family_share(run, numerator_prefix, denominator):
    """Percent: the counters with a prefix, summed, over one counter,
    over the window's jobs; nothing where no job has a counter of the
    prefix."""
    jobs = [j for j in run["jobs"]
            if any(k.startswith(numerator_prefix) for k in j["counters"])]
    den = sum(j["counters"].get(denominator, 0) for j in jobs)
    if not den:
        return None
    return 100.0 * sum(v for j in jobs for k, v in j["counters"].items()
                       if k.startswith(numerator_prefix)) / den


def poa_ops_bytes(counters: dict, served: int) -> tuple:
    """(integer ops, HBM bytes) of the consensus DP a job's counters
    describe, from the graphs it built: every admitted layer base meets
    every node its window's graph holds at the time.  The graph grows
    from the backbone to the count the kernel reports, and concavely
    (most nodes come early), so the mean of the two ends is under the
    time average: the operations are a floor, and the share of the
    roofline with them.  ``costs.poa_ops_bytes`` fixes the graph at
    ``NODE_GROWTH`` = 2.0 x the window class whatever the depth; here it
    is (backbone + final nodes) / 2, which reads 2.3 x at 100 layers."""
    bases = counters.get("poa.layers.bases")
    used = counters.get("poa.nodes.used")
    if not bases or not used or not served:
        return 0.0, 0.0
    windows = backbone = 0
    for key, val in counters.items():
        m = _POA_WINDOWS.match(key)
        if m:
            windows += val
            backbone += val * int(m.group(2))
    if not windows:
        return 0.0, 0.0
    mean_graph = (backbone / windows + used / served) / 2
    ops = bases * mean_graph * costs.POA_OPS_PER_CELL
    byts = bases * costs.POA_LAYER_BYTES + 2 * backbone * 5
    return ops, byts


def roofline(run, phase_span, op_patterns):
    """``device.roofline`` for the consensus kernel with
    :func:`poa_ops_bytes` as the cost: least time for the DP cells the
    traced jobs' graphs needed over the kernel's device time inside the
    program's ``phase_span``, percent."""
    trace, rate = run.get("trace"), run["facts"].get("int32_ops_per_s")
    if trace is None or not rate:
        return None
    traced = [j for j in run["jobs"] if j.get("clock_offset_ns") is not None]
    intervals = [(s - j["clock_offset_ns"], s + d - j["clock_offset_ns"])
                 for j in traced for s, d in j["spans"].get(phase_span, ())]
    seconds = xplane.kernel_seconds(trace, op_patterns, intervals)
    if seconds <= 0:
        return None
    ops = byts = 0.0
    for j in traced:
        served = ((j["phases"].get("consensus") or {}).get("served")
                  or {}).get("ls", 0)
        o, b = poa_ops_bytes(j["counters"], served)
        ops, byts = ops + o, byts + b
    if ops <= 0:
        return None
    chips = max(len(trace.ops), 1)
    t_ops = ops / (rate * chips)
    t_bytes = byts / (run["peaks"]["hbm_bytes_per_s"] * chips)
    run["notes"]["deep_poa_roofline"] = {
        "kernel_device_s": seconds, "int_ops": ops, "hbm_bytes": byts,
        "least_s_ops": t_ops, "least_s_bytes": t_bytes,
        "binds": "int32 ops" if t_ops >= t_bytes else "HBM bytes",
        "int32_ops_per_s": rate, "traced_jobs": len(traced)}
    return 100.0 * max(t_ops, t_bytes) / seconds


REDUCERS = {"counter_family_share": counter_family_share,
            "deep_roofline": roofline}
