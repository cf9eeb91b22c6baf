"""Readers of the profiler trace: idle share, a kernel's roofline share."""

from __future__ import annotations

from .. import costs, xplane


def idle_share(run):
    """Percent of the traced window in which no operation ran on the
    chip that was busy least."""
    return (run.get("device") or {}).get("idle_share_worst_chip")


def roofline(run, kernel, phase_span, op_patterns):
    """Least time the chip could take for the kernel's DP cells over the
    kernel's device time in the trace, percent.

    The kernels carry no stable name yet (all are ``kernel`` inside a
    jitted ``fn``), so a device operation is the kernel's when its name
    matches ``op_patterns`` (data, in the metric's file) *and* it starts
    inside the program's ``phase_span`` of a traced job, placed on the
    profiler's clock through the benchmark's own annotation.  Least time
    is the larger of bytes over the published HBM bandwidth and integer
    ops over the int32 elementwise rate ``probe.py`` measured in this
    run; ``run["notes"]`` says which term binds.
    """
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
        if kernel == "poa":
            o, b = costs.poa_ops_bytes(j["counters"])
        else:
            o, b = costs.align_ops_bytes(j["counters"],
                                         run["data"].get("pair_bases", 0))
        ops, byts = ops + o, byts + b
    if ops <= 0:
        return None
    chips = max(len(trace.ops), 1)
    t_ops = ops / (rate * chips)
    t_bytes = byts / (run["peaks"]["hbm_bytes_per_s"] * chips)
    run["notes"][f"{kernel}_roofline"] = {
        "kernel_device_s": seconds, "int_ops": ops, "hbm_bytes": byts,
        "least_s_ops": t_ops, "least_s_bytes": t_bytes,
        "binds": "int32 ops" if t_ops >= t_bytes else "HBM bytes",
        "int32_ops_per_s": rate, "traced_jobs": len(traced)}
    return 100.0 * max(t_ops, t_bytes) / seconds


REDUCERS = {"idle_share": idle_share, "roofline": roofline}
