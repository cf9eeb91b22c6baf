"""The benchmark's arithmetic and its verdict, apart from any run.

Everything here is a pure function of job results, reports and edit
distances, so ``benchmark/tests`` can pin it on canned inputs.  The rules
of a healthy report are a copy of ``chip_smoke.judge_report`` (PR 21),
taken so that a later change to the smoke cannot loosen ``correct``.
"""

from __future__ import annotations

import statistics

#: tiers that run on the device, per phase; everything else a report
#: counts (``host``, ``backbone``, ``journal``) did not
DEVICE_TIERS = {"consensus": ("ls", "v2", "xla"),
                "alignment": ("hirschberg", "xla")}

#: device edit distance to the truth may exceed the host's by 10 %, or by
#: 2 edits per 10 kb of truth where that is more: the two paths break
#: ties differently (the smoke's rule and reason, PR 21) ...
DEVICE_VS_HOST_MARGIN = 0.10
DEVICE_VS_HOST_PER_BP = 2e-4
#: ... and polishing must leave less than a quarter of the draft's edits
POLISH_MAX_SHARE_OF_DRAFT = 0.25

#: metrics whose source is the benchmark's own work on the host but that
#: are counts, not timings, so a CPU rehearsal may print them
COUNTED_ON_THE_HOST = ("err_removed_vs_host", "residual_err_per_100kb",
                       "host_residual_err_per_100kb")



def is_a_count(metric: dict) -> bool:
    """True for a metric that counts and does not time, which is all a
    CPU rehearsal may print."""
    return (metric["name"] in COUNTED_ON_THE_HOST
            or (metric["source"] == "program_counter"
                and metric["unit"] in ("%", "count")))


#: instant events of the program's trace that mean a job left its tier
BAD_EVENTS = ("lattice.demote", "lattice.quarantine", "watchdog.timeout")


#: completed window jobs ``correct`` needs: the median of fewer is one
#: job's wall, and one job shows nothing of the loop between two
LEAST_JOBS = 2


def median_says_fits(elapsed_s: float, walls: list, seconds: float) -> bool:
    """The running median job wall says another job ends inside the
    window."""
    return elapsed_s + statistics.median(walls) <= seconds


def next_job_fits(elapsed_s: float, walls: list, seconds: float) -> bool:
    """The stop rule of the closed loop.  The first job always starts.
    While fewer than ``LEAST_JOBS`` are done, the next starts if the one
    before it ended inside the window: the open window is the evidence,
    not a median of one sample, which would count a stall in the first
    job twice (once in ``elapsed_s``, once as the forecast).  After that
    another starts only while the running median job wall says it ends
    inside the window."""
    if not walls:
        return True
    if len(walls) < LEAST_JOBS:
        return elapsed_s < seconds
    return median_says_fits(elapsed_s, walls, seconds)


def served_units(phases: dict) -> tuple:
    """(units a device tier served, units that needed serving) of one
    job's report phases.  Windows the backbone served needed no
    consensus; replayed units would be ``journal`` and count as not
    served by the device (a window job never replays)."""
    device = needed = 0
    for phase, tiers in DEVICE_TIERS.items():
        ph = phases.get(phase)
        if not ph:
            continue
        served = ph.get("served", {})
        device += sum(served.get(t, 0) for t in tiers)
        needed += ph.get("total", 0) - served.get("backbone", 0)
    return device, needed


def device_served_share(all_phases: list):
    """Percent of the window's work units a device tier served."""
    device = needed = 0
    for phases in all_phases:
        d, n = served_units(phases)
        device += d
        needed += n
    return 100.0 * device / needed if needed else None


def polished_mbp_per_s(polished_bp: list, completions_s: list):
    """The median over the window's jobs of polished bases over the wall
    the job took in the loop: from the completion before it (the
    window's start for the first) to its own, so that whatever the loop
    does between two jobs is counted.  The median and not the total over
    the wall to the last completion: the chip machines share their
    host's cores, and a burst of another tenant's work that slows one or
    two of a window's jobs then moves nothing."""
    if not polished_bp or len(polished_bp) != len(completions_s):
        return None
    starts = [0.0] + list(completions_s[:-1])
    if any(done <= start for start, done in zip(starts, completions_s)):
        return None
    return statistics.median(
        bp / 1e6 / (done - start)
        for bp, start, done in zip(polished_bp, starts, completions_s))


def residual_err_per_100kb(edits: int, truth_bp: int) -> float:
    return edits / (truth_bp / 1e5)


def err_removed_vs_host(draft: int, host: int, device: int):
    """Of the draft errors the host path removes, the percent the device
    path removes, on the same inputs.  Paired with the host on one data
    set, so the seed's own scatter (a residual of ~15 edits per 100 kb is
    a Poisson count) cancels; one edit more or less moves it by
    1 / (draft - host)."""
    if draft <= host:
        return None
    return 100.0 * (draft - device) / (draft - host)


def accuracy_limits(draft: int, host: int, truth_bp: int) -> tuple:
    """(the most edits the device path may leave, the count it has to
    stay below) to the truth."""
    slack = max(DEVICE_VS_HOST_MARGIN * host,
                DEVICE_VS_HOST_PER_BP * truth_bp)
    return host + slack, POLISH_MAX_SHARE_OF_DRAFT * draft


def accuracy_problems(draft: int, host: int, device: int,
                      truth_bp: int) -> list:
    bad = []
    at_most, below = accuracy_limits(draft, host, truth_bp)
    if device > at_most:
        bad.append(f"device-polished edit distance {device} is more than "
                   f"{at_most - host:.0f} above the host's {host}")
    if device >= below:
        bad.append(f"device-polished edit distance {device} is not below "
                   f"a quarter of the draft's {draft}")
    return bad


def report_problems(report: dict, expect: dict, *, platform: str,
                    chips: int, interpreted: bool) -> list:
    """Problems with one served job's report ([] = healthy).  ``expect``
    is the cell's (``workloads/<cell>.json``): ``alignment`` says whether
    the job has a device alignment phase, the shares are the least a
    healthy run serves from each device tier."""
    bad = []
    dev = report.get("device") or {}
    if dev.get("platform") != platform or dev.get("count") != chips:
        bad.append(f"report's device {dev}, expected {platform} x{chips}")
    phases = report.get("phases") or {}
    counters = ((report.get("obs") or {}).get("metrics") or {}).get(
        "counters") or {}

    for name, ph in phases.items():
        if ph.get("degradations"):
            bad.append(f"{name} degraded: {ph['degradations']}")
        for key in ("retries", "bisections", "quarantined"):
            if ph.get(key):
                bad.append(f"{name} {key}: {ph[key]}")
        if ph.get("served", {}).get("journal"):
            bad.append(f"{name} replayed {ph['served']['journal']} units "
                       "from a journal")
        kernels = (ph.get("extra") or {}).get("kernels")
        if kernels and bool(kernels.get("interpreted")) != interpreted:
            bad.append(f"{name} kernels interpreted="
                       f"{kernels.get('interpreted')}")

    cons = phases.get("consensus")
    if not cons or not cons.get("total"):
        bad.append("no consensus phase in the report")
    else:
        served = cons.get("served", {})
        kernel_windows = max(cons["total"] - served.get("backbone", 0), 1)
        for tier in expect.get("consensus_tiers_at_zero", ()):
            if served.get(tier, 0):
                bad.append(f"consensus tier {tier} served {served[tier]}")
        tier, least = expect["consensus_tier"], expect["consensus_min_share"]
        if served.get(tier, 0) < least * kernel_windows:
            bad.append(f"{tier} served {served.get(tier, 0)} of "
                       f"{kernel_windows} windows (< {least:.0%})")

    ali = phases.get("alignment")
    if expect.get("alignment"):
        if not ali or not ali.get("total"):
            bad.append("no alignment jobs in the report")
        else:
            served = ali.get("served", {})
            for tier in expect.get("alignment_tiers_at_zero", ()):
                if served.get(tier, 0):
                    bad.append(f"alignment tier {tier} served "
                               f"{served[tier]}")
            tier = expect["alignment_tier"]
            least = expect["alignment_min_share"]
            if served.get(tier, 0) < least * ali["total"]:
                bad.append(f"{tier} served {served.get(tier, 0)} of "
                           f"{ali['total']} jobs (< {least:.0%})")
    elif ali and ali.get("total"):
        bad.append(f"{ali['total']} alignment jobs in a cell without an "
                   "alignment phase")

    if counters.get("shard.demotions", 0):
        bad.append(f"shard demotions: {counters['shard.demotions']}")
    if chips > 1:
        rows = [counters.get(f"shard.rows.d{i}", 0) for i in range(chips)]
        if not all(rows):
            bad.append(f"rows not spread over all devices: {rows}")
    return bad


def window_problems(job: dict) -> list:
    """What may not happen inside the measured window, from one job's
    facts (``run.py`` gathers them): a compile, a kernel build, an event
    that means a tier was left."""
    bad = []
    if job.get("cache_misses"):
        bad.append(f"{job['cache_misses']} compile-cache misses")
    if job.get("kernel_builds"):
        bad.append(f"{job['kernel_builds']} kernel builds")
    if job.get("journal_replayed"):
        bad.append(f"{job['journal_replayed']} units replayed")
    bad += [f"event {name}" for name in job.get("events", ())
            if name in BAD_EVENTS]
    return bad
