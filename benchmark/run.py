#!/usr/bin/env python3
"""One run of one cell of racon-tpu's benchmark.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, which holds the cell's chips itself.  Set-up (all of it is
``setup_s``, process start to window start, always in this order): the
device, the native library built on this machine, the cell's data from
``--seed``, the host oracle, a ``PolishSession`` warmed for the cell's
window lengths, the cell's job once as the warm-up job.  Window: the
same job back to back through ``PolishSession.run_job`` (closed loop, one
client, fresh job id each time) while ``judge.next_job_fits`` says so:
the job that makes ``judge.LEAST_JOBS`` starts whenever the one before it
ended inside ``--seconds``, any further one while the running median job
wall says it ends inside.  After the window, outside the timing: bytes
compared, edit distances to the truth, and with ``--trace 1`` the
profiler trace reduced and the per-layer readers run.

The last line of stdout is the contract's JSON object; the last lines of
stderr are each number ``correct`` compared beside its limit and every
problem found.  Without a TPU
(or with another chip count than the cell's) it exits non-zero and
prints no result; ``JAX_PLATFORMS=cpu`` by name is the toy-size
rehearsal, which says ``"platform": "cpu"`` and writes no timing.
"""

from __future__ import annotations

import time

T0 = time.monotonic()          # process start, as near as Python gets

import argparse                # noqa: E402
import contextlib              # noqa: E402
import json                    # noqa: E402
import os                      # noqa: E402
import shutil                  # noqa: E402
import sys                     # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

NO_PROGRAM, NO_DEVICE = 2, 3   # exit codes; no result line with either


def say(msg: str) -> None:
    print(f"[bench +{time.monotonic() - T0:7.1f}s] {msg}", flush=True)


def parse_args(argv):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


class Refused(Exception):
    """The run cannot be made here; carries the exit code."""

    def __init__(self, code: int, why: str):
        super().__init__(why)
        self.code = code


def claim_device(cell, rehearsal: bool) -> dict:
    """First touch of the device: identity, chip count, peaks."""
    from racon_tpu import device as rt_device

    try:
        ident = rt_device.require_tpu()
    except rt_device.DeviceUnavailable as e:
        raise Refused(NO_DEVICE, str(e)) from None
    if ident["count"] != cell.chips:
        raise Refused(NO_DEVICE, f"cell {cell.name} needs {cell.chips} "
                      f"chip(s), JAX found {ident['count']} "
                      f"{ident['platform']} device(s)")
    peaks = cell.peaks.get(ident["device_kind"])
    if peaks and "same_as" in peaks:
        peaks = cell.peaks[peaks["same_as"]]
    if peaks is None and not rehearsal:
        raise Refused(NO_DEVICE, f"no peaks for device kind "
                      f"{ident['device_kind']!r} in benchmark/peaks.json")
    return {**ident, "peaks": peaks or {}}


def job_files(result: dict) -> dict:
    """What a finished job left on disk, read after the window: phases
    and counters of its report, spans and instant events of its trace
    file (exact durations; the report's span histograms are log2
    buckets), its FASTA."""
    with open(result["report"]) as f:
        report = json.load(f)
    with open(result["trace"]) as f:
        trace = json.load(f)
    with open(result["output"], "rb") as f:
        fasta = f.read()
    t0 = trace["otherData"]["t0_monotonic_ns"]
    spans, events = {}, []
    for e in trace["traceEvents"]:
        if e.get("ph") == "X":
            spans.setdefault(e["name"], []).append(
                (t0 + e["ts"] * 1000, e["dur"] * 1000))
        elif e.get("ph") == "i":
            events.append(e["name"])
    counters = ((report.get("obs") or {}).get("metrics") or {}).get(
        "counters") or {}
    return {"report": report, "phases": report.get("phases") or {},
            "counters": counters, "spans": spans, "events": events,
            "fasta": fasta}


def job_summary(job: dict) -> dict:
    """A job's facts for the detail file (no report, no bytes)."""
    out = {k: job[k] for k in (
        "id", "wall_s", "polished_bp", "kernel_builds", "cache_misses",
        "cache_requests", "events", "counters")}
    for key in ("done_s", "started_by_least"):   # none for the warm-up job
        out[key] = job.get(key)
    out["phases"] = {p: {k: ph.get(k) for k in ("total", "served", "wall_s",
                                                 "extra")}
                     for p, ph in job["phases"].items()}
    spans = sorted(job["spans"].items())
    out["span_s"] = {n: sum(d for _, d in items) / 1e9 for n, items in spans}
    out["span_n"] = {n: len(items) for n, items in spans}
    return out


def measure_window(run_job, seconds: float, trace_dir, n_traced: int):
    """The closed loop.  Returns (jobs, errors of jobs that raised);
    each job carries ``done_s``, its completion in seconds from the
    window's start, and ``started_by_least``: whether only the rule for
    the first ``judge.LEAST_JOBS`` jobs started it, where the running
    median said it would end after the window.  With
    ``n_traced`` the profiler runs around the first that many jobs, each
    inside the benchmark's own ``bench.job`` annotation."""
    import jax

    from benchmark import judge

    tracing = False
    if n_traced:
        shutil.rmtree(trace_dir, ignore_errors=True)
        # device ops and TraceMe annotations only: the Python tracer
        # would slow the host the trace is there to watch
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        tracing = True

    jobs, raised = [], []
    t_win = time.monotonic()
    try:
        while True:
            elapsed, walls = (time.monotonic() - t_win,
                              [j["wall_s"] for j in jobs])
            if not judge.next_job_fits(elapsed, walls, seconds):
                break
            by_least = bool(walls) and not judge.median_says_fits(
                elapsed, walls, seconds)
            job_id = f"w{len(jobs) + len(raised):04d}"
            annotate = (jax.profiler.TraceAnnotation("bench.job", job=job_id)
                        if tracing else contextlib.nullcontext())
            try:
                with annotate:
                    mono = time.monotonic_ns()
                    job = run_job(job_id)
            except Exception as e:  # noqa: BLE001 — a failed operation
                # is counted, not fatal; two in a row end the window
                raised.append(f"{job_id}: {type(e).__name__}: {e}")
                say(f"job raised: {raised[-1]}")
                if len(raised) >= 2:
                    break
                continue
            job["done_s"] = time.monotonic() - t_win
            job["started_by_least"] = by_least
            job["mono_ns_at_annotation"] = mono if tracing else None
            jobs.append(job)
            if tracing and len(jobs) >= n_traced:
                jax.profiler.stop_trace()
                tracing = False
    finally:
        if tracing:
            jax.profiler.stop_trace()
    return jobs, raised


def verdict(cell, first: dict, jobs: list, raised: list, dev: dict,
            rehearsal: bool) -> tuple:
    """(problems, failed jobs): every rule of ``correct`` but accuracy."""
    from benchmark import judge

    problems, failed = list(raised), len(raised)
    healthy = dict(platform=dev["platform"], chips=cell.chips,
                   interpreted=rehearsal)
    expect = cell.workload["expect"]
    for job in jobs:
        bad = judge.window_problems(job)
        bad += judge.report_problems(job["report"], expect, **healthy)
        if job["fasta"] != first["fasta"]:
            bad.append("output differs from the warm-up job's bytes")
        if bad:
            failed += 1
            problems += [f"{job['id']}: {b}" for b in bad]
    problems += [f"warmup: {b}" for b in judge.report_problems(
        first["report"], expect, **healthy)]
    if len(jobs) < judge.LEAST_JOBS:
        problems.append(f"{len(jobs)} job(s) completed in the window; "
                        f"fewer than {judge.LEAST_JOBS}")
    return problems, failed


def window_facts(jobs: list) -> dict:
    """Facts of the window for the printed record, not metrics: when the
    last job completed (a job started inside the window may end after
    it) and how many jobs only the ``judge.LEAST_JOBS`` rule started: 0
    in a healthy run, 1 after a stall in the job before."""
    return {"window_end_s": jobs[-1]["done_s"] if jobs else None,
            "jobs_started_by_least": sum(j["started_by_least"]
                                         for j in jobs)}


def compared(jobs: list, failed: int, edits: dict, truth_bp: int,
             problems: list) -> list:
    """Each number ``correct`` compared beside its limit, then every
    problem found: the run's last lines on stderr, which is what the
    driver keeps of a run that is not correct."""
    from benchmark import judge

    at_most, below = judge.accuracy_limits(edits["draft"], edits["host"],
                                           truth_bp)
    return [f"jobs completed in the window: {len(jobs)} (at least "
            f"{judge.LEAST_JOBS}); " + json.dumps(window_facts(jobs)),
            f"jobs failed: {failed} (limit 0)",
            f"device edit distance: {edits['device']} (at most "
            f"{at_most:.0f} beside the host's {edits['host']}; below "
            f"{below:.0f}, a quarter of the draft's {edits['draft']})"
            ] + [f"PROBLEM {p}" for p in problems]


def read_trace(trace_dir: str, jobs: list):
    """(DeviceTrace, its reduction) of the newest profile under
    ``trace_dir``, with each traced job's offset between the program's
    monotonic clock and the profiler's (from the job's annotation), so
    that program spans can be placed on the device's timeline."""
    from benchmark import loader, xplane

    xp = xplane.newest_xplane(trace_dir)
    if xp is None:
        return None, None
    with open(os.path.join(loader.BENCH_DIR, "trace_layout.json")) as f:
        trace = xplane.read(xp, json.load(f))
    starts = {job: ev.start for job, ev in trace.jobs}
    for job in jobs:
        mono, start = job["mono_ns_at_annotation"], starts.get(job["id"])
        job["clock_offset_ns"] = (mono - start
                                  if mono and start is not None else None)
    # innermost program span of a traced job covering a profiler time
    table = sorted(
        (dur, start - j["clock_offset_ns"], name)
        for j in jobs if j["clock_offset_ns"] is not None
        for name, items in j["spans"].items() for start, dur in items)

    def label(t):
        return next((name for dur, lo, name in table
                     if lo <= t < lo + dur), None)

    say(f"trace: {xp} ({os.path.getsize(xp)} bytes), planes "
        + json.dumps(trace.planes)[:1500])
    return trace, xplane.reduce(trace, label)


def main(argv=None) -> int:
    args = parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "racon_tpu", "native", "src")):
        print("benchmark: the program (racon_tpu/) is not in this "
              "checkout; nothing to measure", file=sys.stderr)
        return NO_PROGRAM

    from benchmark import judge, loader, prepare, probe, reducers

    t = time.monotonic()           # device_init_s starts with the import
    from racon_tpu import device as rt_device
    rehearsal = rt_device.cpu_requested()
    if rehearsal:
        # off the chip the drivers would pick the XLA twin and the host
        # aligner; the rehearsal asks for the tiers the chip runs,
        # interpreted, as chip_smoke.py's does.  A chip run sets nothing.
        os.environ.setdefault("RACON_TPU_PALLAS", "1")
        os.environ.setdefault("RACON_TPU_DEVICE_ALIGNER", "hirschberg")
    tag = "[REHEARSAL on cpu, not a chip result] " if rehearsal else ""

    # -- set-up, in this order in every run ----------------------------------
    try:
        cell = loader.load_cell(args.workload)
        dev = claim_device(cell, rehearsal)
    except loader.BenchmarkError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return NO_PROGRAM
    except Refused as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return e.code
    import jax
    from racon_tpu import native
    from racon_tpu.serve.session import JobSpec, PolishSession

    facts = {"device_init_s": time.monotonic() - t}
    threads = os.cpu_count() or 1
    say(f"{tag}cell {cell.name}: config {cell.config_name}, traffic "
        f"{cell.traffic_name}, seed {args.seed}, {args.seconds:g} s, trace "
        f"{args.trace}; device {dev['platform']} {dev['device_kind']!r} "
        f"x{dev['count']}; num_threads {threads} on both paths")

    say(f"native: {prepare.ensure_native()}")
    data_dir, data = prepare.ensure_data(cell, args.seed, rehearsal)
    params = data["params"]
    say(f"data: {data_dir} " + json.dumps(
        {k: data[k] for k in ("truth_bp", "reads", "read_bases", "cached")}))
    polish_args = dict(cell.config["polish_args"], num_threads=threads)
    oracle_path, oracle_wall = prepare.ensure_oracle(
        data_dir, params, polish_args, timed=bool(args.trace))
    if oracle_wall is not None:
        facts["host_path_wall_s"] = oracle_wall
        facts["host_path_mbp_per_s"] = (
            len(prepare.read_fasta(oracle_path)) / 1e6 / oracle_wall)
        say(f"host oracle: {oracle_wall:.2f} s on {threads} threads")

    out_dir = os.path.join(loader.BENCH_DIR, "out", cell.name)
    run_tag = f"seed{args.seed}-trace{args.trace}"
    work = os.path.join(out_dir, f"work-{run_tag}")
    shutil.rmtree(work, ignore_errors=True)     # a fresh journal per run
    os.makedirs(work)
    reads, overlaps, draft = prepare.inputs(data_dir, params)
    session = PolishSession(work, backend="tpu")
    pa = cell.config["polish_args"]
    facts["warm_wall_s"] = session.warm_for_target(
        draft, pa["window_length"], pa["match"], pa["mismatch"], pa["gap"])

    def run_job(job_id: str) -> dict:
        t0 = time.monotonic()
        c0 = rt_device.cache_traffic()
        res = session.run_job(JobSpec(reads, overlaps, draft,
                                      args=polish_args, job_id=job_id))
        c1 = rt_device.cache_traffic()
        return {"id": job_id, "wall_s": time.monotonic() - t0,
                "polished_bp": res["polished_bp"],
                "kernel_builds": res["kernel_builds"],
                "journal_replayed": res["journal_replayed"],
                "cache_misses": c1["misses"] - c0["misses"],
                "cache_requests": c1["requests"] - c0["requests"],
                "result": res}

    first = run_job("warmup")
    cache = rt_device.cache_traffic()
    facts.update(first_job_wall_s=first["wall_s"],
                 cache_load_s=cache["compile_s"],
                 cache_misses_warm=cache["misses"],
                 cache_hits_warm=cache["hits"])
    say(f"warm-up: warm {facts['warm_wall_s']:.1f} s, first job "
        f"{first['wall_s']:.1f} s, jax_cache {cache}")

    # -- the window -----------------------------------------------------------
    trace_dir = os.path.join(out_dir, "trace")
    n_traced = int(cell.traffic.get("trace_jobs", 2)) if args.trace else 0
    facts["setup_s"] = time.monotonic() - T0
    jobs, raised = measure_window(run_job, args.seconds, trace_dir,
                                  n_traced)
    say(f"window: {len(jobs)} jobs, completed at "
        f"{[round(j['done_s'], 3) for j in jobs]} s "
        f"(walls {[round(j['wall_s'], 3) for j in jobs]})")

    # -- after the window -------------------------------------------------------
    for job in [first] + jobs:
        job.update(job_files(job.pop("result")))
    problems, failed = verdict(cell, first, jobs, raised, dev, rehearsal)

    truth = prepare.read_fasta(os.path.join(data_dir, "genome.fasta"))
    edits = {name: native.edit_distance(prepare.read_fasta(path), truth)
             for name, path in (
                 ("draft", draft), ("host", oracle_path),
                 ("device", os.path.join(work, "jobs", "warmup",
                                         "polished.fasta")))}
    problems += judge.accuracy_problems(edits["draft"], edits["host"],
                                        edits["device"], len(truth))
    say(f"edit distance to the truth ({len(truth)} bp): {edits}")

    memory_peak = max(int((d.memory_stats() or {}).get(
        "peak_bytes_in_use", 0)) for d in jax.local_devices())
    facts["hbm_peak_gb"] = memory_peak / 1e9
    line = {"correct": True, "attempted": len(jobs) + len(raised),
            **window_facts(jobs), "failed": failed, "metrics": {},
            "device": {"platform": dev["platform"],
                       "kind": dev["device_kind"], "count": dev["count"],
                       "memory_peak_bytes": memory_peak}}
    run = {"facts": facts, "jobs": jobs, "data": data, "edits": edits,
           "peaks": dev["peaks"], "notes": {}, "trace": None,
           "device": None}

    if not args.trace:
        values = {
            "polished_mbp_per_s": judge.polished_mbp_per_s(
                [j["polished_bp"] for j in jobs],
                [j["done_s"] for j in jobs]),
            "err_removed_vs_host": judge.err_removed_vs_host(
                edits["draft"], edits["host"], edits["device"]),
            "setup_s": facts["setup_s"]}
        wanted = cell.end_to_end
    else:
        run["trace"], run["device"] = read_trace(trace_dir, jobs)
        reduced = run["device"] or {}
        if reduced.get("busy_s"):
            line["device"].update(busy_s=reduced["busy_s"],
                                  window_s=reduced["window_s"])
            line["breakdown"] = {"device_ops": reduced["device_ops"],
                                 "idle_gaps": reduced["idle_gaps"]}
        elif not rehearsal:
            problems.append("the trace shows no operation on the device "
                            "inside the traced window")
        if not rehearsal:
            # after the window, so that it moves neither the window nor
            # the order in which set-up lowers its programs
            facts["int32_ops_per_s"] = probe.int32_ops_per_s()
        registry = reducers.registry()
        values = {m["name"]: registry[m["reducer"]](run, **m.get("params", {}))
                  for m in cell.per_layer}
        wanted = cell.per_layer

    # a CPU rehearsal writes no timing: counted metrics only
    line["metrics"] = {
        m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
        for m in wanted if values.get(m["name"]) is not None
        and (not rehearsal or judge.is_a_count(m))}
    if not args.trace and not rehearsal:
        missing = [m["name"] for m in wanted
                   if m["name"] not in line["metrics"]]
        if missing:
            problems.append(f"end-to-end metrics not measured: {missing}")
    line["correct"] = not problems
    if rehearsal:
        line["rehearsal"] = True

    # -- what else is worth reading, and the line -------------------------------
    detail = {
        "cell": cell.name, "seed": args.seed, "seconds": args.seconds,
        "trace": args.trace, "threads": threads, "problems": problems,
        "facts": facts, "edits": edits, "notes": run["notes"],
        "device_reduction": run["device"],
        "data": {k: v for k, v in data.items() if k != "params"},
        "jobs": [job_summary(j) for j in [first] + jobs],
        "line": line}
    with open(os.path.join(out_dir, f"{run_tag}.json"), "w") as f:
        json.dump(detail, f, indent=1)
    shutil.rmtree(work, ignore_errors=True)
    for p in problems:
        say(f"{tag}PROBLEM {p}")
    for name, note in run["notes"].items():
        say(f"{name}: {json.dumps(note)}")
    say(f"{tag}detail: {os.path.join(out_dir, run_tag + '.json')}")
    for text in compared(jobs, failed, edits, len(truth), problems):
        print(f"benchmark: {tag}{text}", file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
