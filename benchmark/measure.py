#!/usr/bin/env python3
"""Runs a cell several times, as the driver does, and reports the spread.

    python3 benchmark/measure.py --workload <cell> --plan 0:1,1:1,0:2,0:3 \
        [--seconds S] [--out DIR]

Each item of the plan is ``<trace>:<seed>`` and is one fresh process of
``benchmark/run.py`` run to its end before the next starts (this script
never imports JAX, so the chip has one owner at a time).  The first run
in a checkout compiles and is reported apart.  For each metric it prints
the values, the median and the spread the builder's contract uses (the
distance between the quartiles over the median); bounds are set from
these.  Output files (each run's stdout and stderr, the detail JSON of
``benchmark/out/``, a trace small enough to carry) go to ``--out``,
by default ``chiprun_out/bench/<cell>/``, which the chip tool brings back.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MAX_TRACE_BYTES = 24 << 20


def spread(values: list):
    if len(values) < 2 or not statistics.median(values):
        return None
    q = statistics.quantiles(values, n=4, method="inclusive")
    return (q[2] - q[0]) / abs(statistics.median(values))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--plan", required=True,
                   help="comma-separated <trace>:<seed> runs, in order")
    p.add_argument("--seconds", type=float, default=None,
                   help="default: BENCHMARK.json's run_seconds")
    p.add_argument("--out", default=None)
    p.add_argument("--timeout", type=float, default=1200,
                   help="seconds a run may take before it is killed "
                        "(the contract: 1200 for a run that compiles, "
                        "360 after)")
    args = p.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        seconds = args.seconds or json.load(f)["run_seconds"]
    out = os.path.abspath(args.out or os.path.join(
        ROOT, "chiprun_out", "bench", args.workload))
    os.makedirs(out, exist_ok=True)

    runs = []
    for i, item in enumerate(args.plan.split(",")):
        trace, seed = (int(x) for x in item.split(":"))
        tag = f"run{i:02d}-trace{trace}-seed{seed}"
        t0 = time.monotonic()
        with open(os.path.join(out, tag + ".stdout"), "w") as so, \
                open(os.path.join(out, tag + ".stderr"), "w") as se:
            try:
                rc = subprocess.run(
                    [sys.executable, os.path.join("benchmark", "run.py"),
                     "--workload", args.workload, "--seed", str(seed),
                     "--seconds", str(seconds), "--trace", str(trace)],
                    cwd=ROOT, stdout=so, stderr=se,
                    timeout=args.timeout).returncode
            except subprocess.TimeoutExpired:
                rc = f"killed after {args.timeout:g} s"
        wall = time.monotonic() - t0
        with open(os.path.join(out, tag + ".stdout")) as f:
            lines = f.read().splitlines()
        line = None
        if rc == 0 and lines:
            try:
                line = json.loads(lines[-1])
            except json.JSONDecodeError:
                pass
        for text in lines:
            if text.startswith("[bench") and len(text) < 2500:
                print(text)
        print(f"== {tag}: rc {rc}, process wall {wall:.1f} s")
        if line is None:
            with open(os.path.join(out, tag + ".stderr")) as f:
                print(f.read()[-3000:])
        else:
            print(json.dumps(line)[:6000])
        runs.append({"tag": tag, "trace": trace, "seed": seed, "rc": rc,
                     "wall_s": wall, "line": line, "first": i == 0})
        sys.stdout.flush()

    detail = os.path.join(ROOT, "benchmark", "out", args.workload)
    if os.path.isdir(detail):
        for name in os.listdir(detail):
            if name.endswith(".json"):
                shutil.copy(os.path.join(detail, name), out)
        for dirpath, _, files in os.walk(os.path.join(detail, "trace")):
            for name in files:
                path = os.path.join(dirpath, name)
                if (name.endswith(".xplane.pb")
                        and os.path.getsize(path) <= MAX_TRACE_BYTES):
                    shutil.copy(path, os.path.join(out, "trace.xplane.pb"))

    print(f"\n== summary of {args.workload}, {seconds:g} s runs "
          "(the first run in the checkout compiles: listed, not counted)")
    ok = [r for r in runs if r["line"]]
    summary = {"workload": args.workload, "seconds": seconds, "runs": runs,
               "metrics": {}}
    for trace in (0, 1):
        names = sorted({n for r in ok if r["trace"] == trace
                        for n in r["line"]["metrics"]})
        for name in names:
            rows = [(r["line"]["metrics"][name]["value"], r) for r in ok
                    if r["trace"] == trace and name in r["line"]["metrics"]]
            counted = [v for v, r in rows if not r["first"]] or \
                [v for v, _ in rows]
            sp = spread(counted)
            summary["metrics"][name] = {
                "trace": trace, "values": [v for v, _ in rows],
                "seeds": [r["seed"] for _, r in rows],
                "median": statistics.median(counted), "spread": sp}
            print(f"  {name:32s} trace{trace} median "
                  f"{statistics.median(counted):.6g}  spread "
                  f"{'n/a' if sp is None else format(sp, '.4f')}  values "
                  + " ".join(f"{v:.6g}{'*' if r['first'] else ''}"
                             for v, r in rows))
    print("  correct: " + " ".join(
        f"{r['tag']}={r['line']['correct'] if r['line'] else 'no line'}"
        for r in runs))
    with open(os.path.join(out, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1)
    return 0 if all(r["line"] and r["line"]["correct"] for r in runs) else 1


if __name__ == "__main__":
    sys.exit(main())
