"""`python -m racon_tpu.serve` / `python -m racon_tpu.cli serve` —
run the resident polishing daemon, or (with ``--stats-watch``) poll a
running daemon's live telemetry without starting one."""

from __future__ import annotations

import argparse
import json
import signal
import sys
import time

from .server import ServeDaemon


def build_arg_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="racon-tpu serve",
        description="Resident polishing daemon: kernels stay hot across "
        "jobs, a queue-based scheduler multiplexes concurrent submissions "
        "onto one device set, every job journals for preemption-safe "
        "resume (protocol: newline-JSON over localhost TCP; see "
        "docs/architecture.md, 'Serving').")
    p.add_argument("--state-dir", default="./racon-serve",
                   help="daemon state directory: serve.json (bound port) "
                   "plus one subdirectory per job holding its spec, "
                   "journal, trace, report, and polished output "
                   "(default ./racon-serve)")
    p.add_argument("--port", type=int, default=None,
                   help="TCP port to bind on 127.0.0.1 (default: "
                   "RACON_TPU_SERVE_PORT, 0 = ephemeral)")
    p.add_argument("--backend", choices=("tpu", "cpu"), default="tpu",
                   help="session backend for the device lane "
                   "(default tpu)")
    p.add_argument("--queue-depth", type=int, default=None,
                   help="queued-job admission cap (default: "
                   "RACON_TPU_SERVE_QUEUE_DEPTH)")
    p.add_argument("--max-jobs", type=int, default=None,
                   help="unfinished-job admission cap (default: "
                   "RACON_TPU_SERVE_MAX_JOBS)")
    p.add_argument("--window-budget", type=int, default=None,
                   help="per-job window budget; bigger jobs run on the "
                   "host lane (default: RACON_TPU_SERVE_WINDOW_BUDGET, "
                   "0 = unlimited)")
    p.add_argument("--no-warm", action="store_true",
                   help="skip the startup kernel warm-up (first job then "
                   "pays the compiles; RACON_TPU_SERVE_WARMUP=0 is the "
                   "env equivalent)")
    p.add_argument("--warm-window", type=int, action="append", default=None,
                   metavar="W",
                   help="window length(s) to pre-compile geometries for "
                   "(repeatable; default 500 — pass the -w your jobs use)")
    p.add_argument("--no-host-lane", action="store_true",
                   help="disable the host demotion lane (device failures "
                   "then fail the job instead of retrying on the host)")
    p.add_argument("--fleet-max", type=int, default=None,
                   help="elastic fleet worker ceiling; > 0 runs the "
                   "device lane through the chunk-level fleet plane "
                   "with autoscaling and work-stealing (default: "
                   "RACON_TPU_FLEET_MAX_WORKERS, 0 = in-process device "
                   "lane)")
    p.add_argument("--fleet-min", type=int, default=None,
                   help="elastic fleet worker floor (default: "
                   "RACON_TPU_FLEET_MIN_WORKERS)")
    p.add_argument("--metrics-port", type=int, default=None,
                   help="Prometheus exposition HTTP port on 127.0.0.1 "
                   "(GET /metrics; default: RACON_TPU_METRICS_PORT, "
                   "0 = disabled — the `metrics` wire op still works)")
    p.add_argument("-m", "--match", type=int, default=3,
                   help="match score to warm kernels for (default 3)")
    p.add_argument("-x", "--mismatch", type=int, default=-5,
                   help="mismatch score to warm kernels for (default -5)")
    p.add_argument("-g", "--gap", type=int, default=-4,
                   help="gap penalty to warm kernels for (default -4)")
    p.add_argument("--stats-watch", action="store_true",
                   help="do not start a daemon: connect to the one whose "
                   "serve.json lives in --state-dir and print its stats "
                   "(one JSON line per poll), then exit")
    p.add_argument("--interval", type=float, default=2.0,
                   help="seconds between --stats-watch polls (default 2)")
    p.add_argument("--count", type=int, default=1,
                   help="number of --stats-watch polls before exiting "
                   "(default 1; 0 = poll until the daemon goes away)")
    return p


def stats_watch(state_dir: str, interval: float, count: int) -> int:
    """Poll a running daemon's ``stats`` op and print one JSON line per
    sample.  Exits 0 after ``count`` polls, 1 if the daemon cannot be
    reached (including when it goes away mid-watch)."""
    from .client import ServeClient, ServeError
    polls = 0
    while True:
        try:
            with ServeClient.from_state_dir(state_dir, timeout=10.0) as c:
                resp = c.stats()
        except (OSError, ValueError, ServeError) as e:
            print(f"[racon_tpu::serve] stats-watch: daemon unreachable: "
                  f"{e}", file=sys.stderr)
            return 1
        resp.pop("ok", None)
        print(json.dumps(resp, sort_keys=True), flush=True)
        polls += 1
        if count > 0 and polls >= count:
            return 0
        time.sleep(max(0.1, interval))


def main(argv=None) -> int:
    args = build_arg_parser().parse_args(argv)

    if args.stats_watch:
        return stats_watch(args.state_dir, args.interval, args.count)

    from ..resilience import faults
    try:
        faults.validate_env()
    except ValueError as e:
        print(e, file=sys.stderr)
        return 1

    from ..device import DeviceUnavailable
    try:
        daemon = ServeDaemon(
            args.state_dir, backend=args.backend, port=args.port,
            queue_depth=args.queue_depth, max_jobs=args.max_jobs,
            window_budget=args.window_budget,
            warm=False if args.no_warm else None,
            warm_window_lengths=tuple(args.warm_window or (500,)),
            warm_scores=(args.match, args.mismatch, args.gap),
            host_lane=not args.no_host_lane,
            fleet_min=args.fleet_min, fleet_max=args.fleet_max,
            metrics_port=args.metrics_port)
    except DeviceUnavailable as e:
        print(f"[racon_tpu::serve] {e}", file=sys.stderr)
        return 1

    from ..obs import flight
    flight.set_role("serve")
    flight.set_dir(args.state_dir)

    def _stop(signum, frame):
        print(f"[racon_tpu::serve] signal {signum}: shutting down "
              f"(queued jobs stay recoverable)", file=sys.stderr)
        flight.dump("sigterm", signal=int(signum))
        daemon.stop(wait=False)

    signal.signal(signal.SIGTERM, _stop)
    signal.signal(signal.SIGINT, _stop)
    daemon.serve_forever()
    return 0


if __name__ == "__main__":
    sys.exit(main())
