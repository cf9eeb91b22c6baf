"""CLI for the distrib coordinator: `racon-tpu distrib [options]
<sequences> <overlaps> <target>` (also `python -m racon_tpu.distrib`).

Polish flags mirror the main CLI; the polished FASTA goes to stdout
(or ``-o``), byte-identical to the single-process run over the same
inputs.  A one-line summary of the fleet accounting lands on stderr.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import tempfile


def build_arg_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="racon-tpu distrib",
        description="polish with a fault-tolerant multi-process "
                    "chunk-worker fleet (leases, heartbeats, journal "
                    "resume, speculative re-dispatch; output is "
                    "byte-identical to the single-process CLI)")
    p.add_argument("sequences")
    p.add_argument("overlaps")
    p.add_argument("targets")
    p.add_argument("-u", "--include-unpolished", action="store_true",
                   help="output unpolished target sequences")
    p.add_argument("-f", "--fragment-correction", action="store_true",
                   help="perform fragment correction instead of contig "
                   "polishing")
    p.add_argument("-w", "--window-length", type=int, default=500)
    p.add_argument("-q", "--quality-threshold", type=float, default=10.0)
    p.add_argument("-e", "--error-threshold", type=float, default=0.3)
    p.add_argument("--no-trimming", action="store_true")
    p.add_argument("-m", "--match", type=int, default=3)
    p.add_argument("-x", "--mismatch", type=int, default=-5)
    p.add_argument("-g", "--gap", type=int, default=-4)
    p.add_argument("-t", "--threads", type=int, default=1)
    p.add_argument("--tpu", action="store_true",
                   help="workers run the accelerated path")
    p.add_argument("--workers", type=int, default=None,
                   help="fleet size (default: RACON_TPU_DISTRIB_WORKERS)")
    p.add_argument("--chunks", type=int, default=None,
                   help="target chunk count hint (default: 2x workers)")
    p.add_argument("-o", "--output", metavar="PATH", default=None,
                   help="write the polished FASTA here instead of stdout")
    p.add_argument("--state-dir", metavar="DIR", default=None,
                   help="coordinator working directory holding chunks, "
                   "journals, and worker logs (default: a fresh temp dir)")
    p.add_argument("--timeout", type=float, default=0.0,
                   help="abort the run after this many seconds "
                   "(0 = no deadline)")
    p.add_argument("--report", metavar="PATH", default=None,
                   help="write the coordinator's JSON run report "
                   "(distrib phase: fleet/local serving mix, "
                   "re-dispatches, degradations) to PATH")
    p.add_argument("--trace", metavar="PATH", default=None,
                   help="write a Chrome-trace JSON of the coordinator "
                   "(per-chunk dispatch/done events, distrib.* counters) "
                   "to PATH")
    return p


def main(argv=None) -> int:
    args = build_arg_parser().parse_args(argv)

    from ..resilience import faults
    try:
        faults.validate_env()
    except ValueError as e:
        print(e, file=sys.stderr)
        return 1

    from .coordinator import Coordinator

    workdir = args.state_dir or tempfile.mkdtemp(prefix="racon-distrib-")
    out_path = args.output or os.path.join(workdir, "polished.fasta")

    from ..obs import flight

    def _on_sigterm(signum, frame):
        # post-mortem before the default die: the coordinator's ring
        # lands next to the worker dumps it would have swept
        flight.dump("sigterm", dir_path=workdir, signal=int(signum))
        raise SystemExit(143)

    signal.signal(signal.SIGTERM, _on_sigterm)
    try:
        coord = Coordinator(
            args.sequences, args.overlaps, args.targets, workdir,
            args={
                "window_length": args.window_length,
                "quality_threshold": args.quality_threshold,
                "error_threshold": args.error_threshold,
                "trim": not args.no_trimming,
                "fragment_correction": args.fragment_correction,
                "match": args.match, "mismatch": args.mismatch,
                "gap": args.gap, "num_threads": args.threads,
            },
            include_unpolished=args.include_unpolished,
            backend="tpu" if args.tpu else "cpu",
            workers=args.workers, chunks_hint=args.chunks,
            trace_path=args.trace, report_path=args.report)
        result = coord.run(out_path, timeout=args.timeout or None)
    except (RuntimeError, TimeoutError, OSError) as e:
        print(f"[racon_tpu::distrib] {e}", file=sys.stderr)
        return 1
    print(f"[racon_tpu::distrib] {json.dumps(result['summary'])}",
          file=sys.stderr)
    if args.output is None:
        with open(out_path) as f:
            sys.stdout.write(f.read())
    return 0


if __name__ == "__main__":
    sys.exit(main())
