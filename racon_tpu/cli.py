"""Command-line interface, flag-compatible with the reference `racon` binary
(/root/reference/src/main.cpp:18-38,166-229) plus TPU backend flags in place
of the CUDA ones.

Usage: racon-tpu [options ...] <sequences> <overlaps> <target sequences>
       racon-tpu serve [options ...]   (resident polishing daemon)
       racon-tpu distrib [options ...] <sequences> <overlaps> <targets>
                                       (multi-process chunk-worker fleet)
"""

from __future__ import annotations

import argparse
import sys

from . import __version__
from .polisher import create_polisher


def build_arg_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="racon-tpu",
        description="TPU-native consensus module for raw de novo genome "
        "assembly of long uncorrected reads",
        epilog="subcommands: `racon-tpu serve` runs the resident "
        "polishing daemon (hot kernels, job queue, preemption-safe "
        "jobs — see `racon-tpu serve --help`); `racon-tpu distrib` "
        "polishes with a fault-tolerant multi-process chunk-worker "
        "fleet (leases, heartbeats, journal resume — see `racon-tpu "
        "distrib --help`).",
    )
    p.add_argument("sequences", help="FASTA/FASTQ file (optionally gzipped) "
                   "containing sequences used for correction")
    p.add_argument("overlaps", help="MHAP/PAF/SAM file (optionally gzipped) "
                   "containing overlaps between sequences and target "
                   "sequences")
    p.add_argument("targets", help="FASTA/FASTQ file (optionally gzipped) "
                   "containing sequences which will be corrected")
    p.add_argument("-u", "--include-unpolished", action="store_true",
                   help="output unpolished target sequences")
    p.add_argument("-f", "--fragment-correction", action="store_true",
                   help="perform fragment correction instead of contig "
                   "polishing (overlaps file should contain dual/self "
                   "overlaps!)")
    p.add_argument("-w", "--window-length", type=int, default=500,
                   help="size of window on which POA is performed (default "
                   "500)")
    p.add_argument("-q", "--quality-threshold", type=float, default=10.0,
                   help="threshold for average base quality of windows used "
                   "in POA (default 10.0)")
    p.add_argument("-e", "--error-threshold", type=float, default=0.3,
                   help="maximum allowed error rate used for filtering "
                   "overlaps (default 0.3)")
    p.add_argument("--no-trimming", action="store_true",
                   help="disables consensus trimming at window ends")
    p.add_argument("-m", "--match", type=int, default=3,
                   help="score for matching bases (default 3)")
    p.add_argument("-x", "--mismatch", type=int, default=-5,
                   help="score for mismatching bases (default -5)")
    p.add_argument("-g", "--gap", type=int, default=-4,
                   help="gap penalty, must be negative (default -4)")
    p.add_argument("-t", "--threads", type=int, default=1,
                   help="number of host threads (default 1)")
    p.add_argument("--tpu", action="store_true",
                   help="run the accelerated path (batched alignment + POA "
                   "on the JAX backend, host fallback for rejected work)")
    p.add_argument("--report", metavar="PATH", default=None,
                   help="write a machine-readable JSON run report (per-phase "
                   "serving tiers, fallback causes, retries, quarantined "
                   "windows, wall time per tier) to PATH")
    p.add_argument("--trace", metavar="PATH", default=None,
                   help="write a Chrome-trace/Perfetto JSON timeline of the "
                   "run (phase spans, per-bucket POA batches, lattice "
                   "events, kernel builds, embedded metrics snapshot) to "
                   "PATH; inspect with `python -m racon_tpu.obs PATH` or "
                   "ui.perfetto.dev (env: RACON_TPU_TRACE)")
    jr = p.add_mutually_exclusive_group()
    jr.add_argument("--journal", metavar="PATH", default=None,
                    help="append every served window/CIGAR to a crash-safe "
                    "journal at PATH (fsynced JSONL; overwrites an existing "
                    "file) so an interrupted run can be resumed")
    jr.add_argument("--resume-journal", metavar="PATH", default=None,
                    help="resume from the journal at PATH: replay every "
                    "already-served window, recompute only the rest, and "
                    "keep appending; output is byte-identical to an "
                    "uninterrupted run (errors out if the journal belongs "
                    "to different inputs/parameters; starts fresh if PATH "
                    "does not exist)")
    p.add_argument("--version", action="version", version=__version__)
    return p


def main(argv=None) -> int:
    if argv is None:
        argv = sys.argv[1:]
    # Subcommand seam (the reference binary's split/subsample pattern):
    # `racon-tpu serve` hands the rest of the argv to the daemon before
    # the polish-flags parser ever sees it.
    if argv and argv[0] == "serve":
        from .serve.__main__ import main as serve_main
        return serve_main(argv[1:])
    if argv and argv[0] == "distrib":
        from .distrib.__main__ import main as distrib_main
        return distrib_main(argv[1:])
    args = build_arg_parser().parse_args(argv)

    from .device import DeviceUnavailable
    from .native import NativeError
    from .resilience import faults
    from .resilience.journal import JournalError

    # Validate the fault-injection spec up front (same contract as the
    # file-extension checks: single-line error, exit 1) — a malformed
    # RACON_TPU_FAULT must not surface as a mid-run traceback.
    try:
        faults.validate_env()
    except ValueError as e:
        print(e, file=sys.stderr)
        return 1

    # Typo'd knobs must not be silently ignored: a RACON_TPU_* var the
    # registry doesn't know is almost always a misspelled real one.
    from . import config
    stale = config.unknown_env_knobs()
    if stale:
        print(f"[racon_tpu] WARNING: unknown RACON_TPU_* environment "
              f"variable(s) ignored: {', '.join(stale)} (known knobs: "
              f"see README.md)", file=sys.stderr)

    try:
        polisher = create_polisher(
            args.sequences, args.overlaps, args.targets,
            backend="tpu" if args.tpu else "cpu",
            fragment_correction=args.fragment_correction,
            window_length=args.window_length,
            quality_threshold=args.quality_threshold,
            error_threshold=args.error_threshold,
            trim=not args.no_trimming,
            match=args.match, mismatch=args.mismatch, gap=args.gap,
            num_threads=args.threads,
            journal_path=args.resume_journal or args.journal,
            resume_journal=args.resume_journal is not None,
            trace_path=args.trace)
        polisher.initialize()
        for name, data in polisher.polish(not args.include_unpolished):
            sys.stdout.write(f">{name}\n{data}\n")
        if args.report:
            polisher.report.write(args.report)
    except (JournalError, DeviceUnavailable) as e:
        # same single-line contract as a malformed fault spec: resuming
        # against the wrong inputs, or --tpu without a TPU, must fail
        # loudly before any compute
        print(e, file=sys.stderr)
        return 1
    except NativeError as e:
        # the reference binary surfaces runtime errors as the what() text
        # and a non-zero exit (src/main.cpp catches nothing); a Python
        # traceback is not that interface — and errors fire well past
        # construction (empty target set, duplicate sequences, ... in
        # rt_pipeline.cpp initialize/stitch)
        print(e, file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
