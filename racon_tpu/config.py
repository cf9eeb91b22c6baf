"""Central registry of every ``RACON_TPU_*`` environment knob.

Every environment variable the runtime, tools, benchmarks, or tests read
is declared here — name, default, type, and a docstring — and read
through the typed accessors below.  This file is the ground truth for:

* the ``env-registry`` lint rule (``racon_tpu/analysis``): any
  ``os.environ`` / ``os.getenv`` read of a ``RACON_TPU_*`` name outside
  this module is a violation, so a knob cannot be introduced without a
  registered name and documentation;
* the ``knob-docs`` lint rule: every registered knob must appear in
  README.md's configuration table;
* the run report's stale-knob check (``unknown_env_knobs``): variables
  set in the environment with the ``RACON_TPU_`` prefix but unknown to
  this registry are surfaced in ``Polisher.report`` instead of being
  silently ignored — a typo'd knob is visible, not a no-op.

Only the stdlib is imported so this module is importable from anywhere
(including ``racon_tpu/__init__`` before jax initializes).
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Dict, List, Optional

PREFIX = "RACON_TPU_"


@dataclass(frozen=True)
class Knob:
    """One registered environment knob."""

    name: str          # full variable name, RACON_TPU_… prefix included
    default: Optional[str]  # raw default ('' / None = unset semantics)
    kind: str          # 'str' | 'int' | 'float' | 'bool' — documentation
    doc: str           # one-line effect description (README table text)
    scope: str = "runtime"   # 'runtime' | 'tools' | 'bench' | 'test'
    #: The byte-identity contract, per knob: False declares the knob
    #: *cost-only* — it may change tiers, batching, timing, or memory,
    #: never output bytes — and the determinism taint auditor
    #: (racon_tpu/analysis/determinism, Engine 5) statically rejects
    #: any dataflow path from its read sites into the consensus/CIGAR
    #: install seams (`determinism-leak`).  True declares it
    #: output-affecting: a runtime-scoped True knob must then be
    #: covered by every complete fingerprint composition in
    #: racon_tpu/fingerprint.py (`fingerprint-gap` otherwise).
    affects_output: bool = False


def _k(name: str, default: Optional[str], kind: str, doc: str,
       scope: str = "runtime", affects_output: bool = False) -> Knob:
    assert name.startswith(PREFIX), name
    return Knob(name, default, kind, doc, scope, affects_output)


#: The registry.  Order matters only for documentation output.
KNOBS: Dict[str, Knob] = {k.name: k for k in (
    # -- device-path (production) knobs -----------------------------------
    _k("RACON_TPU_PALLAS", None, "bool",
       "fused Pallas kernels vs the XLA twin (default: 1 on TPU, 0 "
       "elsewhere)"),
    _k("RACON_TPU_DEVICE_ALIGNER", "auto", "str",
       "phase-1 aligner: auto | hirschberg | 0/host"),
    _k("RACON_TPU_BAND", "0", "bool",
       "banded DP on the hot kernels: Ukkonen-banded Hirschberg "
       "alignment + diagonal-banded POA with verify-and-widen "
       "re-dispatch, falling back to the flat kernels on band-hit "
       "exhaustion (output is byte-identical either way)"),
    _k("RACON_TPU_BAND_SLACK", "32", "int",
       "banded DP initial half-band slack: first band width is the "
       "query/target length delta plus this many diagonals before "
       "bucketing"),
    _k("RACON_TPU_BAND_MAX_WIDENINGS", "2", "int",
       "banded DP widening budget: band-hit jobs double their band this "
       "many times before taking the banded->flat lattice edge"),
    _k("RACON_TPU_BATCH_WINDOWS", None, "int",
       "windows per device batch (default: 64 on TPU, 4 elsewhere)"),
    _k("RACON_TPU_PIPELINE_DEPTH", "2", "int",
       "in-flight device chunks (host packs ahead of execution)"),
    _k("RACON_TPU_PIPELINE_PHASES", None, "bool",
       "overlap alignment and consensus across target chunks: POA for "
       "early contigs starts while late alignment cohorts are in flight "
       "(multi-contig FASTA targets; output stays byte-identical)"),
    _k("RACON_TPU_HANDOFF_DEPTH", "1", "int",
       "phase-pipeline handoff queue depth: aligned target chunks the "
       "worker may buffer ahead of consensus"),
    _k("RACON_TPU_NODE_FACTOR", "3", "int",
       "POA graph node capacity of the base rung = factor x window "
       "length (windows of up to ~55 long-read layers); deeper windows "
       "run on the upper rung, 5 x, which is derived and no knob"),
    _k("RACON_TPU_ALIGN_COHORT", None, "int",
       "phase-1 jobs materialized per device cohort (default 64)"),
    _k("RACON_TPU_SHARD", "1", "bool",
       "shard kernel batches over the device mesh (0 forces "
       "single-device dispatch; output is byte-identical either way)"),
    _k("RACON_TPU_MESH_SHAPE", None, "str",
       "device mesh as 'data[,model]' (e.g. '8' or '4,2'; default: all "
       "devices on the data axis)"),
    _k("RACON_TPU_SHARD_MIN_BATCH", "0", "int",
       "smallest batch worth sharding (0 = one row per mesh shard); "
       "smaller batches dispatch single-device without padding"),
    # -- resilience knobs -------------------------------------------------
    _k("RACON_TPU_TIER_RETRIES", "1", "int",
       "extra attempts per kernel tier before bisecting/demoting"),
    _k("RACON_TPU_DEVICE_TIMEOUT", "0", "float",
       "per-device-call watchdog in seconds (0 = off)"),
    _k("RACON_TPU_FAULT", None, "str",
       "deterministic fault injection spec (see resilience/faults.py)"),
    _k("RACON_TPU_REPORT", None, "str",
       "write the JSON run report to this path after every polish"),
    _k("RACON_TPU_WEDGE_LIMIT", "3", "int",
       "consecutive watchdog timeouts before a tier is declared wedged "
       "and demoted without retry (0 = off)"),
    _k("RACON_TPU_JOURNAL", None, "str",
       "crash-safe window journal path; auto-resumes when the input "
       "fingerprint matches (fresh otherwise)"),
    _k("RACON_TPU_JOURNAL_FSYNC", "1", "bool",
       "fsync the journal after every record (0 trades durability for "
       "speed: a crash may lose buffered records)"),
    _k("RACON_TPU_SANITIZE", None, "bool",
       "runtime sanitizer: finite/in-range device-output checks, "
       "sampled host-vs-device parity, guarded driver stats "
       "(diagnostic mode; output stays byte-identical)"),
    _k("RACON_TPU_SANITIZE_PARITY", "8", "int",
       "sanitize mode: host-recompute and byte-compare every Nth "
       "device-served window (0 disables the parity probe)"),
    # -- memory-budget knobs (resilience/budget.py) -----------------------
    _k("RACON_TPU_MEM_BUDGET_MB", "0", "int",
       "peak-RSS budget in MiB: arms the memory watchdog, enables the "
       "streaming input path, and drives the soft/hard watermark "
       "degradations (0 = unbudgeted)"),
    _k("RACON_TPU_MEM_SOFT_FRAC", "0.8", "float",
       "soft watermark as a fraction of the memory budget: above it "
       "backpressure applies (handoff depth shrinks, queued working "
       "sets spill to disk)"),
    _k("RACON_TPU_MEM_HARD_FRAC", "0.95", "float",
       "hard watermark as a fraction of the memory budget: above it the "
       "pressure lattice edges fire (pipelined->sequential, "
       "batched->stream-sequential) and the flight recorder dumps"),
    _k("RACON_TPU_MEM_SPILL_DIR", None, "str",
       "directory for parked chunk working sets under memory pressure "
       "(default: a per-run temp directory)"),
    _k("RACON_TPU_MEM_POLL_MS", "200", "int",
       "memory watchdog sampling interval in milliseconds"),
    _k("RACON_TPU_STREAM_INPUT", None, "bool",
       "stream per-chunk read/overlap working sets instead of handing "
       "the full files to every chunk pipeline (auto-enabled when a "
       "memory budget is set; output is byte-identical either way)"),
    # -- observability knobs ----------------------------------------------
    _k("RACON_TPU_TRACE", None, "str",
       "write a Chrome-trace/Perfetto JSON span timeline of every polish "
       "to this path (CLI --trace overrides; see racon_tpu/obs)"),
    _k("RACON_TPU_METRICS", None, "bool",
       "collect the in-process metrics registry (per-tier counters + "
       "histograms) and embed a snapshot in the run report even without "
       "a trace file"),
    _k("RACON_TPU_TRACE_DEVICE", None, "bool",
       "with tracing armed on a real TPU backend, also capture a "
       "jax.profiler device trace next to the trace file"),
    _k("RACON_TPU_MACHINE_PROFILE", "auto", "str",
       "machine profile for cost-model predictions: auto | cpu-host | "
       "tpu-v5e (auto picks by platform and device_kind)"),
    _k("RACON_TPU_FLIGHT", "1", "bool",
       "always-on crash flight recorder: ring of the last N spans/events "
       "per process, dumped to the job dir on faults, TierDead, worker "
       "crash, or SIGTERM (0 disables; see obs/flight.py)"),
    _k("RACON_TPU_FLIGHT_EVENTS", "256", "int",
       "flight-recorder ring capacity: most-recent events kept per "
       "process for the post-mortem dump"),
    _k("RACON_TPU_OBS_SHIP_EVENTS", "1500", "int",
       "span-shipping cap: trace events a distrib worker / serve job "
       "returns with each result for the merged fleet timeline (bounded "
       "so shipments fit the wire's line limit)"),
    _k("RACON_TPU_TELEMETRY_RING", "64", "int",
       "live-telemetry ring capacity: periodic metrics snapshots kept "
       "per process, scraped through the serve/distrib 'stats' verb"),
    # -- SLO / exposition knobs (obs/slo.py, obs/export.py) ---------------
    _k("RACON_TPU_SLO_LATENCY_S", None, "str",
       "per-tenant job-latency SLO targets in seconds: a bare float is "
       "the default target, key=value pairs set per-tenant targets "
       "(e.g. 'default=2.5,tenant-a=1.0'); unset = no latency objective"),
    _k("RACON_TPU_SLO_AVAILABILITY", "0.99", "float",
       "SLO availability objective: the fraction of jobs that must "
       "finish inside their latency target (error budget = 1 - this)"),
    _k("RACON_TPU_SLO_FAST_WINDOW_S", "60", "float",
       "fast burn-rate window in seconds (the reactive half of the "
       "multi-window alert)"),
    _k("RACON_TPU_SLO_SLOW_WINDOW_S", "600", "float",
       "slow burn-rate window in seconds (the confirming half of the "
       "multi-window alert)"),
    _k("RACON_TPU_SLO_BURN_ALERT", "2.0", "float",
       "burn-rate alert threshold: both windows burning past it fires "
       "the slo.alert event and drives the fleet autoscaler (0 disables "
       "SLO alerting)"),
    _k("RACON_TPU_SLO_SHED_BURN", "0", "float",
       "burn-rate shedding threshold: new submissions shed (counted "
       "shed_slo) while both windows burn past it (0 = never shed on "
       "SLO burn)"),
    _k("RACON_TPU_METRICS_PORT", "0", "int",
       "Prometheus exposition HTTP port on the serve daemon (GET "
       "/metrics, localhost only; 0 = disabled, the `metrics` wire op "
       "still serves the same text)"),
    # -- serving knobs ----------------------------------------------------
    _k("RACON_TPU_SERVE_PORT", "0", "int",
       "TCP port for the `racon-tpu serve` daemon (0 = pick a free "
       "ephemeral port, recorded in <state-dir>/serve.json)"),
    _k("RACON_TPU_SERVE_QUEUE_DEPTH", "16", "int",
       "serve admission control: queued (not yet running) jobs beyond "
       "which new submissions are rejected"),
    _k("RACON_TPU_SERVE_MAX_JOBS", "64", "int",
       "serve admission control: total unfinished (queued + running) "
       "jobs the daemon will track at once"),
    _k("RACON_TPU_SERVE_WARMUP", "1", "bool",
       "pre-compile the consensus kernel geometries once at serve "
       "startup so the first job pays no kernel builds (0 disables)"),
    _k("RACON_TPU_SERVE_WINDOW_BUDGET", "0", "int",
       "serve per-job window budget: jobs whose estimated window count "
       "exceeds it are demoted to the host lane instead of occupying "
       "the device queue (0 = unlimited)"),
    # -- distributed-fleet knobs ------------------------------------------
    _k("RACON_TPU_DISTRIB_WORKERS", "2", "int",
       "`racon-tpu distrib` fleet size: chunk-worker processes the "
       "coordinator spawns (CLI --workers overrides)"),
    _k("RACON_TPU_DISTRIB_LEASE_TTL", "10", "float",
       "distrib chunk-lease TTL in seconds: a lease not renewed by a "
       "heartbeat within the TTL expires and the chunk is re-dispatched"),
    _k("RACON_TPU_DISTRIB_HEARTBEAT", None, "float",
       "distrib worker heartbeat interval in seconds (default: lease "
       "TTL / 3)"),
    _k("RACON_TPU_DISTRIB_RETRY_BASE", "0.25", "float",
       "distrib retry backoff base in seconds: attempt N of a chunk "
       "waits base * 2^(N-1) before becoming eligible again"),
    _k("RACON_TPU_DISTRIB_MAX_RETRIES", "3", "int",
       "distrib per-chunk failure budget: a chunk failing more than "
       "this many times falls back to local (in-coordinator) execution"),
    _k("RACON_TPU_DISTRIB_SPECULATE", "2.5", "float",
       "distrib straggler threshold: a running chunk whose elapsed time "
       "exceeds this factor x the median completed-chunk wall gets a "
       "speculative duplicate on an idle worker (0 disables)"),
    _k("RACON_TPU_DISTRIB_FAULT_WORKER", "0", "int",
       "distrib fault scoping: the worker index that inherits "
       "RACON_TPU_FAULT (other workers get it stripped), so chaos tests "
       "kill exactly one worker", scope="test"),
    # -- elastic fleet knobs (racon_tpu/fleet) ----------------------------
    _k("RACON_TPU_FLEET_MIN_WORKERS", "1", "int",
       "elastic fleet floor: worker processes the autoscaling pool "
       "keeps alive even when idle"),
    _k("RACON_TPU_FLEET_MAX_WORKERS", "0", "int",
       "elastic fleet ceiling: worker processes the pool may grow to "
       "under load; in the serve daemon 0 disables the fleet plane "
       "(jobs run in-process as before)"),
    _k("RACON_TPU_FLEET_SCALE_P95_MS", "250", "float",
       "autoscaler trigger: grow the pool when the recent chunk "
       "queueing p95 exceeds this many milliseconds with a backlog "
       "pending"),
    _k("RACON_TPU_FLEET_STEAL", "1", "bool",
       "fleet work stealing: an idle worker whose affinity job has no "
       "eligible chunks takes a chunk from another job (0 pins workers "
       "to their job until it finishes)"),
    _k("RACON_TPU_FLEET_TENANT_QUOTA", "0", "int",
       "per-tenant admission quota: unfinished jobs one submitter may "
       "hold in the scheduler/fleet plane at once (0 = unlimited)"),
    # -- test / bench knobs ----------------------------------------------
    _k("RACON_TPU_HW_TESTS", None, "bool",
       "assert exact on-hardware pins against a real TPU backend",
       scope="test"),
    _k("RACON_TPU_FULL_GOLDEN", None, "bool",
       "run the slow golden scenarios", scope="test"),
    _k("RACON_TPU_TEST_DATA", "/root/reference/test/data/", "str",
       "directory holding the lambda-phage fixture data", scope="test",
       affects_output=True),
    _k("RACON_TPU_BENCH_MBP", "0.5", "float",
       "benchmark workload size in polished megabases", scope="bench",
       affects_output=True),
    _k("RACON_TPU_BENCH_INPUT", "paf", "str",
       "benchmark overlap format: paf | sam", scope="bench",
       affects_output=True),
    _k("RACON_TPU_BENCH_PROFILE", "ont", "str",
       "benchmark read profile: ont | sr", scope="bench",
       affects_output=True),
    _k("RACON_TPU_BENCH_FORCE_DEVICE", None, "bool",
       "treat the current backend as the measured device (CPU rehearsal)",
       scope="bench"),
)}


# --------------------------------------------------------------------------
# typed accessors — the only sanctioned way to READ a RACON_TPU_* variable
# --------------------------------------------------------------------------

def _knob(name: str) -> Knob:
    try:
        return KNOBS[name]
    except KeyError:
        raise KeyError(
            f"{name!r} is not a registered knob; add it to "
            f"racon_tpu/config.py (and README.md)") from None


def get_raw(name: str) -> Optional[str]:
    """The raw environment value, or the registered default (may be
    None).  Exists so call sites with bespoke parsing keep byte-identical
    behavior while still going through the registry."""
    k = _knob(name)
    return os.environ.get(name, k.default)


def get_str(name: str) -> str:
    v = get_raw(name)
    return "" if v is None else v


def get_int(name: str) -> int:
    """int(value); raises ValueError on garbage exactly like the direct
    int(os.environ.get(...)) reads this replaced."""
    v = get_raw(name)
    if v is None:
        raise KeyError(f"{name} has no value and no registered default")
    return int(v)


def get_float(name: str) -> float:
    v = get_raw(name)
    if v is None:
        raise KeyError(f"{name} has no value and no registered default")
    return float(v)


def get_bool(name: str) -> bool:
    """True iff the variable is set to '1' (the repo-wide convention)."""
    return get_raw(name) == "1"


def is_set(name: str) -> bool:
    """Whether the variable is present in the environment at all."""
    _knob(name)
    return name in os.environ


def unknown_env_knobs(environ=None) -> List[str]:
    """RACON_TPU_* variables set in the environment but absent from the
    registry — almost always a typo'd knob that would otherwise be
    silently ignored.  Surfaced in the run report (see
    resilience/report.py)."""
    env = os.environ if environ is None else environ
    return sorted(v for v in env
                  if v.startswith(PREFIX) and v not in KNOBS)
