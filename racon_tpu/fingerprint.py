"""One registry for every cache/journal fingerprint composition.

Four seams in the tree key cached or resumable artifacts on an
identity fingerprint:

* the **journal** header (`resilience/journal.py`) — one polishing
  problem's identity, deciding whether a crash-resume may replay a
  previous run's records;
* the **kernel cache** (`ops/kernel_cache.device_keyed_cache`) — the
  implicit device-topology prefix every memoized kernel build is keyed
  under;
* the **serve job dir** (`serve/session.py` / `serve/scheduler.py`) —
  the per-job artifact namespace whose backend-keyed journal turns a
  re-submitted job into a resume;
* the **program cache** (`ops/kernel_cache.Program`) — the file name a
  lowered kernel program is kept under beside the compile cache, so the
  next process loads it instead of tracing and lowering it again.

They used to compose their keys ad hoc, one per module.  This module is
now the single authority: the helpers below build the actual keys, and
the ``SITES`` / ``OUTPUT_SOURCES`` literals describe *what the keys
cover* so the determinism taint auditor (``racon_tpu/analysis/
determinism``, Engine 5) can statically cross-check every composition
against the knob registry:

* an output-affecting input or knob missing from a ``complete`` site is
  a ``fingerprint-gap`` (a cache could serve stale bytes);
* a component covering only cost-only knobs is a
  ``fingerprint-overkey`` (spurious cache misses).

The ``--emit-manifest`` output of Engine 5 is derived from these
literals; ROADMAP open item 5 (the content-addressed window cache) is
expected to consume that manifest as its fingerprint schema instead of
inventing a fourth ad-hoc composition.

Only the stdlib is imported (config.py-style) so this module is
importable from anywhere, including before jax initializes.
"""

from __future__ import annotations

import glob
import hashlib
import os
from typing import Dict, Optional, Sequence, Tuple

#: Journal header schema version (the journal refuses to replay records
#: written under a different version).
JOURNAL_VERSION = 1

#: Polish parameters excluded from the journal fingerprint because they
#: provably cannot change output bytes (thread count only schedules
#: work).  Everything else passed to the polisher is hashed.
EXCLUDED_PARAMS = ("num_threads",)

#: Output-affecting sources every *complete* fingerprint composition
#: must cover.  ``input:*`` tokens are the polisher's problem inputs;
#: Engine 5 adds a ``knob:<NAME>`` token for every runtime knob whose
#: registry entry declares ``affects_output=True`` (racon_tpu/config.py)
#: and fails the build if a complete site misses one.
OUTPUT_SOURCES = (
    "input:sequences",
    "input:overlaps",
    "input:target",
    "input:params",
    "input:backend",
)

#: The fingerprint-site registry.  PURE LITERAL — Engine 5 parses this
#: dict out of the AST, so no computed values, spreads, or helpers.
#:
#: Per site: ``helper`` names the function below that builds the real
#: key; ``complete: True`` means the key must cover every output-
#: affecting source (journal-style identity keys); ``complete: False``
#: means the keyed artifact is a pure function of its explicit
#: arguments (kernel builds) and only the listed extras matter.
#: ``components`` maps each key component to the source tokens it
#: covers; ``site:<name>`` nests another site's coverage (the serve job
#: dir contains a journal, so it inherits the journal's coverage).
SITES = {
    "journal": {
        "helper": "journal_fingerprint",
        "description": "resilience/journal.py header: may a resume "
                       "replay this journal's records?",
        "complete": True,
        "components": {
            "schema": ("const:journal-version",),
            "backend": ("input:backend",),
            "params": ("input:params",),
            "input_bytes": ("input:sequences", "input:overlaps",
                            "input:target"),
        },
    },
    "kernel_cache": {
        "helper": "kernel_cache_key",
        "description": "ops/kernel_cache.device_keyed_cache implicit "
                       "prefix: a built kernel is a pure function of "
                       "its builder args plus the device topology",
        "complete": False,
        "components": {
            "n_devices": ("topology:n_devices",),
            "platform": ("topology:platform",),
            "builder_args": ("args:builder",),
        },
    },
    "program_cache": {
        "helper": "program_key",
        "description": "ops/kernel_cache.Program file name: a lowered "
                       "program is a pure function of its builder's "
                       "arguments, its input shapes, the device it was "
                       "lowered for, the libraries that lowered it and "
                       "the source of the kernel bodies",
        "complete": False,
        "components": {
            "builder": ("args:builder",),
            "avals": ("args:avals",),
            "n_devices": ("topology:n_devices",),
            "platform": ("topology:platform",),
            "device_kind": ("topology:device_kind",),
            "versions": ("const:jax-version", "const:jaxlib-version",
                         "const:backend-version",
                         "const:export-calling-convention"),
            "source": ("const:kernel-source-bytes",),
        },
    },
    "serve_job_dir": {
        "helper": "serve_job_paths",
        "description": "serve/session.py per-job artifact namespace: "
                       "job id + backend key the journal a re-run "
                       "resumes",
        "complete": True,
        "components": {
            "job_id": ("input:job_id",),
            "backend": ("input:backend",),
            "journal": ("site:journal",),
        },
    },
}


# --------------------------------------------------------------------------
# the actual key builders (the helpers the SITES entries name)
# --------------------------------------------------------------------------

def journal_fingerprint(paths: Sequence[str], params: dict,
                        backend: str) -> str:
    """Identity of one polishing problem: input bytes + parameters +
    backend.  Streamed, so fingerprinting costs one read of the inputs
    (they are about to be parsed anyway).

    The serving environment (kernel tiers, batch size, pipeline depth,
    ...) is deliberately excluded — a resume may legally mix journaled
    device windows with recomputed ones, exactly like an uninterrupted
    run mixes tiers when the lattice degrades.  Engine 5 is the proof
    that the exclusion is sound: any knob with a dataflow path into
    output bytes is a ``determinism-leak`` finding.
    """
    h = hashlib.sha256()
    h.update(f"racon-tpu-journal-v{JOURNAL_VERSION}".encode())
    h.update(f"\0backend={backend}".encode())
    for k in sorted(params):
        if k in EXCLUDED_PARAMS:
            continue
        h.update(f"\0{k}={params[k]!r}".encode())
    for p in paths:
        h.update(b"\0file\0")
        with open(p, "rb") as f:
            for blk in iter(lambda: f.read(1 << 20), b""):
                h.update(blk)
    return h.hexdigest()


def kernel_cache_key(n_dev: int, platform: str) -> Tuple[int, str]:
    """The implicit key prefix ``device_keyed_cache`` prepends to every
    memoized kernel build (the builder's own args are the rest of the
    key — a built kernel is a pure function of both)."""
    return (int(n_dev), str(platform))


#: Every module a kernel body can come from, relative to the package.
KERNEL_SOURCES = ("ops/*.py", "parallel/*.py", "device.py")


def kernel_source_digest(package_dir: str) -> str:
    """sha256 over the bytes of ``KERNEL_SOURCES``, each under its name
    relative to the package: an edit to a kernel file moves it, the
    checkout's path and a file's line numbers as such do not."""
    h = hashlib.sha256()
    for pattern in KERNEL_SOURCES:
        for path in sorted(glob.glob(os.path.join(package_dir, pattern))):
            h.update(os.path.relpath(path, package_dir).encode())
            h.update(b"\0")
            with open(path, "rb") as f:
                h.update(f.read())
            h.update(b"\0")
    return h.hexdigest()


def program_key(builder, avals, topology, versions, source: str) -> str:
    """File name (less its suffix) of one lowered program in the program
    cache.  ``builder`` is the builder's name and arguments, ``avals``
    the inputs' (shape, dtype) pairs, ``topology`` (device count,
    platform, device kind), ``versions`` the libraries that lower
    (jax, jaxlib, the backend's own version string, ``jax.export``'s
    calling convention) and ``source`` :func:`kernel_source_digest`.
    Each part goes in by its ``repr``: ints, strings, bools and tuples
    of them, so the same program gets the same name in every process."""
    h = hashlib.sha256()
    for part in (builder, tuple(avals), tuple(topology), tuple(versions),
                 source):
        h.update(repr(part).encode())
        h.update(b"\0")
    return h.hexdigest()


def serve_job_paths(workdir: str, job_id: str,
                    backend: Optional[str] = None) -> Dict[str, str]:
    """Every path the serve layer derives from a job id: the job
    directory plus (when ``backend`` is given) the artifact paths
    inside it.  The journal filename is backend-keyed so a job demoted
    from the device lane to the host lane never replays device-tier
    records into a cpu run."""
    jd = os.path.join(workdir, "jobs", job_id)
    out = {"dir": jd}
    if backend is not None:
        out.update(
            journal=os.path.join(jd, f"journal.{backend}.jsonl"),
            output=os.path.join(jd, "polished.fasta"),
            trace=os.path.join(jd, "trace.json"),
            report=os.path.join(jd, "report.json"),
        )
    return out
