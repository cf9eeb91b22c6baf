"""Crash-safe, append-only journal of served window/overlap results.

A polishing run that is 90% done must survive a SIGKILL: the drivers
append one JSONL record per served unit as it is installed, so a
`--resume-journal` run replays everything already served and recomputes
only the rest, reproducing byte-identical output (the host and device
paths are deterministic under a fixed environment).

Format (one JSON object per line; keys sorted for stable bytes):

    {"fingerprint": "<sha256>", "kind": "header", "version": 1}
    {"contig": 0, "i": 17, "kind": "window", "payload": "ACGT...",
     "polished": true, "rank": 3, "sha": "<sha256(payload)[:16]>",
     "tier": "ls"}
    {"cigar": "120=1X...", "i": 4, "kind": "cigar", "tier": "hirschberg"}

Durability: every append is flushed and fsynced
(``RACON_TPU_JOURNAL_FSYNC``, default on) so a crash can lose at most
the record being written.  A journal write failure is degradation, not
death: the journal disarms itself with a warning and the polish
continues unjournaled.

Torn-write tolerance: replay scans from the top and stops at the first
incomplete, unparseable, or hash-mismatched line; the file is truncated
back to the last good byte before appending resumes.  A torn tail is
expected (that is what a SIGKILL mid-write produces), never fatal.

Input fingerprint: sha256 over the input files' bytes, the polish
parameters, and the backend.  Replaying records produced from different
inputs or parameters would corrupt output silently, so a mismatched
journal is refused — `--resume-journal` errors out (exit 1), the
`RACON_TPU_JOURNAL` auto-resume path warns and starts fresh.  Thread
count is excluded (it cannot change output); the serving environment
(kernel tiers, batch size, ...) is deliberately excluded too — a resume
may legally mix journaled device windows with recomputed ones, exactly
like an uninterrupted run mixes tiers when the lattice degrades.

Host-side alignment CIGARs are *not* journaled (the native engine has no
per-job getter and recomputes them deterministically); only device-
served CIGARs are.  Consensus records cover every window: device tiers,
host fallback, and backbone passthrough.

The `journal.append` / `journal.replay` fault points make both seams
deterministically testable — including `kill=1`, which turns an armed
append into the mid-run SIGKILL the subsystem exists to survive.
"""

from __future__ import annotations

import hashlib
import json
import os
import sys
from dataclasses import dataclass
from typing import Dict, Optional, Sequence, Set

from .. import config, fingerprint, obs
from . import faults

VERSION = fingerprint.JOURNAL_VERSION


class JournalError(RuntimeError):
    """A journal cannot be used for this run (fingerprint mismatch)."""


def _sha16(payload: bytes) -> str:
    return hashlib.sha256(payload).hexdigest()[:16]


def input_fingerprint(paths: Sequence[str], params: dict,
                      backend: str) -> str:
    """Identity of one polishing problem — the `journal` site of the
    unified fingerprint registry (racon_tpu/fingerprint.py), kept under
    its historical name for the drivers and tests that import it."""
    return fingerprint.journal_fingerprint(paths, params, backend)


@dataclass
class WindowRecord:
    payload: bytes
    polished: bool
    tier: str


@dataclass
class CigarRecord:
    cigar: str
    tier: str


class Journal:
    """One run's append handle + whatever a previous run left behind."""

    def __init__(self, path: str, fingerprint: str, *,
                 resume: bool = False, on_mismatch: str = "error"):
        assert on_mismatch in ("error", "fresh")
        self.path = path
        self.fingerprint = fingerprint
        self.resumed = False
        self.dead = False
        self.appended = 0
        self.windows: Dict[int, WindowRecord] = {}
        self.cigars: Dict[int, CigarRecord] = {}
        self._fsync = config.get_raw("RACON_TPU_JOURNAL_FSYNC") != "0"
        self._f = None
        if resume and os.path.exists(path) and os.path.getsize(path) > 0:
            self._open_resume(on_mismatch)
        else:
            self._open_fresh()

    # -- opening -----------------------------------------------------------
    def _open_fresh(self) -> None:
        self._f = open(self.path, "wb")
        header = {"fingerprint": self.fingerprint, "kind": "header",
                  "version": VERSION}
        self._f.write((json.dumps(header, sort_keys=True) + "\n").encode())
        self._f.flush()
        if self._fsync:
            os.fsync(self._f.fileno())

    def _open_resume(self, on_mismatch: str) -> None:
        good_end = 0
        header_ok = False
        with open(self.path, "rb") as f:
            for raw in f:
                if not raw.endswith(b"\n"):
                    break            # torn tail: crash mid-write
                try:
                    rec = json.loads(raw.decode("utf-8"))
                    if not isinstance(rec, dict):
                        break
                    if not header_ok:
                        if (rec.get("kind") != "header"
                                or rec.get("version") != VERSION):
                            break
                        if rec.get("fingerprint") != self.fingerprint:
                            if on_mismatch == "error":
                                raise JournalError(
                                    f"journal {self.path} was written for "
                                    f"different inputs/parameters "
                                    f"(fingerprint "
                                    f"{str(rec.get('fingerprint'))[:12]}… != "
                                    f"{self.fingerprint[:12]}…); refusing "
                                    f"to resume — rerun without "
                                    f"--resume-journal to start fresh")
                            print(f"[racon_tpu::journal] WARNING: "
                                  f"{self.path} belongs to different "
                                  f"inputs/parameters; starting fresh",
                                  file=sys.stderr)
                            self.windows.clear()
                            self.cigars.clear()
                            self._open_fresh()
                            return
                        header_ok = True
                    elif rec.get("kind") == "window":
                        payload = str(rec["payload"]).encode("latin-1")
                        if _sha16(payload) != rec.get("sha"):
                            break    # corrupt record: stop trusting here
                        self.windows[int(rec["i"])] = WindowRecord(
                            payload, bool(rec.get("polished")),
                            str(rec.get("tier", "?")))
                    elif rec.get("kind") == "cigar":
                        self.cigars[int(rec["i"])] = CigarRecord(
                            str(rec["cigar"]), str(rec.get("tier", "?")))
                    # unknown kinds from a newer writer: skip, keep offset
                except JournalError:
                    raise
                except Exception:  # noqa: BLE001 — any undecodable line
                    # ends the trusted prefix (torn/corrupt tail)
                    break
                good_end += len(raw)
        if not header_ok:
            # unreadable or foreign file: refuse to silently clobber it
            # on an explicit resume only if it parsed as a mismatched
            # journal (handled above); an empty/torn header is ours to
            # restart
            self.windows.clear()
            self.cigars.clear()
            self._open_fresh()
            return
        size = os.path.getsize(self.path)
        if good_end < size:
            print(f"[racon_tpu::journal] WARNING: {self.path}: dropping "
                  f"{size - good_end} torn trailing byte(s) "
                  f"(crash mid-append)", file=sys.stderr)
            with open(self.path, "r+b") as f:
                f.truncate(good_end)
        self._f = open(self.path, "ab")
        self.resumed = True

    # -- appending ---------------------------------------------------------
    def _append(self, rec: dict) -> None:
        if self.dead or self._f is None:
            return
        try:
            faults.check("journal.append")
            self._f.write(
                (json.dumps(rec, sort_keys=True) + "\n").encode("utf-8"))
            self._f.flush()
            if self._fsync:
                os.fsync(self._f.fileno())
            self.appended += 1
        except Exception as e:  # noqa: BLE001 — durability must never
            # fail the polish; a dead journal is a degraded run, not a
            # failed one
            self.dead = True
            print(f"[racon_tpu::journal] WARNING: journal write failed "
                  f"({type(e).__name__}: {e}); continuing without "
                  f"journaling", file=sys.stderr)

    def append_window(self, i: int, contig: int, rank: int, tier: str,
                      consensus: bytes, polished: bool) -> None:
        self._append({"contig": int(contig), "i": int(i), "kind": "window",
                      "payload": consensus.decode("latin-1"),
                      "polished": bool(polished), "rank": int(rank),
                      "sha": _sha16(consensus), "tier": tier})

    def append_cigar(self, job: int, tier: str, cigar: str) -> None:
        self._append({"cigar": cigar, "i": int(job), "kind": "cigar",
                      "tier": tier})

    def close(self) -> None:
        if self._f is not None:
            try:
                self._f.close()
            except OSError:
                pass
            self._f = None

    def __del__(self):
        self.close()


# --------------------------------------------------------------------------
# replay helpers shared by the CPU polisher and the device drivers
# --------------------------------------------------------------------------

def replay_windows(pipeline, journal: Optional[Journal], n: int,
                   report=None) -> Set[int]:
    """Install journaled consensus payloads; returns the replayed window
    indices.  A poisoned replay (the `journal.replay` fault point)
    degrades to recomputing everything — correctness never depends on
    the journal."""
    if journal is None or not journal.windows:
        return set()
    try:
        faults.check("journal.replay", sorted(journal.windows))
    except Exception as e:  # noqa: BLE001 — replay seam: a bad journal
        # must degrade to a fresh computation, not abort the polish
        print(f"[racon_tpu::journal] WARNING: replay failed "
              f"({type(e).__name__}: {e}); recomputing all windows",
              file=sys.stderr)
        if report is not None:
            report.record_failure("journal", e)
        return set()
    done: Set[int] = set()
    with obs.span("journal.replay", kind="windows") as sp:
        for i in sorted(journal.windows):
            if not 0 <= i < n:
                continue         # defensive: fingerprint should prevent
            rec = journal.windows[i]
            # determinism: replayed bytes are journal records
            # fingerprint-matched to this exact run's inputs (see the
            # `journal` site in racon_tpu/fingerprint.py)
            pipeline.set_consensus(i, rec.payload, rec.polished)
            done.add(i)
            if report is not None:
                report.record_served("journal")
        sp.set(replayed=len(done))
    return done


def replay_cigars(pipeline, journal: Optional[Journal], n: int,
                  report=None) -> Set[int]:
    """Install journaled device CIGARs; returns the replayed job
    indices (they are excluded from device batching, and the native
    host pass skips any job whose CIGAR is already set)."""
    if journal is None or not journal.cigars:
        return set()
    try:
        faults.check("journal.replay", sorted(journal.cigars))
    except Exception as e:  # noqa: BLE001 — replay seam (see above)
        print(f"[racon_tpu::journal] WARNING: cigar replay failed "
              f"({type(e).__name__}: {e}); realigning all jobs",
              file=sys.stderr)
        if report is not None:
            report.record_failure("journal", e)
        return set()
    done: Set[int] = set()
    with obs.span("journal.replay", kind="cigars") as sp:
        for job in sorted(journal.cigars):
            if not 0 <= job < n:
                continue
            # determinism: replayed CIGARs are journal records
            # fingerprint-matched to this exact run's inputs (see the
            # `journal` site in racon_tpu/fingerprint.py)
            pipeline.set_job_cigar(job, journal.cigars[job].cigar)
            done.add(job)
            if report is not None:
                report.record_served("journal")
        sp.set(replayed=len(done))
    return done


class CigarTap:
    """Pipeline proxy that journals each CIGAR as an engine installs it.

    The device aligner (`align_pallas.run_jobs`) installs results
    through `pipeline.set_job_cigar`; wrapping the pipeline taps that one
    seam without the engine knowing the journal exists.  Everything else
    delegates untouched."""

    def __init__(self, pipeline, journal: Journal, tier: str):
        self._pipeline = pipeline
        self._journal = journal
        self._tier = tier

    def __getattr__(self, name):
        return getattr(self._pipeline, name)

    def set_job_cigar(self, job: int, cigar: str) -> None:
        self._pipeline.set_job_cigar(job, cigar)
        self._journal.append_cigar(job, self._tier, cigar)
