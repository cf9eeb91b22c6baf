"""Engine 5: determinism taint auditor.

Statically proves the repo's byte-identity contract: every knob in the
``config.py`` registry is classified **output-affecting** or
**cost-only** by propagating explicit dataflow taint from its read
sites through the interprocedural call graph to the consensus/CIGAR
install seams (``pipeline.set_consensus`` / ``pipeline.set_job_cigar``
— ``poa_driver._install``, ``align_pallas.run_jobs``, the CPU polisher
stitch and journal replay).  The verdicts are then cross-checked against
the fingerprint compositions declared in ``racon_tpu/fingerprint.py``:

* ``determinism-leak`` — a cost-only knob's value reaches an install
  seam (the contract broken in code);
* ``fingerprint-gap`` — an output-affecting source missing from a
  composition declared complete (a cache could serve stale bytes);
* ``fingerprint-overkey`` (warning) — a component keyed only on
  cost-only, taint-clean knobs (needless cache misses).

Violations are ordinary ``lint.Violation`` objects, so the baseline /
suppression / CLI plumbing applies unchanged; intentional flows carry a
``# determinism: <reason>`` waiver on (or directly above) the flagged
line.  ``--emit-manifest`` writes the full knob/site classification as
``determinism.json``.
"""

from __future__ import annotations

import os
import shutil
import tempfile
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

from ..lint import Violation, repo_root_for
from .rules import WARNING_RULES

__all__ = [
    "AuditResult", "MUTANTS", "WARNING_RULES", "build_audit",
    "run_determinism", "run_mutant",
]


@dataclass
class AuditResult:
    """One Engine 5 run: hard violations, warnings, and the manifest."""

    violations: List[Violation] = field(default_factory=list)
    warnings: List[Violation] = field(default_factory=list)
    manifest: Dict = field(default_factory=dict)


def build_audit(repo_root: Optional[str] = None,
                paths: Optional[Sequence[str]] = None) -> AuditResult:
    """Run the full audit over one repo tree.

    paths — repo-relative file subset: the taint model is built from
    just these files (flows through unlisted code are invisible by
    design, like ``--concurrency``); the knob and fingerprint
    registries are always read from their canonical root files so the
    fingerprint rules judge the real contract either way.
    """
    from ..concurrency.model import Model
    from . import fingerprints, knobs, manifest, rules, taint
    root = repo_root or repo_root_for()
    model = Model.build(root, list(paths) if paths is not None else None)
    state = taint.analyze(model)
    decls = knobs.extract_registry(root) or {}
    fp_reg = fingerprints.extract_registry(root)
    viols = rules.evaluate(state, decls, fp_reg)
    return AuditResult(
        violations=[v for v in viols if v.rule not in WARNING_RULES],
        warnings=[v for v in viols if v.rule in WARNING_RULES],
        manifest=manifest.build(state, decls, fp_reg, viols))


def run_determinism(repo_root: Optional[str] = None,
                    paths: Optional[Sequence[str]] = None
                    ) -> List[Violation]:
    """The hard (non-warning) violations of one audit — the shape every
    other engine's ``run_*`` entry point returns."""
    return build_audit(repo_root, paths).violations


# --------------------------------------------------------------------------
# seeded mutants: prove the auditor catches what it claims to catch
# --------------------------------------------------------------------------

#: (name, doc, expected-rule, patches) — each patch is a
#: (relpath, old-text, new-text) exact-match textual substitution
#: applied to a scratch copy of the tree.  ``--det-mutate NAME`` (or
#: index) must then report the expected rule, else the self-test
#: failed.  CI runs every entry and requires a non-zero (caught) exit.
MUTANTS = [
    ("drop-input-bytes",
     "remove the input_bytes component from the journal fingerprint "
     "composition: the declared-complete site no longer covers the "
     "problem's input bytes",
     "fingerprint-gap",
     [("racon_tpu/fingerprint.py",
       '            "params": ("input:params",),\n'
       '            "input_bytes": ("input:sequences", "input:overlaps",\n'
       '                            "input:target"),\n',
       '            "params": ("input:params",),\n')]),
    ("leak-pipeline-depth",
     "route the RACON_TPU_PIPELINE_DEPTH value into the device "
     "consensus payload installed by poa_driver._install",
     "determinism-leak",
     [("racon_tpu/ops/poa_driver.py",
       "        payload = decode(kept_codes)\n",
       "        payload = decode(kept_codes) + str(\n"
       "            config.get_int(\"RACON_TPU_PIPELINE_DEPTH\"))"
       ".encode()\n")]),
    ("overkey-tier",
     "key the journal fingerprint on the Pallas-or-twin tier knob: a "
     "cost-only, taint-clean knob would force fingerprint misses "
     "between byte-identical runs",
     "fingerprint-overkey",
     [("racon_tpu/fingerprint.py",
       '            "backend": ("input:backend",),\n'
       '            "params": ("input:params",),\n',
       '            "backend": ("input:backend",),\n'
       '            "tier": ("knob:RACON_TPU_PALLAS",),\n'
       '            "params": ("input:params",),\n')]),
    ("drop-journal-waiver",
     "strip the documented waiver from the journal window-replay "
     "install: the intentional journal-bytes flow must resurface as a "
     "determinism-leak",
     "determinism-leak",
     [("racon_tpu/resilience/journal.py",
       "            # determinism: replayed bytes are journal records\n",
       "            # (waiver stripped by the seeded mutant)\n")]),
]


def run_mutant(repo_root: Optional[str], which: str) -> tuple:
    """Apply one seeded mutant to a scratch copy of the tree and audit
    it.  Returns ``(mutant, AuditResult, caught)``."""
    from ..lint import _EXTRA_FILES
    root = repo_root or repo_root_for()
    by_name = {m[0]: m for m in MUTANTS}
    if which in by_name:
        mutant = by_name[which]
    else:
        try:
            mutant = MUTANTS[int(which)]
        except (ValueError, IndexError):
            raise ValueError(
                f"unknown determinism mutant {which!r}; see "
                f"--list-det-mutations") from None
    tmp = tempfile.mkdtemp(prefix="racon-det-mutant-")
    try:
        shutil.copytree(os.path.join(root, "racon_tpu"),
                        os.path.join(tmp, "racon_tpu"))
        for extra in _EXTRA_FILES:
            src = os.path.join(root, extra)
            if os.path.exists(src):
                shutil.copy(src, os.path.join(tmp, extra))
        for rel, old, new in mutant[3]:
            path = os.path.join(tmp, rel)
            with open(path) as f:
                text = f.read()
            if old not in text:
                raise RuntimeError(
                    f"determinism mutant {mutant[0]}: patch anchor not "
                    f"found in {rel} (tree drifted; update MUTANTS)")
            with open(path, "w") as f:
                f.write(text.replace(old, new, 1))
        audit = build_audit(tmp)
        caught = any(v.rule == mutant[2]
                     for v in audit.violations + audit.warnings)
        return mutant, audit, caught
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
