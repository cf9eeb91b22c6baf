"""First-party static analysis: TPU-invariant lint + jaxpr audit +
concurrency/contract audits + protocol model checker + determinism
taint auditor.

Five engines, one CLI (``python -m racon_tpu.analysis``) and one shared
parsed-AST cache (`astcache.py`).  The two founding engines:

* **AST lint** (`lint.py` + `rules/`): repo-specific rules over the
  Python sources — invariants that every round-5 advisor finding turned
  out to violate silently: tracer leaks inside jit/Pallas regions,
  kernel-builder caches not keyed on device topology, `RACON_TPU_*` env
  reads bypassing the central knob registry (racon_tpu/config.py),
  fault-point names unknown to the resilience registry, and broad
  excepts around device seams that don't document the degradation
  lattice boundary.

* **Jaxpr audit** (`jaxpr_audit.py`): abstractly traces the POA
  kernel over the bucket-config grid and statically rejects forbidden
  primitives (host callbacks, infeed/outfeed, float64) and recompile
  blow-ups (distinct jit signatures across the grid vs. the budget
  declared in `ops/poa_driver.py`).

The later engines live in their own subpackages: `concurrency/` (lock
discipline + contract cross-checks, ``--concurrency``/``--contracts``),
`protocol/` (explicit-state fleet-lifecycle model checker,
``--model-check``), and `determinism/` (knob-to-install-seam taint
audit of the byte-identity contract vs the fingerprint registry,
``--determinism``, on by default for full-tree runs).

Suppression: append ``# lint: disable=<rule-id>`` to the flagged line,
or record existing debt in a baseline file (``--write-baseline``) — the
CLI then fails only on NEW violations.  `docs/static-analysis.md` lists
every rule with rationale.
"""

from .lint import Violation, iter_source_files, run_lint  # noqa: F401
from .jaxpr_audit import run_audit  # noqa: F401

__all__ = ["Violation", "iter_source_files", "run_lint", "run_audit"]
