"""wall-clock: no wall-clock deadlines in the resilience layer.

``time.time()`` jumps when NTP steps the clock — a deadline, backoff, or
elapsed-time computation built on it can go negative or balloon by
minutes mid-run.  The resilience layer (watchdog timeouts, retry
backoff, run reports) and the hardware-session driver (per-step
budgets, lease renewal) are exactly the code that must survive such
steps, so they use ``time.monotonic()`` (or ``time.perf_counter`` for
fine-grained spans) exclusively.  The observability tracer is scoped for
the same reason: span durations computed from a stepped wall clock show
up as negative/garbage bars in Perfetto.  Wall-clock reads are fine
elsewhere —
log timestamps, unique directory names — hence the narrow scope.
"""

from __future__ import annotations

import ast
from typing import Iterable

from ..lint import FileContext, Violation
from . import dotted_name

#: Scope: the resilience package and the observability layer (trace
#: spans must be monotonic or Perfetto renders negative durations).
_SCOPED = (("resilience",), ("obs",))


class WallClockRule:
    id = "wall-clock"
    doc = ("no time.time() in racon_tpu/resilience/ or racon_tpu/obs/; "
           "deadlines, elapsed-time math, and trace spans use "
           "time.monotonic()")

    def check(self, ctx: FileContext) -> Iterable[Violation]:
        if not any(ctx.in_package(*p) for p in _SCOPED):
            return
        # `from time import time` makes every bare time() call a
        # wall-clock read; track the local name it lands on.
        bare_names = set()
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.ImportFrom) and node.module == "time":
                for alias in node.names:
                    if alias.name == "time":
                        bare_names.add(alias.asname or alias.name)
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            name = dotted_name(node.func)
            if name == "time.time" or name in bare_names:
                yield Violation(
                    self.id, ctx.relpath, node.lineno,
                    "time.time() jumps with NTP steps; use "
                    "time.monotonic() for deadlines/elapsed time "
                    "(wall-clock timestamps belong outside the "
                    "resilience layer)")
