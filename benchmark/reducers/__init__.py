"""Per-layer metric readers, looked up by name.

A reader is a function ``f(run, **params) -> number | None`` listed in
its module's ``REDUCERS`` dict.  Every module in this directory joins the
registry by being here; a metric's file (``layer_metrics/<name>.json``)
names its reader under ``"reducer"`` and the reader's parameters under
``"params"``.  A reader that finds nothing to read returns ``None`` and
the harness leaves the metric out of the line.

``run`` is the dict ``run.py`` gathers: ``facts`` (numbers of set-up and
of the device), ``jobs`` (per window job: ``wall_s``, ``polished_bp``,
``phases`` of its report, ``counters``, ``spans`` by name as
``[(start_mono_ns, dur_ns)]``), ``trace`` / ``device`` (the profiler
trace and its reduction), ``data`` (facts of the generated data),
``edits`` (edit distances to the truth) and ``peaks``.
"""

from __future__ import annotations

import importlib
import pkgutil


def registry() -> dict:
    found = {}
    for info in sorted(pkgutil.iter_modules(__path__), key=lambda i: i.name):
        mod = importlib.import_module(f"{__name__}.{info.name}")
        for name, fn in getattr(mod, "REDUCERS", {}).items():
            if name in found:
                raise ValueError(f"reducer {name!r} defined twice "
                                 f"(again in {info.name})")
            found[name] = fn
    return found
