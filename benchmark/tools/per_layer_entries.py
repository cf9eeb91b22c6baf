#!/usr/bin/env python3
"""Writes BENCHMARK.json's ``per_layer`` entries from the metric files.

``layer_metrics/<name>.json`` is the source: a later PR adds a file
there and runs this to get the entry to paste into ``BENCHMARK.json``
(it prints, it edits nothing).
"""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
METRICS = os.path.join(os.path.dirname(HERE), "layer_metrics")
KEYS = ("name", "unit", "better", "source", "layer", "moves")


def entries() -> list:
    out = []
    for fn in sorted(os.listdir(METRICS)):
        with open(os.path.join(METRICS, fn)) as f:
            spec = json.load(f)
        entry = {k: spec[k] for k in KEYS}
        if "workloads" in spec:
            entry["workloads"] = spec["workloads"]
        out.append(entry)
    return out


if __name__ == "__main__":
    json.dump(entries(), sys.stdout, indent=1)
    print()
