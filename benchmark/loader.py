"""Finds a cell's files by the names ``BENCHMARK.json`` gives them.

Nothing here knows a cell, a configuration, a traffic mix or a metric by
name: a later PR adds ``configs/<name>.json``, ``traffic/<mix>.json``,
``workloads/<cell>.json`` or ``layer_metrics/<metric>.json`` plus one
entry in ``BENCHMARK.json`` and edits no file that is there.
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass, field

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


class BenchmarkError(Exception):
    """A fault in the benchmark's own files (not in the program)."""


def _read_json(path: str) -> dict:
    try:
        with open(path) as f:
            doc = json.load(f)
    except FileNotFoundError:
        raise BenchmarkError(f"missing file {path}") from None
    except json.JSONDecodeError as e:
        raise BenchmarkError(f"{path}: {e}") from None
    if not isinstance(doc, dict):
        raise BenchmarkError(f"{path}: expected a JSON object")
    return doc


@dataclass
class Cell:
    """One entry of ``workloads``, with every file it names loaded."""

    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    workload: dict
    end_to_end: list          # metric entries of BENCHMARK.json
    per_layer: list           # BENCHMARK.json entry merged with its file
    peaks: dict = field(default_factory=dict)


def load_benchmark(root: str = ROOT) -> dict:
    return _read_json(os.path.join(root, "BENCHMARK.json"))


def _for_cell(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT,
              bench_dir: str = BENCH_DIR) -> Cell:
    bm = load_benchmark(root)
    entry = next((w for w in bm["workloads"] if w["name"] == name), None)
    if entry is None:
        known = ", ".join(w["name"] for w in bm["workloads"])
        raise BenchmarkError(f"unknown workload {name!r}; BENCHMARK.json "
                             f"has: {known}")
    cfg_entry = next((c for c in bm["configs"]
                      if c["name"] == entry["config"]), None)
    if cfg_entry is None:
        raise BenchmarkError(f"workload {name!r} names configuration "
                             f"{entry['config']!r}, which BENCHMARK.json "
                             "does not list")
    config = _read_json(os.path.join(root, cfg_entry["file"]))
    traffic = _read_json(os.path.join(bench_dir, "traffic",
                                      entry["traffic"] + ".json"))
    workload = _read_json(os.path.join(bench_dir, "workloads",
                                       name + ".json"))
    for key in ("config", "traffic", "chips"):
        if workload.get(key) != entry[key]:
            raise BenchmarkError(
                f"workloads/{name}.json says {key}={workload.get(key)!r}, "
                f"BENCHMARK.json says {entry[key]!r}")
    per_layer = []
    for m in bm["per_layer"]:
        if not _for_cell(m, name):
            continue
        spec = _read_json(os.path.join(bench_dir, "layer_metrics",
                                       m["name"] + ".json"))
        for key in ("unit", "better", "source", "layer", "moves"):
            if spec.get(key) != m[key]:
                raise BenchmarkError(
                    f"layer_metrics/{m['name']}.json says {key}="
                    f"{spec.get(key)!r}, BENCHMARK.json says {m[key]!r}")
        per_layer.append({**spec, **m})
    return Cell(
        name=name, chips=int(entry["chips"]), config_name=entry["config"],
        traffic_name=entry["traffic"], config=config, traffic=traffic,
        workload=workload,
        end_to_end=[m for m in bm["end_to_end"] if _for_cell(m, name)],
        per_layer=per_layer,
        peaks=_read_json(os.path.join(bench_dir, "peaks.json")))
