"""``native_pool_items_per_task`` (PR 40): its file, its entry, and what
its reader gives a report with the two counters and one without."""

import pytest

from benchmark import loader, reducers

NAME = "native_pool_items_per_task"


def _run(*counter_dicts):
    return {"jobs": [{"counters": c, "phases": {}, "spans": {}}
                     for c in counter_dicts],
            "notes": {}, "facts": {}, "trace": None}


def _read(cell, run):
    spec = {m["name"]: m for m in loader.load_cell(cell).per_layer}[NAME]
    return reducers.registry()[spec["reducer"]](run, **spec["params"])


def test_listed_in_every_cell_under_the_phases_layer():
    bm = loader.load_benchmark()
    cells = [w["name"] for w in bm["workloads"]]
    entry = bm["per_layer"][-1]
    assert entry["name"] == NAME and entry["workloads"] == cells
    for cell in cells:
        spec = {m["name"]: m for m in loader.load_cell(cell).per_layer}[NAME]
        assert spec["layer"] == "phases" and spec["better"] == "higher"
        assert spec["moves"] == "polished_mbp_per_s" and spec["what"]
        assert spec["source"] == "program_counter"


def test_reads_items_over_tasks_as_the_median_job():
    run = _run({"native.pool.items": 106_000, "native.pool.tasks": 26},
               {"native.pool.items": 106_000, "native.pool.tasks": 26},
               {"native.pool.items": 3_800, "native.pool.tasks": 26})
    assert _read("chr20-sr.sam", run) == pytest.approx(106_000 / 26)


def test_reads_nothing_from_a_program_without_the_counters():
    run = _run({"overlaps.kept": 53_000, "polish.targets": 1},
               {"overlaps.kept": 53_000, "polish.targets": 1})
    assert _read("chr20-sr.sam", run) is None
    assert _read("ecoli-ont-x4.paf", _run({})) is None
