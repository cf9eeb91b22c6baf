"""The six ``full_*`` metrics of ``ecoli-ont-full-x4.sam`` (PR 50): each
file against its ``BENCHMARK.json`` entry, looked up by name (an entry a
later PR appends moves nothing here), what the readers give a job with
the counters and one without, and ``reference_full``'s launch bounds."""

import json
import os

import pytest

from benchmark import loader, reducers
from benchmark import reference_full as rf

CELL = "ecoli-ont-full-x4.sam"
NAMES = ("full_poa_launch_ahead_share", "full_poa_full_launch_share",
         "full_poa_job_share", "full_job_boundary_share",
         "full_poa_roofline", "full_peak_rss_gb")


def _run(*jobs):
    return {"jobs": [{"counters": {}, "phases": {}, "spans": {}, **j}
                     for j in jobs],
            "notes": {}, "facts": {}, "trace": None}


@pytest.mark.parametrize("name", NAMES)
def test_metric_file_agrees_with_its_entry(name):
    entries = {m["name"]: m for m in loader.load_benchmark()["per_layer"]}
    with open(os.path.join(loader.BENCH_DIR, "layer_metrics",
                           name + ".json")) as f:
        spec = json.load(f)
    entry = entries[name]
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for key in entry:
        assert spec[key] == entry[key], key
    assert entry["workloads"] == [CELL] and spec["what"]
    assert spec["reducer"] in reducers.registry()
    assert name in {m["name"] for m in loader.load_cell(CELL).per_layer}
    assert name not in {m["name"] for m in
                        loader.load_cell("ecoli-ont-x4.sam").per_layer}


def _read(name, run):
    spec = {m["name"]: m for m in loader.load_cell(CELL).per_layer}[name]
    return reducers.registry()[spec["reducer"]](run, **spec.get("params", {}))


def test_readers_over_two_jobs_counters_and_spans():
    second = 10 ** 9
    job = {"counters": {"poa.launches": 75, "poa.launches.full": 70,
                        "poa.queue.behind": 72, "poa.queue.empty": 3,
                        "job.rss.peak_mb": 2000},
           "spans": {"job": [(0, 20 * second)],
                     "phase.poa": [(2 * second, 15 * second)],
                     "job.open": [(0, second)],
                     "job.close": [(19 * second, second // 2)]}}
    later = {**job, "counters": {**job["counters"], "job.rss.peak_mb": 2100}}
    run = _run(job, later)
    assert _read("full_poa_launch_ahead_share", run) == pytest.approx(96.0)
    assert _read("full_poa_full_launch_share", run) == pytest.approx(
        100 * 70 / 75)
    assert _read("full_poa_job_share", run) == pytest.approx(75.0)
    assert _read("full_job_boundary_share", run) == pytest.approx(7.5)
    assert _read("full_peak_rss_gb", run) == pytest.approx(
        2100 * 2 ** 20 / 1e9)
    assert _read("full_poa_roofline", run) is None     # no device trace


def test_readers_find_nothing_in_a_program_without_the_counters():
    older = _run({"counters": {"poa.launches": 75, "poa.rows.pad": 428}})
    for name in NAMES:
        assert _read(name, older) is None, name


def test_launch_bounds_of_the_cells_reckoned_groups():
    # 9200 windows in three depth buckets, 128 rows a launch
    b = rf.launch_bounds({(8, 512): 72, (32, 512): 4300, (200, 512): 4800},
                         4)
    assert b["rows"] == 128 and b["windows"] == 9172
    assert b["unsplit"] == {"launches": 1 + 34 + 38, "full": 0 + 33 + 37,
                            "pad_rows": 73 * 128 - 9172}
    assert b["launches"] == (72, 75) and b["full"][1] == 71
    assert b["pad_rows"] == (72 * 128 - 9172, 75 * 128 - 9172)
