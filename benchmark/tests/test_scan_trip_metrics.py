"""``poa_dp_scan_trips_per_step`` and
``poa_traceback_scan_trips_per_step`` (PR 53): their files against their
entries, looked up by name, and what their reader gives a report with
``racon_poa_ls``'s two scan counters, one of the parent's (which counts
the steps and not the scans) and one without any."""

import pytest

from benchmark import loader, reducers

QUOTIENTS = {
    "poa_dp_scan_trips_per_step": ("poa.ls.steps.dp_scan",
                                   "poa.ls.steps.dp"),
    "poa_traceback_scan_trips_per_step": ("poa.ls.steps.tb_scan",
                                          "poa.ls.steps.traceback")}


def _run(*counter_dicts):
    return {"jobs": [{"counters": c, "phases": {}, "spans": {}}
                     for c in counter_dicts],
            "notes": {}, "facts": {}, "trace": None}


def _read(name, cell, run):
    spec = {m["name"]: m for m in loader.load_cell(cell).per_layer}[name]
    return reducers.registry()[spec["reducer"]](run, **spec["params"])


@pytest.mark.parametrize("name", sorted(QUOTIENTS))
def test_listed_in_every_cell_under_the_kernels_layer(name):
    from racon_tpu.ops import poa_pallas_ls

    bm = loader.load_benchmark()
    cells = [w["name"] for w in bm["workloads"]]
    entry = {m["name"]: m for m in bm["per_layer"]}[name]
    assert entry["workloads"] == cells and len(cells) >= 11
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    counters = {f"poa.ls.{c}" for c in poa_pallas_ls.STEP_COUNTERS}
    for cell in cells:
        spec = {m["name"]: m for m in loader.load_cell(cell).per_layer}[name]
        assert (spec["layer"], spec["moves"], spec["unit"], spec["better"],
                spec["source"]) == ("kernels", "polished_mbp_per_s", "count",
                                    "lower", "program_counter")
        assert spec["reducer"] == "counter_quotient" and spec["what"]
        num, den = QUOTIENTS[name]
        assert spec["params"] == {"numerator": num, "denominator": den}
        assert {num, den} <= counters     # what an ls launch counts


def test_the_two_entries_are_the_last_of_per_layer():
    """Appended, in the order ISSUE 53 names them: nothing put before an
    entry that was there."""
    names = [m["name"] for m in loader.load_benchmark()["per_layer"]]
    assert set(QUOTIENTS) <= set(names)
    first = names.index("poa_dp_scan_trips_per_step")
    assert names[first + 1] == "poa_traceback_scan_trips_per_step"
    assert names.index("poa_traceback_steps_per_layer") < first


@pytest.mark.parametrize("cell", ["ecoli-ont.sam", "ecoli-ont-cap.sam",
                                  "ecoli-ont-x4.paf"])
def test_quotients_read_the_counters_or_nothing(cell):
    job = {"poa.ls.steps.dp": 1000, "poa.ls.steps.dp_scan": 2500,
           "poa.ls.steps.traceback": 1280, "poa.ls.steps.tb_scan": 1600,
           "poa.ls.layers": 1}
    assert _read("poa_dp_scan_trips_per_step", cell,
                 _run(job, job)) == pytest.approx(2.5)
    assert _read("poa_traceback_scan_trips_per_step", cell,
                 _run(job, job)) == pytest.approx(1.25)
    # the median over the window's jobs
    deep = dict(job, **{"poa.ls.steps.dp_scan": 4000})
    assert _read("poa_dp_scan_trips_per_step", cell,
                 _run(job, deep, deep)) == pytest.approx(4.0)
    # the parent's report (PR 52's counters, no scan among them), a
    # report of the XLA twin, and a job whose programs walked no rank
    parent = {k: v for k, v in job.items() if "scan" not in k}
    for name in QUOTIENTS:
        assert _read(name, cell, _run(parent, parent)) is None
        assert _read(name, cell, _run({"poa.launches": 18})) is None
        assert _read(name, cell, _run(
            {**job, "poa.ls.steps.dp": 0, "poa.ls.steps.dp_scan": 0,
             "poa.ls.steps.traceback": 0, "poa.ls.steps.tb_scan": 0})) is None
