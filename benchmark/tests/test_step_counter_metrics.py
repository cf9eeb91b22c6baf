"""``poa_insert_shift_steps_per_firing``,
``poa_insert_firings_per_update_step``, ``poa_dp_steps_per_layer`` and
``poa_traceback_steps_per_layer`` (PR 52): their files against their
entries, looked up by name, and what their reader gives a report with
``racon_poa_ls``'s step counters and one without."""

import pytest

from benchmark import loader, reducers

QUOTIENTS = {
    "poa_insert_shift_steps_per_firing": ("poa.ls.insert.shift_steps",
                                          "poa.ls.insert.firings"),
    "poa_insert_firings_per_update_step": ("poa.ls.insert.firings",
                                           "poa.ls.steps.update"),
    "poa_dp_steps_per_layer": ("poa.ls.steps.dp", "poa.ls.layers"),
    "poa_traceback_steps_per_layer": ("poa.ls.steps.traceback",
                                      "poa.ls.layers")}


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
    counters = {f"poa.ls.{c}"
                for c in ("layers",) + poa_pallas_ls.STEP_COUNTERS}
    for cell in cells:
        spec = {m["name"]: m for m in loader.load_cell(cell).per_layer}[name]
        assert (spec["layer"], spec["moves"], spec["unit"], spec["better"],
                spec["source"]) == ("kernels", "polished_mbp_per_s", "count",
                                    "lower", "program_counter")
        assert spec["reducer"] == "counter_quotient" and spec["what"]
        num, den = QUOTIENTS[name]
        assert spec["params"] == {"numerator": num, "denominator": den}
        assert {num, den} <= counters     # what an ls launch counts


@pytest.mark.parametrize("cell", ["ecoli-ont.sam", "ecoli-ont.paf",
                                  "ecoli-ont-full-x4.sam"])
def test_quotients_read_the_counters_or_nothing(cell):
    job = {"poa.ls.insert.shift_steps": 900, "poa.ls.insert.firings": 600,
           "poa.ls.steps.update": 400, "poa.ls.steps.dp": 300,
           "poa.ls.steps.traceback": 300, "poa.ls.layers": 200}
    deep = dict(job, **{"poa.ls.insert.shift_steps": 1500})
    for name in QUOTIENTS:
        assert _read(name, cell, _run(job, job)) == pytest.approx(1.5)
    # the median over the window's jobs
    assert _read("poa_insert_shift_steps_per_firing", cell,
                 _run(job, deep, deep)) == pytest.approx(2.5)
    # the parent's report, and a job that fired no insertion
    assert _read("poa_insert_shift_steps_per_firing", cell,
                 _run({"poa.launches": 18})) is None
    assert _read("poa_insert_shift_steps_per_firing", cell, _run(
        {**job, "poa.ls.insert.firings": 0,
         "poa.ls.insert.shift_steps": 0})) is None
