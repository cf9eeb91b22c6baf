"""The ``cap_*`` readers, and the deep cell's readers this cell is
appended to, on hand-made run records; the loader on the files that make
the cell ``ecoli-ont-cap.sam``; its generator."""

import json
import os
import shutil

import pytest

from benchmark import loader, prepare, reducers

CELL = "ecoli-ont-cap.sam"

#: a job of 200 windows at ~195 layers offered, 120 of them capped, as
#: the program counts it (racon_tpu/ops/poa_driver.py)
COUNTERS = {
    "poa.rows.real": 200, "poa.rows.pad": 56, "poa.launches": 4,
    "poa.windows.capped": 120, "poa.windows.capped.redone": 2,
    "poa.layers.admitted": 36000, "poa.layers.capped": 3000,
    "poa.layers.capped.bases": 1_500_000, "poa.layers.bases": 18_000_000,
    "poa.windows.rung.base": 10, "poa.windows.rung.upper": 190,
    "poa.windows.rung.miss.d8": 0, "poa.windows.rung.miss.d32": 0,
    "poa.windows.rung.miss.d200": 3,
    "poa.windows.overflow.nodes": 3, "poa.windows.overflow.edges": 1,
    "poa.windows.overflow.distance": 0, "poa.windows.overflow.other": 0,
    "poa.nodes.used": 392_000, "poa.nodes.capacity": 501_760,
    "poa.lockstep.layers.real": 36000, "poa.lockstep.layers.slots": 37500,
    "poa.programs.wide": 16, "poa.programs.narrow": 0,
    "poa.windows.trim.admitted": 196, "poa.windows.trim.full": 4,
    "poa.windows.d200.c512": 195, "poa.windows.d32.c512": 5}


def _run(*counter_dicts):
    return {"jobs": [{"counters": c, "spans": {}, "wall_s": 15.0,
                      "polished_bp": 100_000,
                      "phases": {"consensus": {"served": {"ls": 196}}}}
                     for c in counter_dicts],
            "facts": {}, "data": {}, "edits": {}, "notes": {},
            "trace": None, "device": None, "peaks": {}}


def _values(run):
    cell, registry = loader.load_cell(CELL), reducers.registry()
    return {m["name"]: registry[m["reducer"]](run, **m.get("params", {}))
            for m in cell.per_layer
            if m["name"].startswith(("cap_", "deep_poa_"))}


def test_each_reader_on_a_hand_made_record():
    v = _values(_run(COUNTERS, COUNTERS))
    assert len(v) == 10                  # three of its own, seven shared
    assert v["cap_poa_capped_window_share"] == pytest.approx(60.0)
    assert v["cap_poa_capped_layer_share"] == pytest.approx(
        100 * 3000 / 39000)
    assert v["cap_poa_edge_overflow_window_share"] == pytest.approx(0.5)
    # the deep cell's readers, which this cell is appended to
    assert v["deep_poa_layers_per_window"] == pytest.approx(180.0)
    # (its denominator prefix takes the rule's misses in: 190 of 203)
    assert v["deep_poa_upper_rung_window_share"] == pytest.approx(
        100 * 190 / 203)
    assert v["deep_poa_overflow_window_share"] == pytest.approx(2.0)
    assert v["deep_poa_node_fill_share"] == pytest.approx(
        100 * 392_000 / 501_760)
    assert v["deep_poa_lockstep_fill_share"] == pytest.approx(96.0)
    assert v["deep_poa_wide_program_share"] == 100.0
    assert v["deep_poa_roofline"] is None         # no device trace


def test_the_parent_reads_what_it_counts_and_nothing_else():
    """The parent of PR 43 counts ``poa.layers.capped`` (it dropped the
    layers, and said nothing of which) and not ``poa.windows.capped``:
    the one reader returns nothing, none raises."""
    older = {k: v for k, v in COUNTERS.items()
             if not k.startswith(("poa.windows.capped", "poa.windows.trim."))
             and k != "poa.layers.capped.bases"}
    v = _values(_run(older))
    assert v["cap_poa_capped_window_share"] is None
    assert v["cap_poa_capped_layer_share"] == pytest.approx(
        100 * 3000 / 39000)
    bare = _values(_run({"poa.launches": 4, "poa.rows.real": 200}))
    assert {k: x for k, x in bare.items() if k.startswith("cap_")} == \
        dict.fromkeys(k for k in v if k.startswith("cap_"))


def test_counter_over_sum_reads_nothing_without_a_whole():
    from benchmark.reducers import cap
    run = _run({"poa.layers.capped": 0, "poa.layers.admitted": 0})
    assert cap.counter_over_sum(run, "poa.layers.capped", [
        "poa.layers.admitted", "poa.layers.capped"]) is None
    run = _run({"poa.layers.capped": 0, "poa.layers.admitted": 50})
    assert cap.counter_over_sum(run, "poa.layers.capped", [
        "poa.layers.admitted", "poa.layers.capped"]) == 0.0


def test_the_loader_finds_the_cell_from_new_files_and_one_entry_each(
        tmp_path):
    """The cell's own files under names no cell has, in a copy of the
    benchmark: one entry each in ``BENCHMARK.json`` and nothing edited."""
    root = tmp_path / "checkout"
    bench = root / "benchmark"
    shutil.copytree(loader.BENCH_DIR, bench, ignore=shutil.ignore_patterns(
        ".cache", "out", "__pycache__"))
    shutil.copy(os.path.join(loader.ROOT, "BENCHMARK.json"), root)
    mine = loader.load_cell(CELL)

    def put(kind, name, doc):
        (bench / kind / f"{name}.json").write_text(json.dumps(doc))

    put("configs", "sandbox-cap", dict(mine.config, name="sandbox-cap"))
    put("traffic", "sam-sandbox", dict(mine.traffic, name="sam-sandbox"))
    put("workloads", "sandbox-cap.sam", dict(
        mine.workload, name="sandbox-cap.sam", config="sandbox-cap",
        traffic="sam-sandbox"))
    metric = dict(next(m for m in mine.per_layer
                       if m["name"] == "cap_poa_capped_layer_share"),
                  name="sandbox_capped_layer_share",
                  workloads=["sandbox-cap.sam"])
    put("layer_metrics", metric["name"],
        {k: v for k, v in metric.items()})
    bm = json.loads((root / "BENCHMARK.json").read_text())
    bm["configs"].append({"name": "sandbox-cap", "source": "x",
                          "file": "benchmark/configs/sandbox-cap.json",
                          "reduced": ["genome_mbp"], "why": "x"})
    bm["workloads"].append({"name": "sandbox-cap.sam",
                            "config": "sandbox-cap",
                            "traffic": "sam-sandbox", "chips": 1,
                            "why": "x"})
    bm["per_layer"].append({k: metric[k] for k in (
        "name", "unit", "better", "source", "layer", "moves", "workloads")})
    (root / "BENCHMARK.json").write_text(json.dumps(bm))

    cell = loader.load_cell("sandbox-cap.sam", root=str(root),
                            bench_dir=str(bench))
    assert cell.config["reads"]["coverage"] == 180
    assert prepare.data_params(cell, False)["genome_mbp"] == 0.1
    assert prepare.data_params(cell, True)["genome_mbp"] == 0.0011
    names = {m["name"] for m in cell.per_layer}
    assert "sandbox_capped_layer_share" in names
    assert not any(n.startswith("cap_") for n in names)
    # and the cell that is there still loads, with its own and no other
    again = loader.load_cell(CELL, root=str(root), bench_dir=str(bench))
    assert {m["name"] for m in again.per_layer} == \
        {m["name"] for m in mine.per_layer}
