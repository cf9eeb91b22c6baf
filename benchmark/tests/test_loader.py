"""The harness is driven by data: a new configuration, traffic mix, cell,
per-layer metric and reducer module are picked up from new files and one
``BENCHMARK.json`` entry each, with no edit to a file that is there."""

import json
import os
import re
import shutil
import sys

import pytest

from benchmark import loader, prepare, reducers

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_every_listed_cell_loads():
    bm = loader.load_benchmark()
    registry = reducers.registry()
    for w in bm["workloads"]:
        cell = loader.load_cell(w["name"])
        assert cell.chips == w["chips"]
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert cell.per_layer, w["name"]
        for m in cell.per_layer:
            assert m["reducer"] in registry, m["name"]
        params = prepare.data_params(cell, rehearsal=False)
        assert params["overlaps"] in params["formats"]
        assert params["genome_mbp"] > prepare.data_params(
            cell, rehearsal=True)["genome_mbp"]


def test_contract_limits():
    """The limits of the builder's contract that a file can break."""
    path = os.path.join(loader.ROOT, "BENCHMARK.json")
    assert os.path.getsize(path) <= 64 * 1024
    bm = loader.load_benchmark()
    assert set(bm) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert 1 <= bm["run_seconds"] <= 51
    names = [m["name"] for m in bm["end_to_end"] + bm["per_layer"]]
    assert len(set(names)) == len(names)
    for m in bm["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in bm["end_to_end"]}
    assert "setup_s" in e2e
    layers = set()
    for m in bm["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["moves"] in e2e
        layers.add(m["layer"])
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    cells = {w["name"] for w in bm["workloads"]}
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells
    assert len({(w["config"], w["traffic"]) for w in bm["workloads"]}) \
        == len(bm["workloads"])
    assert sum(w["chips"] == 4 for w in bm["workloads"]) <= max(
        1, len(bm["workloads"]) // 2)
    used = {w["config"] for w in bm["workloads"]}
    for c in bm["configs"]:
        assert c["name"] in used
        assert c["file"].startswith("benchmark/")
        assert len(c["source"]) <= 200 and len(c["why"]) <= 200
        assert all(NAME.match(k) for k in c["reduced"])
    for w in bm["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    # at most ~24 cells fit the check's budget at this run length
    assert (2 + 14 * 24) * (bm["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200


@pytest.fixture
def sandbox(tmp_path):
    """A copy of BENCHMARK.json and the benchmark's data directories that
    a test may add to."""
    root = tmp_path / "checkout"
    shutil.copytree(loader.BENCH_DIR, root / "benchmark",
                    ignore=shutil.ignore_patterns(".cache", "out",
                                                  "__pycache__"))
    shutil.copy(os.path.join(loader.ROOT, "BENCHMARK.json"), root)
    return root


def test_new_files_and_one_entry_each_add_a_cell(sandbox, monkeypatch):
    bench = sandbox / "benchmark"
    first = loader.load_benchmark()["workloads"][0]
    base = loader.load_cell(first["name"])

    config = dict(base.config, name="lambda-ont")
    (bench / "configs" / "lambda-ont.json").write_text(json.dumps(config))
    traffic = dict(base.traffic, name="paf-48kb",
                   data={"genome_mbp": 0.048, "overlaps": "paf",
                         "formats": ["paf"]})
    (bench / "traffic" / "paf-48kb.json").write_text(json.dumps(traffic))
    workload = dict(base.workload, name="lambda-ont.paf",
                    config="lambda-ont", traffic="paf-48kb")
    (bench / "workloads" / "lambda-ont.paf.json").write_text(
        json.dumps(workload))
    metric = {"name": "jobs_in_window", "layer": "entry", "unit": "count",
              "better": "higher", "source": "host_clock",
              "moves": "polished_mbp_per_s", "reducer": "job_count",
              "params": {}, "workloads": ["lambda-ont.paf"]}
    (bench / "layer_metrics" / "jobs_in_window.json").write_text(
        json.dumps(metric))
    (bench / "reducers" / "extra_counts.py").write_text(
        "def job_count(run):\n    return len(run['jobs'])\n\n\n"
        "REDUCERS = {'job_count': job_count}\n")

    bm = json.loads((sandbox / "BENCHMARK.json").read_text())
    bm["configs"].append({"name": "lambda-ont", "source": "x",
                          "file": "benchmark/configs/lambda-ont.json",
                          "reduced": [], "why": "x"})
    bm["workloads"].append({"name": "lambda-ont.paf",
                            "config": "lambda-ont", "traffic": "paf-48kb",
                            "chips": 1, "why": "x"})
    bm["per_layer"].append({k: metric[k] for k in (
        "name", "unit", "better", "source", "layer", "moves", "workloads")})
    (sandbox / "BENCHMARK.json").write_text(json.dumps(bm))

    cell = loader.load_cell("lambda-ont.paf", root=str(sandbox),
                            bench_dir=str(bench))
    assert cell.config["name"] == "lambda-ont"
    assert prepare.data_params(cell, False)["genome_mbp"] == 0.048
    assert "jobs_in_window" in {m["name"] for m in cell.per_layer}
    # the metric is this cell's only: the cells that were there do not
    # report it
    old = loader.load_cell(first["name"], root=str(sandbox),
                           bench_dir=str(bench))
    assert "jobs_in_window" not in {m["name"] for m in old.per_layer}

    # the registry finds the new module by its being in the directory
    for mod in [m for m in sys.modules if m.startswith("benchmark")]:
        monkeypatch.delitem(sys.modules, mod)
    monkeypatch.syspath_prepend(str(sandbox))
    import benchmark.reducers as fresh
    assert fresh.__file__.startswith(str(sandbox))
    reg = fresh.registry()
    assert reg["job_count"]({"jobs": [1, 2, 3]}) == 3
    assert "span_s_per_mbp" in reg


def test_disagreeing_files_are_refused(sandbox):
    bench = sandbox / "benchmark"
    first = loader.load_benchmark()["workloads"][0]["name"]
    path = bench / "workloads" / f"{first}.json"
    doc = json.loads(path.read_text())
    doc["chips"] = 4 if doc["chips"] == 1 else 1
    path.write_text(json.dumps(doc))
    with pytest.raises(loader.BenchmarkError, match="chips"):
        loader.load_cell(first, root=str(sandbox), bench_dir=str(bench))
    with pytest.raises(loader.BenchmarkError, match="unknown workload"):
        loader.load_cell("no-such-cell")
