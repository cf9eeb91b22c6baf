"""``program_cache_hit_share`` (PR 42): its file, its entry, and what its
reader gives a program with the program cache's counts and one without."""

import pytest

from benchmark import loader, reducers

NAME = "program_cache_hit_share"


def _read(monkeypatch, traffic):
    from racon_tpu import device

    monkeypatch.setattr(device, "cache_traffic", lambda: traffic)
    run = {"jobs": [], "notes": {}, "facts": {}, "trace": None}
    spec = {m["name"]: m
            for m in loader.load_cell("ecoli-ont.paf").per_layer}[NAME]
    return reducers.registry()[spec["reducer"]](run, **spec["params"]), run


def test_listed_in_every_cell_under_the_trace_and_lower_layer():
    bm = loader.load_benchmark()
    cells = [w["name"] for w in bm["workloads"]]
    entry = next(m for m in bm["per_layer"] if m["name"] == NAME)
    assert set(cells[:8]) <= set(entry["workloads"])
    for cell in entry["workloads"]:
        spec = {m["name"]: m for m in loader.load_cell(cell).per_layer}[NAME]
        assert spec["layer"] == "trace + lower" and spec["unit"] == "%"
        assert spec["moves"] == "setup_s" and spec["better"] == "higher"
        assert spec["source"] == "program_counter" and spec["what"]


@pytest.mark.parametrize("hits,misses,share", [(0, 91, 0.0), (91, 0, 100.0),
                                               (88, 3, 100.0 * 88 / 91)])
def test_reads_hits_over_hits_and_misses(monkeypatch, hits, misses, share):
    value, run = _read(monkeypatch, {
        "program_hits": hits, "program_misses": misses,
        "program_skipped": 0, "program_load_s": 0.25, "trace_s": 1.0})
    assert value == pytest.approx(share)
    assert run["notes"]["program_cache"] == {
        "hits": hits, "misses": misses, "skipped": 0, "program_load_s": 0.25}


def test_reads_nothing_from_a_program_without_the_cache(monkeypatch):
    # the parent's cache_traffic(), and a process whose programs never
    # met the cache (a CPU rehearsal, JAX_COMPILATION_CACHE_DIR="")
    assert _read(monkeypatch, {"trace_s": 1.0, "lower_s": 2.0,
                               "hits": 3, "misses": 0})[0] is None
    value, run = _read(monkeypatch, {"program_hits": 0, "program_misses": 0})
    assert value is None and not run["notes"]
