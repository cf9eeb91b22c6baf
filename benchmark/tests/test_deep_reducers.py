"""``benchmark/reducers/deep.py`` on hand-made ``run`` dicts: the
arithmetic, and ``None`` from a program without the counters."""

import pytest

from benchmark import costs
from benchmark.reducers import deep

COUNTERS = {"poa.rows.real": 300, "poa.windows.overflow.nodes": 6,
            "poa.windows.overflow.edges": 0,
            "poa.windows.overflow.distance": 3,
            "poa.windows.overflow.other": 0,
            "poa.layers.bases": 15_000_000, "poa.nodes.used": 520_000,
            "poa.windows.d200.c512": 290, "poa.windows.d32.c512": 10}


def _run(*counter_dicts):
    return {"jobs": [{"counters": c, "phases": {}, "spans": {}}
                     for c in counter_dicts],
            "notes": {}, "facts": {}, "trace": None}


def test_overflow_share_sums_the_causes():
    run = _run(COUNTERS, COUNTERS)
    assert deep.counter_family_share(
        run, "poa.windows.overflow.", "poa.rows.real") == pytest.approx(3.0)


def test_overflow_share_reads_nothing_without_the_counters():
    run = _run({"poa.rows.real": 300, "poa.launches": 6})
    assert deep.counter_family_share(
        run, "poa.windows.overflow.", "poa.rows.real") is None


def test_ops_follow_the_graphs_the_job_built():
    ops, byts = deep.poa_ops_bytes(COUNTERS, served=291)
    mean_graph = (512 + 520_000 / 291) / 2
    assert ops == pytest.approx(
        15_000_000 * mean_graph * costs.POA_OPS_PER_CELL)
    assert byts == pytest.approx(15_000_000 * 5 + 2 * 300 * 512 * 5)
    # a deep job's graphs are larger than costs.NODE_GROWTH x the class
    assert mean_graph > costs.NODE_GROWTH * 512


def test_ops_are_nothing_without_the_counters():
    assert deep.poa_ops_bytes({"poa.windows.d200.c512": 290}, 290) == (0, 0)
    assert deep.poa_ops_bytes(COUNTERS, served=0) == (0, 0)


def test_roofline_reads_nothing_without_a_trace():
    assert deep.roofline(_run(COUNTERS), "phase.poa",
                         ["tpu_custom_call"]) is None
