"""The readers of the launch-level spans and counters, on hand-made
``run`` dicts: the arithmetic, and that a program without the spans
gives ``None`` and never raises."""

import pytest

from benchmark.reducers import launches

MS = 1_000_000      # ns


def _job(spans=None, counters=None, served=None, events=()):
    return {"spans": spans or {}, "counters": counters or {},
            "events": list(events), "polished_bp": 100_000,
            "phases": {p: {"served": s} for p, s in (served or {}).items()}}


def _run(*jobs):
    return {"jobs": list(jobs), "notes": {}, "facts": {}}


# ------------------------------------------------------- uncovered_share

def test_uncovered_share_takes_the_union_of_what_is_inside():
    job = _job(spans={
        "outer": [(0, 100 * MS)],
        # nested: b inside a; overlapping: c starts inside a and ends
        # after it; another thread's span d overlaps c
        "a": [(10 * MS, 30 * MS)],          # 10..40
        "b": [(15 * MS, 5 * MS)],           # 15..20, inside a
        "c": [(35 * MS, 15 * MS)],          # 35..50
        "d": [(45 * MS, 15 * MS)],          # 45..60, other thread
        # reaches outside the outer span: only 90..100 counts
        "e": [(90 * MS, 50 * MS)],
        "unrelated": [(60 * MS, 30 * MS)],
    })
    run = _run(job)
    share = launches.uncovered_share(run, "outer", ["a", "b", "c", "d", "e"])
    # covered: 10..60 and 90..100 = 60 of 100
    assert share == pytest.approx(40.0)
    note = run["notes"]["uncovered_share:outer"]
    assert note["span_s"] == pytest.approx(0.1)
    assert note["uncovered_s"] == pytest.approx(0.04)
    assert note["inside_s"]["e"] == pytest.approx(0.01)
    assert note["trace_events"] == 7


def test_uncovered_share_over_several_outer_spans_and_a_prefix():
    job = _job(spans={
        "align.cohort": [(0, 10 * MS), (20 * MS, 10 * MS)],
        "align.dispatch": [(1 * MS, 2 * MS), (21 * MS, 1 * MS)],
        "align.wait": [(3 * MS, 4 * MS), (22 * MS, 6 * MS)],
        # between the cohorts: covers nothing of them
        "align.pack": [(12 * MS, 5 * MS)],
        "jit.trace": [(8 * MS, 1 * MS)],
    })
    share = launches.uncovered_share(
        _run(job), "align.cohort", ["align.dispatch", "align.wait"])
    assert share == pytest.approx(100 * (1 - 13 / 20))
    with_jit = launches.uncovered_share(
        _run(job), "align.cohort", ["align.*", "jit.*"])
    # align.* takes in the cohort itself: nothing is left uncovered
    assert with_jit == pytest.approx(0.0)
    only_jit = launches.uncovered_share(_run(job), "align.cohort", ["jit.*"])
    assert only_jit == pytest.approx(95.0)


def test_uncovered_share_is_the_median_job_and_none_without_spans():
    full = _job(spans={"p": [(0, 10 * MS)], "x": [(0, 10 * MS)]})
    half = _job(spans={"p": [(0, 10 * MS)], "x": [(0, 5 * MS)]})
    none = _job(spans={"p": [(0, 10 * MS)], "x": [(50 * MS, 5 * MS)]})
    assert launches.uncovered_share(_run(full, half, none), "p", ["x"]) \
        == pytest.approx(50.0)
    # a program that predates the inside spans, or a job without the
    # outer one, has nothing to read
    bare = _job(spans={"p": [(0, 10 * MS)]})
    run = _run(bare, _job())
    assert launches.uncovered_share(run, "p", ["x", "jit.*"]) is None
    assert run["notes"] == {}


# ------------------------------------------------------- span_per_unit_ms

def test_span_per_unit_ms_divides_by_the_units_the_tier_served():
    j1 = _job(spans={"poa.wait": [(0, 600 * MS), (700 * MS, 400 * MS)]},
              served={"consensus": {"ls": 100, "host": 3}})
    j2 = _job(spans={"poa.wait": [(0, 3000 * MS)]},
              served={"consensus": {"ls": 100}})
    j3 = _job(spans={"poa.wait": [(0, 2000 * MS)]},
              served={"consensus": {"ls": 100}})
    assert launches.span_per_unit_ms(_run(j1), ["poa.wait"],
                                     "consensus", "ls") == pytest.approx(10)
    assert launches.span_per_unit_ms(_run(j1, j2, j3), ["poa.wait"],
                                     "consensus", "ls") == pytest.approx(20)
    # no such span (the parent), or nothing served by the tier
    old = _job(spans={"phase.poa": [(0, MS)]},
               served={"consensus": {"ls": 100}})
    assert launches.span_per_unit_ms(_run(old), ["poa.wait"],
                                     "consensus", "ls") is None
    assert launches.span_per_unit_ms(_run(j1), ["poa.wait"],
                                     "consensus", "v2") is None
    assert launches.span_per_unit_ms(_run(), ["poa.wait"],
                                     "consensus", "ls") is None


# ------------------------------------------------------------- counters

def test_counter_per_unit_and_counter_sum():
    names = ["align.launches.edge", "align.launches.base"]
    j1 = _job(counters={"align.launches.edge": 60, "align.launches.base": 40,
                        "poa.launches": 4},
              served={"alignment": {"hirschberg": 50}})
    j2 = _job(counters={"align.launches.edge": 80, "align.launches.base": 20,
                        "poa.launches": 4, "jit.traces": 2},
              served={"alignment": {"hirschberg": 25}})
    assert launches.counter_per_unit(_run(j1), names, "alignment",
                                     "hirschberg") == pytest.approx(2.0)
    assert launches.counter_per_unit(_run(j1, j2), names, "alignment",
                                     "hirschberg") == pytest.approx(3.0)
    old = _job(counters={"served.alignment.hirschberg": 50},
               served={"alignment": {"hirschberg": 50}})
    assert launches.counter_per_unit(_run(old), names, "alignment",
                                     "hirschberg") is None

    # a counter that never fired reads 0 where the witness says the
    # program counts at all, and None where it does not
    assert launches.counter_sum(_run(j1), ["jit.traces"],
                                "poa.launches") == 0
    assert launches.counter_sum(_run(j1, j2), ["jit.traces"],
                                "poa.launches") == 2
    assert launches.counter_sum(_run(old), ["jit.traces"],
                                "poa.launches") is None


# ------------------------------------------------------------ trace + lower

def test_setup_trace_lower_s_takes_the_window_jobs_share_off(monkeypatch):
    from racon_tpu import device

    traffic = {"requests": 3, "hits": 3, "misses": 0, "compile_s": 0.5,
               "trace_s": 12.0, "lower_s": 20.0, "traces": 7,
               "lowerings": 7,
               "by_fun": {"racon_poa_ls": {"trace_s": 9.0, "lower_s": 18.0,
                                           "compile_s": 0.3, "n": 3},
                          "convert_element_type": {
                              "trace_s": 3.0, "lower_s": 2.0,
                              "compile_s": 0.2, "n": 4}}}
    monkeypatch.setattr(device, "cache_traffic", lambda: traffic)
    quiet = _job(spans={"phase.poa": [(0, MS)]})
    retraced = _job(spans={"jit.trace": [(0, 500 * MS)],
                           "jit.lower": [(500 * MS, 1500 * MS)],
                           "jit.compile": [(2000 * MS, 100 * MS)]})
    run = _run(quiet, retraced)
    assert launches.setup_trace_lower_s(run) == pytest.approx(30.0)
    note = run["notes"]["trace_lower_s"]
    assert list(note["by_fun"]) == ["racon_poa_ls", "convert_element_type"]
    assert note["in_window_s"] == pytest.approx(2.0)
    # the parent's listener has no such keys
    monkeypatch.setattr(device, "cache_traffic", lambda: {
        "requests": 3, "hits": 3, "misses": 0, "compile_s": 0.5})
    assert launches.setup_trace_lower_s(_run(quiet)) is None
