"""The metric arithmetic and the verdict on canned job results."""

import copy

import pytest

from benchmark import judge
from benchmark.reducers import registry

SAM_PHASES = {
    "alignment": {"total": 0, "served": {"hirschberg": 0, "xla": 0,
                                         "host": 0, "journal": 0}},
    "consensus": {"total": 1000,
                  "served": {"ls": 977, "v2": 0, "xla": 0, "host": 21,
                             "backbone": 2, "journal": 0},
                  "extra": {"kernels": {"interpreted": False, "batch": 64,
                                        "shards": 1},
                            "pack_wall_s": 0.5, "kernel_wall_s": 11.5}},
}
PAF_PHASES = {
    "alignment": {"total": 1875,
                  "served": {"hirschberg": 1856, "xla": 0, "host": 19,
                             "journal": 0},
                  "extra": {"kernels": {"interpreted": False},
                            "kernel_wall_s": 60.0}},
    "consensus": SAM_PHASES["consensus"],
}
EXPECT_SAM = {"alignment": False, "consensus_tier": "ls",
              "consensus_min_share": 0.9,
              "consensus_tiers_at_zero": ["v2", "xla"]}
EXPECT_PAF = dict(EXPECT_SAM, alignment=True, alignment_tier="hirschberg",
                  alignment_min_share=0.8, alignment_tiers_at_zero=["xla"])


def report(phases, **counters):
    return {"device": {"platform": "tpu", "device_kind": "TPU v5 lite",
                       "count": 1},
            "phases": copy.deepcopy(phases),
            "obs": {"metrics": {"counters": counters}}}


@pytest.mark.parametrize("elapsed, walls, seconds, expected", [
    (0.0, [], 51, True),                     # the first always starts
    (200.0, [], 51, True),
    (28.0, [14.0, 14.2], 51, True),          # ends at 42.1
    (42.3, [14.0, 14.2, 14.1], 51, False),
    (36.9, [14.0, 14.2, 14.1], 51, True),
    # the median, not the mean: one slow job does not end the window
    (30.0, [14.0, 14.0, 40.0], 51, True),
    # one job done: the job that makes two starts while the window is
    # open, however long the first took (2 x 25.6 > 51 refused PR 24 on
    # the parent's side; 26.9 s was PR 23's stalled SAM job) ...
    (25.6, [25.6], 51, True),
    (26.9, [26.9], 51, True),
    (22.4, [22.4], 40, True),
    # ... and never once the window has closed
    (51.2, [51.2], 51, False),
    (22.4, [22.4], 20, False),
    # two done: back on the running median (40.4 + 20.2 > 51)
    (40.4, [26.9, 13.5], 51, False),
])
def test_stop_rule(elapsed, walls, seconds, expected):
    assert judge.next_job_fits(elapsed, walls, seconds) is expected


class SteppedClock:
    """Stands in for ``time`` in ``benchmark/run.py``: the fake job moves
    it, nothing else does."""

    def __init__(self):
        self.now = 1000.0

    def monotonic(self):
        return self.now

    def monotonic_ns(self):
        return int(self.now * 1e9)


@pytest.mark.parametrize("walls, seconds, done, by_least", [
    ([27.0, 22.0], 51, [27.0, 49.0], 1),     # a 4.6 s stall in job 1
    ([22.4, 22.4], 51, [22.4, 44.8], 0),     # the healthy PAF window
    ([22.4, 22.4], 40, [22.4, 44.8], 1),     # the chip demonstration
    ([22.4], 20, [22.4], 0),                 # job 1 ends after the window
    ([27.0, 13.5, 13.5], 51, [27.0, 40.5], 1),   # PR 23's SAM stall
    ([13.5, 13.5, 13.5, 13.5], 51, [13.5, 27.0, 40.5], 0),
])
def test_the_window_holds_two_jobs_after_a_stalled_first(
        monkeypatch, walls, seconds, done, by_least):
    from benchmark import run

    clock = SteppedClock()
    monkeypatch.setattr(run, "time", clock)
    monkeypatch.setattr(run, "say", lambda msg: None)
    left = list(walls)

    def fake_job(job_id):
        wall = left.pop(0)
        clock.now += wall
        return {"id": job_id, "wall_s": wall, "events": [],
                "report": report(SAM_PHASES), "fasta": b">c\nACGT\n"}

    jobs, raised = run.measure_window(fake_job, seconds, None, 0)
    assert raised == []
    assert [j["done_s"] for j in jobs] == pytest.approx(done)
    facts = run.window_facts(jobs)
    assert facts == {"window_end_s": pytest.approx(done[-1]),
                     "jobs_started_by_least": by_least}

    class Cell:
        chips = 1
        workload = {"expect": EXPECT_SAM}

    first = {"report": report(SAM_PHASES), "fasta": b">c\nACGT\n"}
    problems, failed = run.verdict(Cell, first, jobs, raised,
                                   {"platform": "tpu"}, False)
    assert failed == 0
    assert any("fewer than" in p for p in problems) == (len(done) < 2)
    # an answer altered where it is produced is not correct
    jobs[-1]["fasta"] = b">c\nACGA\n"
    problems, failed = run.verdict(Cell, first, jobs, raised,
                                   {"platform": "tpu"}, False)
    assert failed == 1 and any("bytes" in p for p in problems)


def test_device_served_share_is_the_smokes_figure():
    assert judge.served_units(SAM_PHASES) == (977, 998)
    assert judge.served_units(PAF_PHASES) == (977 + 1856, 998 + 1875)
    assert judge.device_served_share([SAM_PHASES] * 3) == pytest.approx(
        100 * 977 / 998)
    assert judge.device_served_share([]) is None


def test_throughput_is_the_median_job_in_the_loop():
    bp = [500_000] * 3
    assert judge.polished_mbp_per_s(bp, [14.0, 28.0, 42.0]) == \
        pytest.approx(0.5 / 14.0)
    # a burst that slows one job of three moves nothing ...
    assert judge.polished_mbp_per_s(bp, [14.0, 33.0, 47.0]) == \
        pytest.approx(0.5 / 14.0)
    # ... and time the loop spends between jobs is counted
    assert judge.polished_mbp_per_s(bp, [15.0, 30.0, 45.0]) == \
        pytest.approx(0.5 / 15.0)
    # two jobs: the mean of the two rates
    assert judge.polished_mbp_per_s([100_000] * 2, [20.0, 45.0]) == \
        pytest.approx((0.1 / 20 + 0.1 / 25) / 2)
    assert judge.polished_mbp_per_s([], []) is None
    assert judge.polished_mbp_per_s(bp, [14.0, 14.0, 42.0]) is None


def test_accuracy_metrics_and_verdict():
    assert judge.residual_err_per_100kb(76, 500_000) == pytest.approx(15.2)
    assert judge.err_removed_vs_host(3763, 76, 76) == pytest.approx(100.0)
    one_more = judge.err_removed_vs_host(3763, 76, 77)
    assert one_more == pytest.approx(100 * 3686 / 3687)
    assert judge.err_removed_vs_host(10, 10, 10) is None
    assert judge.accuracy_problems(3763, 76, 76, 500_000) == []
    # 2 edits per 10 kb of 0.5 Mbp = 100 edits of slack (> 10 % of 76)
    assert judge.accuracy_problems(3763, 76, 176, 500_000) == []
    assert len(judge.accuracy_problems(3763, 76, 177, 500_000)) == 1
    assert len(judge.accuracy_problems(300, 76, 76, 500_000)) == 1


def test_healthy_reports_pass():
    assert judge.report_problems(report(SAM_PHASES), EXPECT_SAM,
                                 platform="tpu", chips=1,
                                 interpreted=False) == []
    assert judge.report_problems(report(PAF_PHASES), EXPECT_PAF,
                                 platform="tpu", chips=1,
                                 interpreted=False) == []


@pytest.mark.parametrize("mutate, needle", [
    (lambda r: r["device"].update(platform="cpu"), "device"),
    (lambda r: r["device"].update(count=4), "device"),
    (lambda r: r["phases"]["consensus"].update(
        degradations=[{"from": "ls", "to": "v2"}]), "degraded"),
    (lambda r: r["phases"]["consensus"]["served"].update(v2=3), "tier v2"),
    (lambda r: r["phases"]["consensus"]["served"].update(ls=800, host=198),
     "ls served 800"),
    (lambda r: r["phases"]["consensus"].update(retries=1), "retries"),
    (lambda r: r["phases"]["consensus"].update(quarantined=[5]),
     "quarantined"),
    (lambda r: r["phases"]["consensus"]["served"].update(journal=10),
     "replayed"),
    (lambda r: r["phases"]["consensus"]["extra"]["kernels"].update(
        interpreted=True), "interpreted"),
    (lambda r: r["obs"]["metrics"]["counters"].update(
        {"shard.demotions": 1}), "shard demotions"),
    (lambda r: r["phases"]["alignment"].update(total=5), "alignment jobs"),
])
def test_each_fault_is_named(mutate, needle):
    rep = report(SAM_PHASES)
    mutate(rep)
    bad = judge.report_problems(rep, EXPECT_SAM, platform="tpu", chips=1,
                                interpreted=False)
    assert any(needle in b for b in bad), bad


def test_alignment_shares_and_sharding():
    rep = report(PAF_PHASES)
    rep["phases"]["alignment"]["served"].update(hirschberg=1000, host=875)
    bad = judge.report_problems(rep, EXPECT_PAF, platform="tpu", chips=1,
                                interpreted=False)
    assert any("hirschberg served 1000" in b for b in bad)
    rep = report(SAM_PHASES, **{"shard.rows.d0": 272, "shard.rows.d1": 272,
                                "shard.rows.d2": 272})
    rep["device"]["count"] = 4
    bad = judge.report_problems(rep, EXPECT_SAM, platform="tpu", chips=4,
                                interpreted=False)
    assert any("rows not spread" in b for b in bad)


def test_window_problems():
    assert judge.window_problems({"cache_misses": 0, "kernel_builds": 0,
                                  "journal_replayed": 0,
                                  "events": ["serve.job"]}) == []
    bad = judge.window_problems({"cache_misses": 2, "kernel_builds": 1,
                                 "journal_replayed": 3,
                                 "events": ["serve.job", "lattice.demote"]})
    assert len(bad) == 4


def _job(wall, poa_s, bp=500_000):
    return {"wall_s": wall, "polished_bp": bp, "phases": SAM_PHASES,
            "counters": {"shard.pad_rows": 90, "shard.rows.d0": 272,
                         "shard.rows.d1": 272, "shard.rows.d2": 272,
                         "shard.rows.d3": 272},
            "spans": {"phase.poa": [(0, poa_s * 1e9)],
                      "phase.parse": [(0, 0.2e9)],
                      "phase.stitch": [(0, 0.1e9)]}}


def test_span_readers_take_the_median_over_window_jobs():
    reg = registry()
    run = {"facts": {"first_job_wall_s": 56.0, "device_init_s": 9.0},
           "jobs": [_job(14.0, 13.0), _job(14.4, 13.4), _job(30.0, 29.0)],
           "edits": {"device": 76, "host": 76},
           "data": {"truth_bp": 500_000}}
    assert reg["fact"](run, key="device_init_s") == 9.0
    assert reg["fact"](run, key="absent") is None
    assert reg["first_job_excess"](run) == pytest.approx(56.0 - 14.4)
    assert reg["span_s_per_mbp"](run, spans=["phase.poa"]) == \
        pytest.approx(13.4 / 0.5)
    assert reg["span_s_per_mbp"](
        run, spans=["phase.parse", "phase.window_assign",
                    "phase.stitch"]) == pytest.approx(0.3 / 0.5)
    assert reg["overhead_share"](
        run, span="phase.poa", phase="consensus",
        walls=["kernel_wall_s", "pack_wall_s"]) == pytest.approx(
            100 * (1 - 12.0 / 13.4))
    assert reg["overhead_share"](run, span="phase.align",
                                 phase="alignment",
                                 walls=["kernel_wall_s"]) is None
    assert reg["wall_per_unit_ms"](
        run, phase="consensus", wall="kernel_wall_s",
        tier="ls") == pytest.approx(11500 / 977)
    assert reg["wall_per_unit_ms"](run, phase="alignment",
                                   wall="kernel_wall_s",
                                   tier="hirschberg") is None
    assert reg["counter_ratio"](
        run, numerator="shard.pad_rows",
        denominator_prefix="shard.rows.d") == pytest.approx(
            100 * 90 / (4 * 272))
    assert reg["served_share"](run) == pytest.approx(100 * 977 / 998)
    assert reg["residual"](run, which="device") == pytest.approx(15.2)
    assert reg["residual"](run, which="draft") is None
