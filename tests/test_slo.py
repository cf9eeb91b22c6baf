"""Per-job latency ledger + per-tenant SLO engine + Prometheus
exposition + critical-path attribution.

Covers the contracts docs/observability.md promises for the
observability control plane: ledger stamps/derived stages and the
explicit ``unattributed_s`` remainder, burn-rate math and multi-window
alerting (a single fast-window spike cannot alert), the ``slo.burn``
injected-slowdown drill CI keys off, exposition-format rendering, and
the critpath analyzer's per-job attribution + exit-code gate.
"""

import json

import pytest

from racon_tpu import obs
from racon_tpu.obs import __main__ as obs_cli
from racon_tpu.obs import critpath, export, ledger, slo
from racon_tpu.resilience import faults


@pytest.fixture(autouse=True)
def _fresh_engine():
    """The SLO engine is process-global (scheduler/plane/exposition all
    read the same one): never leak one test's knobs into the next."""
    slo.reset()
    yield
    slo.reset()
    faults.reset()
    obs.reset()


def _engine(monkeypatch, **knobs):
    for k, v in knobs.items():
        monkeypatch.setenv(k, v)
    return slo.SLOEngine()


# ------------------------------------------------------------ unit: targets

def test_parse_targets_bare_pairs_and_malformed():
    assert slo.parse_targets("2.5") == {"default": 2.5}
    assert slo.parse_targets("default=2.5, gold=0.5") == \
        {"default": 2.5, "gold": 0.5}
    # malformed / non-positive fragments are skipped, never fatal
    assert slo.parse_targets("gold=abc,=1.0x,silver=-3,bronze=4") == \
        {"bronze": 4.0}
    assert slo.parse_targets("") == {}
    assert slo.parse_targets(None) == {}


# --------------------------------------------------------- unit: burn math

def test_burn_rates_and_multiwindow_alert(monkeypatch):
    eng = _engine(monkeypatch,
                  RACON_TPU_SLO_LATENCY_S="default=1.0,gold=0.5",
                  RACON_TPU_SLO_AVAILABILITY="0.9",
                  RACON_TPU_SLO_FAST_WINDOW_S="10",
                  RACON_TPU_SLO_SLOW_WINDOW_S="100",
                  RACON_TPU_SLO_BURN_ALERT="2.0")
    now = 1000.0
    for _ in range(10):
        eng.record("t0", 0.2, ok=True, now=now)
    assert eng.burn_rates("", now=now) == {"fast": 0.0, "slow": 0.0}
    assert not eng.alerting("", now=now)
    # 10 overruns join the window: bad-fraction 0.5 over a 0.1 error
    # budget = burn 5.0 on BOTH windows -> alert (transitions counted
    # once per tenant key: "t0" via record(), "" via alerting())
    for _ in range(10):
        eng.record("t0", 2.0, ok=True, now=now + 5.0)
    rates = eng.burn_rates("", now=now + 5.0)
    assert rates == {"fast": 5.0, "slow": 5.0}
    assert eng.alerting("", now=now + 5.0)
    alerts_after_first = eng.snapshot(now=now + 5.0)["counters"]["alerts"]
    assert eng.alerting("", now=now + 5.0)          # still alerting...
    snap = eng.snapshot(now=now + 5.0)
    assert snap["counters"]["alerts"] == alerts_after_first  # ...not re-counted
    assert snap["overall"]["alerting"] is True
    assert snap["counters"]["observed"] == 20
    assert snap["counters"]["bad"] == 10
    # the bad burst ages out of the fast window: the slow window still
    # burns but multi-window alerting needs BOTH -> alert clears
    later = now + 20.0
    rates = eng.burn_rates("", now=later)
    assert rates["fast"] == 0.0 and rates["slow"] >= 2.0
    assert not eng.alerting("", now=later)


def test_per_tenant_targets_and_failures(monkeypatch):
    eng = _engine(monkeypatch,
                  RACON_TPU_SLO_LATENCY_S="default=1.0,gold=0.5",
                  RACON_TPU_SLO_AVAILABILITY="0.99")
    now = 10.0
    eng.record("gold", 0.7, ok=True, now=now)     # overran gold's 0.5
    eng.record("t1", 0.7, ok=True, now=now)       # within default 1.0
    eng.record("t1", 0.2, ok=False, now=now)      # failed: always bad
    assert eng.target_for("gold") == 0.5
    assert eng.target_for("anyone-else") == 1.0
    assert eng.burn_rates("gold", now=now)["fast"] == 100.0   # 1/1 over 0.01
    assert eng.burn_rates("t1", now=now)["fast"] == 50.0      # 1/2 over 0.01
    snap = eng.snapshot(now=now)
    assert set(snap["tenants"]) == {"gold", "t1"}
    assert snap["tenants"]["gold"]["target_s"] == 0.5


def test_no_targets_means_failures_only(monkeypatch):
    monkeypatch.delenv("RACON_TPU_SLO_LATENCY_S", raising=False)
    eng = slo.SLOEngine()
    eng.record("t0", 999.0, ok=True, now=5.0)      # no target: not bad
    assert eng.burn_rates("", now=5.0)["fast"] == 0.0
    eng.record("t0", 0.1, ok=False, now=5.0)       # failure: still bad
    assert eng.burn_rates("", now=5.0)["fast"] > 0.0


def test_should_shed_gated_by_knob(monkeypatch):
    eng = _engine(monkeypatch,
                  RACON_TPU_SLO_LATENCY_S="0.5",
                  RACON_TPU_SLO_AVAILABILITY="0.9",
                  RACON_TPU_SLO_SHED_BURN="1.0")
    now = 100.0
    for _ in range(4):
        eng.record("t0", 2.0, ok=True, now=now)    # all overruns
    assert eng.should_shed("t0", now=now)
    assert eng.snapshot(now=now)["counters"]["shed"] >= 1
    # shed_burn=0 (the default) disables shedding entirely
    off = _engine(monkeypatch, RACON_TPU_SLO_SHED_BURN="0")
    for _ in range(4):
        off.record("t0", 2.0, ok=False, now=now)
    assert not off.should_shed("t0", now=now)


# -------------------------------------------------- drill: slo.burn fault

def test_slo_burn_drill_forces_alert_then_decays(monkeypatch):
    """The ``slo.burn`` fault point: an armed raise is absorbed as a
    forced burn — both windows report the alert threshold for one fast
    window — so the CI injected-slowdown drill proves the alert ->
    scale-up path deterministically, with zero bad traffic."""
    monkeypatch.setenv("RACON_TPU_SLO_BURN_ALERT", "2.0")
    monkeypatch.setenv("RACON_TPU_SLO_FAST_WINDOW_S", "10")
    monkeypatch.setenv("RACON_TPU_FAULT", "slo.burn")
    faults.reset()
    slo.reset()
    eng = slo.engine()
    now = 50.0
    assert eng.alerting("", now=now)           # forced: no traffic at all
    snap = eng.snapshot(now=now)
    assert snap["forced"] is True
    assert snap["counters"]["burn_faults"] >= 1
    assert snap["counters"]["alerts"] >= 1
    assert snap["overall"]["burn"]["fast"] >= 2.0
    # disarm the fault: the forcing decays after one fast window
    monkeypatch.delenv("RACON_TPU_FAULT")
    faults.reset()
    assert not eng.alerting("", now=now + 11.0)
    assert eng.snapshot(now=now + 11.0)["forced"] is False


# ------------------------------------------------------- unit: job ledger

def test_job_ledger_marks_derived_stages_and_unattributed():
    led = ledger.JobLedger("j1", tenant="t0")
    t0 = led._marks["submit"]
    led.mark("admit", t_ns=t0 + 1_000_000_000)
    led.mark("dispatch", t_ns=t0 + 3_000_000_000)
    led.mark("dispatch", t_ns=t0 + 9_000_000_000)   # idempotent: first wins
    led.add_stage("align", 2.0)
    led.add_stage("align", 0.5)                     # accumulates per chunk
    led.add_stage("poa", -1.0)                      # negative: ignored
    led.add_stage("poa", "garbage")                 # malformed: ignored
    led.merge_stage_s({"poa": 1.0, "kernel_build": 0.25})
    led.merge_stage_s("not a dict")                 # tolerated
    led.mark("finish", t_ns=t0 + 8_000_000_000)
    led.mark("result_ship", t_ns=t0 + 8_500_000_000)
    d = led.as_dict()
    assert d["job"] == "j1" and d["tenant"] == "t0"
    assert d["marks"]["submit"] == 0.0
    assert d["marks"]["admit"] == 1.0 and d["marks"]["dispatch"] == 3.0
    assert d["stage_s"]["queue"] == 2.0             # admit -> dispatch
    assert d["stage_s"]["result_ship"] == 0.5       # finish -> ship
    assert d["stage_s"]["align"] == 2.5
    assert d["wall_s"] == 8.5
    # kernel_build overlaps compute: excluded from the additive sum
    assert d["attributed_s"] == 2.0 + 0.5 + 2.5 + 1.0
    assert d["unattributed_s"] == 2.5               # reported, never hidden
    # stage_s follows the canonical STAGES order
    assert list(d["stage_s"]) == [k for k in ledger.STAGES
                                  if k in d["stage_s"]]


def test_job_ledger_without_ship_mark_falls_back_to_finish():
    led = ledger.JobLedger("j2")
    t0 = led._marks["submit"]
    led.mark("finish", t_ns=t0 + 2_000_000_000)
    d = led.as_dict()
    assert d["wall_s"] == 2.0
    assert "result_ship" not in d["stage_s"]


def test_stage_seconds_sums_per_tier_walls():
    summary = {
        "parse": {"wall_s": {"host": 0.5}},
        "alignment": {"wall_s": {"hirschberg": 1.0, "host": 0.25}},
        "consensus": {"wall_s": 2.0},                 # scalar tolerated
        "stitch": {"wall_s": {"host": "x", "ls": 0.5}},   # garbage skipped
        "memory": {"extra": {"peak_rss_mb": 1}},      # not a ledger stage
        "bogus_phase": {"wall_s": {"host": 9.0}},
    }
    assert ledger.stage_seconds(summary) == \
        {"parse": 0.5, "align": 1.25, "poa": 2.0, "stitch": 0.5}
    assert ledger.stage_seconds(None) == {}
    assert ledger.stage_seconds({"parse": "nope"}) == {}


def test_overlay_seconds_from_metrics_snapshot():
    snap = {"histograms": {
        "span_us.kernel.build": {"sum": 1_500_000.0},
        "span_us.journal.replay": {"sum": 0},             # zero: omitted
        "span_us.phase.poa": {"sum": 9e9},                # not an overlay
    }}
    assert ledger.overlay_seconds(snap) == {"kernel_build": 1.5}
    assert ledger.overlay_seconds(None) == {}
    assert ledger.overlay_seconds({"histograms": "x"}) == {}


def test_summarize_aggregates_and_skips_malformed():
    l1 = {"stage_s": {"align": 1.0, "queue": 0.5},
          "wall_s": 2.0, "unattributed_s": 0.5}
    l2 = {"stage_s": {"align": 2.0}, "wall_s": 3.0, "unattributed_s": 1.0}
    s = ledger.summarize([l1, None, "garbage", {"no": "stage_s"}, l2])
    assert s == {"jobs": 2, "stage_s": {"align": 3.0, "queue": 0.5},
                 "wall_s": 5.0, "unattributed_s": 1.5}
    assert ledger.summarize([]) is None
    assert ledger.summarize(None) is None


# -------------------------------------------------- unit: exposition text

def test_prometheus_text_exposition():
    metrics = {"counters": {"served.poa.fleet": 3},
               "histograms": {"span_us.phase.poa": {
                   "count": 3, "sum": 70.0, "min": 10.0, "max": 40.0,
                   "buckets": {"16": 1, "32": 1, "64": 1}}}}
    slo_snap = {
        "overall": {"burn": {"fast": 1.5, "slow": 0.5}, "alerting": True},
        "tenants": {"t0": {"burn": {"fast": 0.0, "slow": 0.0},
                           "alerting": False}},
        "objectives": {"availability": 0.99, "latency_s": {}},
        "counters": {"alerts": 2},
    }
    text = export.prometheus_text(
        metrics=metrics, slo=slo_snap,
        gauges={"serve_queued_jobs": 4, "fleet_live_workers": None})
    assert text.endswith("\n")
    lines = text.splitlines()
    assert "racon_tpu_served_poa_fleet_total 3" in lines
    # histogram buckets are CUMULATIVE with a closing +Inf
    assert 'racon_tpu_span_us_phase_poa_bucket{le="16"} 1' in lines
    assert 'racon_tpu_span_us_phase_poa_bucket{le="64"} 3' in lines
    assert 'racon_tpu_span_us_phase_poa_bucket{le="+Inf"} 3' in lines
    assert "racon_tpu_span_us_phase_poa_sum 70" in lines
    assert "racon_tpu_span_us_phase_poa_count 3" in lines
    assert "racon_tpu_serve_queued_jobs 4" in lines
    assert not any("fleet_live_workers" in ln for ln in lines)  # None gauge
    assert 'racon_tpu_slo_burn_rate{tenant="",window="fast"} 1.5' in lines
    assert 'racon_tpu_slo_alerting{tenant=""} 1' in lines
    assert 'racon_tpu_slo_alerting{tenant="t0"} 0' in lines
    assert "racon_tpu_slo_availability_objective 0.99" in lines
    assert "racon_tpu_slo_alerts_total 2" in lines
    # a disarmed registry still renders a valid (near-empty) scrape
    assert export.prometheus_text(metrics=None, slo=None) == "\n"


# ------------------------------------------- critpath: attribution + CLI

def _merged_doc():
    """A minimal merged fleet trace: one job, one dispatched chunk with
    phase spans + a kernel.build overlay, scheduler submit/done marks."""
    ab = "ab" * 8
    ev = [
        {"name": "serve.job.submit", "ph": "i", "ts": 0, "pid": 1,
         "tid": 1, "args": {"job": "j1", "tenant": "t0"}},
        {"name": "distrib.dispatch", "ph": "i", "ts": 1000, "pid": 1,
         "tid": 1, "args": {"span_id": "cafe0001", "trace_id": ab,
                            "job": "j1", "worker": 0, "chunk": 0}},
        {"name": "distrib.chunk", "ph": "X", "ts": 2000, "dur": 10000,
         "pid": 2, "tid": 1,
         "args": {"chunk": 0, "parent": "cafe0001", "trace_id": ab}},
        {"name": "phase.align", "ph": "X", "ts": 2500, "dur": 4000,
         "pid": 2, "tid": 1, "args": {}},
        {"name": "kernel.build", "ph": "X", "ts": 2600, "dur": 500,
         "pid": 2, "tid": 1, "args": {}},
        {"name": "phase.poa", "ph": "X", "ts": 6500, "dur": 5000,
         "pid": 2, "tid": 1, "args": {}},
        {"name": "serve.job.done", "ph": "i", "ts": 12500, "pid": 1,
         "tid": 1, "args": {"job": "j1", "state": "done"}},
    ]
    return {"traceEvents": ev}


def test_critpath_attribution_sums_to_wall():
    res = critpath.analyze(_merged_doc())
    assert res["chunks"] == 1
    (job,) = res["jobs"]
    assert job["job"] == "j1" and job["tenant"] == "t0"
    assert job["wall_us"] == 12500.0
    p = job["path_us"]
    assert p["admit_queue"] == 1000.0      # submit -> dispatch
    assert p["queue"] == 1000.0            # dispatch -> chunk start
    assert p["setup"] == 500.0 and p["teardown"] == 500.0
    assert p["align"] == 4000.0 and p["poa"] == 5000.0
    assert p["gather"] == 500.0            # chunk end -> job done
    # overlays are informational, never added to the sum
    assert job["overlay_us"] == {"kernel_build": 500.0}
    assert job["attributed_us"] == 12500.0
    assert job["unattributed_frac"] <= 0.10    # the acceptance bound
    # single job: stage percentiles collapse onto the one sample
    assert res["stages"]["poa"]["p99_us"] == 5000.0
    assert res["wall_p50_us"] == 12500.0


def test_critpath_cli_exit_codes(tmp_path, capsys):
    path = tmp_path / "merged.json"
    path.write_text(json.dumps(_merged_doc()))
    assert obs_cli.main(["critpath", str(path)]) == 0
    assert "OK: every job attributed" in capsys.readouterr().out
    assert obs_cli.main(["critpath", "--json", str(path)]) == 0
    j = json.loads(capsys.readouterr().out)
    assert j["jobs"][0]["job"] == "j1"
    # threshold gate: any unattributed fraction past --max-unattributed
    # is exit 3 (here forced with a negative tolerance)
    assert obs_cli.main(["critpath", str(path),
                         "--max-unattributed", "-0.5"]) == 3
    assert "UNATTRIBUTED" in capsys.readouterr().err
    # unreadable stays exit 2; a chunk-free trace is exit 0 (nothing
    # to attribute is not a failure)
    assert obs_cli.main(["critpath", str(tmp_path / "missing.json")]) == 2
    empty = tmp_path / "empty.json"
    empty.write_text(json.dumps({"traceEvents": []}))
    assert obs_cli.main(["critpath", str(empty)]) == 0
    assert "nothing to attribute" in capsys.readouterr().out


def test_critpath_costmodel_crosscheck_reads_merged_counters():
    doc = _merged_doc()
    doc["otherData"] = {"platform": "cpu"}
    doc["racon_tpu"] = {"metrics": {"counters": {
        "align.cells.total": 1_000_000, "poa.cells.d8.c512": 500_000}}}
    res = critpath.analyze(doc, profile="cpu-host")
    cc = res["costmodel"]
    assert cc is not None and cc["profile"] == "cpu-host"
    assert set(cc["phases"]) == {"align", "poa"}
    assert cc["phases"]["poa"]["measured_s"] == 0.005
    assert cc["phases"]["poa"]["predicted_s"] > 0.0


# ------------------------------------------------ ledger end-to-end: serve

class _LedgerSession:
    """Duck-typed session whose run_job ships a pre-aggregated
    ``ledger.stage_s`` fragment, like a fleet-plane result would."""

    backend = "tpu"

    def __init__(self, workdir):
        import os
        self.workdir = str(workdir)
        os.makedirs(os.path.join(self.workdir, "jobs"), exist_ok=True)

    def job_dir(self, job_id):
        import os
        return os.path.join(self.workdir, "jobs", job_id)

    def stats(self):
        return {"jobs_run": 0}

    def run_job(self, spec, cancel_event=None):
        return {"job_id": spec.job_id, "backend": "tpu", "cold": False,
                "wall_s": 0.01, "records": 1, "polished_bp": 1,
                "kernel_builds": 0, "journal_replayed": 0,
                "output": "", "report": "", "trace": "", "summary": None,
                "ledger": {"stage_s": {"align": 0.004, "poa": 0.005}}}


def test_scheduler_finish_feeds_engine_and_persists_ledger(monkeypatch,
                                                           tmp_path):
    """The scheduler's _finish seam end-to-end: the compute-side
    stage_s fragment folds into the job ledger, the persisted
    result.json carries the ledger without result_ship (it cannot time
    its own write), the wire copy is re-finalized with the ship stamp,
    and the completion reaches the process SLO engine."""
    import os

    from racon_tpu.serve.scheduler import Scheduler
    from racon_tpu.serve.session import JobSpec

    monkeypatch.setenv("RACON_TPU_SLO_LATENCY_S", "1000")
    slo.reset()
    paths = []
    for name in ("reads.fasta", "ovl.sam", "targets.fasta"):
        p = tmp_path / name
        p.write_text(">r1\nACGT\n" if name.endswith(".fasta") else "")
        paths.append(str(p))
    ses = _LedgerSession(tmp_path / "state")
    sched = Scheduler(ses, queue_depth=4, max_jobs=4, host_lane=False)
    sched.start()
    try:
        job = sched.submit(JobSpec(paths[0], paths[1], paths[2],
                                   job_id="led1", submitter="tenant0"))
        assert job.done.wait(30)
        assert job.state == "done"
        led = job.result["ledger"]
        assert led["tenant"] == "tenant0"
        assert led["stage_s"]["align"] == 0.004
        assert led["stage_s"]["poa"] == 0.005
        assert {"submit", "admit", "dispatch", "finish", "result_ship"} <= \
            set(led["marks"])
        assert "result_ship" in led["stage_s"]
        assert led["wall_s"] >= led["marks"]["finish"]
        assert led["unattributed_s"] >= 0.0
        # the persisted copy predates the ship stamp by design
        with open(os.path.join(ses.job_dir(job.id), "result.json")) as f:
            persisted = json.load(f)["result"]["ledger"]
        assert "result_ship" not in persisted["stage_s"]
        assert "result_ship" not in persisted["marks"]
        # the completion reached the process SLO engine
        snap = slo.engine().snapshot()
        assert snap["counters"]["observed"] == 1
        assert "tenant0" in snap["tenants"]
    finally:
        sched.shutdown(wait=True, timeout=10)
