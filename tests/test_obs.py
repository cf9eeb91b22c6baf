"""Observability layer: span tracer + metrics registry + CLI.

Covers the contracts docs/observability.md promises: thread-safe span
nesting, Chrome-trace schema validity of a real traced polish with all
five phase spans, the served-sum invariant (metrics counters vs the run
report, cross-checked — not assumed), byte-identical polished output
armed vs disarmed (and no trace file when disarmed), the CLI's four
exit codes, and the align-driver accounting regression: a mid-cohort
engine death after partial CIGAR installs must not erase the
device-served count.
"""

import json
import random
import threading

import pytest

import racon_tpu
from racon_tpu import obs
from racon_tpu.obs import __main__ as obs_cli
from racon_tpu.obs.metrics import Histogram, Metrics, hist_quantile
from racon_tpu.obs.tracer import NULL_SPAN, Tracer


@pytest.fixture(autouse=True)
def _disarm_after():
    """Module-level obs state must never leak between tests."""
    yield
    obs.reset()


# ------------------------------------------------------------ unit: tracer

def test_tracer_thread_pool_nesting():
    tr = Tracer()
    # the barrier keeps all 8 threads alive at once: Python reuses
    # thread idents of finished threads, which would fold the per-thread
    # name metadata this test asserts on
    gate = threading.Barrier(8)

    def work(k):
        gate.wait()
        # µs-aligned ns stamps: _ts_us floor-divides (t - epoch) by 1000,
        # so sub-µs offsets would make the rounded nesting depend on the
        # epoch's ns remainder (and the durations collapse to 0)
        t0 = 1_000_000 * k
        tr.add_complete(f"outer.{k}", t0, t0 + 500_000, idx=k)
        tr.add_complete(f"inner.{k}", t0 + 100_000, t0 + 200_000)
        gate.wait()

    threads = [threading.Thread(target=work, args=(k,), name=f"w{k}")
               for k in range(8)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    events = tr.events()
    assert len(events) == 16
    # every event carries its recording thread's tid, and each thread's
    # inner span nests inside its outer span on the same timeline row
    by_name = {e["name"]: e for e in events}
    for k in range(8):
        outer, inner = by_name[f"outer.{k}"], by_name[f"inner.{k}"]
        assert outer["tid"] == inner["tid"]
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
    # thread-name metadata rides along in the written document
    doc = tr.to_dict()
    names = {e["args"]["name"] for e in doc["traceEvents"]
             if e.get("ph") == "M"}
    assert {f"w{k}" for k in range(8)} <= names


def test_tracer_bounded_buffer():
    tr = Tracer(max_events=3)
    for k in range(5):
        tr.add_instant(f"e{k}")
    assert len(tr.events()) == 3 and tr.dropped == 2
    assert tr.to_dict()["otherData"]["dropped_events"] == 2


def test_span_records_error_on_exception():
    tr = Tracer()
    with pytest.raises(RuntimeError):
        with obs.Span(tr, "boom", {}):
            raise RuntimeError("x")
    (ev,) = tr.events()
    assert ev["args"]["error"] == "RuntimeError" and ev["dur"] >= 0


# ----------------------------------------------------------- unit: metrics

def test_metrics_counters_and_prefix_sum():
    m = Metrics()
    m.count("served.consensus.ls", 3)
    m.count("served.consensus.host")
    m.count("served.alignment.host", 7)
    assert m.counter("served.consensus.ls") == 3
    assert m.prefix_sum("served.consensus.") == 4
    assert m.prefix_sum("served.") == 11


def test_histogram_log2_buckets():
    h = Histogram()
    for v in (0.0, 0.5, 1.0, 3.0, 1000.0):
        h.observe(v)
    d = h.as_dict()
    assert d["count"] == 5 and d["min"] == 0.0 and d["max"] == 1000.0
    assert d["buckets"] == {"0": 1, "1": 2, "4": 1, "1024": 1}


# ----------------------------------------------------- unit: armed/disarmed

def test_disarmed_hooks_are_noops():
    obs.reset()
    assert not obs.enabled()
    assert obs.span("anything", k=1) is NULL_SPAN
    obs.event("x")        # must not raise
    obs.count("x")
    obs.observe("x", 1.0)
    assert obs.snapshot() is None
    assert obs.write_trace() is None


def test_configure_metrics_only_collects_without_file(tmp_path):
    obs.reset()
    obs.configure(metrics=True)
    assert obs.enabled() and obs.trace_path() is None
    with obs.span("s"):
        obs.count("c", 2)
    assert obs.snapshot()["counters"] == {"c": 2}
    assert obs.write_trace() is None   # no path configured
    obs.reset()


# ------------------------------------------------------------ e2e fixtures

def _write_dataset(tmp_path, n_targets=3, n_reads=4):
    """Identical-read PAF dataset (no CIGARs, so phase 1 has real align
    jobs): device- and host-served results are byte-comparable."""
    rng = random.Random(11)
    with open(tmp_path / "targets.fasta", "w") as tf, \
            open(tmp_path / "reads.fasta", "w") as rf, \
            open(tmp_path / "ovl.paf", "w") as of:
        for t in range(n_targets):
            seq = "".join(rng.choice("ACGT") for _ in range(200))
            tf.write(f">t{t}\n{seq}\n")
            for i in range(n_reads):
                rf.write(f">t{t}r{i}\n{seq}\n")
                of.write(f"t{t}r{i}\t200\t0\t200\t+\tt{t}\t200\t0\t200"
                         f"\t200\t200\t60\n")
    return (str(tmp_path / "reads.fasta"), str(tmp_path / "ovl.paf"),
            str(tmp_path / "targets.fasta"))


_ARGS = dict(window_length=100, quality_threshold=10, error_threshold=0.3,
             match=5, mismatch=-4, gap=-8, num_threads=1)


def _tpu_run(paths, monkeypatch, env, **kwargs):
    base = {"RACON_TPU_PALLAS": "0", "RACON_TPU_BATCH_WINDOWS": "8"}
    for k, v in {**base, **env}.items():
        monkeypatch.setenv(k, v)
    p = racon_tpu.create_polisher(*paths, backend="tpu", **_ARGS, **kwargs)
    p.initialize()
    res = p.polish(True)
    return res, p


# --------------------------------------------------- e2e: traced tpu polish

def test_traced_polish_trace_schema_and_phases(tmp_path, monkeypatch):
    paths = _write_dataset(tmp_path)
    trace = tmp_path / "run_trace.json"
    res, p = _tpu_run(paths, monkeypatch,
                      {"RACON_TPU_DEVICE_ALIGNER": "hirschberg"},
                      trace_path=str(trace))
    assert res and trace.exists()
    doc, errors = obs_cli.load_trace(str(trace))
    assert errors == [], errors
    # all five pipeline phases appear as phase.* complete events
    walls = obs_cli.phase_walls_us(doc)
    assert set(obs.PHASES) <= set(walls), walls
    # served-sum invariant: the served.* counters embedded in the trace
    # reconcile exactly with the run report's per-phase served totals
    b = obs_cli.breakdown(doc)
    d = p.report.as_dict()
    for phase, rep in d["phases"].items():
        assert sum(b["served"][phase].values()) == rep["total"], (phase, b)
    assert d["obs"]["armed"] is True
    assert all(v["ok"] for v in d["obs"]["served_sum"].values()), d["obs"]
    # the report summary carries the per-phase tier walls bench.py stamps
    for rep in p.report.summary().values():
        if isinstance(rep, dict):
            assert "wall_s" in rep


def test_disarmed_polish_byte_identical_no_trace(tmp_path, monkeypatch):
    paths = _write_dataset(tmp_path)
    monkeypatch.delenv("RACON_TPU_TRACE", raising=False)
    monkeypatch.delenv("RACON_TPU_METRICS", raising=False)
    plain, p_plain = _tpu_run(paths, monkeypatch, {})
    assert not obs.enabled()
    assert p_plain.report.as_dict()["obs"] == {"armed": False}
    trace = tmp_path / "armed_trace.json"
    traced, _ = _tpu_run(paths, monkeypatch, {}, trace_path=str(trace))
    assert traced == plain          # observability never changes output
    assert trace.exists()
    assert not (tmp_path / "ghost.json").exists()
    # disarmed run again (fresh polisher resets obs): still no stray file
    replain, _ = _tpu_run(paths, monkeypatch, {})
    assert replain == plain
    assert list(tmp_path.glob("*.json")) == [trace]


def test_env_knob_arms_tracing(tmp_path, monkeypatch):
    paths = _write_dataset(tmp_path)
    trace = tmp_path / "env_trace.json"
    res, _ = _tpu_run(paths, monkeypatch,
                      {"RACON_TPU_TRACE": str(trace)})
    assert res and trace.exists()
    doc, errors = obs_cli.load_trace(str(trace))
    assert errors == []
    assert doc["racon_tpu"]["metrics"]["counters"]


# ------------------------------------- e2e: align accounting under faults

def test_partial_install_death_keeps_device_count(tmp_path, monkeypatch):
    """Regression (satellite): the device engine dying mid-cohort AFTER some
    CIGARs were installed must keep those jobs counted as device-served —
    the old `stats["device"] = run_jobs(...)` assignment lost them all,
    over-reporting the host share."""
    paths = _write_dataset(tmp_path)        # 12 align jobs, all eligible
    oracle_p = racon_tpu.create_polisher(*paths, backend="cpu", **_ARGS)
    oracle_p.initialize()
    oracle = oracle_p.polish(True)
    res, p = _tpu_run(paths, monkeypatch, {
        "RACON_TPU_DEVICE_ALIGNER": "hirschberg",
        "RACON_TPU_FAULT": "align.install:window=5",
    })
    assert res == oracle            # host finished the rest, byte-equal
    d = p.report.as_dict()
    align_rep = d["phases"]["alignment"]
    # jobs 0..4 were installed before the fault on job 5 killed the
    # engine: they must survive as device-served
    assert align_rep["served"].get("hirschberg") == 5, align_rep
    assert sum(align_rep["served"].values()) == align_rep["total"]
    assert align_rep["degradations"], "engine death must be recorded"


# -------------------------------------------------------------- CLI: exits

def _trace_doc(poa_us):
    return {"traceEvents": [
        {"name": "phase.poa", "ph": "X", "ts": 0, "dur": poa_us,
         "pid": 1, "tid": 1, "args": {}},
        {"name": "phase.stitch", "ph": "X", "ts": poa_us, "dur": 10,
         "pid": 1, "tid": 1, "args": {}},
    ]}


def test_cli_exit_0_valid(tmp_path, capsys):
    path = tmp_path / "t.json"
    path.write_text(json.dumps(_trace_doc(5000)))
    assert obs_cli.main([str(path)]) == 0
    assert "phase" in capsys.readouterr().out
    assert obs_cli.main(["--validate", str(path)]) == 0


def test_cli_exit_1_schema_violation(tmp_path):
    doc = _trace_doc(5000)
    doc["traceEvents"].append({"name": "bad", "ph": "Z", "ts": 0,
                               "pid": 1, "tid": 1})
    doc["traceEvents"].append({"name": "", "ph": "X", "ts": -1, "dur": -2,
                               "pid": "x", "tid": 1})
    path = tmp_path / "bad.json"
    path.write_text(json.dumps(doc))
    assert obs_cli.main(["--validate", str(path)]) == 1


def test_cli_exit_2_unreadable(tmp_path):
    assert obs_cli.main([str(tmp_path / "missing.json")]) == 2
    notjson = tmp_path / "notjson.json"
    notjson.write_text("{nope")
    assert obs_cli.main([str(notjson)]) == 2
    nottrace = tmp_path / "nottrace.json"
    nottrace.write_text(json.dumps({"hello": 1}))
    assert obs_cli.main([str(nottrace)]) == 2
    # argument errors are exit 2 as well
    assert obs_cli.main(["--diff", str(notjson)]) == 2


def test_cli_exit_3_diff_regression(tmp_path):
    old = tmp_path / "old.json"
    new = tmp_path / "new.json"
    old.write_text(json.dumps(_trace_doc(10_000)))
    new.write_text(json.dumps(_trace_doc(20_000)))
    assert obs_cli.main(["--diff", str(old), str(new)]) == 3
    # within threshold (or shrinking): no regression
    assert obs_cli.main(["--diff", str(old), str(old)]) == 0
    assert obs_cli.main(["--diff", str(new), str(old)]) == 0
    # huge relative growth under --min-delta-us is noise, not regression
    assert obs_cli.main(["--diff", str(old), str(new),
                         "--min-delta-us", "50000"]) == 0


def test_cli_diff_json_output(tmp_path, capsys):
    old = tmp_path / "old.json"
    new = tmp_path / "new.json"
    old.write_text(json.dumps(_trace_doc(10_000)))
    new.write_text(json.dumps(_trace_doc(40_000)))
    assert obs_cli.main(["--diff", "--json", str(old), str(new)]) == 3
    out = json.loads(capsys.readouterr().out)
    assert any("phase.poa" in r for r in out["regressions"])


def test_cli_diff_one_sided_phase_is_flagged_not_crashed(tmp_path, capsys):
    """Satellite: a phase present on only one side (a resumed run that
    replayed align from the journal has no phase.align span) is flagged
    only-in-old/new with the missing side counted as 0 — previously
    infinite-percent material."""
    both = tmp_path / "both.json"
    both.write_text(json.dumps(_trace_doc(10_000)))
    doc = _trace_doc(10_000)
    doc["traceEvents"].append({"name": "phase.align", "ph": "X", "ts": 0,
                               "dur": 50_000, "pid": 1, "tid": 1})
    extra = tmp_path / "extra.json"
    extra.write_text(json.dumps(doc))
    # phase only in OLD: not a regression (new side is 0), just a note
    assert obs_cli.main(["--diff", str(extra), str(both)]) == 0
    out = capsys.readouterr().out
    assert "only-in-old" in out and "phase.align" in out
    # phase only in NEW past min-delta: flagged AND gated as a regression
    assert obs_cli.main(["--diff", str(both), str(extra)]) == 3
    out = capsys.readouterr().out
    assert "only-in-new" in out
    assert obs_cli.main(["--diff", "--json", str(both), str(extra)]) == 3
    j = json.loads(capsys.readouterr().out)
    assert any("only-in-new" in f for f in j["only_in"])
    assert any("only-in-new" in r for r in j["regressions"])
    # under min-delta the structural note stays but nothing gates
    assert obs_cli.main(["--diff", str(both), str(extra),
                         "--min-delta-us", "60000"]) == 0


def test_cli_validate_reports_dropped_events(tmp_path, capsys):
    doc = _trace_doc(5000)
    doc["otherData"] = {"dropped_events": 12}
    path = tmp_path / "t.json"
    path.write_text(json.dumps(doc))
    assert obs_cli.main(["--validate", str(path)]) == 0
    out = capsys.readouterr().out
    assert "12 event(s)" in out and "truncated" in out
    assert obs_cli.main(["--validate", "--json", str(path)]) == 0
    assert json.loads(capsys.readouterr().out)["dropped_events"] == 12
    # the breakdown warns too
    assert obs_cli.main([str(path)]) == 0
    assert "dropped" in capsys.readouterr().out


# ------------------------------- e2e: span quantiles + cell counters

def test_traced_polish_span_quantiles_and_cost_counters(tmp_path,
                                                        monkeypatch):
    """The on_complete callback feeds span_us.* histograms for every
    finished span (buffer-dropped ones included), the drivers count the
    measured DP cells the cost model predicts against, and the platform
    provenance stamp lands in otherData."""
    paths = _write_dataset(tmp_path)
    trace = tmp_path / "q_trace.json"
    res, _ = _tpu_run(paths, monkeypatch,
                      {"RACON_TPU_DEVICE_ALIGNER": "hirschberg"},
                      trace_path=str(trace))
    assert res
    doc, errors = obs_cli.load_trace(str(trace))
    assert errors == []
    q = obs_cli.span_quantiles(doc)
    for phase in obs.PHASES:
        name = f"phase.{phase}"
        assert name in q, (name, sorted(q))
        assert q[name]["count"] >= 1
        assert 0 <= q[name]["p50_us"] <= q[name]["p99_us"]
    assert "span durations" in obs_cli.render(doc, str(trace))
    counters = doc["racon_tpu"]["metrics"]["counters"]
    assert any(k.startswith("poa.cells.d") for k in counters), counters
    assert "align.cells.total" in counters
    assert doc["otherData"]["platform"] == "cpu"
    # the measured-cell counters drive a structurally complete validation
    from racon_tpu.obs import costmodel
    v = costmodel.validate_trace(doc, costmodel.PROFILES["cpu-host"])
    assert set(v["phases"]) == {"poa", "align"}
    assert v["phases"]["poa"]["predicted_s"] > 0.0
    assert any(b["kind"] == "poa" for b in v["buckets"])


# --------------------------------------- fleet tracing: context + shipping

def test_trace_context_mint_child_activate():
    from racon_tpu.obs import context

    ctx = context.fresh()
    assert len(ctx["trace_id"]) == 16 and ctx["parent"] is None
    kid = context.child(ctx)
    assert kid["trace_id"] == ctx["trace_id"]
    assert len(kid["parent"]) == 8
    assert context.child(kid)["parent"] != kid["parent"]   # fresh per call
    assert context.child(None) is None

    context.activate(kid)
    assert context.current() == kid
    context.current()["parent"] = "mutated"        # returns a copy
    assert context.current() == kid
    context.activate({"trace_id": ""})             # invalid -> deactivated
    assert context.current() is None
    context.clear()


def test_configure_idempotent_and_scoped(tmp_path):
    """Satellite regression: re-configuring with the SAME trace path must
    keep the armed tracer (and its spans); a DIFFERENT path starts a
    fresh scope; release() disarms so spans cannot leak across scopes."""
    obs.reset()
    p1 = str(tmp_path / "a.json")
    obs.configure(trace_path=p1)
    with obs.span("first"):
        pass
    obs.configure(trace_path=p1)               # idempotent: same scope
    with obs.span("second"):
        pass
    names = {e["name"] for e in obs.tracer().events()}
    assert {"first", "second"} <= names

    p2 = str(tmp_path / "b.json")
    obs.configure(trace_path=p2)               # new scope: fresh tracer
    names2 = {e["name"] for e in obs.tracer().events()}
    assert "first" not in names2

    path = obs.release(write=True)
    assert path == p2
    assert not obs.enabled()                   # released scope is disarmed
    doc = json.load(open(p2))
    assert "first" not in {e.get("name") for e in doc["traceEvents"]}
    obs.reset()


def test_export_ingest_rebase_and_tracks(tmp_path):
    """A worker-side export absorbed by a coordinator-side tracer keeps
    its pid track, gets its timestamps re-based onto the absorber's
    epoch, and the merged document validates."""
    coord = Tracer()
    worker = Tracer()
    worker.pid = coord.pid + 1           # simulate a second process
    worker.role = "worker9"
    worker._t0 = coord.t0_ns + 2_000_000     # worker clock starts 2ms later
    worker.add_complete("distrib.chunk", worker.t0_ns,
                        worker.t0_ns + 1_000_000, chunk=0)
    ship = worker.export(max_events=10, metrics={"counters": {"c": 1}})
    assert ship["role"] == "worker9" and ship["metrics"]["counters"] == {"c": 1}

    assert coord.ingest(ship) == 1
    assert coord.ingest("garbage") == 0
    assert coord.ingest({"events": "nope"}) == 0
    doc = coord.to_dict()
    chunk = [e for e in doc["traceEvents"]
             if e.get("name") == "distrib.chunk"][0]
    assert chunk["pid"] == worker.pid
    assert chunk["ts"] == 2000               # re-based: 2ms offset in µs
    pnames = {(e["pid"], e["args"]["name"]) for e in doc["traceEvents"]
              if e.get("ph") == "M" and e["name"] == "process_name"}
    assert (worker.pid, "worker9") in pnames

    path = tmp_path / "merged_inline.json"
    path.write_text(json.dumps(doc))
    assert obs_cli.main(["--validate", str(path)]) == 0


def test_export_truncation_counts_dropped():
    t = Tracer()
    for i in range(5):
        t.add_complete(f"s{i}", 0, 1000)
    ship = t.export(max_events=2)
    assert len(ship["events"]) == 2
    assert ship["dropped"] == 3
    assert ship["events"][-1]["name"] == "s4"    # newest win


def test_cli_merge_rebases_and_fleet_checks(tmp_path):
    a = Tracer()
    a.role = "coordinator"
    a.add_instant("distrib.dispatch", span_id="cafe0001",
                  trace_id="ab" * 8)
    b = Tracer()
    b.pid = a.pid + 1
    b.role = "worker0"
    b._t0 = a.t0_ns + 5_000_000
    b.add_complete("distrib.chunk", b.t0_ns, b.t0_ns + 1000,
                   parent="cafe0001", trace_id="ab" * 8)
    pa, pb = str(tmp_path / "a.json"), str(tmp_path / "b.json")
    a.write(pa)
    b.write(pb)
    merged = str(tmp_path / "m.json")
    assert obs_cli.main(["merge", "--out", merged, pb, pa]) == 0
    assert obs_cli.main(["--validate", merged]) == 0
    doc = json.load(open(merged))
    assert len(doc["racon_tpu"]["processes"]) == 2
    chunk = [e for e in doc["traceEvents"]
             if e.get("name") == "distrib.chunk"][0]
    assert chunk["ts"] == 5000           # worker epoch 5ms after base
    assert obs_cli.main(["fleet", merged]) == 0

    # drop the dispatch: the chunk's parent dangles -> exit 1
    doc["traceEvents"] = [e for e in doc["traceEvents"]
                          if e.get("name") != "distrib.dispatch"]
    bad = str(tmp_path / "bad.json")
    json.dump(doc, open(bad, "w"))
    assert obs_cli.main(["fleet", bad]) == 1
    # unreadable stays exit 2
    assert obs_cli.main(["fleet", str(tmp_path / "missing.json")]) == 2
    assert obs_cli.main(["merge", "--out", merged,
                         str(tmp_path / "missing.json")]) == 2


# ------------------------------------------------- flight recorder + rings

def test_flight_recorder_ring_dump_and_scan(tmp_path, monkeypatch):
    from racon_tpu.obs.flight import FlightRecorder, scan

    fr = FlightRecorder(max_events=16)
    fr.set_role("testproc")
    for i in range(40):
        fr.record(f"ev{i}", step=i)
    assert fr.dump("nowhere") is None            # no dir set -> no dump

    sub = tmp_path / "chunks" / "chunk000"
    fr.set_dir(str(sub))
    path = fr.dump("unit_test", detail_key="v")
    doc = json.load(open(path))
    assert doc["reason"] == "unit_test" and doc["role"] == "testproc"
    assert len(doc["events"]) == 16              # ring capacity held
    assert doc["events"][-1]["name"] == "ev39"   # newest kept
    assert doc["detail"] == {"detail_key": "v"}

    # recursive scan finds nested dumps and skips torn files
    (tmp_path / "flight.999.json").write_text("{torn")
    docs = scan(str(tmp_path))
    assert len(docs) == 1 and docs[0]["path"] == path

    monkeypatch.setenv("RACON_TPU_FLIGHT", "0")
    fr.record("ignored")
    assert fr.dump("disabled") is None           # knob gates dumping too


def test_obs_event_feeds_flight_even_disarmed(monkeypatch):
    from racon_tpu.obs import flight

    monkeypatch.delenv("RACON_TPU_FLIGHT", raising=False)
    obs.reset()
    assert not obs.enabled()
    obs.event("breadcrumb.disarmed", k=1)
    names = [e["name"] for e in flight.recorder()._ring]
    assert "breadcrumb.disarmed" in names


def test_telemetry_ring_bounded(monkeypatch):
    monkeypatch.setenv("RACON_TPU_TELEMETRY_RING", "4")
    obs.reset()
    import racon_tpu.obs as o
    o._telemetry = None                  # force re-size from the knob
    for i in range(10):
        entry = obs.telemetry_tick(queue_depth=i)
    assert entry["queue_depth"] == 9
    assert "t_mono_ns" in entry
    ring = obs.telemetry()
    assert len(ring) == 4                # bounded by the knob
    assert ring[-1]["queue_depth"] == 9
    assert obs.telemetry(last=2) == ring[-2:]
    o._telemetry = None


# ------------------------------------------- hist_quantile interpolation

def test_hist_quantile_interpolates_within_bucket():
    h = Histogram()
    for _ in range(50):
        h.observe(3.0)
    for _ in range(50):
        h.observe(3.5)
    d = h.as_dict()
    # all values share the (2, 4] bucket; the old estimator returned
    # the bucket's upper bound (4.0) for every quantile
    p50 = hist_quantile(d, 0.5)
    assert 3.0 <= p50 <= 3.5          # clamped to observed [min, max]
    assert p50 < 4.0
    # monotone in q
    qs = [hist_quantile(d, q) for q in (0.5, 0.9, 0.99)]
    assert qs == sorted(qs)
    # the "0" bucket holds only <= 0 values
    z = Histogram()
    z.observe(0.0)
    z.observe(-1.0)
    assert hist_quantile(z.as_dict(), 0.99) == 0.0
    # empty / malformed -> None, never a crash
    assert hist_quantile(Histogram().as_dict(), 0.5) is None
    assert hist_quantile({"count": "x"}, 0.5) is None
    assert hist_quantile("nope", 0.5) is None


def test_hist_quantile_error_bounded_by_bucket_width():
    """The estimate and the exact rank quantile share the winning log2
    bucket, so |est - exact| is bounded by that bucket's width."""
    import math

    rng = random.Random(20)
    vals = [rng.uniform(0.001, 900.0) for _ in range(500)]
    h = Histogram()
    for v in vals:
        h.observe(v)
    d = h.as_dict()
    s = sorted(vals)
    for q in (0.5, 0.9, 0.99):
        est = hist_quantile(d, q)
        exact = s[max(1, math.ceil(q * len(s))) - 1]
        hi = float(2 ** max(0, math.ceil(math.log2(exact))))
        width = hi - (hi / 2.0 if hi >= 2.0 else 0.0)
        assert abs(est - exact) <= width + 1e-9, (q, est, exact, width)


# --------------------------------------- epoch re-basing: ingest + merge

def test_export_ingest_negative_epoch_delta_clamps(tmp_path):
    """A worker whose monotonic epoch PREDATES the coordinator's (it
    booted first) re-bases to a negative delta: events from before the
    coordinator's epoch clamp to ts 0 instead of going negative (the
    Chrome-trace schema and the validator both require ts >= 0)."""
    coord = Tracer()
    worker = Tracer()
    worker.pid = coord.pid + 1
    worker.role = "worker_old"
    worker._t0 = coord.t0_ns - 3_000_000       # worker booted 3ms earlier
    worker.add_complete("early", worker.t0_ns,
                        worker.t0_ns + 1_000)  # before coord's epoch
    worker.add_complete("late", worker.t0_ns + 5_000_000,
                        worker.t0_ns + 5_001_000)
    ship = worker.export(max_events=10)
    assert coord.ingest(ship) == 2
    doc = coord.to_dict()
    by_name = {e["name"]: e for e in doc["traceEvents"]
               if e.get("name") in ("early", "late")}
    assert by_name["early"]["ts"] == 0         # clamped, not negative
    assert by_name["late"]["ts"] == 2000       # -3ms + 5ms = +2ms in µs
    path = tmp_path / "clamped.json"
    path.write_text(json.dumps(doc))
    assert obs_cli.main(["--validate", str(path)]) == 0


def test_cli_merge_worker_epoch_predating_coordinator(tmp_path):
    """merge re-bases onto the OLDEST known epoch, so a worker that
    booted before the coordinator keeps its early events at small
    positive ts and the coordinator's events shift right."""
    a = Tracer()
    a.role = "coordinator"
    a.add_instant("coord.mark")
    b = Tracer()
    b.pid = a.pid + 1
    b.role = "worker0"
    b._t0 = a.t0_ns - 5_000_000                # worker epoch 5ms earlier
    b.add_complete("distrib.chunk", b.t0_ns, b.t0_ns + 1000)
    pa, pb = str(tmp_path / "a.json"), str(tmp_path / "b.json")
    a.write(pa)
    b.write(pb)
    merged = str(tmp_path / "m.json")
    assert obs_cli.main(["merge", "--out", merged, pa, pb]) == 0
    assert obs_cli.main(["--validate", merged]) == 0
    doc = json.load(open(merged))
    chunk = [e for e in doc["traceEvents"]
             if e.get("name") == "distrib.chunk"][0]
    mark = [e for e in doc["traceEvents"]
            if e.get("name") == "coord.mark"][0]
    assert chunk["ts"] == 0                    # worker owns the base epoch
    assert mark["ts"] >= 5000                  # coordinator shifted +5ms


def test_cli_merge_doc_without_epoch_keeps_own_timebase(tmp_path):
    """A trace doc with no epoch stamp (foreign/hand-built) cannot be
    re-based: merge keeps its own timebase instead of guessing."""
    a = Tracer()
    a.role = "coordinator"
    a.add_instant("coord.mark")
    pa = str(tmp_path / "a.json")
    a.write(pa)
    bare = {
        "traceEvents": [
            {"name": "foreign.span", "ph": "X", "ts": 7, "dur": 3,
             "pid": 999, "tid": 1, "cat": "racon_tpu", "args": {}},
        ],
        "displayTimeUnit": "ms",
    }
    pb = str(tmp_path / "bare.json")
    json.dump(bare, open(pb, "w"))
    merged = str(tmp_path / "m.json")
    assert obs_cli.main(["merge", "--out", merged, pa, pb]) == 0
    doc = json.load(open(merged))
    foreign = [e for e in doc["traceEvents"]
               if e.get("name") == "foreign.span"][0]
    assert foreign["ts"] == 7                  # untouched: no epoch known
