"""The job boundary's inside (ISSUE 39).

The native engine's stage marks on the spans' clock, span ids and
parents, the root span ``job`` with ``job.open`` / ``job.close`` under
it, the second trace write handed to the next job as
``job.release.prev``, and that none of it moves a byte of output.
"""

import json
import os
import threading
import time

import pytest

import racon_tpu
from racon_tpu import native, obs, pipeline as rt_pipeline
from racon_tpu.obs import __main__ as obs_cli
from racon_tpu.obs.tracer import NULL_SPAN, Tracer
from racon_tpu.pipeline import Pipeline
from racon_tpu.serve.session import JobSpec, PolishSession
from racon_tpu.tools import simulate

_ARGS = dict(window_length=500, quality_threshold=10, error_threshold=0.3,
             match=5, mismatch=-4, gap=-8, num_threads=2)
_FAST_ENV = {"RACON_TPU_PALLAS": "0", "RACON_TPU_BATCH_WINDOWS": "8"}


@pytest.fixture(autouse=True)
def _disarm_after():
    yield
    obs.reset()


@pytest.fixture(scope="module")
def sample(tmp_path_factory):
    """A seeded 60 kb ONT-like workload: (reads, SAM, PAF, draft)."""
    d = str(tmp_path_factory.mktemp("boundary_data"))
    assert simulate.main(["-o", d, "--mbp", "0.06", "--coverage", "12",
                          "--mean-read", "3000"]) == 0
    return (os.path.join(d, "reads.fastq"), os.path.join(d, "overlaps.sam"),
            os.path.join(d, "overlaps.paf"), os.path.join(d, "draft.fasta"))


def _pipeline(sample, overlaps=1) -> Pipeline:
    return Pipeline(sample[0], sample[overlaps], sample[3], **_ARGS)


def _spans(doc_or_events, name=None):
    events = (doc_or_events["traceEvents"]
              if isinstance(doc_or_events, dict) else doc_or_events)
    return [e for e in events if e.get("ph") == "X"
            and (name is None or e["name"] == name)]


def _inside(child, parent, slack_us=1):
    """Event timestamps are whole microseconds, floored."""
    return (parent["ts"] - slack_us <= child["ts"]
            and child["ts"] + child["dur"]
            <= parent["ts"] + parent["dur"] + slack_us)


# ------------------------------------------------------------- (a) one clock

def test_native_steady_clock_is_the_spans_clock():
    lib = native.load()
    for _ in range(100):
        t0 = time.monotonic_ns()
        n = lib.rt_steady_clock_ns()
        t1 = time.monotonic_ns()
        assert t0 <= n <= t1


def test_prepare_marks_lie_inside_the_prepare_span(sample):
    obs.reset()
    obs.configure(metrics=True)
    pl = _pipeline(sample)
    t0 = time.monotonic_ns()
    pl.prepare()
    t1 = time.monotonic_ns()
    for _, m0, m1, _, _ in pl.stage_marks():
        assert t0 <= m0 <= m1 <= t1
    events = _spans(obs.tracer().events())
    outer, = [e for e in events if e["name"] == "native.prepare"]
    marks = [e for e in events if e["name"].startswith("native.prepare.")]
    assert [e["name"] for e in marks] == [
        "native.prepare.targets", "native.prepare.reads",
        "native.prepare.overlaps", "native.prepare.transmute"]
    for e in marks:
        assert _inside(e, outer), (e, outer)
        assert e["parent"] == outer["id"] and e["tid"] == outer["tid"]


# ------------------------------------------------- (b) the marks of each call

def _timed(call):
    t0 = time.monotonic_ns()
    call()
    return t0, time.monotonic_ns()


def _ready_to_stitch(sample):
    """Every window with a consensus of 10 kb: a stitch of a few hundred
    microseconds, against the microseconds of the ABI crossing that no
    mark inside the engine can see."""
    pl = _pipeline(sample)
    pl.prepare()
    pl.build_windows()
    for i in range(pl.num_windows()):
        pl.set_consensus(i, b"ACGT" * 2500, True)
    return pl


def _call_prepare(sample):
    pl = _pipeline(sample)
    return pl, lambda: pl._lib.rt_pipeline_prepare(pl._h)


def _call_build_windows(sample):
    pl = _pipeline(sample)
    pl.prepare()
    return pl, lambda: pl._lib.rt_pipeline_build_windows(pl._h)


def _call_initialize(sample):
    pl = _pipeline(sample, overlaps=2)       # PAF: real alignment jobs
    return pl, lambda: pl._lib.rt_pipeline_initialize(pl._h)


def _call_stitch(sample):
    pl = _ready_to_stitch(sample)
    return pl, lambda: pl._lib.rt_pipeline_stitch(pl._h, 1)


_STAGES_OF = {
    "prepare": (_call_prepare, [0, 1, 2, 3]),
    "build_windows": (_call_build_windows, [5, 6, 7]),
    "initialize": (_call_initialize, [0, 1, 2, 3, 4, 5, 6, 7]),
    "stitch": (_call_stitch, [8]),
}


@pytest.mark.parametrize("call", sorted(_STAGES_OF))
def test_marks_in_order_disjoint_and_cover_the_call(sample, call):
    make, stages = _STAGES_OF[call]
    best = 0.0
    for _ in range(3):         # the cores are shared: the best of three
        pl, run = make(sample)
        t0, t1 = _timed(run)
        native.check_error(pl._lib)
        marks = pl.stage_marks()
        assert [m[0] for m in marks] == stages
        at = t0
        for _, m0, m1, _, _ in marks:
            assert at <= m0 <= m1       # in order, none overlaps
            at = m1
        assert at <= t1
        best = max(best, sum(m1 - m0 for _, m0, m1, _, _ in marks)
                   / (t1 - t0))
        if best >= 0.95:
            break
    assert best >= 0.95, best


def test_marks_count_what_the_counters_count(sample):
    pl = _pipeline(sample)
    pl.prepare()
    targets, parsed, kept = pl._prepare_counts()
    by_stage = {m[0]: m for m in pl.stage_marks()}
    assert by_stage[0][3] == targets == 1
    assert by_stage[0][4] == 60000                      # the draft's bases
    assert by_stage[2][3] == parsed and by_stage[2][4] == kept
    assert by_stage[1][3] >= kept and by_stage[1][4] > 60000 * 10
    assert by_stage[3][3] == by_stage[1][3] + targets   # every sequence
    pl.build_windows()
    breaks, create, layers = pl.stage_marks()
    assert breaks[3] == kept
    assert create[3] == pl.num_windows() == 120
    assert layers[3] == sum(pl.window_info(i)[0] - 1
                            for i in range(pl.num_windows()))
    for i in range(pl.num_windows()):
        pl.set_consensus(i, b"ACGT" * 125, True)
    records = pl.stitch()
    (stage, _, _, items, size), = pl.stage_marks()
    assert stage == 8 and items == len(records) == 1
    assert size == len(records[0][1]) == 60000


def test_a_coarse_call_starts_its_table_afresh(sample):
    pl = _pipeline(sample)
    assert pl.stage_marks() == []
    pl.prepare()
    assert len(pl.stage_marks()) == 4
    pl.build_windows()
    assert [m[0] for m in pl.stage_marks()] == [5, 6, 7]
    assert len(rt_pipeline._STAGES) == 9


# ------------------------------------------------------- (c) ids and parents

def test_ids_are_unique_and_parents_enclose():
    tr = Tracer()
    with obs.Span(tr, "outer", {}) as outer:
        with obs.Span(tr, "inner", {}) as inner:
            with obs.Span(tr, "innermost", {}):
                pass
        with obs.Span(tr, "sibling", {}):
            pass
    by_name = {e["name"]: e for e in tr.events()}
    assert len({e["id"] for e in by_name.values()}) == 4
    assert by_name["outer"]["parent"] is None
    assert by_name["inner"]["parent"] == outer.id == by_name["outer"]["id"]
    assert by_name["innermost"]["parent"] == inner.id
    assert by_name["sibling"]["parent"] == outer.id
    for e in by_name.values():
        assert e["tid"] == threading.get_ident()


def test_worker_thread_span_hangs_under_the_job():
    tr = Tracer()
    tr.begin("job", root=True)
    seen = {}

    def work():
        with obs.Span(tr, "worker.outer", {}) as sp:
            with obs.Span(tr, "worker.inner", {}) as inner:
                seen["inner_parent"] = inner.parent
            seen["outer"] = (sp.id, sp.parent)

    with obs.Span(tr, "main.span", {}) as main:
        th = threading.Thread(target=work)
        th.start()
        th.join()
    tr.end("job")
    root, = _spans(tr.events(), "job")
    assert root["id"] == tr.root_id and root["parent"] is None
    # not the span open on the main thread: a parent is on one's thread
    assert seen["outer"][1] == root["id"] != main.id
    assert seen["inner_parent"] == seen["outer"][0]
    assert main.parent == root["id"]


def test_retroactive_span_takes_the_callers_top():
    tr = Tracer()
    tr.add_complete("before", 10, 20)
    with obs.Span(tr, "open", {}) as sp:
        tr.add_complete("stamped", 10, 20, k=1)
        tr.add_complete("given", 10, 20, parent_id=None)
        tr.add_complete("named", 10, 20, parent_id=77)
    by_name = {e["name"]: e for e in tr.events()}
    assert by_name["before"]["parent"] is None
    assert by_name["stamped"]["parent"] == sp.id
    assert by_name["stamped"]["args"] == {"k": 1}
    assert by_name["given"]["parent"] is None
    assert by_name["named"]["parent"] == 77
    assert len({e["id"] for e in by_name.values()}) == 5


def test_a_span_arg_named_parent_stays_an_arg():
    """``distrib.chunk`` carries the trace context's parent as an arg."""
    tr = Tracer()
    with obs.Span(tr, "distrib.chunk", {"parent": "abcd1234"}):
        pass
    ev, = tr.events()
    assert ev["args"] == {"parent": "abcd1234"} and ev["parent"] is None


def test_begun_span_is_in_the_file_while_open_and_once_after():
    tr = Tracer(t0_ns=time.monotonic_ns() - 5_000_000)
    tr.begin("job", tr.t0_ns, root=True)
    tr.begin("job.open", tr.t0_ns)
    with obs.Span(tr, "job.open.journal", {}) as child:
        pass
    tr.end("job.open")
    tr.end("job.open")                            # nothing open: a no-op
    doc = tr.to_dict()
    root, = _spans(doc, "job")
    opened, = _spans(doc, "job.open")
    assert root["args"] == {"open": True} and root["ts"] == 0
    assert root["dur"] >= 5000 and "open" not in opened["args"]
    assert opened["parent"] == root["id"] and child.parent == opened["id"]
    assert [e["name"] for e in tr.export(max_events=10)["events"]
            if e["name"] == "job"] == ["job"]
    tr.end("job", job="j7")
    root, = _spans(tr.to_dict(), "job")
    assert root["args"] == {"job": "j7"}
    assert not tr._held


def test_disarmed_path_allocates_nothing(sample, monkeypatch):
    obs.reset()
    assert obs.span("job.close.output") is NULL_SPAN
    assert obs.begin("job", root=True) is None and obs.end("job") is None
    assert obs.tracer() is None

    def crossing(self):
        raise AssertionError("the marks were read with no tracer armed")

    monkeypatch.setattr(Pipeline, "stage_marks", crossing)
    pl = _pipeline(sample)
    pl.prepare()
    pl.build_windows()
    # nor was the blocked loops' work counted (ISSUE 40): no registry
    assert obs.snapshot() is None and obs.counter_total("native.pool.") == 0


# ----------------------------------------------------- (d) a served job's file

def _spec(sample, job_id, overlaps=1):
    return JobSpec(sample[0], sample[overlaps], sample[3], args=dict(_ARGS),
                   job_id=job_id)


def _covered_share(doc):
    """Share of the one ``job`` span that the union of its children
    ``job.open``, ``phase.*`` and ``job.close`` covers."""
    root, = _spans(doc, "job")
    kids = sorted((e["ts"], e["ts"] + e["dur"]) for e in _spans(doc)
                  if e["parent"] == root["id"]
                  and (e["name"] in ("job.open", "job.close")
                       or e["name"].startswith("phase.")))
    covered, at = 0, root["ts"]
    for lo, hi in kids:
        lo, hi = max(lo, at), min(hi, root["ts"] + root["dur"])
        if hi > lo:
            covered, at = covered + hi - lo, hi
    return covered / root["dur"]


@pytest.fixture(scope="module")
def served(sample, tmp_path_factory):
    """Two jobs through one host-lane session: results and trace docs."""
    s = PolishSession(str(tmp_path_factory.mktemp("boundary_state")),
                      backend="cpu")
    results = [s.run_job(_spec(sample, f"j{i}")) for i in range(2)]
    docs = []
    for r in results:
        doc, errors = obs_cli.load_trace(r["trace"])
        assert errors == []
        docs.append(doc)
    obs.reset()
    return results, docs


def test_served_job_has_one_root_and_its_children_cover_it(served):
    results, docs = served
    for r, doc in zip(results, docs):
        root, = _spans(doc, "job")
        assert root["args"] == {"job": r["job_id"], "backend": "cpu",
                                "cold": r["job_id"] == "j0"}
        assert root["parent"] is None and root["ts"] == 0
        assert _covered_share(doc) >= 0.95
        ids = [e["id"] for e in _spans(doc)]
        assert len(set(ids)) == len(ids)
        # every other span of the request hangs under the root
        by_id = {e["id"]: e for e in _spans(doc)}
        for e in _spans(doc):
            if e["name"] in ("job", "job.release.prev"):
                continue
            while e["parent"] is not None:
                e = by_id[e["parent"]]
            assert e is root


def test_boundary_seams_each_carry_their_name(served):
    _, docs = served
    names = {e["name"] for e in _spans(docs[1])}
    assert names >= {
        "job.open", "job.open.journal", "job.open.pipeline", "job.close",
        "job.close.journal", "job.close.report", "job.close.trace",
        "job.close.output", "job.close.ship", "native.stitch.join",
        "native.stitch.copy", "native.initialize.align"}
    by_id = {e["id"]: e for e in _spans(docs[1])}
    for e in _spans(docs[1]):
        head = e["name"].rsplit(".", 1)[0]
        if head in ("job.open", "job.close"):
            assert by_id[e["parent"]]["name"] == head
            assert _inside(e, by_id[e["parent"]])
    opened, = _spans(docs[1], "job.open")
    parse, = _spans(docs[1], "phase.parse")
    assert opened["ts"] + opened["dur"] <= parse["ts"] + 1
    stitch, = _spans(docs[1], "phase.stitch")
    closed, = _spans(docs[1], "job.close")
    assert stitch["ts"] + stitch["dur"] <= closed["ts"] + 1


def test_second_jobs_file_holds_the_first_jobs_release(served):
    results, docs = served
    assert not _spans(docs[0], "job.release.prev")
    prev, = _spans(docs[1], "job.release.prev")
    assert prev["args"]["job"] == "j0" and prev["parent"] is None
    assert prev["dur"] == pytest.approx(results[0]["release_s"] * 1e6, abs=2)
    # its true start: after the first job's root span ended, before the
    # second one's began
    t0 = [d["otherData"]["t0_monotonic_ns"] for d in docs]
    root0, = _spans(docs[0], "job")
    assert (t0[0] + (root0["ts"] + root0["dur"]) * 1000 - 1000
            <= prev["args"]["t0_mono_ns"] <= t0[1])
    for r in results:
        assert 0 < r["release_s"] < r["wall_s"]
        with open(r["report"]) as f:
            assert json.load(f)["trace"] == r["trace"]


def test_span_table_lists_self_time(served):
    _, docs = served
    rows = obs_cli.span_self_times(docs[1])
    for name in ("job", "job.open", "job.close", "native.prepare.reads",
                 "native.build_windows.layers", "job.release.prev"):
        assert rows[name]["count"] == 1, name
    assert rows["job"]["self_us"] <= 0.05 * rows["job"]["total_us"]
    assert rows["job.close"]["self_us"] < rows["job.close"]["total_us"]
    # self times add up to the time under the roots
    roots = rows["job"]["total_us"] + rows["job.release.prev"]["total_us"]
    assert sum(r["self_us"] for r in rows.values()) == pytest.approx(
        roots, abs=len(_spans(docs[1])))
    text = obs_cli.render(docs[1], "j1")
    assert "self (less its children)" in text
    assert "native.prepare.overlaps" in text and "job.close.ship" in text
    # a trace without ids (an older file) renders without the table
    for e in docs[0]["traceEvents"]:
        e.pop("id", None)
    assert obs_cli.span_self_times(docs[0]) == {}


# -------------------------------- (e) armed and disarmed: the same bytes

@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """1.2 kb at 6x from PAF: a device-path job (the XLA twins, on the
    CPU) of a few seconds."""
    d = str(tmp_path_factory.mktemp("boundary_tiny"))
    assert simulate.main(["-o", d, "--mbp", "0.0012", "--coverage", "6",
                          "--mean-read", "400"]) == 0
    return (os.path.join(d, "reads.fastq"), os.path.join(d, "overlaps.sam"),
            os.path.join(d, "overlaps.paf"), os.path.join(d, "draft.fasta"))


def _device_run(sample, tmp_path, monkeypatch, tag, trace=False, env=None):
    for k, v in {**_FAST_ENV, **(env or {})}.items():
        monkeypatch.setenv(k, v)
    journal = tmp_path / f"{tag}.journal"
    p = racon_tpu.create_polisher(
        sample[0], sample[2], sample[3], backend="tpu",
        journal_path=str(journal),
        trace_path=str(tmp_path / f"{tag}.json") if trace else None,
        **dict(_ARGS, window_length=200, num_threads=1))
    p.initialize()
    out = p.polish(True)
    return out, journal.read_bytes(), p.report.as_dict()


def test_armed_and_disarmed_leave_the_same_bytes(tiny, tmp_path,
                                                 monkeypatch):
    sample = tiny
    monkeypatch.delenv("RACON_TPU_TRACE", raising=False)
    monkeypatch.delenv("RACON_TPU_METRICS", raising=False)
    plain, plain_journal, plain_rep = _device_run(
        sample, tmp_path, monkeypatch, "plain")
    assert not obs.enabled() and plain_rep["obs"] == {"armed": False}
    armed, armed_journal, armed_rep = _device_run(
        sample, tmp_path, monkeypatch, "armed", trace=True)
    counted, counted_journal, counted_rep = _device_run(
        sample, tmp_path, monkeypatch, "counted",
        env={"RACON_TPU_METRICS": "1"})
    assert armed == plain == counted
    assert armed_journal == plain_journal == counted_journal
    for name, rep in plain_rep["phases"].items():
        for key in ("total", "served"):
            assert armed_rep["phases"][name][key] == rep[key], (name, key)
    counters, counted = (dict(rep["obs"]["metrics"]["counters"])
                         for rep in (armed_rep, counted_rep))
    # the process's peak so far, not the job's: it can grow from one run
    # of a process to the next
    assert counters.pop("job.rss.peak_mb") <= counted.pop("job.rss.peak_mb")
    assert counters == counted
    assert counters["polish.targets"] == 1 and counters["overlaps.kept"] > 0
    # the armed run's own file: a bare polisher leaves the root open,
    # and the file holds it up to the write
    doc, errors = obs_cli.load_trace(str(tmp_path / "armed.json"))
    assert errors == []
    root, = _spans(doc, "job")
    assert root["args"] == {"open": True}
    assert _covered_share(doc) >= 0.95
    prepare, = _spans(doc, "native.prepare")
    for e in _spans(doc):
        if e["name"].startswith("native.prepare."):
            assert e["parent"] == prepare["id"] and _inside(e, prepare)
    assert {e["name"] for e in _spans(doc)} >= {
        "job.open.journal", "job.open.pipeline", "job.close.journal",
        "job.close.report", "native.build_windows.breaks",
        "native.build_windows.create", "native.build_windows.layers"}
    assert list(tmp_path.glob("*.json")) == [tmp_path / "armed.json"]
