"""The job boundary's reader on a small trace kept beside the tests
(``data/boundary``: two jobs, two chips, two threads, nested spans, a
head gap whose midpoint falls in no span), and the new metric files."""

import json
import os

import pytest

from benchmark import loader, reducers, xplane
from benchmark.reducers import boundary

DATA = os.path.join(os.path.dirname(__file__), "data", "boundary")
US = 1000

NEW_METRICS = (
    "job_open_s_per_mbp", "job_close_s_per_mbp", "job_unattributed_share",
    "prepare_reads_s_per_mbp", "prepare_overlaps_s_per_mbp",
    "prepare_transmute_s_per_mbp", "prepare_unattributed_share",
    "window_assign_breaks_s_per_mbp", "window_assign_layers_s_per_mbp",
    "boundary_idle_s_per_job", "boundary_idle_unnamed_share")


def _run(with_files: bool = True) -> dict:
    with open(os.path.join(DATA, "device.json")) as f:
        dev = json.load(f)
    trace = xplane.DeviceTrace(
        ops={int(c): [xplane.Event(n, s * US, d * US) for n, s, d in evs]
             for c, evs in dev["ops"].items()},
        jobs=[(j, xplane.Event("bench.job", s * US, d * US))
              for j, s, d in dev["jobs"]])
    jobs = [{"id": j, "clock_offset_ns": dev["clock_offset_us"] * US,
             "spans": {},
             "report": ({"trace": os.path.join(DATA, f"{j}.trace.json")}
                        if with_files else {})}
            for j, _, _ in dev["jobs"]]
    return {"trace": trace, "device": xplane.reduce(trace), "jobs": jobs,
            "notes": {}, "facts": {}}


def _us(by_span: dict) -> dict:
    return {k: round(v * 1e6) for k, v in by_span.items()}


def test_head_and_tail_on_the_least_busy_chip():
    run = _run()
    assert run["device"]["worst_chip"] == 1
    # job 0: 1000..3000 and 9000..11000; job 1: 12000..15000, 19000..22000
    assert boundary.boundary_idle_s(run) == pytest.approx(5000e-6)
    note = run["notes"]["boundary_idle"]
    assert note["chip"] == 1 and note["traced_jobs"] == 2
    assert note["head_s"] == pytest.approx(5000e-6)
    assert note["tail_s"] == pytest.approx(5000e-6)
    assert note["jobs_shared_out"] == 2
    # no more than the chip's idle in the traced window
    idle_s = run["device"]["window_s"] - run["device"]["busy_s_per_chip"]["1"]
    assert note["head_s"] + note["tail_s"] <= idle_s


def test_gaps_are_shared_out_by_overlap_on_the_driver_thread():
    run = _run()
    boundary.boundary_idle_s(run)
    note = run["notes"]["boundary_idle"]
    assert _us(note["head_by_span_s"]) == {
        # job 0: the tracer arms 100 us into the annotation; the worker
        # thread's span over the same stretch gets nothing
        boundary.NO_SPAN: 100 + 1800, "job.open": 300 + 400,
        "job.open.journal": 200, "phase.parse": 100 + 20,
        "native.prepare": 20 + 480, "native.prepare.reads": 400,
        "native.prepare.overlaps": 480, "phase.poa": 100 + 50,
        "poa.pack": 250 + 200, "poa.dispatch": 50 + 50}
    assert _us(note["tail_by_span_s"]) == {
        "poa.wait": 10 + 20, "poa.install": 500 + 480,
        "phase.poa": 90 + 100, "phase.stitch": 100 + 100,
        "job": 100 + 50, "job.close": 300 + 550,
        "job.close.report": 300 + 1000, "job.close.trace": 400,
        "job.close.output": 200,
        # job 0's second trace write, from job 1's file; job 1 has no
        # job after it, so what follows its root span has no name
        "job.release.prev": 150, boundary.NO_SPAN: 50 + 500}
    for key, total in (("head_by_span_s", "head_s"),
                       ("tail_by_span_s", "tail_s")):
        assert sum(note[key].values()) == pytest.approx(note[total])
    # the midpoint of job 1's head gap falls in no span: a label by
    # midpoint gives that gap's 3 ms to nothing, the split 1.8 of them
    assert "watchdog.arm" not in note["head_by_span_s"]


def test_unnamed_share():
    run = _run()
    # no span 2450, job 150, phase.* 660, of 10 000 us
    assert boundary.boundary_idle_unnamed_share(
        run, ["phase."]) == pytest.approx(32.6)
    assert boundary.boundary_idle_unnamed_share(
        run, ["phase.", "job.close"]) == pytest.approx(
            32.6 + (850 + 1300 + 400 + 200) / 100)


def test_nothing_to_read(tmp_path):
    run = _run()
    run["trace"], run["device"] = None, None
    assert boundary.boundary_idle_s(run) is None
    assert boundary.boundary_idle_unnamed_share(run, ["phase."]) is None
    assert "boundary_idle" not in run["notes"]
    # a program whose reports name no trace file: the device's seconds
    # still read, nothing to share them out by
    run = _run(with_files=False)
    assert boundary.boundary_idle_s(run) == pytest.approx(5000e-6)
    assert boundary.boundary_idle_unnamed_share(run, ["phase."]) is None
    assert run["notes"]["boundary_idle"]["head_by_span_s"] == {}
    # spans without ids (an older program's file) read the same way
    run = _run()
    with open(os.path.join(DATA, "w0000.trace.json")) as f:
        doc = json.load(f)
    for e in doc["traceEvents"]:
        e.pop("id", None)
    old = tmp_path / "trace.json"
    old.write_text(json.dumps(doc))
    # where the harness keeps a job's result, its path comes first
    run["jobs"][0]["result"] = {"trace": str(old)}
    assert boundary.boundary_idle_s(run) == pytest.approx(5000e-6)
    assert run["notes"]["boundary_idle"]["jobs_shared_out"] == 1


def test_new_metric_files_load_and_name_a_reader():
    registry = reducers.registry()
    bm = loader.load_benchmark()
    cells = [w["name"] for w in bm["workloads"]]
    listed = {m["name"]: m for m in bm["per_layer"]}
    for cell in cells:
        loaded = {m["name"]: m for m in loader.load_cell(cell).per_layer}
        for name in NEW_METRICS:
            assert listed[name]["workloads"] == cells, name
            m = loaded[name]
            assert m["reducer"] in registry, name
            assert m["moves"] == "polished_mbp_per_s" and m["what"], name
    assert loaded["boundary_idle_s_per_job"]["reducer"] == "boundary_idle_s"
