"""The trace reducer on a small trace kept beside this file: busy union,
idle share, self times, name mapping, gap labels.

    python -m pytest benchmark/tests -q -p no:cacheprovider

Run by hand and in the rehearsal; not part of the repo's tier-1 tests.
"""

import json
import os

import pytest

from benchmark import xplane

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)


@pytest.fixture(scope="module")
def trace():
    with open(os.path.join(BENCH, "trace_layout.json")) as f:
        layout = json.load(f)
    return xplane.read(os.path.join(HERE, "data", "two_chip.xspace.txt"),
                       layout, text_proto=True)


def test_planes_lines_and_annotations(trace):
    assert sorted(trace.ops) == [0, 1]
    assert trace.op_line == {0: ["XLA Ops"], 1: ["XLA Ops"]}
    assert [job for job, _ in trace.jobs] == ["w0000", "w0001"]
    # the Steps line is no operation line
    assert all(e.name != "step 0" for e in trace.ops[0])
    assert xplane.window(trace) == (1000.0, 10000.0)


def test_interval_arithmetic(trace):
    ops = trace.ops[0]
    # [1000,3000] (while + nested) u [5000,6000] u [9000,10000 clipped]
    assert xplane.merged(ops, 1000, 10000) == [
        (1000, 3000), (5000, 6000), (9000, 10000)]
    assert xplane.busy_ns(ops, 1000, 10000) == 4000
    assert xplane.gaps(ops, 1000, 10000) == [(3000, 5000), (6000, 9000)]


def test_self_times_add_up_to_busy(trace):
    inside = [e for e in trace.ops[0] if e.start < 8000]
    st = xplane.self_times(inside)
    # while.1 2000 ns holds custom-call 500 and fusion 1000 (which ends
    # at 2800, inside): self 500
    assert st["while.1"] == pytest.approx(500e-9)
    assert st["custom-call.7"] == pytest.approx(1500e-9)
    assert st["fusion.2"] == pytest.approx(1000e-9)
    assert sum(st.values()) == pytest.approx(
        xplane.busy_ns(inside, 0, 8000) / 1e9)


def test_reduce_busy_idle_and_labels(trace):
    red = xplane.reduce(trace)
    assert red["window_s"] == pytest.approx(9000e-9)
    assert red["busy_s_per_chip"]["0"] == pytest.approx(4000e-9)
    assert red["busy_s_per_chip"]["1"] == pytest.approx(2000e-9)
    assert red["busy_s"] == pytest.approx(3000e-9)
    assert red["worst_chip"] == 1
    assert red["idle_share_worst_chip"] == pytest.approx(100 * 7 / 9)
    gaps = dict(red["idle_gaps"])
    # chip 1 idles 1000-2000, 3000-6000, 7000-10000; the middle gap's
    # midpoint (4500) is the second job's first instant
    assert gaps["in job w0000"] == pytest.approx(1000e-9)
    assert gaps["in job w0001"] == pytest.approx(6000e-9)
    assert red["device_ops"][0][0] == "custom-call.7"

    red = xplane.reduce(trace, label=lambda t: "poa.chunk"
                        if t < 3000 else None)
    assert dict(red["idle_gaps"])["poa.chunk"] == pytest.approx(1000e-9)


def test_kernel_seconds_by_pattern_and_interval(trace):
    # both chips' custom calls that start in [1000, 4000): 500 + 1000 ns,
    # averaged over the two chips
    assert xplane.kernel_seconds(trace, ["custom-call"],
                                 [(1000, 4000)]) == pytest.approx(750e-9)
    assert xplane.kernel_seconds(trace, ["no-such-op"],
                                 [(0, 1e6)]) == 0.0
    assert xplane.kernel_seconds(trace, ["CUSTOM"], []) == 0.0


@pytest.fixture(scope="module")
def recorded():
    """The first 2.2 s of the traced window of ``ecoli-ont.sam`` on one
    TPU v5 lite (my chip run, PR 22), cut down to the device's operation
    lines and the benchmark's annotation: 16 KB, three ``ls`` launches."""
    with open(os.path.join(BENCH, "trace_layout.json")) as f:
        layout = json.load(f)
    return xplane.read(os.path.join(
        HERE, "data", "ecoli-ont.sam.first-launches.xplane.pb"), layout)


def test_recorded_tpu_trace_layout(recorded):
    assert recorded.planes["/device:TPU:0"] == {
        "XLA Modules": 5, "XLA Ops": 152, "Async XLA Ops": 20}
    assert recorded.op_line == {0: ["XLA Ops"]}
    assert len(recorded.ops[0]) == 152
    assert [job for job, _ in recorded.jobs] == ["w0000"]


def test_recorded_tpu_trace_reduction(recorded):
    red = xplane.reduce(recorded)
    assert red["window_s"] == pytest.approx(2.153020183)
    assert red["busy_s"] == pytest.approx(1.806331645)
    assert red["idle_share_worst_chip"] == pytest.approx(16.1024286)
    assert red["idle_gaps"] == [["in job w0000",
                                 pytest.approx(0.346688538)]]
    # an op's name in the trace is its whole HLO text; the short form
    # keeps what tells the ls geometries apart (depth bucket 32 here)
    name, seconds = red["device_ops"][0]
    assert name == ("%fn.1 custom-call [8,1,8]x2 [8,8,32]x3 "
                    "[8,12,8,128]x2 [8,32,7,8,128]x2")
    assert seconds == pytest.approx(2.179809945)
    # the Pallas kernels are the tpu_custom_call ops
    assert xplane.kernel_seconds(recorded, ["tpu_custom_call"],
                                 [(0, 3e9)]) == pytest.approx(2.191209302)
    assert xplane.kernel_seconds(recorded, ["tpu_custom_call"],
                                 [(0, 1e9)]) == pytest.approx(0.983151819)


def test_roofline_reader_on_the_recorded_trace(recorded):
    """``poa_roofline`` end to end: kernel device time from the trace
    inside the program's ``phase.poa`` span (placed on the profiler's
    clock by the job's offset), ops and bytes from the job's counters."""
    from benchmark.reducers import registry

    offset = 5_000_000_000         # program clock = profiler clock + 5 s
    job = {"id": "w0000", "clock_offset_ns": offset,
           "spans": {"phase.poa": [(offset + 0, 1_000_000_000)]},
           "counters": {"poa.cells.d32.c512": 1_000_000,
                        "poa.windows.d32.c512": 64}}
    run = {"trace": recorded, "jobs": [job], "notes": {}, "data": {},
           "facts": {"int32_ops_per_s": 1.0e12},
           "peaks": {"hbm_bytes_per_s": 8.19e11}}
    share = registry()["roofline"](run, kernel="poa",
                                   phase_span="phase.poa",
                                   op_patterns=["tpu_custom_call"])
    ops = 1_000_000 * 2.0 * 512 * 14.0
    assert run["notes"]["poa_roofline"]["int_ops"] == ops
    assert run["notes"]["poa_roofline"]["binds"] == "int32 ops"
    assert run["notes"]["poa_roofline"]["kernel_device_s"] == \
        pytest.approx(0.983151819)
    assert share == pytest.approx(100 * (ops / 1e12) / 0.983151819)
    # no probe rate, no share: the metric is left out, not guessed
    run["facts"] = {}
    assert registry()["roofline"](run, kernel="poa", phase_span="phase.poa",
                                  op_patterns=["tpu_custom_call"]) is None
