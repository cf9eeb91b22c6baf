"""Launch-level spans, jit-stage spans and the one clock (ISSUE 23).

The drivers' per-launch spans (category ``"launch"``) and counters, the
``jax.monitoring`` jit stages in ``racon_tpu/device.py``, the
``TraceAnnotation`` mirror of every armed span, and the shipment cap that
keeps ``phase.*`` spans when launches outnumber it.
"""

import glob
import random

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from racon_tpu import device, obs
from racon_tpu.obs.tracer import NULL_SPAN, Tracer
from racon_tpu.ops import align_pallas
from racon_tpu.ops.encoding import encode
from tests.test_align import mutate
from tests.test_obs import _tpu_run, _write_dataset


@pytest.fixture(autouse=True)
def _disarm_after():
    yield
    obs.reset()


def _armed():
    obs.reset()
    obs.configure(metrics=True)


def _spans(name=None):
    return [e for e in obs.tracer().events()
            if e["ph"] == "X" and (name is None or e["name"] == name)]


# ------------------------------------------------------------- one clock

def _host_plane_events(trace_dir):
    xp, = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(xp)
    host, = [p for p in data.planes if p.name == "/host:CPU"]
    return {ev.name: dict(ev.stats) for line in host.lines
            for ev in line.events}


def test_armed_span_sits_on_the_profilers_host_plane(tmp_path):
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    obs.reset()
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        disarmed = obs.span("launch.disarmed", cat="launch", B=1)
        assert disarmed is NULL_SPAN
        with disarmed:
            pass
        _armed()
        with obs.span("launch.armed", cat="launch", B=64) as sp:
            sp.set(tier="ls")
            jnp.ones(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    found = _host_plane_events(tmp_path)
    assert "launch.disarmed" not in found
    # name, the args it was opened with and the ones set() added later
    assert found["launch.armed"] == {"B": 64, "tier": "ls"}
    ev, = _spans("launch.armed")
    assert ev["cat"] == "launch" and ev["args"] == {"B": 64, "tier": "ls"}


# ------------------------------------------------------ alignment launches

def _pair(rng, n, rate=0.08):
    q = bytes(rng.choice(b"ACGT") for _ in range(n))
    t = mutate(q, rate, rng)
    return tuple(encode(np.frombuffer(s, np.uint8)).astype(np.int32)
                 for s in (q, t))


def test_align_pairs_emits_the_launch_spans_and_counters():
    rng = random.Random(5)
    # two multi-round pairs and one that is a base case from the start
    pairs = [_pair(rng, 1400), _pair(rng, 1100), _pair(rng, 200)]
    _armed()
    res = align_pallas.align_pairs(pairs, interpret=True)
    assert all(r is not None for r in res)

    names = {e["name"] for e in _spans()}
    assert {"align.round", "align.pack", "align.dispatch", "align.wait",
            "align.select", "align.traceback"} <= names
    assert {e["cat"] for e in _spans() if e["name"].startswith("align.")} \
        == {"launch"}

    counters = obs.snapshot()["counters"]
    dispatch, wait = _spans("align.dispatch"), _spans("align.wait")
    rounds = _spans("align.round")
    assert len(rounds) >= 2
    assert all(r["args"]["tasks"] >= 1 and r["args"]["buckets"] >= 1
               for r in rounds)
    # every launch is one dispatch and one wait, told apart by kernel
    assert len(dispatch) == len(wait)
    kernels = [d["args"]["kernel"] for d in dispatch]
    assert set(kernels) == {"edge_fwd", "edge_bwd", "base"}
    assert counters["align.launches.edge"] == sum(
        k != "base" for k in kernels)
    assert counters["align.launches.base"] == kernels.count("base")
    assert counters["align.launches.edge"] \
        + counters["align.launches.base"] == len(dispatch)
    assert all({"rcap", "K", "B"} <= set(d["args"]) for d in dispatch)
    # rows on the device = tasks + the rows that pad a batch to 2**k
    assert counters["align.tasks.real"] + counters["align.tasks.pad"] \
        == sum(d["args"]["B"] for d in dispatch)
    # no span inside a per-task loop: a handful per launch, not per task
    assert len(_spans()) <= 8 * len(dispatch)


def _by_time(events):
    return sorted(events, key=lambda e: (e["ts"], -e["dur"]))


def test_align_launches_are_issued_ahead_of_their_waits(monkeypatch):
    """The issue order that keeps the device fed.  In a round every
    bucket's forward and backward launch is dispatched before the first
    wait; up to BASE_AHEAD base launches are out before the first base
    wait, and each later one goes out as one comes back.  Each launch
    is still one dispatch and one wait, and says once whether it found
    an earlier launch still out (``align.queue.behind``) or the device
    with nothing to do (``.empty``)."""
    rng = random.Random(6)
    # 16 + 120 base tasks: three base launches, against a bound of two
    monkeypatch.setattr(align_pallas, "BASE_AHEAD", 2)
    pairs = [_pair(rng, 1400), _pair(rng, 1100)] + \
        [_pair(rng, 300, rate=0.03) for _ in range(60)]
    _armed()
    res = align_pallas.align_pairs(pairs, interpret=True)
    assert all(r is not None for r in res)
    counters = obs.snapshot()["counters"]
    spans = _by_time(_spans())
    launches = [e for e in spans
                if e["name"] in ("align.dispatch", "align.wait")]
    assert len(_spans("align.dispatch")) == len(_spans("align.wait"))

    # rounds: inside each align.round span, 2 dispatches per bucket and
    # no wait; the waits follow, forward before backward
    rounds = _spans("align.round")
    assert len(rounds) >= 2
    for r in rounds:
        lo, hi = r["ts"], r["ts"] + r["dur"]
        inside = [e for e in launches if lo <= e["ts"] <= hi]
        assert [e["name"] for e in inside] == \
            ["align.dispatch"] * (2 * r["args"]["buckets"])
        assert [e["args"]["kernel"] for e in inside] == \
            ["edge_fwd", "edge_bwd"] * r["args"]["buckets"]
    edge = [e for e in launches if e["args"]["kernel"] != "base"]
    first_wait = next(i for i, e in enumerate(edge)
                      if e["name"] == "align.wait")
    # edge_bwd's dispatch precedes edge_fwd's wait
    assert [(e["name"], e["args"]["kernel"])
            for e in edge[first_wait - 1:first_wait + 2]] == [
        ("align.dispatch", "edge_bwd"), ("align.wait", "edge_fwd"),
        ("align.wait", "edge_bwd")]

    # base: the launches go out ahead of the first wait
    base = [e["name"] for e in launches if e["args"]["kernel"] == "base"]
    assert counters["align.launches.base"] == 3
    assert base == ["align.dispatch"] * 2 + [
        "align.wait", "align.dispatch", "align.wait", "align.wait"]

    assert counters["align.queue.behind"] + counters["align.queue.empty"] \
        == counters["align.launches.edge"] + counters["align.launches.base"]
    # driven alone, the queue is empty when a round starts and when the
    # base phase starts, and never in between
    assert counters["align.queue.empty"] == len(rounds) + 1


def test_no_align_span_is_open_across_a_yield():
    """`Span` enters a `jax.profiler.TraceAnnotation`: one held open
    over a yield would be closed on another cohort's time, out of
    order.  Two cohorts advanced in turn with an instant event at every
    hand-over: no ``align.*`` span contains one, the spans of the two
    nest properly on the one thread, and sharing the set of launches in
    flight shows in the counters: fewer launches find the queue empty
    than when each cohort runs alone."""
    import time

    rng = random.Random(8)
    cohorts = [[_pair(rng, 1400), _pair(rng, 200)],
               [_pair(rng, 1100), _pair(rng, 600), _pair(rng, 90)]]
    empty_alone = 0
    for c in cohorts:
        _armed()
        align_pallas.align_pairs(c, interpret=True)
        empty_alone += obs.snapshot()["counters"]["align.queue.empty"]

    _armed()
    in_flight = align_pallas._InFlight()
    gens = [align_pallas.align_steps(c, interpret=True, in_flight=in_flight)
            for c in cohorts]
    live = list(gens)
    while live:
        for g in list(live):
            try:
                next(g)
            except StopIteration:
                live.remove(g)
            time.sleep(0.002)
            obs.event("test.handover")
            time.sleep(0.002)
    events = obs.tracer().events()
    handovers = [e["ts"] for e in events if e["name"] == "test.handover"]
    spans = [e for e in _spans() if e["name"].startswith("align.")]
    assert len(handovers) >= 8 and spans
    for sp in spans:
        lo, hi = sp["ts"], sp["ts"] + sp["dur"]
        assert not any(lo < t < hi for t in handovers), sp
    # proper nesting: two spans are apart or one holds the other
    for a in spans:
        for b in spans:
            a0, a1 = a["ts"], a["ts"] + a["dur"]
            b0, b1 = b["ts"], b["ts"] + b["dur"]
            assert a1 <= b0 or b1 <= a0 or (a0 <= b0 and b1 <= a1) \
                or (b0 <= a0 and a1 <= b1), (a, b)
    counters = obs.snapshot()["counters"]
    assert counters["align.queue.behind"] + counters["align.queue.empty"] \
        == counters["align.launches.edge"] + counters["align.launches.base"]
    assert counters["align.queue.empty"] < empty_alone
    assert not in_flight


# ------------------------------------------------------- consensus launches

def test_poa_chunk_emits_the_launch_spans_and_counters(tmp_path,
                                                       monkeypatch):
    paths = _write_dataset(tmp_path)
    trace = tmp_path / "trace.json"
    res, p = _tpu_run(paths, monkeypatch, {}, trace_path=str(trace))
    assert res
    events = obs.tracer().events()
    by_name = {}
    for e in events:
        if e["ph"] == "X":
            by_name.setdefault(e["name"], []).append(e)
    for name in ("poa.pack", "poa.dispatch", "poa.wait", "poa.install"):
        assert by_name[name], name
        assert {e["cat"] for e in by_name[name]} == {"launch"}
    assert "phase.poa" in by_name and \
        by_name["phase.poa"][0]["cat"] == "span"
    counters = obs.snapshot()["counters"]
    assert counters["poa.launches"] == len(by_name["poa.dispatch"]) \
        == len(by_name["poa.wait"])
    served = p.report.as_dict()["phases"]["consensus"]["served"]
    assert counters["poa.rows.real"] == served["xla"]
    # every batch is padded to the compiled batch (8 in these runs)
    assert counters["poa.rows.real"] + counters["poa.rows.pad"] \
        == 8 * counters["poa.launches"]
    # per-window native calls are counted per loop, never spanned
    n_windows = p.report.as_dict()["phases"]["consensus"]["total"]
    assert counters["native.calls.window_info"] == n_windows
    assert counters["native.calls.export_window"] >= served["xla"]
    assert not any(n.startswith("native.calls") for n in by_name)


# ------------------------------------------------------------- jit stages

def test_cache_traffic_reports_jit_stages_by_function_name(monkeypatch):
    device.require_tpu()            # registers the listeners once
    # a stage under a millisecond is counted and not spanned; this tiny
    # function's stages are about that long
    monkeypatch.setattr(device, "_SPAN_FLOOR_S", 0.0)
    before = device.cache_traffic()

    def tiny(x):
        return jnp.pad(x, 1) * 3    # jnp.pad is itself jitted: nested

    tiny.__name__ = tiny.__qualname__ = "racon_test_tiny"
    _armed()
    jax.jit(tiny)(jnp.ones(5)).block_until_ready()
    after = device.cache_traffic()

    row = after["by_fun"]["racon_test_tiny"]
    assert row["n"] == 1
    assert row["trace_s"] > 0 and row["lower_s"] > 0 and row["compile_s"] > 0
    assert after["trace_s"] > before["trace_s"]
    assert after["lower_s"] > before["lower_s"]
    assert after["traces"] > before["traces"]
    assert after["lowerings"] > before["lowerings"]
    # the helper traced inside tiny's trace is not a row of its own
    assert "_pad" not in after["by_fun"]
    # armed, each stage is a span on the job's timeline, by name
    for stage in ("jit.trace", "jit.lower", "jit.compile"):
        assert any(e["args"]["fun"] == "racon_test_tiny"
                   for e in _spans(stage)), stage
    assert obs.snapshot()["counters"]["jit.traces"] >= 1
    # the second call makes no program: no stage, no span
    n = len(_spans())
    jax.jit(tiny)(jnp.ones(5)).block_until_ready()
    assert device.cache_traffic()["by_fun"]["racon_test_tiny"] == row
    assert len(_spans()) == n


# ------------------------------------------------------------ shipment cap

def test_export_keeps_phase_spans_when_launches_exceed_the_cap():
    t = Tracer()
    t.add_complete("phase.align", 0, 9_000_000)
    for i in range(50):
        t.add_complete("align.dispatch", 1000 * i, 1000 * i + 500,
                       cat="launch", i=i)
    t.add_complete("phase.poa", 9_000_000, 9_500_000)
    t.add_instant("serve.job")
    ship = t.export(max_events=10)
    names = [e["name"] for e in ship["events"]]
    assert len(names) == 10 and ship["dropped"] == 43
    assert {"phase.align", "phase.poa", "serve.job"} <= set(names)
    # the room that is left goes to the newest launches, in time order
    kept = [e["args"]["i"] for e in ship["events"]
            if e["name"] == "align.dispatch"]
    assert kept == list(range(43, 50))
    assert names[0] == "phase.align" and names[-1] == "serve.job"
    # more other events than the cap: launches go first, newest win
    ship = t.export(max_events=2)
    assert [e["name"] for e in ship["events"]] == ["phase.poa", "serve.job"]
    assert ship["dropped"] == 51
