"""Memory budget, streaming input, and pressure-driven degradation
(resilience/budget.py + streamio.py + the consumers).

Units: watermark transitions with a fake RSS sampler (ok -> soft ->
hard, latch, callbacks, flight dump), the spill-file round trip (park /
load / torn), the per-chunk byte-range index, and the three ``mem.*``
fault points.  End-to-end: streaming is byte-identical to the in-memory
path; a tight budget forces the hard watermark and the pressure
lattice's degradation edges — the phase pipeline collapses
(pipelined -> sequential) and the batch executor drains inline
(batched -> stream-sequential) — while output stays byte-identical;
``mem.pressure`` / ``mem.spill`` drills are absorbed; a torn input tail
quarantines its chunk, not the run; and ``mem.oom:kill=1`` really
SIGKILLs a fleet worker whose chunk re-dispatches to a byte-identical
finish.  Plus the admission ladder's memory rung, the ``mem.rss``
telemetry surfaces, and the bench ``stream`` entry contract.
"""

import gzip
import json
import os

import pytest

import racon_tpu
from racon_tpu.resilience import budget, faults
from racon_tpu.resilience.budget import MemoryBudget
from racon_tpu.streamio import StreamIndex, WorkingSet

from test_faults import _ARGS, _assert_report_sums, _oracle, _tpu_run, \
    _write_dataset


def _edges(report_dict):
    """Every (from, to) degradation edge across all phase reports."""
    return [(g["from"], g["to"])
            for ph in report_dict["phases"].values()
            for g in ph.get("degradations", []) if isinstance(g, dict)]


# ------------------------------------------------- unit: watermark machine

def test_watermark_transitions_latch_and_callbacks():
    rss = {"v": 10.0}
    softs, hards = [], []
    b = MemoryBudget(100, rss_source=lambda: rss["v"],
                     on_soft=lambda: softs.append(1),
                     on_hard=lambda: hards.append(1))
    assert b.enabled
    assert b.soft_mb == pytest.approx(80.0)
    assert b.hard_mb == pytest.approx(95.0)
    assert b.poll(fault_check=False) == "ok" and not softs
    rss["v"] = 85.0
    assert b.poll(fault_check=False) == "soft"
    assert softs == [1] and not hards
    rss["v"] = 96.0
    assert b.poll(fault_check=False) == "hard"
    assert hards == [1] and b.hard_latched()
    # recovery drops the level but the hard latch is per-run: the
    # consumers' degradations (collapsed pipeline, inline batching)
    # are one-way edges
    rss["v"] = 10.0
    assert b.poll(fault_check=False) == "ok"
    assert b.level() == "ok" and b.hard_latched()
    assert b.peak_mb() == pytest.approx(96.0)
    rss["v"] = 99.0
    b.poll(fault_check=False)
    assert hards == [1]            # the hard callback fires exactly once


def test_unbudgeted_is_disabled():
    b = MemoryBudget(0, rss_source=lambda: 1e9)
    assert not b.enabled
    assert b.poll(fault_check=False) == "ok"
    assert not b.hard_latched()
    assert budget.at_least("hard", "soft")
    assert budget.at_least("soft", "soft")
    assert not budget.at_least("ok", "soft")


def test_hard_watermark_dumps_flight_recorder(monkeypatch):
    from racon_tpu.obs import flight

    dumps = []
    monkeypatch.setattr(
        flight, "dump",
        lambda reason, dir_path=None, **kw: dumps.append((reason, kw)))
    rss = {"v": 10.0}
    b = MemoryBudget(100, rss_source=lambda: rss["v"])
    b.poll(fault_check=False)
    rss["v"] = 99.0
    b.poll(fault_check=False)
    assert dumps == [("mem_hard_watermark",
                      {"rss_mb": 99.0, "budget_mb": 100, "forced": False})]
    rss["v"] = 99.5
    b.poll(fault_check=False)      # latched: one post-mortem per run
    assert len(dumps) == 1


def test_mem_fault_points_registered():
    assert {"mem.pressure", "mem.spill", "mem.oom"} <= faults.KNOWN_POINTS
    specs = faults.parse_spec("mem.oom:kill=1:count=1,mem.spill")
    assert specs[0].point == "mem.oom" and specs[0].kill
    assert specs[1].point == "mem.spill"


def test_mem_pressure_fault_forces_hard_breach(monkeypatch):
    """An injected mem.pressure raise is absorbed as a forced hard
    breach — the deterministic pressure drill — even when real RSS is
    nowhere near the watermarks."""
    monkeypatch.setenv("RACON_TPU_FAULT", "mem.pressure")
    faults.reset()
    b = MemoryBudget(1000, rss_source=lambda: 1.0)
    assert b.poll() == "hard"
    assert b.hard_latched()
    # the watchdog's polls skip the fault point: invocation counting
    # stays on the synchronous per-chunk schedule
    b2 = MemoryBudget(1000, rss_source=lambda: 1.0)
    faults.reset()
    assert b2.poll(fault_check=False) == "ok"
    faults.reset()


# ------------------------------------------------------ unit: spill files

def test_spill_roundtrip_and_unlink(tmp_path):
    payloads = [("seqs", b"ACGT" * 50), ("ovls", b"r0\t0\tt0\n")]
    path = budget.park_bytes(payloads, str(tmp_path), "chunk0")
    assert path is not None and os.path.exists(path)
    assert budget.load_spill(path) == payloads
    assert not os.path.exists(path)          # spill files are one-shot


def test_torn_spill_file_raises(tmp_path):
    path = budget.park_bytes([("seqs", b"A" * 200)], str(tmp_path), "c1")
    data = open(path, "rb").read()
    with open(path, "wb") as f:
        f.write(data[:-50])
    with pytest.raises(ValueError, match="torn spill"):
        budget.load_spill(path)


def test_working_set_parks_and_realizes_via_spill(tmp_path):
    ws = WorkingSet(2, b">r0\nACGT\n", b"@HD\nr0\t0\tt2\n",
                    "reads.fasta", "ovl.sam")
    assert ws.nbytes() > 0
    assert ws.park(str(tmp_path)) is True
    assert ws.parked() and ws.nbytes() == 0
    seqs_p, ovls_p = ws.realize(str(tmp_path))
    assert open(seqs_p, "rb").read() == b">r0\nACGT\n"
    assert open(ovls_p, "rb").read() == b"@HD\nr0\t0\tt2\n"
    assert not ws.parked()                   # spill consumed on realize


def test_mem_spill_fault_aborts_park_keeps_buffers(tmp_path, monkeypatch):
    monkeypatch.setenv("RACON_TPU_FAULT", "mem.spill")
    faults.reset()
    ws = WorkingSet(0, b"seqbytes", b"ovlbytes", "r.fasta", "o.sam")
    assert ws.park(str(tmp_path)) is False   # park aborted, not the run
    assert not ws.parked() and ws.nbytes() > 0
    seqs_p, ovls_p = ws.realize(str(tmp_path))
    assert open(seqs_p, "rb").read() == b"seqbytes"
    assert open(ovls_p, "rb").read() == b"ovlbytes"
    faults.reset()


# ------------------------------------------------- unit: byte-range index

def test_stream_index_materializes_per_chunk_subsets(tmp_path):
    from racon_tpu.polisher import _split_fasta

    paths = _write_dataset(tmp_path)
    chunks = _split_fasta(paths[2], 3, str(tmp_path))
    assert chunks is not None and len(chunks) == 3
    idx = StreamIndex(paths[0], paths[1], chunks, str(tmp_path))
    assert idx.fmt == "sam"
    assert all(idx.torn(ci) is None for ci in range(3))
    ws = idx.materialize(1)
    seqs_p, ovls_p = ws.realize(str(tmp_path))
    seqs = open(seqs_p, "rb").read()
    ovls = open(ovls_p, "rb").read()
    # the working set is O(chunk): chunk 1 sees only its own records
    assert b">t1r0" in seqs
    assert b">t0r" not in seqs and b">t2r" not in seqs
    assert ovls.startswith(b"@HD")           # headers copied per chunk
    for line in ovls.splitlines()[1:]:
        assert line.split(b"\t")[2] == b"t1"


# ------------------------------------- e2e: streaming polisher (in-process)

def test_streaming_byte_identical_to_in_memory(tmp_path, monkeypatch):
    paths = _write_dataset(tmp_path)
    oracle = _oracle(paths)
    seq_res, _ = _tpu_run(paths, monkeypatch, {})
    stream_res, p = _tpu_run(paths, monkeypatch,
                             {"RACON_TPU_STREAM_INPUT": "1"})
    assert p._stream_index is not None, "3-contig FASTA target must stream"
    assert stream_res == seq_res == oracle
    d = _assert_report_sums(p)
    mem = d["phases"]["memory"]["extra"]
    assert mem["streamed"] is True
    assert mem["budget_mb"] == 0             # streaming forced, unbudgeted
    assert mem["pressure_level"] == "ok"
    assert mem["peak_rss_mb"] > 0
    assert d["phases"]["memory"]["quarantined"] == []


def test_tight_budget_collapses_batched_to_stream_sequential(
        tmp_path, monkeypatch):
    """RACON_TPU_MEM_BUDGET_MB=64 on a JAX-loaded process: the hard
    watermark latches on the first synchronous poll, streaming
    auto-arms, working sets round-trip through the spill file, the
    batch executor takes the batched -> stream-sequential lattice edge
    — and the output is still byte-identical."""
    paths = _write_dataset(tmp_path)
    oracle = _oracle(paths)
    res, p = _tpu_run(paths, monkeypatch,
                      {"RACON_TPU_MEM_BUDGET_MB": "64"})
    assert p._stream, "a memory budget must auto-arm streaming input"
    assert res == oracle
    d = _assert_report_sums(p)
    mem = d["phases"]["memory"]["extra"]
    assert mem["budget_mb"] == 64
    assert mem["pressure_level"] == "hard"
    assert mem["peak_rss_mb"] > 64
    assert ("batched", "stream-sequential") in _edges(d)


def test_pipelined_hard_watermark_collapses_to_sequential(
        tmp_path, monkeypatch):
    paths = _write_dataset(tmp_path)
    oracle = _oracle(paths)
    res, p = _tpu_run(paths, monkeypatch,
                      {"RACON_TPU_PIPELINE_PHASES": "1",
                       "RACON_TPU_MEM_BUDGET_MB": "64"})
    assert p._pipelined and p._stream
    assert res == oracle
    d = _assert_report_sums(p)
    # the align worker stopped running ahead of POA and the pipeline
    # degradation was recorded exactly once
    mem_edges = [(g["from"], g["to"])
                 for g in d["phases"]["memory"].get("degradations", [])]
    assert mem_edges.count(("pipelined", "sequential")) == 1


def test_mem_pressure_drill_byte_identical(tmp_path, monkeypatch):
    """The deterministic pressure drill: a huge budget keeps real RSS
    classified ok, the injected mem.pressure raise forces the hard
    breach anyway, and the degraded schedule changes nothing in the
    output."""
    paths = _write_dataset(tmp_path)
    oracle = _oracle(paths)
    res, p = _tpu_run(paths, monkeypatch,
                      {"RACON_TPU_MEM_BUDGET_MB": "1000000",
                       "RACON_TPU_FAULT": "mem.pressure"})
    assert res == oracle
    d = _assert_report_sums(p)
    assert ("batched", "stream-sequential") in _edges(d)


def test_mem_spill_drill_byte_identical(tmp_path, monkeypatch):
    """mem.spill aborts every park under a tight budget: the working
    sets just stay in memory, and the run ends byte-identical."""
    paths = _write_dataset(tmp_path)
    oracle = _oracle(paths)
    res, p = _tpu_run(paths, monkeypatch,
                      {"RACON_TPU_MEM_BUDGET_MB": "64",
                       "RACON_TPU_FAULT": "mem.spill"})
    assert res == oracle
    d = _assert_report_sums(p)
    assert d["phases"]["memory"]["quarantined"] == []


# --------------------------------------------- e2e: torn-input quarantine

def test_truncated_overlap_tail_quarantines_chunk_not_run(
        tmp_path, monkeypatch):
    """A SAM file torn mid-record: the owning chunk is quarantined in
    the RunReport and polishes from the working set indexed before the
    tear; identical reads make even that output byte-identical."""
    paths = _write_dataset(tmp_path)
    oracle = _oracle(paths)
    data = open(paths[1], "rb").read()
    with open(paths[1], "wb") as f:
        f.write(data[:-30])                  # cut into the last record
    res, p = _tpu_run(paths, monkeypatch, {"RACON_TPU_STREAM_INPUT": "1"})
    assert p._stream_index is not None
    d = _assert_report_sums(p)
    assert d["phases"]["memory"]["quarantined"], \
        "torn overlap tail must quarantine its chunk"
    # chunk 2 kept t2r0..t2r2 (indexed before the tear) — with
    # identical reads every consensus is still exactly the target;
    # only t2's RC:i header tag honestly reports one read fewer
    assert [s for _, s in res] == [s for _, s in oracle]
    assert [n for n, _ in res[:2]] == [n for n, _ in oracle[:2]]
    assert res[2][0] == oracle[2][0].replace("RC:i:4", "RC:i:3")


def test_gzip_corrupt_reads_tail_quarantines_chunk(tmp_path, monkeypatch):
    """A gzip-corrupt reads tail: decompression recovers the prefix,
    the chunk whose referenced read the tear swallowed is quarantined,
    and the run — which the in-memory path would hand straight to the
    native parser — completes."""
    paths = _write_dataset(tmp_path)
    oracle = _oracle(paths)
    raw = open(paths[0], "rb").read()
    cut = raw.rindex(b">t2r3")
    gz = tmp_path / "reads.fasta.gz"
    # a valid member holding everything before t2r3, then a member with
    # a corrupt header: decompression yields exactly the prefix + error
    gz.write_bytes(gzip.compress(raw[:cut]) + b"\x1f\x8b" + b"\x00" * 20)
    paths = (str(gz), paths[1], paths[2])
    res, p = _tpu_run(paths, monkeypatch, {"RACON_TPU_STREAM_INPUT": "1"})
    assert p._stream_index is not None
    d = _assert_report_sums(p)
    assert d["phases"]["memory"]["quarantined"], \
        "swallowed read must quarantine its chunk"
    # every contig still polishes to the exact target; t2's RC:i tag
    # honestly reports the read the tear swallowed
    assert [s for _, s in res] == [s for _, s in oracle]
    assert [n for n, _ in res[:2]] == [n for n, _ in oracle[:2]]
    assert res[2][0] == oracle[2][0].replace("RC:i:4", "RC:i:3")


# ------------------------------------------- e2e: mem.oom fleet OOM-kill

def test_mem_oom_kill_mid_fleet_resumes_byte_identical(
        tmp_path, monkeypatch):
    """mem.oom:kill=1 is a real OOM-style SIGKILL of worker 0 at the
    top of its first chunk polish: the EOF expires the lease, the chunk
    re-dispatches, and the gathered output is byte-identical.  The
    fault fires before the chunk journals anything, so — unlike the
    worker.result drill — resume may legitimately replay zero windows."""
    from racon_tpu.distrib import Coordinator
    from test_distrib import hold_queue_for_worker

    paths = _write_dataset(tmp_path, n_targets=6)
    oracle_b = "".join(
        f">{n}\n{s}\n" for n, s in _oracle(paths)).encode()
    monkeypatch.setenv("RACON_TPU_FAULT", "mem.oom:kill=1:count=1")
    monkeypatch.setenv("RACON_TPU_DISTRIB_FAULT_WORKER", "0")
    hold_queue_for_worker(monkeypatch, 0)
    coord = Coordinator(paths[0], paths[1], paths[2],
                        str(tmp_path / "coord"), args=dict(_ARGS),
                        backend="cpu", workers=3,
                        report_path=str(tmp_path / "report.json"))
    out = str(tmp_path / "polished.fasta")
    result = coord.run(out, timeout=180)
    assert open(out, "rb").read() == oracle_b
    assert result["served"]["fleet"] == result["chunks"]
    assert result["counters"]["workers_dead"] == 1
    assert result["counters"]["redispatches"] >= 1


# ------------------------------------- admission ladder: the memory rung

class _FakeSession:
    backend = "tpu"

    def __init__(self, workdir):
        self.workdir = str(workdir)
        os.makedirs(os.path.join(self.workdir, "jobs"), exist_ok=True)

    def job_dir(self, job_id):
        return os.path.join(self.workdir, "jobs", job_id)

    def stats(self):
        return {}


def _scheduler(tmp_path):
    from racon_tpu.serve import Scheduler

    return Scheduler(_FakeSession(tmp_path / "state"), queue_depth=100,
                     max_jobs=100, window_budget=12, tenant_quota=0)


def test_admission_hard_memory_rejects(tmp_path):
    from racon_tpu.serve import AdmissionError, JobSpec

    paths = _write_dataset(tmp_path)
    sched = _scheduler(tmp_path)
    sched.memory_source = lambda: "hard"     # injectable sampler seam
    with pytest.raises(AdmissionError, match="memory pressure"):
        sched.submit(JobSpec(*paths, args=dict(_ARGS), submitter="acme"))
    assert sched.admission["rejected_memory"] == 1
    assert not sched._queues["device"] and not sched._queues["host"]


def test_admission_soft_memory_sheds_to_host_lane(tmp_path):
    from racon_tpu.serve import JobSpec

    paths = _write_dataset(tmp_path)
    sched = _scheduler(tmp_path)
    sched.memory_source = lambda: "soft"
    sched.submit(JobSpec(*paths, args=dict(_ARGS), submitter="acme"))
    assert len(sched._queues["host"]) == 1
    assert not sched._queues["device"]
    assert sched.admission["shed_memory"] == 1
    job = next(iter(sched._jobs.values()))
    assert job.demotions
    assert job.demotions[0]["cause"].startswith("shed (memory)")


# ------------------------------------------------- telemetry + obs fleet

def test_telemetry_tick_carries_rss_gauge():
    from racon_tpu import obs

    entry = obs.telemetry_tick(queue_depth=3)
    assert entry["queue_depth"] == 3
    assert entry["mem.rss_mb"] > 0.0


def test_obs_fleet_tracks_per_worker_peak_rss():
    from racon_tpu.obs.__main__ import fleet_breakdown

    doc = {"traceEvents": [
        {"name": "mem.rss", "ph": "i", "s": "t", "ts": 0, "pid": 7,
         "tid": 1, "args": {"rss_mb": 123.0, "chunk": 0}},
        {"name": "mem.rss", "ph": "i", "s": "t", "ts": 5, "pid": 7,
         "tid": 1, "args": {"rss_mb": 456.5, "chunk": 1}},
        {"name": "mem.rss", "ph": "i", "s": "t", "ts": 9, "pid": 7,
         "tid": 1, "args": {"rss_mb": "bogus"}},      # ignored, not fatal
    ]}
    b = fleet_breakdown(doc)
    assert not b["violations"]
    (p,) = b["processes"].values()
    assert p["peak_rss_mb"] == 456.5


# --------------------------------------------------- bench stream entry

def test_bench_stream_entry_normalizes_as_fixed_point():
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    try:
        from bench import mem_stamp, normalize_entry
    finally:
        sys.path.remove(root)
    from racon_tpu.obs import bench_track

    entry = {
        "metric": "stream: polished Mbp/sec (synthetic ONT 0.004 Mbp 6x, "
                  "SAM, w=100, streamed, end-to-end)",
        "value": 0.0005, "unit": "Mbp/s", "vs_baseline": None,
        "cost_model": None, "pack_split": None, "serial_steps": None,
        "cells_banded": None, "band_hit_rate": None,
        "peak_rss_mb": 337.7, "budget_mb": 2048,
        "stream": {"contigs": 4, "streamed": True, "pressure_level": "ok",
                   "quarantined": 0, "degradations": 0},
        "mbp": 0.004, "input": "sam", "profile": "stream-ont",
    }
    assert normalize_entry(dict(entry)) == entry
    # stream entries form their own trend series for the regression gate
    assert (bench_track.series_key(entry)
            != bench_track.series_key(dict(entry, profile="ont")))
    # pre-budget entries recover the stamp from the embedded report...
    legacy = {k: v for k, v in entry.items()
              if k not in ("peak_rss_mb", "budget_mb", "stream")}
    legacy["report"] = {"memory": {"extra": {"peak_rss_mb": 300.5,
                                             "budget_mb": 1024}}}
    n = normalize_entry(legacy)
    assert n["peak_rss_mb"] == 300.5 and n["budget_mb"] == 1024
    # ...and entries with no memory accounting get explicit nulls
    legacy2 = {k: v for k, v in entry.items()
               if k not in ("peak_rss_mb", "budget_mb", "stream")}
    norm = normalize_entry(legacy2)
    assert norm["peak_rss_mb"] is None and norm["budget_mb"] is None
    assert mem_stamp({"memory": {"extra": {"peak_rss_mb": 1.0,
                                           "budget_mb": 2}}}) == (1.0, 2)
    assert mem_stamp(None) == (None, None)
