"""Distributed polishing (racon_tpu/distrib): coordinator/worker fleet.

Covers the wire protocol, lease bookkeeping (expiry, backoff, journal
ownership, speculation, duplicate discard) as units on a Coordinator
that never spawns processes, and the real multi-process paths as
integration tests: 2-process byte-identity vs the serial oracle (the
ROADMAP #2 done-criterion), SIGKILL of a worker mid-chunk with journal
resume on re-dispatch, and fleet collapse degrading to the local rung
with the demotion recorded in the run report.

Datasets follow tests/test_serve.py: identical reads, so every serving
mix reproduces the target exactly and outputs are byte-comparable.
"""

import io
import json
import os
import random
import threading
import time

import pytest

import racon_tpu
from racon_tpu.distrib import Coordinator
from racon_tpu.distrib import common as dcommon
from racon_tpu.distrib import worker as dworker
from racon_tpu.resilience import faults
from racon_tpu.serve.protocol import MAX_LINE, read_message, write_message

_ARGS = dict(window_length=100, quality_threshold=10, error_threshold=0.3,
             match=5, mismatch=-4, gap=-8, num_threads=1)


def _write_dataset(tmp_path, n_targets=3, n_reads=4):
    rng = random.Random(11)
    with open(tmp_path / "targets.fasta", "w") as tf, \
            open(tmp_path / "reads.fasta", "w") as rf, \
            open(tmp_path / "ovl.sam", "w") as of:
        of.write("@HD\tVN:1.6\n")
        for t in range(n_targets):
            seq = "".join(rng.choice("ACGT") for _ in range(200))
            tf.write(f">t{t}\n{seq}\n")
            for i in range(n_reads):
                rf.write(f">t{t}r{i}\n{seq}\n")
                of.write(f"t{t}r{i}\t0\tt{t}\t1\t60\t200M\t*\t0\t0\t"
                         f"{seq}\t*\n")
    return (str(tmp_path / "reads.fasta"), str(tmp_path / "ovl.sam"),
            str(tmp_path / "targets.fasta"))


def _oracle_bytes(paths):
    p = racon_tpu.create_polisher(*paths, backend="cpu", **_ARGS)
    p.initialize()
    return "".join(f">{n}\n{d}\n" for n, d in p.polish(True)).encode()


def _coordinator(paths, tmp_path, **over):
    over.setdefault("args", dict(_ARGS))
    over.setdefault("backend", "cpu")
    return Coordinator(paths[0], paths[1], paths[2],
                       str(tmp_path / "coord"), **over)


def hold_queue_for_worker(monkeypatch, worker=0):
    """The kill drills scope their fault to one worker
    (RACON_TPU_DISTRIB_FAULT_WORKER), and a fleet's workers come up in
    whatever order the machine's load gives: the others can drain a
    queue of millisecond chunks before that worker has said hello, and
    then nobody dies.  Hold the queue until it holds a chunk: every
    other worker is told to wait, as it is whenever nothing is
    eligible."""
    real_fetch = Coordinator._fetch
    holds_a_chunk = threading.Event()

    def fetch(self, w):
        if w != worker and not holds_a_chunk.is_set():
            return {"ok": True, "wait": True, "poll_s": 0.05}
        resp = real_fetch(self, w)
        if w == worker and "chunk" in resp:
            holds_a_chunk.set()
        return resp

    monkeypatch.setattr(Coordinator, "_fetch", fetch)


# ------------------------------------------------------------ wire protocol

def test_protocol_roundtrip():
    buf = io.BytesIO()
    write_message(buf, {"op": "ping", "n": 1})
    buf.seek(0)
    assert read_message(buf) == {"op": "ping", "n": 1}
    assert read_message(buf) is None                     # clean EOF
    with pytest.raises(ValueError, match="JSON object"):
        read_message(io.BytesIO(b"[1, 2]\n"))
    big = b"x" * (MAX_LINE + 10)
    with pytest.raises((ValueError, json.JSONDecodeError)):
        read_message(io.BytesIO(big))


def test_rpc_raises_on_eof_and_not_ok():
    class _Pipe(io.BytesIO):
        def __init__(self, reply=b""):
            super().__init__(reply)

        def write(self, data):       # request bytes are discarded
            return len(data)

        def flush(self):
            pass

    with pytest.raises(dcommon.WireError, match="closed"):
        dcommon.rpc(_Pipe(), {"op": "fetch"})
    with pytest.raises(dcommon.WireError, match="nope"):
        dcommon.rpc(_Pipe(b'{"ok": false, "error": "nope"}\n'),
                    {"op": "fetch"})


def test_knob_defaults(monkeypatch):
    assert dcommon.distrib_workers() == 2
    assert dcommon.distrib_lease_ttl() == 10.0
    assert dcommon.distrib_heartbeat(9.0) == pytest.approx(3.0)
    monkeypatch.setenv("RACON_TPU_DISTRIB_HEARTBEAT", "0.5")
    assert dcommon.distrib_heartbeat(9.0) == 0.5
    assert dcommon.distrib_retry_base() == 0.25
    assert dcommon.distrib_max_retries() == 3
    assert dcommon.distrib_speculate() == 2.5
    assert dcommon.distrib_fault_worker() == 0


# ------------------------------------------------- coordinator lease units

def test_fault_points_registered():
    assert {"worker.spawn", "worker.heartbeat",
            "worker.result"} <= faults.KNOWN_POINTS
    # the grammar parses the distributed points like any other
    specs = faults.parse_spec("worker.result:kill=1:count=1,"
                              "worker.heartbeat:raise=RuntimeError")
    assert specs[0].point == "worker.result" and specs[0].kill
    assert specs[1].raise_name == "RuntimeError"


def test_assign_expiry_backoff_and_journal_ownership(tmp_path):
    paths = _write_dataset(tmp_path)
    coord = _coordinator(paths, tmp_path, workers=2, lease_ttl=0.01)
    os.makedirs(coord.workdir, exist_ok=True)
    coord._layout()
    assert len(coord.chunks) == 3        # one per contig

    resp = coord._fetch(worker=0)
    a = resp["chunk"]
    c = coord.chunks[a["index"]]
    assert c.state == "running" and c.journal_held
    assert a["journal"] == c.journal     # first attempt holds canonical

    time.sleep(0.05)                     # outlive the 10ms TTL
    coord._expire_leases()
    assert c.state == "pending" and not c.leases
    assert c.journal_held                # holder may still be alive
    assert c.next_eligible > time.monotonic() - 0.01
    assert coord.counters["lease_expired"] == 1
    first_eligible = c.next_eligible

    # a second failure backs off further (exponential)
    with coord._cv:
        coord._fail_chunk(c, RuntimeError("again"))
    assert c.next_eligible >= first_eligible

    # re-dispatch while the journal is held gets a side journal: the
    # TTL-expired holder may still be alive and writing, so two live
    # writers never share a journal file
    c.next_eligible = 0.0
    resp2 = coord._fetch(worker=1)
    a2 = resp2["chunk"]
    assert a2["index"] == a["index"] and a2["journal"] != c.journal
    # death of the SIDE holder does not release the canonical journal
    coord._worker_dead(1, "test")
    assert c.journal_held
    assert c.state == "pending"


def test_worker_death_releases_canonical_journal(tmp_path):
    paths = _write_dataset(tmp_path)
    coord = _coordinator(paths, tmp_path, workers=1)
    os.makedirs(coord.workdir, exist_ok=True)
    coord._layout()
    a = coord._fetch(worker=0)["chunk"]
    c = coord.chunks[a["index"]]
    assert c.journal_held
    # confirmed death (EOF / process exit) frees the canonical journal
    # so the re-dispatch resumes it instead of recomputing
    coord._worker_dead(0, "sigkill")
    assert not c.journal_held
    assert c.state == "pending"
    assert coord.counters["workers_dead"] == 1
    assert coord.counters["lease_expired"] == 1
    c.next_eligible = 0.0      # skip the backoff for the test
    b = coord._fetch(worker=1)
    assert b["chunk"]["index"] == c.index
    assert b["chunk"]["journal"] == c.journal


def test_redispatch_prefers_untried_worker(tmp_path):
    paths = _write_dataset(tmp_path)
    coord = _coordinator(paths, tmp_path, workers=2)
    os.makedirs(coord.workdir, exist_ok=True)
    coord._layout()
    a = coord._fetch(worker=0)["chunk"]
    chunk = coord.chunks[a["index"]]
    with coord._cv:
        chunk.leases.clear()
        coord._fail_chunk(chunk, RuntimeError("boom"))
        chunk.next_eligible = 0.0
    # worker 0 fetching again gets a chunk it has NOT tried first
    b = coord._fetch(worker=0)["chunk"]
    assert b["index"] != a["index"]


def test_first_result_wins_duplicate_discarded(tmp_path):
    paths = _write_dataset(tmp_path)
    coord = _coordinator(paths, tmp_path, workers=2)
    os.makedirs(coord.workdir, exist_ok=True)
    coord._layout()
    a1 = coord._fetch(worker=0)["chunk"]
    c = coord.chunks[a1["index"]]
    c.next_eligible = 0.0
    with coord._cv:
        coord.chunks[a1["index"]].leases.clear()
        c.state = "pending"
    a2 = coord._fetch(worker=1)["chunk"]
    assert a2["index"] == a1["index"]

    r1 = coord._result({"worker": 1, "chunk": a2["index"],
                        "attempt": a2["attempt"], "output": "one.fasta",
                        "stats": {"journal_replayed": 2}})
    assert r1["accepted"] and c.state == "done"
    r2 = coord._result({"worker": 0, "chunk": a1["index"],
                        "attempt": a1["attempt"], "output": "two.fasta",
                        "stats": {}})
    assert not r2["accepted"]
    assert c.output == "one.fasta"       # deterministic: first wins
    assert coord.counters["duplicates"] == 1
    assert coord.counters["journal_replayed"] == 2
    assert coord.phase.served["fleet"] == 1


def test_speculative_dispatch_on_straggler(tmp_path):
    paths = _write_dataset(tmp_path)
    coord = _coordinator(paths, tmp_path, workers=2)
    os.makedirs(coord.workdir, exist_ok=True)
    coord._layout()
    # drain the pending queue onto worker 0
    assigned = [coord._fetch(worker=0)["chunk"] for _ in range(3)]
    assert all("index" in a for a in assigned)
    assert coord._fetch(worker=1).get("wait")    # nothing completed yet

    # complete two chunks quickly; the third becomes the straggler
    for a in assigned[:2]:
        coord._result({"worker": 0, "chunk": a["index"],
                       "attempt": a["attempt"],
                       "output": f"o{a['index']}.fasta", "stats": {}})
    lag = coord.chunks[assigned[2]["index"]]
    for lease in lag.leases.values():
        lease.t_start -= 60.0            # way past factor x median
    spec = coord._fetch(worker=1)
    assert "chunk" in spec and spec["chunk"]["index"] == lag.index
    assert coord.counters["speculative"] == 1
    assert len(lag.leases) == 2
    # worker 1 already tried it now; no third duplicate for worker 1
    assert coord._fetch(worker=1).get("wait")


def test_heartbeat_renews_and_cancels(tmp_path):
    paths = _write_dataset(tmp_path)
    coord = _coordinator(paths, tmp_path, workers=1, lease_ttl=5.0)
    os.makedirs(coord.workdir, exist_ok=True)
    coord._layout()
    a = coord._fetch(worker=0)["chunk"]
    c = coord.chunks[a["index"]]
    old = c.leases[a["attempt"]].deadline
    time.sleep(0.01)
    hb = coord._heartbeat(0, a["index"], a["attempt"])
    assert not hb["cancel"]
    assert c.leases[a["attempt"]].deadline > old
    # a superseded attempt is told to stand down
    assert coord._heartbeat(0, a["index"], a["attempt"] + 7)["cancel"]


def test_heartbeat_fault_stops_renewal(monkeypatch):
    """worker.heartbeat:raise silently ends the renewal loop — the
    heartbeat-loss failure mode, exercised without any socket."""
    monkeypatch.setenv("RACON_TPU_FAULT",
                       "worker.heartbeat:raise=RuntimeError")
    faults.reset()
    stop = threading.Event()
    t0 = time.monotonic()
    # f=None: the injected raise fires before the wire is ever touched
    dworker._heartbeat_loop(None, 0, 0, 1, 0.01, stop)
    assert time.monotonic() - t0 < 5.0
    faults.reset()


def test_bench_distrib_entry_normalizes_as_fixed_point():
    """The distrib bench entry must round-trip normalize_entry unchanged
    and form its own bench-history series (profile distrib-*)."""
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    try:
        from bench import normalize_entry
    finally:
        sys.path.remove(root)
    from racon_tpu.obs import bench_track

    entry = {
        "metric": "distrib: polished Mbp/sec (synthetic ONT 0.5 Mbp 30x, "
                  "PAF, w=500, 3 workers/6 chunks, end-to-end)",
        "value": 2.34, "unit": "Mbp/s", "vs_baseline": None,
        "cost_model": None, "pack_split": None, "serial_steps": None,
        "cells_banded": None, "band_hit_rate": None,
        "peak_rss_mb": None, "budget_mb": None,
        "distrib": {"workers": 3, "chunks": 6,
                    "served": {"fleet": 6, "local": 0},
                    "redispatches": 1, "journal_replayed": 2},
        "fleet": {"workers": {"0": {"chunks": 6}},
                  "queueing_p95_s": 0.01, "staleness_max_s": 0.2},
        "pool": {"min": 3, "max": 3, "timeline": [[0.0, 3]]},
        "ledger": {"stage_s": {"align": 0.2, "poa": 0.5}},
        "slo": None,
        "mbp": 0.5, "input": "paf", "profile": "distrib-ont",
    }
    assert normalize_entry(dict(entry)) == entry
    plain = dict(entry, profile="ont")
    assert (bench_track.series_key(entry)
            != bench_track.series_key(plain))
    # pre-telemetry distrib entries get the explicit "not scraped" null
    legacy = {k: v for k, v in entry.items() if k != "fleet"}
    assert normalize_entry(legacy)["fleet"] is None
    # pre-elastic-pool entries get the explicit "no timeline" null
    legacy = {k: v for k, v in entry.items() if k != "pool"}
    assert normalize_entry(legacy)["pool"] is None
    # pre-ledger / pre-SLO entries get the explicit nulls too
    legacy = {k: v for k, v in entry.items() if k not in ("ledger", "slo")}
    normalized = normalize_entry(legacy)
    assert normalized["ledger"] is None and normalized["slo"] is None


# ------------------------------------------------ integration: real fleets

def test_two_process_byte_identity(tmp_path):
    """ROADMAP #2 done-criterion: a 2-process localhost fleet produces
    chunk-order-stable output byte-identical to the single-process
    oracle."""
    paths = _write_dataset(tmp_path)
    oracle = _oracle_bytes(paths)
    coord = _coordinator(paths, tmp_path, workers=2,
                         report_path=str(tmp_path / "report.json"))
    out = str(tmp_path / "polished.fasta")
    result = coord.run(out, timeout=180)
    assert open(out, "rb").read() == oracle
    assert result["served"] == {"fleet": 3, "local": 0}
    assert result["counters"].get("workers_dead", 0) == 0
    assert not result["degradations"]
    rep = json.load(open(tmp_path / "report.json"))
    assert rep["phases"]["distrib"]["served"]["fleet"] == 3


def test_worker_sigkill_redispatch_resumes(tmp_path, monkeypatch):
    """The chaos acceptance path: worker 0 is SIGKILLed after its first
    chunk is fully journaled but before the result is delivered
    (worker.result:kill=1).  The EOF expires its lease, the chunk
    re-dispatches to a different worker, the re-run resumes the journal
    (replayed > 0), and the gathered output is still byte-identical.

    Six chunks across three workers, and the queue held until worker 0
    has fetched one (`hold_queue_for_worker`)."""
    paths = _write_dataset(tmp_path, n_targets=6)
    oracle = _oracle_bytes(paths)
    monkeypatch.setenv("RACON_TPU_FAULT", "worker.result:kill=1:count=1")
    monkeypatch.setenv("RACON_TPU_DISTRIB_FAULT_WORKER", "0")
    hold_queue_for_worker(monkeypatch, 0)
    coord = _coordinator(paths, tmp_path, workers=3,
                         report_path=str(tmp_path / "report.json"))
    out = str(tmp_path / "polished.fasta")
    result = coord.run(out, timeout=180)
    assert open(out, "rb").read() == oracle
    assert result["served"]["fleet"] == result["chunks"]
    assert result["served"]["local"] == 0
    assert result["counters"]["workers_dead"] == 1
    assert result["counters"]["redispatches"] >= 1
    assert result["counters"]["journal_replayed"] > 0
    rep = json.load(open(tmp_path / "report.json"))
    extra = rep["phases"]["distrib"]["extra"]
    assert extra["journal_replayed"] > 0


def test_fleet_collapse_degrades_to_local(tmp_path, monkeypatch):
    """Every spawn fails (worker.spawn armed in the coordinator): the
    fleet is empty, the run degrades to the local rung, finishes, and
    the demotion lands in the RunReport."""
    paths = _write_dataset(tmp_path)
    oracle = _oracle_bytes(paths)
    monkeypatch.setenv("RACON_TPU_FAULT", "worker.spawn:raise=RuntimeError")
    coord = _coordinator(paths, tmp_path, workers=2,
                         report_path=str(tmp_path / "report.json"))
    out = str(tmp_path / "polished.fasta")
    result = coord.run(out, timeout=180)
    assert open(out, "rb").read() == oracle
    assert result["served"] == {"fleet": 0, "local": 3}
    assert len(result["degradations"]) == 1
    assert result["degradations"][0]["from"] == "fleet"
    assert result["degradations"][0]["to"] == "local"
    rep = json.load(open(tmp_path / "report.json"))
    assert rep["phases"]["distrib"]["degradations"][0]["to"] == "local"
    assert rep["phases"]["distrib"]["extra"]["spawn_failures"] == 2


def test_cli_distrib_subcommand(tmp_path):
    """`racon-tpu distrib` end-to-end through the CLI seam: output file,
    trace validated by the obs schema checker, exit 0."""
    import subprocess
    import sys

    paths = _write_dataset(tmp_path)
    oracle = _oracle_bytes(paths)
    out = str(tmp_path / "cli.fasta")
    trace = str(tmp_path / "trace.json")
    rc = subprocess.call(
        [sys.executable, "-m", "racon_tpu.cli", "distrib",
         "-w", "100", "-m", "5", "-x", "-4", "-g", "-8",
         "--workers", "2", "--state-dir", str(tmp_path / "state"),
         "-o", out, "--trace", trace, "--timeout", "180",
         paths[0], paths[1], paths[2]])
    assert rc == 0
    assert open(out, "rb").read() == oracle
    rc = subprocess.call([sys.executable, "-m", "racon_tpu.obs",
                          "--validate", trace])
    assert rc == 0


# --------------------------------------------- fleet tracing + flight

def test_fleet_trace_merges_validates_and_parents(tmp_path):
    """Tentpole acceptance: a traced 3-worker run leaves a coordinator
    trace (with absorbed worker shipments) plus per-chunk worker traces;
    `obs merge` folds them into one timeline that passes `--validate`,
    and `obs fleet` proves every `distrib.chunk` span is parented under
    a coordinator `distrib.dispatch` span via one shared trace id —
    while the fleet served-sum still matches the serial oracle's
    output byte-for-byte."""
    import glob
    import subprocess
    import sys

    paths = _write_dataset(tmp_path)
    oracle = _oracle_bytes(paths)
    trace = str(tmp_path / "coord" / "trace.json")
    coord = _coordinator(paths, tmp_path, workers=3, trace_path=trace)
    out = str(tmp_path / "polished.fasta")
    result = coord.run(out, timeout=180)
    assert open(out, "rb").read() == oracle
    assert sum(result["served"].values()) == result["chunks"]

    # the coordinator absorbed worker span shipments into its own trace
    assert result["counters"].get("obs_events_absorbed", 0) > 0
    # live-telemetry aggregates rode back in the result
    tel = result["telemetry"]
    # which workers win chunks is a race (on a loaded machine the first
    # one up can drain the queue before the others connect): every
    # reporting worker is one of ours and together they served it all
    assert tel["workers"] and set(tel["workers"]) <= {"0", "1", "2"}
    for ws in tel["workers"].values():
        assert ws["chunks"] >= 1
        assert ws["kernel_wall_s"] >= 0.0
    assert (sum(ws["chunks"] for ws in tel["workers"].values())
            >= result["chunks"])
    assert tel["queueing_p95_s"] is not None

    worker_traces = sorted(glob.glob(
        str(tmp_path / "coord" / "chunks" / "*" / "trace.a*.json")))
    assert len(worker_traces) == result["chunks"]
    merged = str(tmp_path / "merged.json")
    rc = subprocess.call([sys.executable, "-m", "racon_tpu.obs", "merge",
                          "--out", merged, trace] + worker_traces)
    assert rc == 0
    rc = subprocess.call([sys.executable, "-m", "racon_tpu.obs",
                          "--validate", merged])
    assert rc == 0
    r = subprocess.run([sys.executable, "-m", "racon_tpu.obs", "fleet",
                        merged, "--json"], capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
    b = json.loads(r.stdout)
    assert not b["violations"]
    assert len(b["trace_ids"]) == 1            # one fleet run, one trace
    roles = {p["role"] for p in b["processes"].values()}
    assert "coordinator" in roles
    assert any(r and r.startswith("worker") for r in roles)
    chunks = sum(p["chunks"] for p in b["processes"].values())
    assert chunks >= result["chunks"]          # every chunk span present


def test_fleet_breakdown_flags_dangling_parent(tmp_path):
    """`obs fleet` exit-1 contract: a chunk span whose parent matches no
    dispatch span id is a causality violation, not a rendering quirk."""
    import subprocess
    import sys

    doc = {"traceEvents": [
        {"name": "distrib.dispatch", "ph": "i", "s": "t", "ts": 0,
         "pid": 1, "tid": 1,
         "args": {"span_id": "aabbccdd", "trace_id": "f" * 16}},
        {"name": "distrib.chunk", "ph": "X", "ts": 5, "dur": 10,
         "pid": 2, "tid": 1,
         "args": {"parent": "deadbeef", "trace_id": "f" * 16}},
    ]}
    path = str(tmp_path / "bad.json")
    json.dump(doc, open(path, "w"))
    r = subprocess.run([sys.executable, "-m", "racon_tpu.obs", "fleet",
                        path], capture_output=True, text=True)
    assert r.returncode == 1
    assert "deadbeef" in r.stderr


def test_sigkilled_worker_leaves_flight_dump(tmp_path, monkeypatch):
    """Tentpole acceptance: worker 0 SIGKILLed mid-chunk (worker.result
    kill fault) leaves a parseable flight-recorder dump in its chunk
    directory — written *before* the uncatchable signal — and the
    coordinator's RunReport references it."""
    import glob

    paths = _write_dataset(tmp_path, n_targets=6)
    oracle = _oracle_bytes(paths)
    monkeypatch.setenv("RACON_TPU_FAULT", "worker.result:kill=1:count=1")
    monkeypatch.setenv("RACON_TPU_DISTRIB_FAULT_WORKER", "0")
    hold_queue_for_worker(monkeypatch, 0)
    coord = _coordinator(paths, tmp_path, workers=3,
                         report_path=str(tmp_path / "report.json"))
    out = str(tmp_path / "polished.fasta")
    result = coord.run(out, timeout=180)
    assert open(out, "rb").read() == oracle
    assert result["counters"]["workers_dead"] == 1

    dumps = glob.glob(str(tmp_path / "coord" / "**" / "flight.*.json"),
                      recursive=True)
    kill_docs = []
    for p in dumps:
        doc = json.load(open(p))            # must parse — tmp+replace
        assert doc["clock"] == "monotonic"
        assert isinstance(doc["events"], list)
        if doc["reason"] == "fault_kill":
            kill_docs.append(doc)
    assert kill_docs, f"no fault_kill dump among {dumps}"
    assert kill_docs[0]["role"] == "worker0"
    # the ring caught the chunk in flight
    names = [e["name"] for e in kill_docs[0]["events"]]
    assert any(n.startswith("distrib.") or n == "fault.fired"
               for n in names)

    # the coordinator swept the dumps into the run report
    assert result["flight"], "coordinator run result references no dumps"
    rep = json.load(open(tmp_path / "report.json"))
    reasons = {d["reason"] for d in rep["flight"]}
    assert "fault_kill" in reasons
    assert all(d["path"] for d in rep["flight"])


def test_fleet_stats_scrapes_live_coordinator(tmp_path):
    """The deepened `stats` wire verb: while a fleet run is in flight, a
    one-shot `fleet_stats` scrape answers with chunk/lease/worker counts
    and the coordinator's telemetry ring."""
    import threading as _threading

    paths = _write_dataset(tmp_path, n_targets=6)
    coord = _coordinator(paths, tmp_path, workers=2)
    out = str(tmp_path / "polished.fasta")
    scraped = []

    def probe():
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            port = getattr(coord, "port", None)
            if port:
                try:
                    scraped.append(dcommon.fleet_stats(port, timeout=5.0))
                    return
                except (OSError, dcommon.WireError):
                    pass
            time.sleep(0.05)

    t = _threading.Thread(target=probe, name="loadtest-stats", daemon=True)
    t.start()
    coord.run(out, timeout=180)
    t.join(timeout=10)
    assert scraped, "stats probe never reached the coordinator"
    s = scraped[0]
    assert s["ok"] is True
    assert set(s["chunks"]) == {"pending", "running", "done"}
    assert "workers" in s and "staleness_s" in s
    assert isinstance(s["telemetry"], list)
