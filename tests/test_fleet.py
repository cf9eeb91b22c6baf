"""Elastic fleet (racon_tpu/fleet): tenant queues, the heartbeat clamp,
atomic window-budget admission under concurrent submits, the FleetPlane
dispatch core (affinity, cross-job stealing, priority, speculation
seams), the four control-plane fault points (pool.scale_up,
pool.scale_down, pool.steal, lease.reclaim), and the chaos acceptance
paths: a worker SIGKILLed mid-chunk recovers in-process, and a daemon
SIGKILLed mid-resize re-queues its unfinished jobs on restart with
journals turning the re-runs into byte-identical resumes.

Conventions follow tests/test_serve.py: identical-read datasets (every
serving mix reproduces the target exactly, so outputs are
byte-comparable to the CpuPolisher oracle) and cpu-backend fleets (the
workers run the host-oracle path — the fleet's scaling axis is
processes, not kernels).
"""

import glob
import json
import os
import random
import subprocess
import sys
import threading
import time

import pytest

import racon_tpu
from racon_tpu.distrib.common import HEARTBEAT_FLOOR, distrib_heartbeat
from racon_tpu.fleet.pool import ElasticPool
from racon_tpu.fleet.queues import TenantQueues
from racon_tpu.serve import (AdmissionError, JobSpec, Scheduler,
                             ServeClient, ServeDaemon)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_ARGS = dict(window_length=100, quality_threshold=10, error_threshold=0.3,
             match=5, mismatch=-4, gap=-8, num_threads=1)

_FAST_ENV = {"RACON_TPU_PALLAS": "0", "RACON_TPU_BATCH_WINDOWS": "8"}


def _write_dataset(tmp_path, n_targets=3, n_reads=4, seed=11):
    rng = random.Random(seed)
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


def _oracle_fasta(paths):
    p = racon_tpu.create_polisher(*paths, backend="cpu", **_ARGS)
    p.initialize()
    return "".join(f">{n}\n{d}\n" for n, d in p.polish(True))


# ------------------------------------------------------ unit: TenantQueues

def test_tenant_queues_rotation_priority_remove():
    q = TenantQueues()
    q.push("a", "a1")
    q.push("a", "a2")
    q.push("b", "b1")
    # round-robin among tenants at the same priority
    assert q.pop() == "a1"
    assert q.pop() == "b1"
    assert q.pop() == "a2"
    assert q.pop() is None
    # a higher priority outranks FIFO order and tenant rotation
    q.push("a", "lo", priority=0)
    q.push("b", "hi", priority=5)
    q.push("a", "hi2", priority=5)
    assert q.pop() == "hi"
    assert q.pop() == "hi2"
    assert q.pop() == "lo"
    # remove() unlinks a queued item (cancellation path)
    q.push("a", "x")
    q.push("a", "y")
    assert q.remove("a", "x") is True
    assert q.remove("a", "x") is False
    assert len(q) == 1 and q.queued_for("a") == 1
    assert q.per_tenant() == {"a": 1, "b": 0}
    assert q.pop() == "y"


# --------------------------------------- satellite: heartbeat floor clamp

def test_heartbeat_clamped_to_floor(monkeypatch):
    """Regression: RACON_TPU_DISTRIB_LEASE_TTL=0.01 must not busy-spin
    the renewal thread — TTL/3 clamps to the floor, and so does an
    explicit tiny RACON_TPU_DISTRIB_HEARTBEAT."""
    monkeypatch.delenv("RACON_TPU_DISTRIB_HEARTBEAT", raising=False)
    assert distrib_heartbeat(0.01) == HEARTBEAT_FLOOR
    assert distrib_heartbeat(3.0) == pytest.approx(1.0)
    monkeypatch.setenv("RACON_TPU_DISTRIB_HEARTBEAT", "0.001")
    assert distrib_heartbeat(0.01) == HEARTBEAT_FLOOR
    monkeypatch.setenv("RACON_TPU_DISTRIB_HEARTBEAT", "0.5")
    assert distrib_heartbeat(0.01) == pytest.approx(0.5)


# ------------------------------- satellite: atomic window-budget admission

class _FakeSession:
    backend = "tpu"

    def __init__(self, workdir):
        self.workdir = str(workdir)
        os.makedirs(os.path.join(self.workdir, "jobs"), exist_ok=True)

    def job_dir(self, job_id):
        return os.path.join(self.workdir, "jobs", job_id)

    def stats(self):
        return {}


def test_concurrent_submits_never_oversubscribe_budget(tmp_path):
    """Many threads race submit() against a device-lane window budget:
    the check-and-reserve under the scheduler lock must admit exactly
    budget//est jobs to the device lane and shed the rest — never two
    winners squeezed into the same headroom."""
    paths = _write_dataset(tmp_path)           # 3 contigs x 200bp: est=6
    sched = Scheduler(_FakeSession(tmp_path / "state"), queue_depth=100,
                      max_jobs=100, window_budget=12, tenant_quota=0)
    errors = []
    barrier = threading.Barrier(10)

    def one(i):
        try:
            barrier.wait()
            sched.submit(JobSpec(*paths, args=dict(_ARGS),
                                 submitter=f"t{i}"))
        except Exception as e:  # noqa: BLE001 — collected for the assert
            errors.append(e)

    threads = [threading.Thread(target=one, args=(i,)) for i in range(10)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    # est=6, budget=12: exactly 2 reserve the device lane, 8 shed
    assert sum(sched._reserved.values()) == 12
    assert len(sched._queues["device"]) == 2
    assert len(sched._queues["host"]) == 8
    assert sched.admission["shed"] == 8
    shed_jobs = [j for j in sched._jobs.values() if j.demotions]
    assert len(shed_jobs) == 8
    assert all("shed" in j.demotions[0]["cause"] for j in shed_jobs)


def test_tenant_quota_rejects_flooding_submitter(tmp_path):
    paths = _write_dataset(tmp_path)
    sched = Scheduler(_FakeSession(tmp_path / "state"), queue_depth=100,
                      max_jobs=100, window_budget=0, tenant_quota=1)
    sched.submit(JobSpec(*paths, args=dict(_ARGS), submitter="flood"))
    with pytest.raises(AdmissionError, match="tenant quota"):
        sched.submit(JobSpec(*paths, args=dict(_ARGS), submitter="flood"))
    sched.submit(JobSpec(*paths, args=dict(_ARGS), submitter="other"))
    assert sched.admission["rejected_quota"] == 1


# ------------------------------------- unit: FleetPlane dispatch (no pool)

def _plane(tmp_path, **over):
    """An unstarted plane: no sockets, no processes — _fetch/_result are
    driven directly, exactly what a worker's RPCs would do."""
    from racon_tpu.fleet.plane import FleetPlane
    kw = dict(workdir=str(tmp_path / "plane"), min_workers=0,
              max_workers=2, backend="cpu")
    kw.update(over)
    return FleetPlane(**kw)


def _submit(plane, tmp_path, job_id, tenant="acme", priority=0,
            on_done=None, n_targets=2):
    d = tmp_path / f"data-{job_id}"
    d.mkdir(exist_ok=True)
    paths = _write_dataset(d, n_targets=n_targets)
    wd = str(tmp_path / f"wd-{job_id}")
    return plane.submit_job(job_id, paths[0], paths[1], paths[2],
                            dict(_ARGS), False, "cpu", wd, tenant=tenant,
                            priority=priority, on_done=on_done)


def _deliver(plane, resp, worker=0, body=">x\nACGT\n"):
    """Deliver a fake result for an assignment response."""
    ch = resp["chunk"]
    with open(ch["output"], "w") as f:
        f.write(body)
    return plane._result({"worker": worker, "chunk": ch["index"],
                          "attempt": ch["attempt"], "output": ch["output"],
                          "stats": {}})


def test_plane_affinity_then_steal(tmp_path):
    plane = _plane(tmp_path)
    _submit(plane, tmp_path, "A", tenant="acme")
    _submit(plane, tmp_path, "B", tenant="bcorp")
    # worker 0's first two fetches serve job A (affinity: chunks of the
    # job it last served come first)
    r1 = plane._fetch(0)
    r2 = plane._fetch(0)
    assert {r1["chunk"]["index"], r2["chunk"]["index"]} == {0, 1}
    assert plane.counters.get("steals", 0) == 0
    # job A is live but starved; job B has eligible chunks: the steal
    r3 = plane._fetch(0)
    assert r3["chunk"]["index"] in (2, 3)
    assert plane.counters["steals"] == 1


def test_plane_steal_gate_and_fault(tmp_path, monkeypatch):
    plane = _plane(tmp_path)
    _submit(plane, tmp_path, "A", tenant="acme")
    _submit(plane, tmp_path, "B", tenant="bcorp")
    plane._fetch(0)
    plane._fetch(0)               # job A fully leased to worker 0
    # RACON_TPU_FLEET_STEAL=0 pins the worker to its job
    monkeypatch.setenv("RACON_TPU_FLEET_STEAL", "0")
    assert plane._fetch(0).get("wait") is True
    monkeypatch.delenv("RACON_TPU_FLEET_STEAL")
    # an armed pool.steal fault is absorbed: the fetch waits, the chunk
    # stays eligible, and the fault is counted
    monkeypatch.setenv("RACON_TPU_FAULT", "pool.steal")
    assert plane._fetch(0).get("wait") is True
    assert plane.counters["steal_faults"] == 1
    monkeypatch.delenv("RACON_TPU_FAULT")
    assert "chunk" in plane._fetch(0)      # fault gone: the steal lands
    assert plane.counters["steals"] == 1


def test_plane_priority_orders_cross_tenant_picks(tmp_path):
    plane = _plane(tmp_path)
    _submit(plane, tmp_path, "lo", tenant="acme", priority=0)
    hi = _submit(plane, tmp_path, "hi", tenant="acme", priority=5)
    r = plane._fetch(0)
    assert plane.chunks[r["chunk"]["index"]].job is hi


def test_plane_gather_is_ordered_and_duplicates_counted(tmp_path):
    done = []
    plane = _plane(tmp_path)
    job = _submit(plane, tmp_path, "G", on_done=lambda *a: done.append(a))
    r1 = plane._fetch(0)
    r2 = plane._fetch(0)
    by_index = {r["chunk"]["index"]: r for r in (r1, r2)}
    # deliver out of order; the gather must still be position-ordered
    assert _deliver(plane, by_index[1], body=">c1\nTTTT\n")["accepted"]
    assert _deliver(plane, by_index[0], body=">c0\nAAAA\n")["accepted"]
    assert job.done.wait(10) and job.state == "done"
    assert done and done[0][0] == "done"
    out = open(job.result["output"]).read()
    assert out == ">c0\nAAAA\n>c1\nTTTT\n"
    assert job.result["fleet"]["served"] == {"fleet": 2}
    # a late re-delivery of a finished chunk is a counted duplicate
    assert _deliver(plane, by_index[0])["accepted"] is False
    assert plane.counters["duplicates"] == 1


def test_plane_drain_answer_and_stopping(tmp_path):
    plane = _plane(tmp_path)
    _submit(plane, tmp_path, "D")
    plane.pool._draining.add(7)
    assert plane._fetch(7).get("drain") is True
    with plane._cv:
        plane._stopping = True
    assert plane._fetch(0).get("drain") is True


def test_lease_reclaim_fault_drill_and_requeue(tmp_path, monkeypatch):
    """lease.reclaim: an armed raise is absorbed and counted — the
    reclaim itself always proceeds, releasing the dead holder's
    canonical journal and re-queueing the chunk."""
    plane = _plane(tmp_path)
    _submit(plane, tmp_path, "R")
    r = plane._fetch(0)
    c = plane.chunks[r["chunk"]["index"]]
    assert c.state == "running" and c.journal_held
    monkeypatch.setenv("RACON_TPU_FAULT", "lease.reclaim")
    plane._worker_dead(0, "unit test")
    assert plane.counters["reclaim_faults"] == 1
    assert plane.counters["lease_reclaimed"] == 1
    assert plane.counters["workers_dead"] == 1
    assert c.state == "pending" and not c.leases and not c.journal_held
    assert c.next_eligible > time.monotonic()   # backoff applied


def test_pool_scale_fault_drills(tmp_path, monkeypatch):
    """pool.scale_up / pool.scale_down: an armed raise is absorbed —
    the resize step is skipped (counted), the pool stays safe."""

    class _FakeProc:
        returncode = None

        def poll(self):
            return None

    pool = ElasticPool(logs_dir=str(tmp_path / "logs"), min_workers=0,
                       max_workers=2)
    monkeypatch.setenv("RACON_TPU_FAULT", "pool.scale_up")
    assert pool.scale_up(1, cause="drill") == 0
    assert pool.counters["scale_up_faults"] == 1
    assert pool.live() == 0                      # nothing spawned
    pool._procs[0] = _FakeProc()
    monkeypatch.setenv("RACON_TPU_FAULT", "pool.scale_down")
    assert pool.scale_down(1, cause="drill") == []
    assert pool.counters["scale_down_faults"] == 1
    assert not pool.is_draining(0)
    monkeypatch.delenv("RACON_TPU_FAULT")
    assert pool.scale_down(1, cause="idle") == [0]
    assert pool.is_draining(0)
    assert pool.counters["scale_downs"] == 1


# -------------------------------------------- loadtest telemetry helpers

def test_loadtest_pool_series_and_saturation_curve():
    from racon_tpu.serve.loadtest import pool_series, saturation_curve

    samples = [
        {"t": 0.5, "queued": {"device": 3},
         "fleet": {"workers": {"live": 1, "active": 1}, "min_workers": 1,
                   "max_workers": 4, "chunks_pending": 3,
                   "timeline": [[0.0, 1]]}},
        {"t": 1.5, "queued": {"device": 1},
         "fleet": {"workers": {"live": 3, "active": 3}, "min_workers": 1,
                   "max_workers": 4, "chunks_pending": 1,
                   "timeline": [[0.0, 1], [1.2, 3]]}},
    ]
    pool = pool_series(samples)
    assert pool["min"] == 1 and pool["max"] == 4
    assert pool["timeline"] == [[0.0, 1], [1.2, 3]]
    assert [s["live"] for s in pool["samples"]] == [1, 3]
    assert pool_series([{"t": 0.1}]) is None    # no plane: no series

    completed = [{"t_done": 0.4, "latency_s": 0.4},
                 {"t_done": 1.9, "latency_s": 1.0}]
    curve = saturation_curve(completed, samples, 2.0, buckets=2)
    assert len(curve) == 2
    assert curve[0]["jobs_done"] == 1 and curve[1]["jobs_done"] == 1
    assert curve[0]["workers"] == 1 and curve[1]["workers"] == 3
    assert curve[0]["max_queued"] == 3
    assert saturation_curve([], samples, 2.0) == []


# ------------------------------------------- integration: in-process fleet

def test_fleet_daemon_end_to_end_byte_identity(tmp_path):
    """Two tenants' jobs through a real elastic fleet (cpu workers):
    every chunk served by the fleet, output byte-identical to the
    serial oracle, stats carrying the fleet snapshot + admission
    ledger, and the merged plane trace validating under `obs fleet`."""
    paths = _write_dataset(tmp_path)
    want = _oracle_fasta(paths)
    state = str(tmp_path / "state")
    daemon = ServeDaemon(state, backend="cpu", port=0, warm=False,
                         fleet_min=1, fleet_max=2)
    daemon.start()
    try:
        with ServeClient(daemon.port, timeout=180) as c:
            j1 = c.submit(*paths, args=dict(_ARGS), submitter="alice",
                          priority=1)
            j2 = c.submit(*paths, args=dict(_ARGS), submitter="bob")
            r1 = c.wait(j1, timeout=180)
            r2 = c.wait(j2, timeout=180)
            st = c.stats()
        for r in (r1, r2):
            assert r["state"] == "done"
            assert open(r["result"]["output"]).read() == want
            assert r["result"]["fleet"]["served"] == {"fleet": 3}
        assert st["fleet"]["min_workers"] == 1
        assert st["fleet"]["max_workers"] == 2
        assert st["fleet"]["counters"]["jobs_done"] == 2
        assert st["fleet"]["timeline"]          # pool-size samples
        assert "reserved_windows" in st["admission"]
    finally:
        daemon.stop(wait=True)
    fdir = os.path.join(state, "fleet")
    with open(os.path.join(fdir, "report.json")) as f:
        rep = json.load(f)
    assert rep["phases"]["fleet"]["served"]["fleet"] == 6
    r = subprocess.run([sys.executable, "-m", "racon_tpu.obs", "fleet",
                        os.path.join(fdir, "trace.json")],
                       capture_output=True, text=True, cwd=ROOT)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "parenting holds" in r.stdout


def test_fleet_worker_killed_midchunk_recovers(tmp_path, monkeypatch):
    """Chaos: worker 0 is SIGKILLed delivering its first result
    (worker.result:kill=1, scoped to worker 0).  EOF reclaims its
    lease, the chunk re-dispatches, the pool respawns capacity, and
    the job still finishes byte-identical."""
    paths = _write_dataset(tmp_path)
    want = _oracle_fasta(paths)
    monkeypatch.setenv("RACON_TPU_FAULT", "worker.result:kill=1:count=1")
    monkeypatch.setenv("RACON_TPU_DISTRIB_FAULT_WORKER", "0")
    daemon = ServeDaemon(str(tmp_path / "state"), backend="cpu", port=0,
                         warm=False, fleet_min=1, fleet_max=2)
    daemon.start()
    try:
        with ServeClient(daemon.port, timeout=240) as c:
            jid = c.submit(*paths, args=dict(_ARGS), submitter="chaos")
            res = c.wait(jid, timeout=240)
        assert res["state"] == "done"
        assert open(res["result"]["output"]).read() == want
        snap = daemon.plane.snapshot()
        assert snap["counters"]["workers_dead"] >= 1
        assert snap["counters"]["lease_reclaimed"] >= 1
    finally:
        daemon.stop(wait=True)


# ---------------------------- satellite: daemon SIGKILLed mid-resize

def _spawn_fleet(state, env):
    from racon_tpu.serve.loadtest import spawn_daemon

    proc = spawn_daemon(str(state), "cpu", window_length=100,
                        extra_args=["--no-warm", "--fleet-min", "1",
                                    "--fleet-max", "3"],
                        env=env, timeout=120)
    with open(os.path.join(str(state), "serve.json")) as f:
        return proc, json.load(f)["port"]


def test_daemon_killed_midresize_requeues_and_resumes(tmp_path):
    """Acceptance: pool.scale_up:kill=1 SIGKILLs the daemon mid-resize
    (a hung worker 0 keeps the backlog up so the autoscaler must fire).
    On restart the unfinished jobs re-queue from their specs, chunk
    leases are gone with the dead plane, and the chunk journals written
    before the crash turn the re-runs into byte-identical resumes."""
    paths = _write_dataset(tmp_path)
    want = _oracle_fasta(paths)
    state = tmp_path / "state"
    env = dict(os.environ, JAX_PLATFORMS="cpu", **_FAST_ENV)
    env.pop("RACON_TPU_FAULT", None)

    # worker 0 hangs 5s before each result delivery: the backlog holds,
    # the autoscaler decides to grow, and the armed kill fires mid-resize
    proc1, port1 = _spawn_fleet(state, dict(
        env, RACON_TPU_FAULT="worker.result:hang=5,pool.scale_up:kill=1",
        RACON_TPU_DISTRIB_FAULT_WORKER="0"))
    try:
        with ServeClient(port1, timeout=30) as c:
            c.submit(*paths, args=dict(_ARGS), job_id="ra",
                     submitter="acme")
            c.submit(*paths, args=dict(_ARGS), job_id="rb",
                     submitter="bcorp")
        assert proc1.wait(timeout=120) == -9     # SIGKILL mid-resize
    finally:
        if proc1.poll() is None:
            proc1.kill()
    for jid in ("ra", "rb"):
        jd = os.path.join(str(state), "jobs", jid)
        assert os.path.isfile(os.path.join(jd, "spec.json"))
        assert not os.path.isfile(os.path.join(jd, "result.json"))
    journaled = [p for p in glob.glob(os.path.join(
        str(state), "jobs", "*", "chunks", "*", "journal*.jsonl"))
        if os.path.getsize(p) > 0]

    proc2, port2 = _spawn_fleet(state, env)
    try:
        with ServeClient(port2, timeout=240) as c:
            ra = c.wait("ra", timeout=240)
            rb = c.wait("rb", timeout=240)
        replayed = 0
        for res in (ra, rb):
            assert res["state"] == "done"
            assert open(res["result"]["output"]).read() == want
            replayed += res["result"]["journal_replayed"]
        if journaled:
            # windows journaled before the crash must replay, not re-run
            assert replayed >= 1
        with ServeClient(port2, timeout=30) as c:
            c.shutdown()
        proc2.wait(timeout=60)
    finally:
        if proc2.poll() is None:
            proc2.kill()
