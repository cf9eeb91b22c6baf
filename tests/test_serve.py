"""Serving layer (racon_tpu/serve): resident PolishSession hot-kernel
reuse, per-job artifact namespacing, scheduler admission/fairness/
demotion, the newline-JSON daemon protocol, preemption + journal resume
across a daemon restart, and the load-test/bench plumbing.

Conventions follow tests/test_faults.py: identical-read datasets (device
and host consensus both reproduce the target exactly, so outputs are
byte-comparable to the CpuPolisher oracle under any serving mix) and the
fast device env (XLA twin, 8-window batches).
"""

import json
import os
import random
import socket
import subprocess
import sys
import threading
import time

import pytest

import racon_tpu
from racon_tpu.serve import (AdmissionError, JobCancelled, JobSpec,
                             PolishSession, Scheduler, ServeClient,
                             ServeDaemon, ServeError)
from racon_tpu.serve.scheduler import estimate_windows

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_ARGS = dict(window_length=100, quality_threshold=10, error_threshold=0.3,
             match=5, mismatch=-4, gap=-8, num_threads=1)

_FAST_ENV = {"RACON_TPU_PALLAS": "0", "RACON_TPU_BATCH_WINDOWS": "8"}


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


def _oracle_fasta(paths):
    """Serial oracle output in the exact byte format the CLI (and the
    session's polished.fasta) emits."""
    p = racon_tpu.create_polisher(*paths, backend="cpu", **_ARGS)
    p.initialize()
    return "".join(f">{n}\n{d}\n" for n, d in p.polish(True))


def _device_env(monkeypatch):
    for k, v in _FAST_ENV.items():
        monkeypatch.setenv(k, v)


def _spec(paths, job_id="", **over):
    return JobSpec(paths[0], paths[1], paths[2], args=dict(_ARGS),
                   job_id=job_id, **over)


def _trace_kernel_builds(trace_path):
    with open(trace_path) as f:
        doc = json.load(f)
    return [e for e in doc["traceEvents"]
            if e.get("name") == "kernel.build"]


# ----------------------------------------------------------- unit: JobSpec

def test_jobspec_validation(tmp_path):
    paths = _write_dataset(tmp_path)
    _spec(paths).validate()   # clean spec passes
    with pytest.raises(ValueError, match="unknown polish arg"):
        JobSpec(*paths, args={"window": 100}).validate()
    with pytest.raises(ValueError, match="unknown backend"):
        JobSpec(*paths, backend="gpu").validate()
    with pytest.raises(ValueError, match="not found"):
        JobSpec(paths[0], paths[1], str(tmp_path / "nope.fa")).validate()
    with pytest.raises(ValueError, match="invalid job id"):
        JobSpec(*paths, job_id="../escape").validate()
    with pytest.raises(ValueError, match="unknown job field"):
        JobSpec.from_dict({"sequences": paths[0], "overlaps": paths[1],
                           "target": paths[2], "frobnicate": 1})
    rt = JobSpec.from_dict(_spec(paths, job_id="j1").as_dict())
    assert rt.as_dict() == _spec(paths, job_id="j1").as_dict()


def test_estimate_windows(tmp_path):
    paths = _write_dataset(tmp_path)          # 3 contigs x 200 bp
    assert estimate_windows(paths[2], 100) == 6
    assert estimate_windows(paths[2], 150) == 6   # ceil(200/150)=2 each
    assert estimate_windows(paths[2], 500) == 3
    assert estimate_windows(str(tmp_path / "missing.fa"), 100) is None
    fq = tmp_path / "reads.fastq"
    fq.write_text("@r1\nACGT\n+\n!!!!\n")
    assert estimate_windows(str(fq), 100) is None


# ------------------------------------------- session: hot kernels, isolation

def test_hot_kernels_across_jobs_and_sessions(tmp_path, monkeypatch):
    """The tentpole invariant: after the first job builds its kernels,
    every later job — same session or a second PolishSession in the same
    process — performs ZERO kernel builds, proven from the per-request
    obs traces (kernel.build span counts) and the per-job counters."""
    _device_env(monkeypatch)
    paths = _write_dataset(tmp_path)
    want = _oracle_fasta(paths)

    s1 = PolishSession(str(tmp_path / "s1"), backend="tpu")
    r1 = s1.run_job(_spec(paths, job_id="a"))
    r2 = s1.run_job(_spec(paths, job_id="b"))
    s2 = PolishSession(str(tmp_path / "s2"), backend="tpu")
    r3 = s2.run_job(_spec(paths, job_id="c"))

    assert r1["cold"] and not r2["cold"]
    # no startup warm() here, so job 1 visibly pays the builds...
    assert r1["kernel_builds"] > 0
    assert len(_trace_kernel_builds(r1["trace"])) == r1["kernel_builds"]
    # ...and everyone after it pays none, across session instances too
    for r in (r2, r3):
        assert r["kernel_builds"] == 0, r
        assert _trace_kernel_builds(r["trace"]) == []
    for r in (r1, r2, r3):
        assert open(r["output"]).read() == want


def test_session_warm_precompiles_first_job(tmp_path, monkeypatch):
    """With the startup warm-up, even the COLD job builds nothing."""
    _device_env(monkeypatch)
    paths = _write_dataset(tmp_path)
    s = PolishSession(str(tmp_path / "state"), backend="tpu")
    assert s.warm([100], _ARGS["match"], _ARGS["mismatch"],
                  _ARGS["gap"]) > 0
    r = s.run_job(_spec(paths, job_id="warmed"))
    assert r["cold"] and r["kernel_builds"] == 0
    assert _trace_kernel_builds(r["trace"]) == []


def test_job_artifacts_namespaced_per_job(tmp_path):
    """Satellite regression: concurrent jobs must never clobber each
    other's artifacts — every report/journal/trace/output path is
    namespaced by job id (host backend: no kernels, fast)."""
    paths = _write_dataset(tmp_path)
    s = PolishSession(str(tmp_path / "state"), backend="cpu")
    ra = s.run_job(_spec(paths, job_id="jobA"))
    rb = s.run_job(_spec(paths, job_id="jobB"))
    assert os.path.dirname(ra["output"]) != os.path.dirname(rb["output"])
    for r, jid in ((ra, "jobA"), (rb, "jobB")):
        jd = s.job_dir(jid)
        for key in ("output", "report", "trace"):
            assert r[key].startswith(jd + os.sep), (key, r[key])
            assert os.path.isfile(r[key])
        assert os.path.getsize(os.path.join(jd, "journal.cpu.jsonl")) > 0
        with open(r["report"]) as f:
            assert json.load(f)["job_id"] == jid
    assert open(ra["output"]).read() == open(rb["output"]).read()


def test_session_rerun_resumes_from_journal(tmp_path):
    """Re-running a job id whose journal already holds served windows
    replays them instead of recomputing (the preemption-resume seam the
    daemon's restart recovery builds on)."""
    paths = _write_dataset(tmp_path)
    s = PolishSession(str(tmp_path / "state"), backend="cpu")
    first = s.run_job(_spec(paths, job_id="r"))
    assert first["journal_replayed"] == 0
    again = s.run_job(_spec(paths, job_id="r"))
    assert again["journal_replayed"] == 6          # all 6 windows replayed
    assert open(first["output"]).read() == open(again["output"]).read()


# ------------------------------------------------------ scheduler: fairness

class _FakeSession:
    """Duck-typed session for scheduler unit tests: records execution
    order, optionally blocks the device lane on an event."""

    backend = "tpu"

    def __init__(self, workdir, gate=None):
        self.workdir = str(workdir)
        self.gate = gate
        self.order = []
        os.makedirs(os.path.join(self.workdir, "jobs"), exist_ok=True)

    def job_dir(self, job_id):
        return os.path.join(self.workdir, "jobs", job_id)

    def stats(self):
        return {"jobs_run": len(self.order)}

    def run_job(self, spec, cancel_event=None):
        if self.gate is not None:
            self.gate.wait(timeout=30)
        if cancel_event is not None and cancel_event.is_set():
            raise JobCancelled(spec.job_id)
        self.order.append(spec.job_id)
        return {"job_id": spec.job_id, "backend": "tpu", "cold": False,
                "wall_s": 0.0, "records": 0, "polished_bp": 0,
                "kernel_builds": 0, "journal_replayed": 0,
                "output": "", "report": "", "trace": "", "summary": None}


def _wait_running(sched, job, timeout=10):
    deadline = time.monotonic() + timeout
    while job.state == "queued":
        assert time.monotonic() < deadline, job.as_status()
        time.sleep(0.01)


def test_scheduler_round_robin_and_admission(tmp_path):
    paths = _write_dataset(tmp_path)
    gate = threading.Event()
    ses = _FakeSession(tmp_path / "state", gate=gate)
    sched = Scheduler(ses, queue_depth=4, max_jobs=10, host_lane=False)
    sched.start()
    try:
        blocker = sched.submit(_spec(paths, job_id="blk", submitter="z"))
        _wait_running(sched, blocker)
        jobs = [sched.submit(_spec(paths, job_id=j, submitter=s))
                for j, s in (("a1", "a"), ("a2", "a"), ("a3", "a"),
                             ("b1", "b"))]
        # queue full (depth 4): the fifth queued submission is rejected
        with pytest.raises(AdmissionError, match="queue full"):
            sched.submit(_spec(paths, job_id="a4", submitter="a"))
        gate.set()
        for j in jobs:
            assert j.done.wait(30), j.as_status()
        # round-robin: submitter a cannot run its whole burst before b
        assert ses.order == ["blk", "a1", "b1", "a2", "a3"]
        # per-job persistence: every terminal job wrote its result.json
        for j in jobs:
            with open(os.path.join(ses.job_dir(j.id), "result.json")) as f:
                assert json.load(f)["state"] == "done"
    finally:
        gate.set()
        sched.shutdown(wait=True, timeout=10)


def test_scheduler_max_jobs_and_cancel_queued(tmp_path):
    paths = _write_dataset(tmp_path)
    gate = threading.Event()
    ses = _FakeSession(tmp_path / "state", gate=gate)
    sched = Scheduler(ses, queue_depth=10, max_jobs=2, host_lane=False)
    sched.start()
    try:
        running = sched.submit(_spec(paths, job_id="run", submitter="a"))
        _wait_running(sched, running)
        queued = sched.submit(_spec(paths, job_id="wait", submitter="a"))
        with pytest.raises(AdmissionError, match="at capacity"):
            sched.submit(_spec(paths, job_id="over", submitter="a"))
        st = sched.cancel("wait")
        assert st["state"] == "cancelled"
        assert queued.done.is_set()
        with open(os.path.join(ses.job_dir("wait"), "result.json")) as f:
            assert json.load(f)["state"] == "cancelled"
        gate.set()
        assert running.done.wait(30)
        assert ses.order == ["run"]               # cancelled job never ran
        with pytest.raises(KeyError):
            sched.get("nope")
    finally:
        gate.set()
        sched.shutdown(wait=True, timeout=10)


def test_scheduler_window_budget_demotes_to_host_lane(tmp_path):
    """A job over the window budget runs on the host lane (CLI
    subprocess) with byte-identical output, and records the demotion —
    the degradation lattice extended to whole jobs."""
    paths = _write_dataset(tmp_path)              # 6 windows at w=100
    want = _oracle_fasta(paths)
    ses = PolishSession(str(tmp_path / "state"), backend="tpu")
    sched = Scheduler(ses, queue_depth=4, max_jobs=8, window_budget=5)
    sched.start()
    try:
        job = sched.submit(_spec(paths, job_id="big"))
        assert job.lane == "host"
        assert "window budget" in job.demotions[0]["cause"]
        assert job.done.wait(120), job.as_status()
        assert job.state == "done", job.error
        assert job.result["backend"] == "cpu"
        assert open(job.result["output"]).read() == want
        assert ses.jobs_run == 0                  # device lane untouched
    finally:
        sched.shutdown(wait=True, timeout=10)


def test_scheduler_device_failure_demotes_to_host_lane(tmp_path):
    """A device-lane crash re-queues the job on the host lane instead of
    failing it (and instead of taking the daemon down)."""
    paths = _write_dataset(tmp_path)
    want = _oracle_fasta(paths)

    class _WedgedSession(_FakeSession):
        def run_job(self, spec, cancel_event=None):
            raise RuntimeError("synthetic device wedge")

    ses = _WedgedSession(tmp_path / "state")
    sched = Scheduler(ses, queue_depth=4, max_jobs=8)
    sched.start()
    try:
        job = sched.submit(_spec(paths, job_id="dj"))
        assert job.done.wait(120), job.as_status()
        assert job.state == "done", job.error
        assert job.demotions[0]["from"] == "device"
        assert "synthetic device wedge" in job.demotions[0]["cause"]
        assert job.result["backend"] == "cpu"
        assert open(job.result["output"]).read() == want
    finally:
        sched.shutdown(wait=True, timeout=10)


def test_recover_tolerates_torn_spec_and_result(tmp_path):
    """Restart-path regression: a daemon SIGKILLed mid-write can leave
    spec.json or result.json torn in arbitrary ways.  recover() must
    (a) discard a torn result.json and re-queue the job from its good
    spec, (b) mark a job with an unparseable or non-object spec failed
    instead of crashing the restart, and (c) leave finished jobs with
    intact results alone."""
    paths = _write_dataset(tmp_path)
    ses = _FakeSession(tmp_path / "state")
    jobs_root = os.path.join(ses.workdir, "jobs")

    def _job_dir(job_id):
        d = os.path.join(jobs_root, job_id)
        os.makedirs(d, exist_ok=True)
        return d

    # jobA: good spec + result torn mid-write -> unfinished, re-queued
    a = _job_dir("jobA")
    with open(os.path.join(a, "spec.json"), "w") as f:
        json.dump(_spec(paths, job_id="jobA").as_dict(), f)
    with open(os.path.join(a, "result.json"), "w") as f:
        f.write('{"job_id": "jobA", "state": "do')
    # jobB: spec parses but is not an object -> failed, not crashed
    b = _job_dir("jobB")
    with open(os.path.join(b, "spec.json"), "w") as f:
        f.write("null\n")
    # jobC: spec truncated mid-write -> failed, not crashed
    c = _job_dir("jobC")
    with open(os.path.join(c, "spec.json"), "w") as f:
        f.write('{"seq')
    # jobD: intact spec + intact result -> finished, left alone
    d = _job_dir("jobD")
    with open(os.path.join(d, "spec.json"), "w") as f:
        json.dump(_spec(paths, job_id="jobD").as_dict(), f)
    with open(os.path.join(d, "result.json"), "w") as f:
        json.dump({"job_id": "jobD", "state": "done"}, f)

    sched = Scheduler(ses, queue_depth=8, max_jobs=8, host_lane=False)
    recovered = sched.recover()              # must not raise
    assert recovered == ["jobA"]
    assert not os.path.exists(os.path.join(a, "result.json"))
    assert sched.get("jobA").state == "queued"
    for jid in ("jobB", "jobC"):
        j = sched.get(jid)
        assert j.state == "failed", j.as_status()
        assert "recovery failed" in j.error
        with open(os.path.join(jobs_root, jid, "result.json")) as f:
            assert json.load(f)["state"] == "failed"
    with pytest.raises(KeyError):
        sched.get("jobD")                    # finished: not re-queued
    with open(os.path.join(d, "result.json")) as f:
        assert json.load(f)["state"] == "done"


# --------------------------------------------------------- daemon protocol

def test_server_e2e_concurrent_jobs_byte_identical(tmp_path, monkeypatch):
    """Acceptance: N concurrent jobs against one daemon produce output
    byte-identical to serial runs, with jobs 2..N performing zero kernel
    builds (asserted from the per-request traces), and every per-request
    trace passing the obs schema validator."""
    _device_env(monkeypatch)
    paths = _write_dataset(tmp_path)
    want = _oracle_fasta(paths)
    daemon = ServeDaemon(str(tmp_path / "state"), backend="tpu", port=0,
                         warm=False)
    daemon.start()
    try:
        with ServeClient(daemon.port) as c1, ServeClient(daemon.port) as c2:
            ids = [c1.submit(*paths, args=dict(_ARGS), submitter="c1"),
                   c2.submit(*paths, args=dict(_ARGS), submitter="c2"),
                   c1.submit(*paths, args=dict(_ARGS), submitter="c1")]
            results = [c1.wait(j, timeout=240)["result"] for j in ids]
        for res in results:
            assert open(res["output"]).read() == want
        builds = [len(_trace_kernel_builds(r["trace"])) for r in results]
        colds = [r["cold"] for r in results]
        assert builds[colds.index(True)] > 0      # first job compiles...
        assert sorted(colds) == [False, False, True]
        for r, b in zip(results, builds):
            if not r["cold"]:
                assert b == 0 and r["kernel_builds"] == 0   # ...others never
        # per-request traces are schema-valid for the obs CLI
        v = subprocess.run([sys.executable, "-m", "racon_tpu.obs",
                            "--validate", results[-1]["trace"]],
                           capture_output=True, text=True, cwd=ROOT)
        assert v.returncode == 0, v.stdout + v.stderr
    finally:
        daemon.stop(wait=True)


def test_server_survives_client_disconnect_midjob(tmp_path):
    """A client that vanishes right after submitting loses only its
    socket: the job completes and stays queryable from new
    connections."""
    paths = _write_dataset(tmp_path)
    daemon = ServeDaemon(str(tmp_path / "state"), backend="cpu", port=0,
                         warm=False)
    daemon.start()
    try:
        c = ServeClient(daemon.port)
        jid = c.submit(*paths, args=dict(_ARGS), submitter="ghost")
        c._sock.close()                           # vanish mid-exchange
        with ServeClient(daemon.port) as c2:
            assert c2.ping()["ok"]
            res = c2.wait(jid, timeout=120)
            assert res["state"] == "done"
            assert os.path.isfile(res["result"]["output"])
    finally:
        daemon.stop(wait=True)


def test_server_protocol_errors_keep_connection_alive(tmp_path):
    paths = _write_dataset(tmp_path)
    daemon = ServeDaemon(str(tmp_path / "state"), backend="cpu", port=0,
                         warm=False)
    daemon.start()
    try:
        sock = socket.create_connection(("127.0.0.1", daemon.port),
                                        timeout=30)
        f = sock.makefile("rwb")

        def rpc(raw):
            f.write(raw + b"\n")
            f.flush()
            return json.loads(f.readline())

        assert rpc(b"this is not json")["ok"] is False
        assert "unknown op" in rpc(b'{"op": "frobnicate"}')["error"]
        bad = rpc(json.dumps({"op": "submit", "sequences": paths[0],
                              "overlaps": paths[1],
                              "target": str(tmp_path / "gone.fa")}).encode())
        assert bad["ok"] is False and "not found" in bad["error"]
        assert "unknown job id" in rpc(
            b'{"op": "status", "job_id": "nope"}')["error"]
        # the same connection still serves good requests after each error
        assert rpc(b'{"op": "ping"}')["ok"] is True
        sock.close()
        with ServeClient(daemon.port) as c:
            with pytest.raises(ServeError, match="unknown polish arg"):
                c.submit(*paths, args={"bogus": 1})
            assert c.stats()["jobs"] == {}
    finally:
        daemon.stop(wait=True)


def test_server_shutdown_op_and_admission_after_stop(tmp_path):
    paths = _write_dataset(tmp_path)
    daemon = ServeDaemon(str(tmp_path / "state"), backend="cpu", port=0,
                         warm=False)
    daemon.start()
    with ServeClient(daemon.port) as c:
        assert c.shutdown()["ok"]
    daemon.scheduler.shutdown(wait=True, timeout=10)
    with pytest.raises(AdmissionError, match="shutting down"):
        daemon.scheduler.submit(_spec(paths, job_id="late"))


# ------------------------------------------- preemption: restart + resume

def _spawn(state, env, *extra):
    from racon_tpu.serve.loadtest import spawn_daemon

    proc = spawn_daemon(str(state), "tpu", window_length=100,
                        extra_args=["--no-warm", *extra], env=env,
                        timeout=120)
    with open(os.path.join(str(state), "serve.json")) as f:
        return proc, json.load(f)["port"]


def test_daemon_killed_midjob_resumes_on_restart(tmp_path):
    """Acceptance: a daemon SIGKILLed mid-job (deterministic
    journal.append fault) is restarted on the same state dir; the job is
    recovered, its journal replays the served prefix, and the output is
    byte-identical to an uninterrupted run."""
    paths = _write_dataset(tmp_path)
    want = _oracle_fasta(paths)
    state = tmp_path / "state"
    env = dict(os.environ, JAX_PLATFORMS="cpu", **_FAST_ENV)

    proc1, port1 = _spawn(state, dict(
        env, RACON_TPU_FAULT="journal.append:batch=3:kill=1"))
    try:
        with ServeClient(port1, timeout=30) as c:
            jid = c.submit(*paths, args=dict(_ARGS), job_id="prem")
        assert proc1.wait(timeout=180) == -9      # SIGKILL mid-job
    finally:
        if proc1.poll() is None:
            proc1.kill()
    jd = os.path.join(str(state), "jobs", "prem")
    assert os.path.isfile(os.path.join(jd, "spec.json"))
    assert not os.path.isfile(os.path.join(jd, "result.json"))
    assert os.path.getsize(os.path.join(jd, "journal.tpu.jsonl")) > 0

    proc2, port2 = _spawn(state, env)
    try:
        with ServeClient(port2, timeout=300) as c:
            res = c.wait(jid, timeout=240)
        assert res["state"] == "done"
        assert res["result"]["journal_replayed"] >= 1
        assert open(res["result"]["output"]).read() == want
        with ServeClient(port2, timeout=30) as c:
            c.shutdown()
        proc2.wait(timeout=60)
    finally:
        if proc2.poll() is None:
            proc2.kill()


# -------------------------------------------------- loadtest + bench seams

def test_loadtest_percentile_and_docs_block(tmp_path):
    from racon_tpu.serve import loadtest

    assert loadtest.percentile([1.0], 99) == 1.0
    vals = [float(i) for i in range(1, 101)]
    # linearly interpolated (same estimator as obs critpath): p50 of 1..100
    # sits halfway between the 50th and 51st order statistics.
    assert loadtest.percentile(vals, 50) == 50.5
    assert loadtest.percentile(vals, 95) == 95.05
    assert loadtest.percentile(vals, 99) == 99.01

    summary = {
        "jobs": 4, "clients": 2, "throughput_mbps": 0.5,
        "warm_mbps": 0.75, "warm_kernel_builds": 0,
        "latency_s": {"p50": 1.0, "p95": 2.0, "p99": 2.5,
                      "mean": 1.2, "max": 2.5},
        "service_s": {"cold_first_job": 3.0, "warm_mean": 1.0,
                      "cold_warm_delta": 2.0},
    }
    doc = tmp_path / "bench.md"
    doc.write_text("# Benchmarks\n\nprose stays.\n")
    loadtest.update_docs(str(doc), summary, "toy workload")
    loadtest.update_docs(str(doc), summary, "toy workload")   # idempotent
    text = doc.read_text()
    assert text.count(loadtest.DOCS_BEGIN) == 1
    assert text.count(loadtest.DOCS_END) == 1
    assert "prose stays." in text and "1.00 / 2.00 / 2.50 s" in text


def test_bench_serve_entry_normalizes_as_fixed_point():
    """The serve bench entry must round-trip normalize_entry unchanged
    and form its own bench-history series (profile serve-*)."""
    sys.path.insert(0, ROOT)
    try:
        from bench import normalize_entry
    finally:
        sys.path.remove(ROOT)
    from racon_tpu.obs import bench_track

    entry = {
        "metric": "serve: warm-path polished Mbp/sec (synthetic ONT 0.5 "
                  "Mbp 30x, PAF, w=500, 4 jobs/2 clients)",
        "value": 1.23, "unit": "Mbp/s", "vs_baseline": None,
        "cost_model": None, "pack_split": None, "serial_steps": None,
        "cells_banded": None, "band_hit_rate": None,
        "peak_rss_mb": None, "budget_mb": None,
        "serve": {"jobs": 4, "clients": 2,
                  "latency_s": {"p50": 1, "p95": 2, "p99": 3}},
        "fleet": {"samples": 3, "max_queued": 2, "last": None},
        "pool": {"min": 1, "max": 3, "timeline": [[0.0, 1], [1.5, 3]]},
        "ledger": {"jobs": 4, "stage_s": {"queue": 0.5},
                   "wall_s": 2.0, "unattributed_s": 0.1},
        "slo": {"counters": {"observed": 4, "bad": 0}},
        "mbp": 0.5, "input": "paf", "profile": "serve-ont",
    }
    assert normalize_entry(dict(entry)) == entry
    plain = dict(entry, profile="ont")
    assert (bench_track.series_key(entry)
            != bench_track.series_key(plain))
    # pre-telemetry serve entries get the explicit "not scraped" null
    legacy = {k: v for k, v in entry.items() if k != "fleet"}
    assert normalize_entry(legacy)["fleet"] is None
    # pre-elastic-pool entries get the explicit "no timeline" null
    legacy = {k: v for k, v in entry.items() if k != "pool"}
    assert normalize_entry(legacy)["pool"] is None
    # pre-ledger / pre-SLO entries get the explicit nulls too
    legacy = {k: v for k, v in entry.items() if k not in ("ledger", "slo")}
    normalized = normalize_entry(legacy)
    assert normalized["ledger"] is None and normalized["slo"] is None


def test_cli_serve_subcommand_dispatches():
    r = subprocess.run([sys.executable, "-m", "racon_tpu.cli", "serve",
                        "--help"], capture_output=True, text=True,
                       cwd=ROOT)
    assert r.returncode == 0
    assert "daemon" in r.stdout
    # the polish parser still owns everything that isn't the subcommand
    r2 = subprocess.run([sys.executable, "-m", "racon_tpu.cli",
                        "--version"], capture_output=True, text=True,
                        cwd=ROOT)
    assert r2.returncode == 0
