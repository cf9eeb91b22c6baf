"""The shared bucketed-batch executor (racon_tpu/ops/batch_exec.py):
every degradation-lattice edge driven deterministically through the
executor itself with a scripted ops object, plus e2e runs proving both
real drivers inherit identical fault semantics from the one seam —
oracle byte-identity and the served-sum invariant intact, including a
kill=1 journal resume.
"""

import json
import os
import subprocess
import sys

import racon_tpu

from racon_tpu.ops.batch_exec import BatchExecutor, pipeline_depth
from racon_tpu.resilience.report import PhaseReport

from test_faults import (_ARGS, _assert_report_sums, _oracle, _tpu_run,
                         _write_dataset)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --------------------------------------------------------- scripted ops

class FakeOps:
    """Executor hooks over trivial integer work units.  `fail` maps an
    attempt invocation index to an exception; `dead_tiers` lists tiers
    whose every dispatch/attempt fails (forcing TierDead -> demote);
    `dispatch_fail` / `unpack_fail` list the invocations of those hooks
    that raise."""

    span_name = "fake.chunk"
    pack_span = "fake.pack"
    install_span = "fake.install"

    def __init__(self, tiers=("fast", "slow", "host"), fail=None,
                 dead_tiers=(), dispatch_fail=None, unpack_fail=None):
        self.tiers = list(tiers)
        self.fail = dict(fail or {})
        self.dead_tiers = set(dead_tiers)
        self.dispatch_fail = set(dispatch_fail or ())
        self.unpack_fail = set(unpack_fail or ())
        self.attempts = 0
        self.dispatches = 0
        self.unpacks = 0
        self.installed = []        # (tier, item, result)
        self.surrendered = []      # (item, exported)
        self.quarantined = []      # (item, exc)
        self.demoted = []          # (from, to)
        self.done_chunks = []
        self.tier = self.tiers[0]

    # -- protocol ---------------------------------------------------------
    def live_tier(self, ctx, kind):
        return self.tier

    def export(self, ctx, idxs):
        return [i for i in idxs if i >= 0]

    def pack(self, ctx, chunk):
        return list(chunk)

    def dispatch(self, ctx, kind, packed, chunk):
        self.dispatches += 1
        if kind in self.dead_tiers:
            raise RuntimeError(f"tier {kind} is dead")
        if self.dispatches in self.dispatch_fail:
            raise RuntimeError(f"dispatch {self.dispatches} failed")
        return [x * 10 for x in packed]

    def attempt(self, ctx, kind, sub):
        self.attempts += 1
        if kind in self.dead_tiers:
            raise RuntimeError(f"tier {kind} is dead")
        exc = self.fail.pop(self.attempts, None)
        if exc is not None:
            raise exc
        return [x * 10 for x in sub]

    def unpack(self, ctx, kind, outs):
        self.unpacks += 1
        if self.unpacks in self.unpack_fail:
            raise RuntimeError(f"unpack {self.unpacks} failed")
        return list(outs)

    def span_args(self, ctx, chunk, pipelined):
        return {"n": len(chunk), "pipelined": pipelined}

    def install(self, ctx, kind, sub, results):
        for item, r in zip(sub, results):
            self.installed.append((kind, item, r))

    def surrender(self, ctx, items, exported):
        self.surrendered.extend((i, exported) for i in items)

    def quarantine(self, ctx, item, exc):
        self.quarantined.append((item, exc))

    def demote(self, ctx, kind, cause):
        nxt = self.tiers[self.tiers.index(kind) + 1]
        self.demoted.append((kind, nxt))
        self.tier = nxt
        return nxt

    def done(self, ctx, chunk):
        self.done_chunks.append(list(chunk))


def _rep(tiers=("fast", "slow", "host")):
    return PhaseReport("t", tuple(tiers))


# ------------------------------------------------------------- unit tests

def test_depth_pipelined_happy_path_uses_cached_dispatch():
    ops = FakeOps()
    ex = BatchExecutor(ops, depth=2, report=_rep())
    ex.submit(None, [1, 2])
    ex.submit(None, [3, 4])   # depth reached: chunk 1 resolves via cache
    ex.flush()
    assert ops.dispatches == 2
    assert ops.unpacks == 2           # both chunks resolved from futures
    assert ops.attempts == 0          # the lattice never re-packed
    assert [(i, r) for _, i, r in ops.installed] == \
        [(1, 10), (2, 20), (3, 30), (4, 40)]
    assert ops.done_chunks == [[1, 2], [3, 4]]
    assert ex.pack_ns > 0 and ex.kernel_ns > 0


def test_stamp_walls_accumulates_into_report_extra():
    ops = FakeOps()
    rep = _rep()
    ex = BatchExecutor(ops, depth=1, report=rep)
    ex.submit(None, [1])
    ex.flush()
    ex.stamp_walls(rep)
    assert rep.extra["pack_wall_s"] > 0
    assert rep.extra["kernel_wall_s"] > 0
    first = rep.extra["pack_wall_s"]
    ex.stamp_walls(rep)               # accumulating, not overwriting
    assert rep.extra["pack_wall_s"] >= 2 * first
    assert "pack_wall_s" in rep.as_dict()["extra"]


def test_sync_engine_resolves_inline():
    """An engine whose chunk is a generator of many launches (the
    Hirschberg ops' shape): `dispatch` advances it to its first wait,
    `unpack` drives it to its end.  At depth 1 the chunk resolves in
    its own submit, through the dispatched generator and not a second
    attempt."""

    class StepOps(FakeOps):
        def dispatch(self, ctx, kind, packed, chunk):
            self.dispatches += 1

            def steps():
                yield                       # launches out, would block
                return [x * 10 for x in packed]

            gen = steps()
            next(gen)
            return gen

        def unpack(self, ctx, kind, gen):
            self.unpacks += 1
            try:
                while True:
                    next(gen)
            except StopIteration as stop:
                return stop.value

    ops = StepOps()
    ex = BatchExecutor(ops, depth=1, report=_rep())
    ex.submit(None, [1, 2])
    # resolved before flush: nothing queues at depth 1
    assert [(i, r) for _, i, r in ops.installed] == [(1, 10), (2, 20)]
    assert ops.dispatches == 1 and ops.unpacks == 1 and ops.attempts == 0
    ex.flush()
    assert len(ops.installed) == 2


def test_dispatch_failure_resolves_through_lattice():
    rep = _rep()
    ops = FakeOps(dispatch_fail={1})
    ex = BatchExecutor(ops, depth=1, report=rep)
    ex.submit(None, [1, 2])
    ex.flush()
    # dispatch blew up synchronously -> recorded as a failure + retry,
    # then the lattice attempt served the chunk at the same tier
    assert [(i, r) for _, i, r in ops.installed] == [(1, 10), (2, 20)]
    assert rep.retries >= 1
    assert rep.causes.get("fast")
    assert ops.attempts >= 1


def test_transient_failure_retried_at_tier(monkeypatch):
    monkeypatch.setenv("RACON_TPU_TIER_RETRIES", "1")
    rep = _rep()
    # the dispatched futures fail at the copy back (lattice attempt 0);
    # the one retry re-attempts the chunk from its packed views
    ops = FakeOps(unpack_fail={1})
    ex = BatchExecutor(ops, report=rep)
    ex.submit(None, [1, 2, 3])
    ex.flush()
    assert [(i, r) for _, i, r in ops.installed] == \
        [(1, 10), (2, 20), (3, 30)]
    assert rep.retries == 1 and rep.bisections == 0
    assert ops.attempts == 1
    assert not ops.demoted


def test_poisoned_item_bisected_and_quarantined(monkeypatch):
    monkeypatch.setenv("RACON_TPU_TIER_RETRIES", "0")

    class PoisonOps(FakeOps):
        def dispatch(self, ctx, kind, packed, chunk):
            if 3 in chunk:
                raise RuntimeError("poisoned")
            return super().dispatch(ctx, kind, packed, chunk)

        def attempt(self, ctx, kind, sub):
            self.attempts += 1
            if 3 in sub:
                raise RuntimeError("poisoned")
            return [x * 10 for x in sub]

    rep = _rep()
    ops = PoisonOps()
    ex = BatchExecutor(ops, report=rep)
    ex.submit(None, [1, 2, 3, 4])
    ex.flush()
    assert sorted(i for _, i, _ in ops.installed) == [1, 2, 4]
    assert [i for i, _ in ops.quarantined] == [3]
    assert rep.bisections >= 1
    assert not ops.demoted


def test_engine_death_demotes_down_to_host(monkeypatch):
    monkeypatch.setenv("RACON_TPU_TIER_RETRIES", "0")
    rep = _rep()
    # every dispatch/attempt at both device tiers fails: fast -> slow ->
    # host, and the chunk surrenders to the host floor (exported=True)
    ops = FakeOps(dead_tiers={"fast", "slow"})
    ex = BatchExecutor(ops, depth=1, report=rep)
    ex.submit(None, [1, 2])
    ex.flush()
    assert ops.demoted == [("fast", "slow"), ("slow", "host")]
    assert ops.surrendered == [(1, True), (2, True)]
    assert not ops.installed
    assert ops.done_chunks == [[1, 2]]   # packed state still released


def test_host_entry_tier_surrenders_unexported():
    ops = FakeOps(tiers=("host",))
    ex = BatchExecutor(ops, report=_rep())
    ex.submit(None, [7, 8])
    ex.flush()
    assert ops.surrendered == [(7, False), (8, False)]
    assert ops.dispatches == 0 and ops.attempts == 0


def test_empty_export_skips_dispatch():
    ops = FakeOps()
    ex = BatchExecutor(ops, report=_rep())
    ex.submit(None, [-1, -2])     # export filters everything out
    ex.flush()
    assert ops.dispatches == 0 and not ops.installed


def test_pipeline_depth_knob(monkeypatch):
    monkeypatch.setenv("RACON_TPU_PIPELINE_DEPTH", "5")
    assert pipeline_depth() == 5
    monkeypatch.setenv("RACON_TPU_PIPELINE_DEPTH", "0")
    assert pipeline_depth() == 1          # floor


# ------------------------------------------ e2e through the real drivers

def test_consensus_driver_full_lattice_chain(tmp_path, monkeypatch):
    """Retry + bisect-quarantine in ONE consensus run, all flowing
    through the shared executor: output byte-identical to the oracle,
    served counts sum, pack/kernel wall split stamped."""
    paths = _write_dataset(tmp_path)
    oracle = _oracle(paths)
    res, p = _tpu_run(paths, monkeypatch, {
        # invocation 0 (pipelined dispatch) fails synchronously, so the
        # executor records the failure and re-resolves through the
        # lattice; the window=2 poison then forces a bisect-quarantine
        "RACON_TPU_FAULT": ("poa.run.xla:batch=0:count=1,"
                            "poa.run.xla:window=2"),
    })
    assert res == oracle
    d = _assert_report_sums(p)
    cons = d["phases"]["consensus"]
    assert cons["quarantined"] == [2]
    assert cons["served"]["host"] == 1 and cons["served"]["xla"] == 5
    assert cons["retries"] >= 1 and cons["bisections"] >= 1
    # the executor stamped the feeder's wall split
    assert cons["extra"]["kernel_wall_s"] > 0
    assert cons["extra"]["pack_wall_s"] > 0


def test_surrender_and_quarantine_share_the_overlapped_fallback(
        tmp_path, monkeypatch):
    """A quarantined window (bisection), a surrendered exported chunk
    (tier death) and surrendered unexported chunks (the geometry already
    at the host floor) all reach the host through the one fallback
    object: each is handed to the native pool on arrival, none is
    computed by the driver thread, and the journal's host records follow
    the order of arrival."""
    from racon_tpu.ops import poa_driver
    from racon_tpu.pipeline import Pipeline
    from test_host_fallback import spy

    paths = _write_dataset(tmp_path, n_targets=16)      # 32 windows of 100
    oracle = _oracle(paths)
    arrived, external, surrenders = [], [], []
    spy(monkeypatch, poa_driver._HostFallback, "append", arrived)
    spy(monkeypatch, Pipeline, "consensus_cpu_one", external)
    spy(monkeypatch, poa_driver._ConsensusOps, "surrender", surrenders,
        key=lambda ctx, items, exported: (len(items), exported))
    for k, v in {"RACON_TPU_PALLAS": "0",
                 "RACON_TPU_BATCH_WINDOWS": "8",
                 # window 2 poisons chunk 0 alone: bisected, quarantined;
                 # 9 and 13 sit in opposite halves of chunk 1: tier dead
                 "RACON_TPU_FAULT": ("poa.run.xla:window=2,"
                                     "poa.run.xla:window=9,"
                                     "poa.run.xla:window=13")}.items():
        monkeypatch.setenv(k, v)
    jp = str(tmp_path / "run.journal")
    p = racon_tpu.create_polisher(*paths, backend="tpu", journal_path=jp,
                                  **_ARGS)
    p.initialize()
    assert p.polish(True) == oracle
    cons = _assert_report_sums(p)["phases"]["consensus"]
    assert cons["quarantined"] == [2]
    assert cons["served"]["host"] == 25 and cons["served"]["xla"] == 7
    assert (8, True) in surrenders and (8, False) in surrenders
    assert sorted(arrived) == [2] + list(range(8, 32)) and arrived[0] == 2
    assert not external                 # the driver thread computed none
    with open(jp) as f:
        records = [json.loads(line) for line in f.read().splitlines()[1:]]
    assert [r["i"] for r in records if r["tier"] == "host"] == arrived


def test_kill_resume_through_executor(tmp_path):
    """kill=1 mid-consensus (inside the executor's dispatch fault
    check), then resume from the journal: already-journaled windows are
    replayed, the rest recomputed, output byte-identical.  Subprocess
    because the fault hard-kills the process."""
    paths = _write_dataset(tmp_path)

    def cli(*extra, env=None):
        cmd = [sys.executable, "-m", "racon_tpu.cli", "--tpu",
               "-w", "100", "-q", "10", "-e", "0.3",
               "-m", "5", "-x", "-4", "-g", "-8", *extra, *paths]
        full_env = dict(os.environ, JAX_PLATFORMS="cpu",
                        RACON_TPU_PALLAS="0",
                        RACON_TPU_BATCH_WINDOWS="2")
        full_env.pop("RACON_TPU_FAULT", None)
        # conftest's 8-virtual-device XLA_FLAGS would round the 2-window
        # batches up to one 8-window dispatch and the kill would not fire
        full_env.pop("XLA_FLAGS", None)
        full_env.update(env or {})
        return subprocess.run(cmd, cwd=ROOT, env=full_env,
                              capture_output=True, timeout=540)

    baseline = cli()
    assert baseline.returncode == 0, baseline.stderr.decode()

    jp = str(tmp_path / "run.journal")
    # batch=2 windows/chunk, depth 2: chunk 0 installs (2 windows
    # journaled) when chunk 1 enters the pipe; the third dispatch kills
    killed = cli("--journal", jp,
                 env={"RACON_TPU_FAULT": "poa.run.xla:batch=2:kill=1"})
    assert killed.returncode != 0
    assert os.path.exists(jp)

    rp = str(tmp_path / "resume_report.json")
    resumed = cli("--resume-journal", jp, "--report", rp)
    assert resumed.returncode == 0, resumed.stderr.decode()
    assert resumed.stdout == baseline.stdout
    rep = json.loads(open(rp).read())
    cons = rep["phases"]["consensus"]
    assert sum(cons["served"].values()) == cons["total"]
    assert cons["served"].get("journal", 0) >= 1
