"""The overlapped host fallback (ISSUE 25): a window the device path
gives up on goes to the native thread pool the moment it is found
(`poa_driver._HostFallback`), and the driver joins the pool once.

(a) the object against a fake pipeline: submit on append, arrival order,
journal and stats written by the calling thread only, a worker's failure
surfacing at the join, nothing native for an empty fallback;
(b) through the real native library, with the kernel's `failed` flag
stubbed for chosen windows: FASTA and journal bytes identical to the
serial loop the driver ran before, at 1 and at 4 pool threads.
"""

import random
import threading

import pytest

import racon_tpu
from racon_tpu import native, obs
from racon_tpu.ops import poa_driver
from racon_tpu.ops.poa_driver import _HostFallback
from racon_tpu.pipeline import Pipeline


# ------------------------------------------------------------------ fakes

class FakePipeline:
    """Records every call with the thread that made it."""

    def __init__(self, hidden=0, fail=False):
        self.calls = []
        self.hidden = hidden
        self.fail = fail

    def _note(self, what, *args):
        self.calls.append((what, args, threading.get_ident()))

    def consensus_cpu_submit(self, i):
        self._note("submit", i)

    def consensus_cpu_join(self, windows):
        self._note("join", tuple(windows))
        if self.fail:
            raise native.NativeError("host consensus failed")
        return [i % 2 == 0 for i in windows], self.hidden

    def window_info(self, i):
        self._note("window_info", i)
        return (5, 100, i % 7, True, 400, i // 7)

    def get_consensus(self, i):
        return b"ACGT" * (i + 1)

    def of(self, what):
        return [args for w, args, _ in self.calls if w == what]


class FakeJournal:
    def __init__(self):
        self.records = []
        self.threads = set()

    def append_window(self, i, contig, rank, tier, consensus, polished):
        self.threads.add(threading.get_ident())
        self.records.append((i, contig, rank, tier, consensus, polished))


class WatchedStats(dict):
    """Which threads wrote to the driver's stats."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.writers = set()

    def __setitem__(self, k, v):
        self.writers.add(threading.get_ident())
        super().__setitem__(k, v)


def spy(monkeypatch, owner, name, log, key=lambda *a: a[0]):
    """Wrap `owner.name` so that each call first appends `key(*args)`
    (by default the first argument after self) to `log`."""
    real = getattr(owner, name)

    def wrapper(self, *args, **kwargs):
        log.append(key(*args, **kwargs))
        return real(self, *args, **kwargs)

    monkeypatch.setattr(owner, name, wrapper)


def _counters_after(fn):
    """The obs counters `fn` left behind, with metrics armed around it."""
    obs.reset()
    obs.configure(metrics=True)
    try:
        fn()
        return obs.snapshot()["counters"]
    finally:
        obs.reset()


# --------------------------------------------------- (a) the object alone

def test_append_submits_at_once_and_remembers_arrival_order():
    pipe = FakePipeline()
    fb = _HostFallback(pipe)
    fb.append(7)
    assert pipe.of("submit") == [(7,)]      # before anything is joined
    fb.extend([3, 11])
    fb.extend(i for i in (5,))              # any iterable, as a list takes
    assert pipe.of("submit") == [(7,), (3,), (11,), (5,)]
    assert len(fb) == 4 and not pipe.of("join")
    stats = WatchedStats(host_fallback=0)
    assert fb.join(None, stats) == [7, 3, 11, 5]
    assert pipe.of("join") == [((7, 3, 11, 5),)]
    assert stats["host_fallback"] == 4
    assert not pipe.of("window_info")       # no journal: no metadata call


def test_join_writes_journal_and_stats_from_the_calling_thread_in_order():
    pipe, journal = FakePipeline(), FakeJournal()
    stats = WatchedStats(host_fallback=0)
    fb = _HostFallback(pipe)
    # the producers run on the driver thread; the join may run elsewhere
    # (it does not here or in the driver) — what matters is that nothing
    # is written before it, and everything by the thread that calls it
    for i in (9, 2, 4):
        fb.append(i)
    assert not journal.records and not stats.writers
    out = {}
    t = threading.Thread(target=lambda: out.update(order=fb.join(journal,
                                                                 stats)))
    t.start()
    t.join()
    assert out["order"] == [9, 2, 4]
    assert [r[0] for r in journal.records] == [9, 2, 4]
    assert journal.threads == stats.writers == {t.ident}
    # the record is the serial loop's: contig, rank, "host", bytes, flag
    assert journal.records[0] == (9, 1, 2, "host", b"ACGT" * 10, False)
    assert journal.records[1] == (2, 0, 2, "host", b"ACGT" * 3, True)
    assert stats["host_fallback"] == 3


def test_worker_failure_surfaces_at_join_and_writes_nothing():
    pipe, journal = FakePipeline(fail=True), FakeJournal()
    stats = WatchedStats(host_fallback=0)
    fb = _HostFallback(pipe)
    fb.extend([1, 2])                       # the appends themselves pass
    with pytest.raises(native.NativeError):
        fb.join(journal, stats)
    assert not journal.records and stats["host_fallback"] == 0


def test_empty_fallback_makes_no_native_call():
    pipe, journal = FakePipeline(), FakeJournal()
    stats = WatchedStats(host_fallback=0)
    fb = _HostFallback(pipe)
    counters = _counters_after(lambda: (fb.join(journal, stats), fb.drain()))
    assert pipe.calls == [] and not stats.writers
    assert not any(k.startswith("poa.fallback.") for k in counters)


def test_join_counts_hidden_and_exposed_windows():
    pipe = FakePipeline(hidden=3)
    fb = _HostFallback(pipe)
    fb.extend(range(5))
    counters = _counters_after(lambda: fb.join(None, {"host_fallback": 0}))
    assert counters["poa.fallback.hidden"] == 3
    assert counters["poa.fallback.exposed"] == 2


def test_failing_phase_drains_the_pool_and_keeps_its_own_error(monkeypatch):
    """An error anywhere in the phase waits the workers out (no pool
    worker outlives the phase) and is the error the caller sees, even
    when the drain itself fails."""
    pipe = FakePipeline(fail=True)
    pipe.num_windows = lambda: 1

    def boom(pipeline, fallback, *a):
        fallback.append(0)
        raise KeyError("the phase's own error")

    monkeypatch.setattr(poa_driver, "_consensus_phase", boom)
    with pytest.raises(KeyError):
        poa_driver.run_consensus_phase(pipe, match=5, mismatch=-4, gap=-8,
                                       trim=True)
    assert pipe.of("submit") == [(0,)] and pipe.of("join") == [((),)]


# ------------------------------- (b) through the real native library

class SerialFallback(list):
    """The driver's fallback as it was before ISSUE 25: a plain list,
    walked one window at a time by the driver thread after the flush."""

    def __init__(self, pipeline, trim=False):
        super().__init__()
        self._pipeline = pipeline

    def join(self, journal, stats):
        for i in self:
            polished = self._pipeline.consensus_cpu_one(i)
            if journal is not None:
                _, _, rank, _, _, tid = self._pipeline.window_info(i)
                journal.append_window(i, tid, rank, "host",
                                      self._pipeline.get_consensus(i),
                                      polished)
            stats["host_fallback"] += 1
        return list(self)

    def drain(self):
        pass


def _noisy_dataset(tmp_path, length=3000, n_reads=6):
    """One target, substitution-noisy reads over its whole length: 30
    windows of 100 whose host consensus is real POA work."""
    rng = random.Random(25)
    truth = "".join(rng.choice("ACGT") for _ in range(length))

    def noisy(seq, rate):
        return "".join(rng.choice("ACGT".replace(c, ""))
                       if rng.random() < rate else c for c in seq)

    with open(tmp_path / "targets.fasta", "w") as f:
        f.write(f">t0\n{noisy(truth, 0.03)}\n")
    with open(tmp_path / "reads.fasta", "w") as rf, \
            open(tmp_path / "ovl.sam", "w") as of:
        of.write("@HD\tVN:1.6\n")
        for i in range(n_reads):
            read = noisy(truth, 0.05)
            rf.write(f">r{i}\n{read}\n")
            of.write(f"r{i}\t0\tt0\t1\t60\t{length}M\t*\t0\t0\t{read}\t*\n")
    return (str(tmp_path / "reads.fasta"), str(tmp_path / "ovl.sam"),
            str(tmp_path / "targets.fasta"))


REJECTED = frozenset({1, 5, 6, 12, 20, 29})     # first, middle, last batch


def _reject_chosen_windows(monkeypatch):
    """The kernel stub: whatever the device computed, the chosen windows
    come back with the kernel's `failed` flag set."""
    real_install = poa_driver._install

    def install(pipeline, chunk, results, *a, **kw):
        results = list(results)
        failed = results[3].copy()
        for bi, (i, _, _) in enumerate(chunk):
            if i in REJECTED:
                failed[bi] = 1
        results[3] = failed
        return real_install(pipeline, chunk, tuple(results), *a, **kw)

    monkeypatch.setattr(poa_driver, "_install", install)


def _polish(paths, journal_path, threads):
    p = racon_tpu.create_polisher(
        *paths, backend="tpu", journal_path=journal_path,
        window_length=100, quality_threshold=10, error_threshold=0.3,
        match=5, mismatch=-4, gap=-8, num_threads=threads)
    p.initialize()
    out = p.polish(True)
    with open(journal_path, "rb") as f:
        return out, f.read(), p.report.as_dict()["phases"]["consensus"]


@pytest.mark.parametrize("threads", [1, 4])
def test_overlapped_fallback_matches_the_serial_loop_byte_for_byte(
        tmp_path, monkeypatch, threads):
    paths = _noisy_dataset(tmp_path)
    for k, v in {"RACON_TPU_PALLAS": "0",
                 "RACON_TPU_BATCH_WINDOWS": "8"}.items():
        monkeypatch.setenv(k, v)
    _reject_chosen_windows(monkeypatch)

    submitted, external = [], []
    spy(monkeypatch, Pipeline, "consensus_cpu_submit", submitted)
    spy(monkeypatch, Pipeline, "consensus_cpu_one", external)

    fasta, journal, cons = _polish(paths, str(tmp_path / "new.journal"),
                                   threads)
    assert sorted(submitted) == sorted(REJECTED) and not external
    assert cons["served"]["host"] == len(REJECTED)
    assert cons["served"]["xla"] == 30 - len(REJECTED)
    assert cons["extra"]["device_rejected"] == len(REJECTED)
    assert sum(cons["served"].values()) == cons["total"] == 30

    with monkeypatch.context() as m:
        m.setattr(poa_driver, "_HostFallback", SerialFallback)
        fasta0, journal0, cons0 = _polish(
            paths, str(tmp_path / "serial.journal"), threads)
    assert sorted(external) == sorted(REJECTED)    # the old loop did run
    assert fasta == fasta0
    assert journal == journal0
    assert cons["served"] == cons0["served"]
    # host records close the journal, in arrival order
    tail = journal.decode().splitlines()[-len(REJECTED):]
    assert all('"tier": "host"' in line for line in tail)


def test_native_submit_and_join_match_consensus_cpu_one(tmp_path):
    """The binding alone: pool workers compute what the external caller
    computes, join reports flags in the caller's order, an index out of
    range is refused at submit, a join with nothing submitted is free."""
    paths = _noisy_dataset(tmp_path, length=1000)
    args = dict(window_length=100, quality_threshold=10, error_threshold=0.3,
                match=5, mismatch=-4, gap=-8)
    pool = Pipeline(*paths, num_threads=3, **args)
    one = Pipeline(*paths, num_threads=1, **args)
    for p in (pool, one):
        p.initialize()
    assert pool.consensus_cpu_join(()) == ([], 0)
    with pytest.raises(native.NativeError, match="out of range"):
        pool.consensus_cpu_submit(pool.num_windows())
    order = [7, 0, 3, 9]
    for i in order:
        pool.consensus_cpu_submit(i)
    polished, hidden = pool.consensus_cpu_join(order)
    assert 0 <= hidden <= len(order)
    assert polished == [one.consensus_cpu_one(i) for i in order]
    assert all(polished)
    for i in order:
        assert pool.get_consensus(i) == one.get_consensus(i)
