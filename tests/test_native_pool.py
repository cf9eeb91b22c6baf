"""The native pool's blocked parallel-for (ISSUE 40).

``rt::ThreadPool::parallel_for`` through its probe entry (every index
exactly once, at most ``num_threads`` runner tasks, the error a
task-per-item loop read in order raises), the two loops of the pipeline
that use it (``native.prepare.transmute``, ``native.build_windows.breaks``)
at 1, 2 and 13 threads, a walk that fails inside the blocked loop, their
marks' ``tasks``, and the two counters ``native.pool.items`` /
``native.pool.tasks``.
"""

import ctypes
import os

import numpy as np
import pytest

from racon_tpu import native, obs
from racon_tpu.pipeline import Pipeline
from racon_tpu.tools import simulate

_ARGS = dict(window_length=500, quality_threshold=10, error_threshold=0.3,
             match=5, mismatch=-4, gap=-8)
TRANSMUTE, BREAKS = 3, 5          # rt::Stage ids


@pytest.fixture(autouse=True)
def _disarm_after():
    yield
    obs.reset()


@pytest.fixture(scope="module")
def sample(tmp_path_factory):
    """A seeded 40 kb ONT-like workload: (reads, SAM, PAF, draft)."""
    d = str(tmp_path_factory.mktemp("pool_data"))
    assert simulate.main(["-o", d, "--mbp", "0.04", "--coverage", "12",
                          "--mean-read", "1000"]) == 0
    return (os.path.join(d, "reads.fastq"), os.path.join(d, "overlaps.sam"),
            os.path.join(d, "overlaps.paf"), os.path.join(d, "draft.fasta"))


def _pipeline(sample, threads, overlaps=1) -> Pipeline:
    return Pipeline(sample[0], sample[overlaps], sample[3],
                    num_threads=threads, **_ARGS)


def _polish(sample, threads, overlaps=1):
    """(FASTA records, window_info of every window) through the three
    coarse calls and the host consensus."""
    pl = _pipeline(sample, threads, overlaps)
    pl.prepare()
    if overlaps == 2:
        pl.align_jobs_cpu()
    pl.build_windows()
    infos = [pl.window_info(i) for i in range(pl.num_windows())]
    pl.consensus_cpu_all()
    return pl.stitch(), infos


# ------------------------------------------------------- the pool entry itself

def _probe(threads, n, fail_from=None):
    lib = native.load()
    visits = np.zeros(max(n, 1), dtype=np.uint32)
    tasks = lib.rt_pool_parallel_for_probe(
        threads, n, n if fail_from is None else fail_from,
        visits.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)))
    return tasks, visits[:n]


def _block(n, threads):
    """The rule of rt_threadpool.hpp, restated."""
    return min(256, max(1, n // (16 * threads)))


@pytest.mark.parametrize("threads,n", [
    (1, 0), (1, 1), (1, 1000), (2, 5), (13, 1), (13, 12), (13, 13),
    (13, 209), (13, 1900), (13, 53000), (30, 1900), (4, 100000)])
def test_every_index_once_and_at_most_a_task_a_thread(threads, n):
    tasks, visits = _probe(threads, n)
    native.check_error(native.load())
    assert (visits == 1).all()
    blocks = -(-n // _block(n, threads))
    assert tasks == min(threads, blocks)
    assert tasks <= threads and (tasks >= 1 or n == 0)


def test_block_size_follows_the_input():
    # the issue's three: the short-read job, an ONT job, the four-chip host
    assert _block(53000, 13) == 254
    assert _block(1900, 13) == 9
    assert _block(1900, 30) == 3
    assert _block(13, 13) == 1 and _block(10 ** 7, 13) == 256


@pytest.mark.parametrize("threads,n,fail_from", [
    (1, 100, 37), (13, 53000, 0), (13, 53000, 26001), (13, 53000, 52999),
    (2, 40, 39), (13, 5, 2)])
def test_the_lowest_failing_item_is_the_error_read(threads, n, fail_from):
    """Every item from ``fail_from`` on throws; a loop of one task an
    item whose futures are read in order raises the first, and so does
    the blocked one, after every runner has ended."""
    tasks, visits = _probe(threads, n, fail_from)
    assert tasks == 0
    with pytest.raises(native.NativeError) as e:
        native.check_error(native.load())
    assert str(e.value) == (
        f"[racon_tpu::parallel_for_probe] error: item {fail_from}!")
    # everything before the failure ran, nothing ran twice, and the
    # runners stopped taking blocks: a block each past the failure at most
    assert (visits[:fail_from] == 1).all() and (visits <= 1).all()
    assert visits[fail_from] == 1
    assert visits[fail_from:].sum() <= threads * _block(n, threads)
    # the library is whole afterwards
    tasks, visits = _probe(threads, n)
    assert tasks >= 1 and (visits == 1).all()


# ------------------------------------------------ the two loops that use it

@pytest.fixture(scope="module")
def one_thread(sample):
    return _polish(sample, 1)


@pytest.mark.parametrize("threads", [1, 2, 13])
def test_same_bytes_and_windows_at_any_thread_count(sample, one_thread,
                                                    threads):
    records, infos = _polish(sample, threads)
    assert records == one_thread[0]
    assert infos == one_thread[1] and len(infos) == 80
    assert len(records) == 1 and len(records[0][1]) > 39000


@pytest.mark.parametrize("threads", [2, 13])
def test_same_bytes_from_paf_where_the_walk_aligns_too(sample, threads):
    """PAF overlaps carry no CIGAR: after ``align_jobs_cpu`` the walks
    are CIGAR scans as from SAM; without it each walk aligns first."""
    aligned, infos = _polish(sample, threads, overlaps=2)
    pl = _pipeline(sample, threads, overlaps=2)
    pl.prepare()
    pl.build_windows()                  # find_breaking_points aligns
    assert [pl.window_info(i) for i in range(pl.num_windows())] == infos
    pl.consensus_cpu_all()
    assert pl.stitch() == aligned


@pytest.mark.parametrize("threads", [1, 2, 13])
def test_marks_carry_the_tasks_the_loop_enqueued(sample, threads):
    pl = _pipeline(sample, threads)
    pl.prepare()
    by_stage = {m[0]: m for m in pl.stage_marks()}
    targets, _, kept = pl._prepare_counts()
    sequences = by_stage[1][3] + targets
    assert by_stage[TRANSMUTE][3] == sequences            # items as before
    assert by_stage[TRANSMUTE][4] == min(
        threads, -(-sequences // _block(sequences, threads)))
    assert 1 <= by_stage[TRANSMUTE][4] <= threads
    pl.build_windows()
    breaks = pl.stage_marks()[0]
    assert breaks[0] == BREAKS and breaks[3] == kept
    assert breaks[4] == min(threads, -(-kept // _block(kept, threads)))
    assert 1 <= breaks[4] <= threads


def test_fused_initialize_carries_both_loops_tasks(sample):
    pl = _pipeline(sample, 2, overlaps=2)
    pl.initialize()
    by_stage = {m[0]: m for m in pl.stage_marks()}
    assert by_stage[TRANSMUTE][4] == 2 and by_stage[BREAKS][4] == 2


# --------------------------------------- a walk that fails, through the C API

@pytest.fixture(scope="module")
def oversized(tmp_path_factory):
    """A 230 kb draft, 22 reads of 1 kb cut from it and two unrelated
    reads of 120 kb whose PAF records span the whole draft: without a
    CIGAR their walks align first, and ``align_global_cigar`` refuses a
    traceback past its 3 GiB budget before it allocates anything.
    (reads, PAF, draft, the two failing items' indices)."""
    d = str(tmp_path_factory.mktemp("pool_oversized"))
    rng = np.random.default_rng(40)

    def bases(n):
        return bytes(rng.choice(np.frombuffer(b"ACGT", np.uint8), n)).decode()

    draft = bases(230_000)
    starts = list(range(5_000, 225_000, 10_000))
    reads = [(f"r{i}", draft[s:s + 1000], s) for i, s in enumerate(starts)]
    reads.insert(7, ("big_a", bases(120_001), 0))
    reads.insert(15, ("big_b", bases(120_000), 0))
    with open(os.path.join(d, "draft.fasta"), "w") as f:
        f.write(f">ctg\n{draft}\n")
    with open(os.path.join(d, "reads.fasta"), "w") as f:
        f.writelines(f">{name}\n{data}\n" for name, data, _ in reads)
    with open(os.path.join(d, "overlaps.paf"), "w") as f:
        for name, data, start in reads:
            end = 230_000 if name.startswith("big") else start + 1000
            f.write(f"{name}\t{len(data)}\t0\t{len(data)}\t+\tctg\t230000\t"
                    f"{start}\t{end}\t1000\t{end - start}\t60\n")
    return (os.path.join(d, "reads.fasta"), os.path.join(d, "overlaps.paf"),
            os.path.join(d, "draft.fasta"), (7, 15))


@pytest.mark.parametrize("threads", [2, 13])
def test_a_failing_walk_reads_as_before_and_leaves_the_handle_whole(
        oversized, threads):
    """Two walks fail; the loop of one task an item, its futures read in
    order, raised the first one's ``rt::Error``, and so does the blocked
    loop (the text below is the parent's, byte for byte). The handle
    then takes the two CIGARs, builds its windows and polishes."""
    reads, paf, draft, failing = oversized
    pl = Pipeline(reads, paf, draft, num_threads=threads,
                  **dict(_ARGS, error_threshold=0.6))
    pl.prepare()
    lengths = pl.align_job_lengths()
    assert [i for i, (q, _) in enumerate(lengths) if q > 1000] == list(failing)
    with pytest.raises(native.NativeError) as e:
        pl.build_windows()
    assert str(e.value) == ("[racon_tpu::align_global_cigar] error: alignment"
                            " of 120001 x 230000 exceeds memory budget!")
    assert pl.num_windows() == 0
    for i in failing:
        q, t = (int(v) for v in lengths[i])
        pl.set_job_cigar(i, f"{q}M{t - q}D")
    pl.build_windows()
    breaks = pl.stage_marks()[0]
    assert breaks[0] == BREAKS and breaks[3] == 24
    assert 1 <= breaks[4] <= threads
    assert pl.num_windows() == 460
    pl.consensus_cpu_all()
    (name, data), = pl.stitch()
    assert name.startswith("ctg") and len(data) > 230_000
    del pl                              # and it is destroyed whole


# ------------------------------------------------------------- the counters

def _counters():
    return obs.snapshot()["counters"]


def test_pool_counters_once_a_coarse_call(sample):
    obs.reset()
    obs.configure(metrics=True)
    pl = _pipeline(sample, 2)
    pl.prepare()
    transmute = {m[0]: m for m in pl.stage_marks()}[TRANSMUTE]
    assert _counters()["native.pool.items"] == transmute[3]
    assert _counters()["native.pool.tasks"] == transmute[4] == 2
    pl.build_windows()
    breaks = pl.stage_marks()[0]
    assert _counters()["native.pool.items"] == transmute[3] + breaks[3]
    assert _counters()["native.pool.tasks"] == 4
    # the spans carry what the counters summed
    spans = {e["name"]: e["args"] for e in obs.tracer().events()
             if e.get("ph") == "X"}
    assert spans["native.prepare.transmute"] == {
        "items": transmute[3], "tasks": 2}
    assert spans["native.build_windows.breaks"] == {
        "items": breaks[3], "tasks": 2}
    assert "tasks" not in spans["native.build_windows.layers"]
    # a call with no blocked loop counts nothing
    for i in range(pl.num_windows()):
        pl.set_consensus(i, b"ACGT", True)
    pl.stitch()
    assert _counters()["native.pool.tasks"] == 4


def test_fused_initialize_counts_both_loops_in_one_step(sample, monkeypatch):
    obs.reset()
    obs.configure(metrics=True)
    counted = []
    real = obs.count
    monkeypatch.setattr(obs, "count",
                        lambda name, n=1: (counted.append(name),
                                           real(name, n))[1])
    pl = _pipeline(sample, 13, overlaps=2)
    pl.initialize()
    assert counted.count("native.pool.items") == 1
    assert counted.count("native.pool.tasks") == 1
    by_stage = {m[0]: m for m in pl.stage_marks()}
    assert _counters()["native.pool.items"] == (by_stage[TRANSMUTE][3]
                                                + by_stage[BREAKS][3])
    assert _counters()["native.pool.tasks"] == (by_stage[TRANSMUTE][4]
                                                + by_stage[BREAKS][4])
    # ~1 000 items over at most 26 tasks: the metric reads over 1
    assert (_counters()["native.pool.items"]
            > _counters()["native.pool.tasks"])


def test_disarmed_counts_nothing_and_reads_no_mark(sample, monkeypatch):
    obs.reset()
    counted = []
    monkeypatch.setattr(obs, "count", lambda *a, **k: counted.append(a))
    monkeypatch.setattr(Pipeline, "stage_marks", lambda self: 1 / 0)
    pl = _pipeline(sample, 2)
    pl.prepare()
    pl.build_windows()
    assert obs.snapshot() is None
    assert not [a for a in counted if a[0].startswith("native.pool.")]
