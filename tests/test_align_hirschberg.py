"""Hirschberg Pallas aligner (ops/align_pallas.py) in interpret mode:
the emitted op path must be a valid alignment whose cost equals the true
(unbanded) edit distance whenever the optimal path stays in band.
"""

import functools
import random

import numpy as np
import pytest

from racon_tpu import native
from racon_tpu.ops import align_pallas
from racon_tpu.ops.encoding import encode
from tests.test_align import mutate


def path_cost(ops: np.ndarray, q: bytes, t: bytes) -> int:
    """Edit cost of the forward-ordered op path (0=M, 1=I, 2=D)."""
    cost = 0
    qi = ti = 0
    for op in ops:
        if op == 0:
            cost += q[qi] != t[ti]
            qi += 1
            ti += 1
        elif op == 1:
            cost += 1
            qi += 1
        else:
            cost += 1
            ti += 1
    assert qi == len(q) and ti == len(t), (qi, len(q), ti, len(t))
    return cost


def _align_one(q: bytes, t: bytes):
    res = align_pallas.align_pairs(
        [(encode(np.frombuffer(q, np.uint8)).astype(np.int32),
          encode(np.frombuffer(t, np.uint8)).astype(np.int32))],
        interpret=True)
    return res[0]


def _rand(rng, n):
    return bytes(rng.choice(b"ACGT") for _ in range(n))


def test_base_case_exact():
    rng = random.Random(1)
    q = _rand(rng, 200)
    t = mutate(q, 0.10, rng)
    ops = _align_one(q, t)
    assert ops is not None
    assert path_cost(ops, q, t) == native.edit_distance(q, t)


def test_multi_round_split_exact():
    rng = random.Random(2)
    q = _rand(rng, 1400)
    t = mutate(q, 0.08, rng)
    ops = _align_one(q, t)
    assert ops is not None
    assert path_cost(ops, q, t) == native.edit_distance(q, t)


def test_identical_pair_all_match():
    rng = random.Random(3)
    q = _rand(rng, 700)
    ops = _align_one(q, q)
    assert ops is not None
    assert (ops == 0).all()
    assert len(ops) == len(q)


def test_length_skew_within_band():
    rng = random.Random(4)
    q = _rand(rng, 900)
    t = q[:400] + q[520:]  # 120-base deletion
    ops = _align_one(q, t)
    assert ops is not None
    assert path_cost(ops, q, t) == native.edit_distance(q, t)


def test_oversize_band_goes_to_host():
    q = b"A" * 100
    t = b"A" * 3000  # drift beyond the largest band bucket
    assert _align_one(q, t) is None


def test_polish_with_hirschberg_engine(tmp_path, monkeypatch):
    """RACON_TPU_DEVICE_ALIGNER=hirschberg serves the PAF alignment phase
    through the Pallas engine end-to-end; consensus matches the
    host-aligned run within tie-break noise."""
    import racon_tpu

    rng = random.Random(11)
    truth = "".join(rng.choice("ACGT") for _ in range(400))

    def mut(s, rate):
        out = []
        for c in s:
            r = rng.random()
            if r < rate / 2:
                out.append(rng.choice("ACGT"))
            elif r < rate:
                continue
            else:
                out.append(c)
        return "".join(out)

    draft = mut(truth, 0.02)
    reads = [mut(truth, 0.05) for _ in range(5)]
    with open(tmp_path / "t.fasta", "w") as f:
        f.write(f">t\n{draft}\n")
    with open(tmp_path / "r.fasta", "w") as rf, \
            open(tmp_path / "o.paf", "w") as of:
        for i, r in enumerate(reads):
            rf.write(f">r{i}\n{r}\n")
            of.write(f"r{i}\t{len(r)}\t0\t{len(r)}\t+\tt\t{len(draft)}\t0\t"
                     f"{len(draft)}\t{min(len(r), len(draft))}\t"
                     f"{max(len(r), len(draft))}\t60\n")

    def run(engine):
        monkeypatch.setenv("RACON_TPU_DEVICE_ALIGNER", engine)
        p = racon_tpu.TpuPolisher(str(tmp_path / "r.fasta"),
                                  str(tmp_path / "o.paf"),
                                  str(tmp_path / "t.fasta"),
                                  window_length=100, match=5, mismatch=-4,
                                  gap=-8)
        p.initialize()
        return p.polish(True)

    dev = run("hirschberg")
    host = run("0")
    assert len(dev) == len(host) == 1
    d = native.edit_distance(dev[0][1].encode(), host[0][1].encode())
    assert d <= 2, d
    assert native.edit_distance(dev[0][1].encode(), truth.encode()) <= 8


def test_sharded_batches_over_mesh_exact(monkeypatch):
    """A homogeneous batch that divides the 8-device mesh runs the edge
    and base kernels under shard_map (the consensus path's no-collective
    batch striping) and must emit the same exact-optimal paths as the
    single-device build."""
    import jax

    if len(jax.devices()) < 8:
        pytest.skip("needs the suite's 8-virtual-device mesh")

    shard_calls = []
    real = align_pallas._shard_over_mesh

    def recording(build_local, batch, n_in, n_out):
        out = real(build_local, batch, n_in, n_out)
        shard_calls.append((batch, out is not None))
        return out

    monkeypatch.setattr(align_pallas, "_shard_over_mesh", recording)
    # fresh builders so cached single-device jits can't bypass the recorder
    align_pallas._build_edge_kernel.cache_clear()
    align_pallas._build_base_kernel.cache_clear()

    rng = random.Random(23)
    pairs = []
    for _ in range(8):  # homogeneous bucket: same lengths -> same (rcap, K)
        q = _rand(rng, 700)
        t = mutate(q, 0.06, rng)
        pairs.append((q, t))
    enc = [(encode(np.frombuffer(q, np.uint8)).astype(np.int32),
            encode(np.frombuffer(t, np.uint8)).astype(np.int32))
           for q, t in pairs]
    results = align_pallas.align_pairs(enc, interpret=True)

    assert any(ok for _, ok in shard_calls), shard_calls  # mesh engaged
    for (q, t), ops in zip(pairs, results):
        assert ops is not None
        assert path_cost(ops, q, t) == native.edit_distance(q, t)

    align_pallas._build_edge_kernel.cache_clear()
    align_pallas._build_base_kernel.cache_clear()


def test_engine_auto_defaults_to_hirschberg_on_tpu(monkeypatch):
    """With no env override, the production tier is the Hirschberg engine
    on a TPU backend and the host Myers aligner elsewhere — the same
    device-on-TPU posture as the consensus path."""
    from racon_tpu.ops import align_driver

    monkeypatch.delenv("RACON_TPU_DEVICE_ALIGNER", raising=False)
    monkeypatch.setattr(align_driver, "_on_tpu", lambda: True)
    assert align_driver._engine() == "hirschberg"
    monkeypatch.setattr(align_driver, "_on_tpu", lambda: False)
    assert align_driver._engine() == "host"
    monkeypatch.setenv("RACON_TPU_DEVICE_ALIGNER", "host")
    monkeypatch.setattr(align_driver, "_on_tpu", lambda: True)
    assert align_driver._engine() == "host"


def test_engine_failure_degrades_to_host(tmp_path, monkeypatch):
    """A hirschberg kernel failure mid-phase must not abort the polish:
    the remaining jobs stay CIGAR-less and the host aligner finishes
    them, mirroring the consensus driver's degrade lattice."""
    import racon_tpu
    from racon_tpu.ops import align_driver, align_pallas as ap

    rng = random.Random(17)
    truth = "".join(rng.choice("ACGT") for _ in range(300))
    reads = [truth for _ in range(3)]
    with open(tmp_path / "t.fasta", "w") as f:
        f.write(f">t\n{truth}\n")
    with open(tmp_path / "r.fasta", "w") as rf, \
            open(tmp_path / "o.paf", "w") as of:
        for i, r in enumerate(reads):
            rf.write(f">r{i}\n{r}\n")
            of.write(f"r{i}\t{len(r)}\t0\t{len(r)}\t+\tt\t{len(truth)}\t0\t"
                     f"{len(truth)}\t{len(r)}\t{len(r)}\t60\n")

    def boom(pairs, *, interpret=None):
        raise RuntimeError("synthetic Mosaic failure")

    monkeypatch.setenv("RACON_TPU_DEVICE_ALIGNER", "hirschberg")
    monkeypatch.setattr(ap, "align_pairs", boom)
    p = racon_tpu.TpuPolisher(str(tmp_path / "r.fasta"),
                              str(tmp_path / "o.paf"),
                              str(tmp_path / "t.fasta"),
                              window_length=100, match=5, mismatch=-4,
                              gap=-8)
    p.initialize()
    res = p.polish(True)
    assert len(res) == 1
    assert res[0][1] == truth

    # and the driver's stats record the degrade: nothing device-served
    pipe = racon_tpu.pipeline.Pipeline(
        str(tmp_path / "r.fasta"), str(tmp_path / "o.paf"),
        str(tmp_path / "t.fasta"), window_length=100, match=5,
        mismatch=-4, gap=-8)
    pipe.prepare()
    stats = align_driver.run_alignment_phase(pipe)
    assert stats["device"] == 0
    assert stats["host"] == pipe.num_align_jobs()


def test_cigar_roundtrip():
    rng = random.Random(5)
    q = _rand(rng, 300)
    t = mutate(q, 0.1, rng)
    ops = _align_one(q, t)
    cigar = align_pallas.ops_to_cigar(ops)
    qc = tc = 0
    num = ""
    for ch in cigar:
        if ch.isdigit():
            num += ch
        else:
            n = int(num)
            num = ""
            if ch in "MI":
                qc += n
            if ch in "MD":
                tc += n
    assert qc == len(q) and tc == len(t)


@pytest.mark.parametrize("seed", [31, 62])
def test_hirschberg_fuzz_exact(seed):
    """Seeded random pairs across the length/error envelope phase 1
    serves (short fragments up to multi-kb reads, 2-18% divergence,
    length skew): every emitted path must be valid and cost-optimal;
    None (band escape / oversize) is acceptable only where the band
    rule says so."""
    rng = random.Random(seed)
    pairs = []
    for _ in range(6):
        n = rng.randrange(60, 2500)
        q = _rand(rng, n)
        t = mutate(q, rng.uniform(0.02, 0.18), rng)
        pairs.append((q, t))
    enc = [(encode(np.frombuffer(q, np.uint8)).astype(np.int32),
            encode(np.frombuffer(t, np.uint8)).astype(np.int32))
           for q, t in pairs]
    results = align_pallas.align_pairs(enc, interpret=True)
    n_served = 0
    for (q, t), ops in zip(pairs, results):
        if ops is None:
            continue
        n_served += 1
        assert path_cost(ops, q, t) == native.edit_distance(q, t), \
            (seed, len(q), len(t))
    assert n_served >= len(pairs) - 1, "band escapes should be rare here"


# -- eight tasks per grid program (lock-step groups) -----------------------

def _enc(q: bytes, t: bytes):
    return (encode(np.frombuffer(q, np.uint8)).astype(np.int32),
            encode(np.frombuffer(t, np.uint8)).astype(np.int32))


def _fresh_kernels():
    # the batch-keyed jitted closures bake in the shard_map decision
    align_pallas._build_edge_kernel.cache_clear()
    align_pallas._build_base_kernel.cache_clear()


@pytest.fixture(params=["one_device", "mesh"])
def placement(request, monkeypatch):
    """Both ways a launch reaches its programs: the single-device jit,
    and the suite's 8-device mesh, where a shard of fewer than GROUP
    rows is one program with idle sublanes and larger launches are dealt
    round the shards."""
    from racon_tpu.parallel import reset_partitioner

    if request.param == "one_device":
        monkeypatch.setenv("RACON_TPU_SHARD", "0")
    reset_partitioner()
    _fresh_kernels()
    yield request.param
    reset_partitioner()
    _fresh_kernels()


@functools.lru_cache(maxsize=1)
def _pool():
    """65 pairs whose first round is one (rcap 512, K 256) launch, from
    the shortest task an edge kernel sees (257 rows: halves of 128 and
    129) to the bucket's cap (1024 rows: R = rcap), plus base-only pairs
    of 1 and 256 rows, which share a base program with everything else."""
    rng = random.Random(77)
    lengths = [257, 1024, 1, 256] + [rng.randrange(258, 1024)
                                     for _ in range(61)]
    pairs = []
    for n in lengths:
        q = _rand(rng, n)
        pairs.append((q, mutate(q, 0.04, rng) if n > 1 else q))
    return pairs


@functools.lru_cache(maxsize=1)
def _pool_alone():
    """Every pair of the pool through align_pairs in a call of its own:
    launches of one task, seven idle sublanes beside it."""
    return [align_pallas.align_pairs([_enc(q, t)], interpret=True)[0]
            for q, t in _pool()]


@pytest.mark.parametrize("n_tasks", [1, 7, 8, 9, 65])
def test_grouping_is_invisible(placement, n_tasks):
    """A task's result does not depend on which tasks share its program:
    the same pairs in shuffled order, in launches of 1, 7, 8, 9 and 65
    tasks (one group, one short of it, one over, many with whole pad
    groups), give the op arrays each pair gives alone — and those cost
    what the host aligner's path costs (op for op the two differ in
    tie-breaks, as they did before)."""
    pool, alone = _pool(), _pool_alone()
    order = list(range(len(pool)))
    random.Random(n_tasks).shuffle(order)
    # the extremes first, so every size past 1 holds R = 129 next to
    # R = rcap in one program (4 for the base kernel: R = 1 next to 256)
    pick = ([0, 1, 2, 3] + [i for i in order if i > 3])[:n_tasks]
    random.Random(n_tasks + 1).shuffle(pick)
    got = align_pallas.align_pairs([_enc(*pool[i]) for i in pick],
                                   interpret=True)
    for i, ops in zip(pick, got):
        assert ops is not None and alone[i] is not None, i
        np.testing.assert_array_equal(ops, alone[i], err_msg=str(i))
        q, t = pool[i]
        assert path_cost(ops, q, t) == native.edit_distance(q, t), i


@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "bwd"])
def test_one_row_task_beside_a_full_one(backward):
    """The edge kernel itself, R = 1 next to R = rcap in one program:
    the short task idles through 511 steps carrying its row, the long
    one is not cut short.  Each equals what it gives in a program of its
    own."""
    rng = random.Random(9)
    rcap, K = 512, 256
    q = _rand(rng, rcap)
    pairs = [_enc(q, mutate(q, 0.05, rng)), _enc(b"A", b"AC")]
    bands = {0: (K, -100), 1: (K, -100)}
    tasks = [align_pallas._Task(0, 0, rcap, 0, len(pairs[0][1])),
             align_pallas._Task(1, 0, 1, 0, 2)]
    kern = align_pallas._build_edge_kernel(rcap, K, backward, True)

    def run(slots):
        args = align_pallas._task_arrays(pairs, slots, bands, rcap, K,
                                         backward)
        return np.asarray(kern(len(slots))(*args))

    both = run(tasks + [None] * 6)
    assert (both[0] < align_pallas.INF).any()
    assert (both[1] < align_pallas.INF).any()
    for g, t in enumerate(tasks):
        np.testing.assert_array_equal(both[g], run([t] + [None] * 7)[0])
    assert (both[2:] >= 0).all()        # idle sublanes: any value, no fault


@pytest.mark.parametrize("placement", ["one_device"], indirect=True)
def test_pad_task_never_lengthens_a_group(placement):
    """The counter pair that says how well the groups engage, checked by
    hand: nine equal pairs of 600 rows.  Round 1 is one launch of 9
    (padded to 16: a full program and one of 1 task + 7 pads), round 2
    one of 18 (padded to 32: 2 + a partial + a program of pads only),
    the base launch 36 (64: 4 + a partial + 3 of pads only).  A pad has
    R = 0: it adds nothing to ``rows.real``, and a program of pads adds
    nothing to ``rows.slots``."""
    from racon_tpu import obs

    rng = random.Random(21)
    pairs = []
    for _ in range(9):
        q = _rand(rng, 600)
        pairs.append(_enc(q, q))
    obs.reset()
    obs.configure(metrics=True)
    try:
        res = align_pallas.align_pairs(pairs, interpret=True)
        counters = obs.snapshot()["counters"]
    finally:
        obs.reset()
    assert all((r == 0).all() and len(r) == 600 for r in res)
    # every round walks every row of every pair once (forward half +
    # backward half), and so does the base launch
    assert counters["align.lockstep.rows.real"] == 3 * 9 * 600
    g = align_pallas.GROUP
    assert counters["align.lockstep.rows.slots"] == g * (
        2 * 2 * 300          # round 1, fwd + bwd: two programs at R 300
        + 2 * 3 * 150        # round 2: three programs at R 150, one at 0
        + 5 * 150)           # base: five programs at R 150, three at 0
    assert counters["align.tasks.pad"] == 2 * 7 + 2 * 14 + 28
