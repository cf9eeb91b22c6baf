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


def _small_paf_polish(tmp_path, monkeypatch, engines):
    """A 400-base draft and five reads as a CIGAR-less PAF, polished
    once per value of RACON_TPU_DEVICE_ALIGNER: (truth, polishers)."""
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
        return p.polish(True), p

    return truth, [run(engine) for engine in engines]


def test_polish_with_hirschberg_engine(tmp_path, monkeypatch):
    """RACON_TPU_DEVICE_ALIGNER=hirschberg serves the PAF alignment phase
    through the Pallas engine end-to-end; consensus matches the
    host-aligned run within tie-break noise."""
    truth, ((dev, _), (host, _)) = _small_paf_polish(
        tmp_path, monkeypatch, ("hirschberg", "0"))
    assert len(dev) == len(host) == 1
    d = native.edit_distance(dev[0][1].encode(), host[0][1].encode())
    assert d <= 2, d
    assert native.edit_distance(dev[0][1].encode(), truth.encode()) <= 8


@pytest.mark.parametrize("value", ["1", "xla"])
def test_retired_engine_values_fall_to_host(tmp_path, monkeypatch, capsys,
                                            value):
    """The moves-matrix aligner went in PR 46 and its two spellings of
    the knob with it: they fall under the rule of every unknown value,
    one warning that names the valid ones and the host aligner for every
    pair, byte-equal to `host`."""
    _, ((res, p), (host, _)) = _small_paf_polish(
        tmp_path, monkeypatch, (value, "host"))
    err = capsys.readouterr().err
    assert err.count("unknown RACON_TPU_DEVICE_ALIGNER") == 1, err
    assert "(valid: auto, 0/host, hirschberg)" in err
    al = p.report.as_dict()["phases"]["alignment"]
    assert al["served"]["host"] == al["total"] == 5
    assert not al["degradations"]
    assert res == host


@pytest.mark.parametrize("shards", [4, 8])
def test_sharded_batches_over_mesh_exact(monkeypatch, shards):
    """Every launch of a homogeneous batch runs the edge and base kernels
    under shard_map over the 4- or 8-device mesh (the consensus path's
    no-collective batch striping), says so in its counters and span
    arguments, and emits the same exact-optimal paths as the
    single-device build."""
    from racon_tpu import obs
    from racon_tpu.parallel import reset_partitioner

    monkeypatch.setenv("RACON_TPU_MESH_SHAPE", str(shards))
    # fresh builders: the jitted kernels keep the mesh they were built under
    align_pallas._build_edge_kernel.cache_clear()
    align_pallas._build_base_kernel.cache_clear()
    reset_partitioner()

    rng = random.Random(23)
    pairs = []
    for _ in range(8):  # homogeneous bucket: same lengths -> same (rcap, K)
        q = _rand(rng, 700)
        t = mutate(q, 0.06, rng)
        pairs.append((q, t))
    enc = [(encode(np.frombuffer(q, np.uint8)).astype(np.int32),
            encode(np.frombuffer(t, np.uint8)).astype(np.int32))
           for q, t in pairs]
    obs.reset()
    obs.configure(metrics=True)
    try:
        results = align_pallas.align_pairs(enc, interpret=True)
        counters = obs.snapshot()["counters"]
        launches = [e["args"] for e in obs.tracer().events()
                    if e["ph"] == "X" and e["name"] == "align.dispatch"]
    finally:
        obs.reset()
        align_pallas._build_edge_kernel.cache_clear()
        align_pallas._build_base_kernel.cache_clear()

    # two rounds (8 tasks of 700 rows, 16 of 350) and the base launch
    # (32 of 175): each one over the mesh, none on one device
    assert [(a["kernel"], a["B"]) for a in launches] == [
        ("edge_fwd", 8), ("edge_bwd", 8), ("edge_fwd", 16),
        ("edge_bwd", 16), ("base", 32)]
    assert all(a["shards"] == shards for a in launches)
    assert counters["align.mesh.launches.sharded"] == 5
    assert "align.mesh.launches.single" not in counters
    assert counters["align.mesh.rows.real"] == 2 * 8 + 2 * 16 + 32
    assert counters["align.mesh.rows.pad"] == 0
    assert all(counters[f"shard.rows.d{i}"] == 80 // shards
               for i in range(shards))
    # a share of eight or more rows is whole programs (the base launch on
    # four devices), a smaller one is one short program per shard
    whole = 32 // 8 if shards == 4 else 0
    assert counters.get("align.mesh.programs.whole", 0) == whole
    assert counters["align.mesh.programs.short"] \
        == shards * (5 if shards == 8 else 4)
    for (q, t), ops in zip(pairs, results):
        assert ops is not None
        assert path_cost(ops, q, t) == native.edit_distance(q, t)


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

    def boom(pairs, **kwargs):
        raise RuntimeError("synthetic Mosaic failure")

    monkeypatch.setenv("RACON_TPU_DEVICE_ALIGNER", "hirschberg")
    # what a cohort's dispatch and every lattice attempt run
    monkeypatch.setattr(ap, "align_steps", boom)
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

def _run_state(pairs, K=None, gdmin=None):
    """The per-pair state of an `align_steps` call over `pairs`, with the
    band and its origin forced where a test fixes them by hand."""
    state = align_pallas._Run(pairs, None, True, align_pallas._InFlight())
    if K is not None:
        state.K[:] = K
    if gdmin is not None:
        state.table[:, 4] = state.gdmin[:] = gdmin
    return state


def _slots(tasks, B):
    """A launch's task table: `tasks` then pad slots (pair -1)."""
    slots = np.zeros((B, 5), np.int32)
    slots[:len(tasks)] = tasks
    slots[len(tasks):, 0] = -1
    return slots


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
    state = _run_state(pairs, K=K, gdmin=-100)
    tasks = np.array([[0, 0, rcap, 0, len(pairs[0][1])], [1, 0, 1, 0, 2]],
                     np.int32)
    kern = align_pallas._build_edge_kernel(rcap, K, backward, True)

    def run(group):
        args = align_pallas._pack_launch(state, _slots(group, 8), rcap, K,
                                         backward)
        return np.asarray(kern(8)(*args))

    both = run(tasks)
    assert (both[0] < align_pallas.INF).any()
    assert (both[1] < align_pallas.INF).any()
    for g in range(2):
        np.testing.assert_array_equal(both[g], run(tasks[g:g + 1])[0])
    assert (both[2:] >= 0).all()        # idle sublanes: any value, no fault


# -- the base kernel's joint walk -------------------------------------------

def _walk_program(case):
    """One base program's eight slots for `case`: -> (K, pairs, tasks,
    per-pair gdmin, slots whose scalars the test overwrites {slot: (R, S,
    dmin)}, the slots that must come out ok)."""
    rng = random.Random(len(case))
    x = _rand(rng, 256)
    if case == "lengths":
        # R = 1 beside R = 256, a task with no target columns (S = 0:
        # insertions only), four more in between; slot 7 is a pad
        specs = [(x[:1], x[:2]), (x, mutate(x, 0.05, rng)), (x[:60], x[:60]),
                 (x[:129], mutate(x[:129], 0.1, rng)), (x[:7], x[:7]),
                 (x[:200], mutate(x[:200], 0.2, rng)),
                 (x[:128], x[:128])]
        return 256, specs, None, {}, range(7)
    if case == "escape":
        # slot 2 leaves the band at its first step (terminal cell 300
        # diagonals off a band of 256: move 3, ok = 0); slot 5's scalars
        # are overwritten with a walk no host sends, R = 0 and S past
        # OPS, which runs left until the op row is full
        specs = [(x[:250], mutate(x[:250], 0.1, rng))
                 for _ in range(8)]
        specs[2] = (x[:100], x[:50] + _rand(rng, 300) + x[50:100])
        return 256, specs, [-127, -127, -10] + [-127] * 5, {5: (0, 700, 0)}, \
            (0, 1, 3, 4, 6, 7)
    K = int(case.rsplit("k", 1)[1])
    if case == "mixed-k512":
        # eight walks of eight lengths at the band no other case builds
        specs = [(x[:n], mutate(x[:n], 0.15, rng))
                 for n in (256, 31, 255, 128, 2, 190, 129, 64)]
        return K, specs, None, {}, range(8)
    run = 200 if K == 256 else 600
    # slot 0: a deletion run that carries the band offset up over chunk
    # edges as the walk goes back, slot 1: an insertion run that carries
    # it down over one; both op counts pass 128 (slot 1's 256 too); the
    # other slots walk the diagonal meanwhile
    specs = [(x[:100], x[:50] + _rand(rng, run) + x[50:100]),
             (x[:28] + _rand(rng, 200) + x[28:56], x[:56]),
             (x[:130], mutate(x[:130], 0.1, rng)),
             (x[:256], mutate(x[:256], 0.1, rng))]
    gdmin = {256: [-20, -230, -100, -127], 1024: [-100, -600, -511, -127]}[K]
    return K, specs, gdmin, {}, range(4)


@pytest.mark.parametrize("case", ["lengths", "escape", "chunks-k256",
                                  "chunks-k1024", "mixed-k512"])
def test_joint_walk_gives_each_task_what_it_gives_alone(case):
    """`racon_hirschberg_base` walks a program's eight tasks back in one
    loop, a 128-lane chunk of the moves read and of the op row written a
    step: every slot's ops, count, ok and distance equal what the task
    gives in a program of its own (in sublane 0, seven idle beside it),
    whatever walks beside it — one of a single step, one that never
    starts (a pad), one that leaves the band or runs its op row full —
    and wherever its band offset and its op count cross a chunk's edge,
    in either direction.  Both equal the task solved a cell at a time
    (`hirschberg_oracle.base_task`: the walk one step after another),
    and what comes out ok is an optimal alignment."""
    from tests import hirschberg_oracle
    K, specs, gdmin, forced, good = _walk_program(case)
    pairs = [_enc(q, t) for q, t in specs]
    state = _run_state(pairs, K=K, gdmin=gdmin)
    tasks = np.array([[p, 0, len(q), 0, len(t)]
                      for p, (q, t) in enumerate(specs)], np.int32)
    if case == "lengths":
        tasks[2] = (2, 10, 50, 20, 20)      # rows 10..50 against no column
    kern, OPS, _, _ = align_pallas._build_base_kernel(K, True)

    def run(slots):
        args = list(align_pallas._pack_launch(
            state, slots, align_pallas.BASE_ROWS, K, False))
        scal = np.array(args[0])
        for slot, (R, S, dmin) in forced.items():
            hit = np.flatnonzero(slots[:, 0] == slot)   # pair = its slot
            scal[hit, :3] = (R, S, dmin)
        return scal, [np.asarray(o) for o in kern(8)(scal, *args[1:])]

    scal, (ops, cnt, ok, dist) = run(_slots(tasks, 8))
    for g in range(len(tasks)):
        _, alone = run(_slots(tasks[g:g + 1], 8))
        _, ia, _, ja, _ = tasks[g]
        plain = hirschberg_oracle.base_task(
            specs[g][0][ia:], specs[g][1][ja:], *scal[g, :3], K, OPS)
        for got, want, cell in zip((ops, cnt, ok, dist), alone, plain):
            np.testing.assert_array_equal(got[g], want[0], err_msg=str(g))
            np.testing.assert_array_equal(got[g], cell, err_msg=str(g))
    for g in range(len(tasks), 8):                      # pads never walk
        assert cnt[g] == 0 and not ops[g].any(), g
    assert [g for g in range(len(tasks)) if ok[g]] == list(good)
    for g in good:
        _, ia, ib, ja, jb = tasks[g]
        q, t = specs[g][0][ia:ib], specs[g][1][ja:jb]
        assert path_cost(ops[g, :cnt[g]][::-1], q, t) == dist[g] \
            == (native.edit_distance(q, t) if t else len(q)), g
    if case == "lengths":
        assert cnt[0] == 2 and cnt[1] >= 256 and cnt[2] == 40
        assert (ops[2, :40] == 1).all()
    elif case == "escape":
        assert (cnt[2], ops[2, 0], ok[2]) == (1, 3, 0)
        assert (cnt[5], ok[5]) == (OPS, 0) and (ops[5] == 2).all()
    elif case.startswith("chunks"):
        # the offsets the two runs walk through, by hand: the band
        # offset of cell (i, j) is j - i - gdmin
        run_len = len(specs[0][1]) - 100
        lo, hi = -gdmin[0], run_len - gdmin[0]
        assert lo // 128 < hi // 128 and hi < K      # slot 0 goes up
        lo, hi = -200 - gdmin[1], -gdmin[1]
        assert lo // 128 < hi // 128 and lo >= 0     # slot 1 goes down
        assert cnt[0] == 100 + run_len and cnt[0] > 128
        assert cnt[1] == 256 and cnt[3] >= 256


@pytest.mark.parametrize("placement", ["one_device"], indirect=True)
def test_traceback_fill_share_reads_its_counter_pair(placement):
    """``align.traceback.steps.real`` / ``.slots`` by hand for a launch
    of known op counts (`_collect_base`, once a launch): sixteen slots
    on one device, two programs; the first program's longest walk is
    300, the second holds one task of 7 and seven pads; the programs are
    as wide as the launch was dispatched.  ``align_traceback_fill_share``
    reads the pair in both PAF cells, and nothing on a program that
    does not count it (the parent)."""
    from benchmark import loader, reducers
    from racon_tpu import obs

    pairs = [_enc(b"A" * 300, b"A" * 300) for _ in range(9)]
    state = _run_state(pairs, K=256, gdmin=-3)
    slots = _slots(np.array([[p, 0, 100, 0, 100] for p in range(9)],
                            np.int32), 16)
    cnt = np.array([100, 300, 250, 1, 0, 120, 299, 64] + [7] + [0] * 7,
                   np.int32)
    outs = (np.zeros((16, 640), np.int32), cnt, np.ones(16, np.int32),
            np.zeros(16, np.int32))
    obs.reset()
    obs.configure(metrics=True)
    try:
        align_pallas._collect_base(state, slots, outs)
        counters = obs.snapshot()["counters"]
    finally:
        obs.reset()
    assert counters["align.traceback.steps.real"] == int(cnt.sum()) == 1141
    assert counters["align.traceback.steps.slots"] == 8 * (300 + 7)
    # over a mesh of four the same sixteen slots are four programs of
    # four (`_Launch.width`, as dispatched), each billed its own longest
    obs.configure(metrics=True)
    try:
        align_pallas._collect_base(state, slots, outs, 4)
        sharded = obs.snapshot()["counters"]
    finally:
        obs.reset()
    assert sharded["align.traceback.steps.slots"] == 8 * (300 + 299 + 7)
    for cell_name in ("ecoli-ont.paf", "ecoli-frag.paf"):
        spec, = (m for m in loader.load_cell(cell_name).per_layer
                 if m["name"] == "align_traceback_fill_share")
        assert spec["workloads"] == ["ecoli-ont.paf", "ecoli-frag.paf"]
        read = reducers.registry()[spec["reducer"]]
        job = {"counters": counters, "spans": {}, "phases": {}}
        run = {"jobs": [job, dict(job)], "facts": {}, "data": {},
               "edits": {}, "notes": {}, "trace": None, "device": None,
               "peaks": {}}
        assert read(run, **spec["params"]) == pytest.approx(
            100 * 1141 / (8 * 307))
        job["counters"] = {"align.tasks.real": 5}
        run["jobs"] = [job, dict(job)]
        assert read(run, **spec["params"]) is None


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


# -- launches issued ahead: the stepped driver ------------------------------

# sha256[:12] of each pair's op array as the driver gave it before
# launches were issued ahead (commit b3fbb9d: one blocking launch at a
# time); None = left to the host
_MIXED_BEFORE = ["9a7b4734df14", "0d118ab5cf13", "68da9370a26a", None,
                 "6edd9f6f9cc9", "c64ad544e0c4", "e921d0da4091", None,
                 "dd781b86c09a", "2467635d72f3", "b7a00cde5115"]


@functools.lru_cache(maxsize=1)
def _mixed():
    """Eleven pairs, no multiple of GROUP: three rounds deep (1400,
    1100), two, one, base-only (200, 30, 64, and 257: a round of one
    task), one past the widest band (the host's) and an empty one."""
    rng = random.Random(32)
    pairs = []
    for n in (1400, 200, 1100, 30, 700, 257, 900, 64, 520):
        q = _rand(rng, n)
        pairs.append((q, mutate(q, 0.07, rng)))
    pairs.insert(3, (b"A" * 100, b"A" * 3000))
    pairs.insert(7, (b"", b"ACGT"))
    return [_enc(q, t) for q, t in pairs]


def _digest(ops):
    import hashlib

    return None if ops is None else hashlib.sha256(
        np.asarray(ops, np.int32).tobytes()).hexdigest()[:12]


def _interleave(gens):
    """Advance the generators in turn, one step each, until all ended;
    their return values in order."""
    done = {}
    while len(done) < len(gens):
        for i, g in enumerate(gens):
            if i in done:
                continue
            try:
                next(g)
            except StopIteration as stop:
                done[i] = stop.value
    return [done[i] for i in range(len(gens))]


@pytest.mark.parametrize("split", [None, 4, 7, 1],
                         ids=["alone", "4+7", "7+4", "1+10"])
def test_stepped_driver_agrees_with_the_blocking_one(split):
    """The generator driven alone to its end, and two generators over a
    split of the same pairs advanced step by step with one set of
    launches in flight between them, give op for op what the driver gave
    when every launch blocked.  When the host waits never changes what
    is computed."""
    pairs = _mixed()
    if split is None:
        steps = align_pallas.align_steps(pairs, interpret=True)
        n_yields = sum(1 for _ in iter(lambda: next(steps, "end"), "end"))
        # it does hand over: a yield per round and per base launch
        assert n_yields >= 4
        got = align_pallas.align_pairs(pairs, interpret=True)
    else:
        in_flight = align_pallas._InFlight()
        a, b = _interleave([
            align_pallas.align_steps(part, interpret=True,
                                     in_flight=in_flight)
            for part in (pairs[:split], pairs[split:])])
        got = a + b
        assert not in_flight            # every launch was waited for
    assert [_digest(r) for r in got] == _MIXED_BEFORE


def test_abandoned_steps_leave_no_launch_in_the_set():
    """A generator dropped at a yield (its cohort failed, or the engine
    stopped) takes its launches out of the shared set, so the next
    cohort's `align.queue.*` counters still tell the truth."""
    in_flight = align_pallas._InFlight()
    steps = align_pallas.align_steps(_mixed()[:3], interpret=True,
                                     in_flight=in_flight)
    next(steps)
    assert len(in_flight) == 2          # round 1: edge_fwd + edge_bwd
    steps.close()
    assert not in_flight


# -- two cohorts in flight, through the executor ----------------------------

class _FakePipe:
    """The three calls run_jobs makes of a pipeline, over byte pairs."""

    def __init__(self, pairs):
        self.pairs = pairs
        self.cigars = {}

    def align_job(self, i):
        q, t = self.pairs[i]
        return np.frombuffer(q, np.uint8), np.frombuffer(t, np.uint8)

    def set_job_cigar(self, i, cigar):
        self.cigars[i] = cigar


@functools.lru_cache(maxsize=1)
def _six_pairs():
    """Six pairs of ~600 rows: one bucket, a round and a base launch;
    at three to a cohort, two cohorts.  With each pair's CIGAR from the
    blocking driver."""
    rng = random.Random(41)
    pairs = []
    for _ in range(6):
        q = _rand(rng, rng.randrange(560, 640))
        pairs.append((q, mutate(q, 0.05, rng)))
    cigars = [align_pallas.ops_to_cigar(r) for r in align_pallas.align_pairs(
        [_enc(q, t) for q, t in pairs], interpret=True)]
    return pairs, cigars


def _run_six(monkeypatch, **env):
    from racon_tpu.resilience import faults
    from racon_tpu.resilience.report import PhaseReport

    for k, v in env.items():
        monkeypatch.setenv(k, v)
    faults.reset()
    pairs, _ = _six_pairs()
    pipe = _FakePipe(pairs)
    rep = PhaseReport("alignment", ("hirschberg", "host"))
    served = align_pallas.run_jobs(pipe, list(range(6)), cohort=3,
                                   report=rep)
    return pipe, rep, served


def _spy_dispatch_and_unpack(monkeypatch):
    """The order in which the executor calls the engine's two hooks."""
    order = []
    real = align_pallas._HirschbergOps
    for hook in ("dispatch", "unpack"):
        def spy(self, ctx, kind, x, *rest, _hook=hook,
                _real=getattr(real, hook)):
            order.append(_hook)
            return _real(self, ctx, kind, x, *rest)
        monkeypatch.setattr(real, hook, spy)
    return order


def test_two_cohorts_in_flight_install_what_the_blocking_driver_gave(
        monkeypatch):
    """Depth 2 (the default): cohort 1's first round goes out before
    cohort 0 is waited for, cohort 0's unpack and install advance
    cohort 1 whenever its launches are back, and every CIGAR is the
    blocking driver's."""
    from racon_tpu import obs

    order = _spy_dispatch_and_unpack(monkeypatch)
    want = _six_pairs()[1]             # before the counters are armed
    obs.reset()
    obs.configure(metrics=True)
    try:
        pipe, rep, served = _run_six(monkeypatch)
        counters = obs.snapshot()["counters"]
    finally:
        obs.reset()
    assert order == ["dispatch", "dispatch", "unpack", "unpack"]
    assert served == 6 and rep.retries == 0 and rep.bisections == 0
    assert [pipe.cigars[i] for i in range(6)] == want
    # 2 cohorts x (2 rounds of fwd + bwd, 1 base launch).  Alone, each
    # cohort's three phases would each start on an empty queue (6); with
    # cohort 1 dispatched behind cohort 0's first round fewer do (how
    # many fewer follows when launches come back: not asserted)
    assert counters["align.launches.edge"] == 8
    assert counters["align.launches.base"] == 2
    assert counters["align.queue.empty"] + counters["align.queue.behind"] \
        == 10
    assert 1 <= counters["align.queue.empty"] < 6


@pytest.mark.parametrize("where", ["dispatch", "neighbour_step"])
def test_fault_in_the_second_cohort_is_charged_to_it(monkeypatch, where):
    """Job 4 poisons cohort 1 = [3, 4, 5] while cohort 0 is in flight:
    at cohort 1's own dispatch (the armed `align.run` fault), or inside
    its generator while cohort 0's unpack is advancing it — that
    exception is kept and raised when cohort 1 is unpacked (if its
    launches were not back in time, it fails in its own unpack: the
    same from there on).  Either way
    the lattice retries, bisects and quarantines within cohort 1 only;
    cohort 0 installs whole, from the launches it had out."""
    env = {"RACON_TPU_TIER_RETRIES": "1"}
    if where == "dispatch":
        env["RACON_TPU_FAULT"] = "align.run:window=4"
    else:
        real = align_pallas._HirschbergOps._steps

        def poisoned(self, sub):
            steps = real(self, sub)
            n = 0
            while True:
                try:
                    waits_for = next(steps)
                except StopIteration as stop:
                    return stop.value
                n += 1
                if n == 2 and 4 in sub:     # past dispatch's first step
                    raise RuntimeError("poisoned job 4")
                yield waits_for

        monkeypatch.setattr(align_pallas._HirschbergOps, "_steps", poisoned)
    pipe, rep, served = _run_six(monkeypatch, **env)
    want = _six_pairs()[1]
    assert served == 5
    assert sorted(pipe.cigars) == [0, 1, 2, 3, 5]
    assert all(pipe.cigars[i] == want[i] for i in pipe.cigars)
    # a cohort that fails at its dispatch is resolved there and then, as
    # for every engine; one that failed on cohort 0's time waits its turn
    assert list(pipe.cigars) == ([3, 5, 0, 1, 2] if where == "dispatch"
                                 else [0, 1, 2, 3, 5])
    assert rep.quarantined == [4]
    assert rep.retries >= 1 and rep.bisections >= 1
    assert rep.served.get("hirschberg") == 5
    assert not rep.as_dict()["degradations"]


def test_hard_memory_watermark_resolves_each_cohort_inline(monkeypatch):
    """Under the hard watermark the executor's depth is 1: a cohort is
    dispatched and unpacked before the next is packed, nothing is
    advanced on another cohort's time, and the CIGARs are the same."""
    from racon_tpu.ops import batch_exec

    order = _spy_dispatch_and_unpack(monkeypatch)
    monkeypatch.setattr(batch_exec.budget, "hard_latched", lambda: True)
    pipe, rep, served = _run_six(monkeypatch)
    assert order == ["dispatch", "unpack", "dispatch", "unpack"]
    assert served == 6
    assert [pipe.cigars[i] for i in range(6)] == _six_pairs()[1]
    assert [(d["from"], d["to"]) for d in rep.as_dict()["degradations"]] \
        == [("batched", "stream-sequential")]


def test_cohort_steps_on_anothers_time_only_when_it_would_not_block():
    """`_Cohort.advance_if_ready`: no step while a launch it waits for
    is still out, one step once all are back, and an exception from that
    step is kept for the cohort's own unpack."""

    class FakeLaunch:
        def __init__(self, back):
            self.back = back

        def ready(self):
            return self.back

    first, second = FakeLaunch(False), FakeLaunch(True)
    trail = []

    def steps():
        trail.append("dispatched")
        yield [second, first]
        trail.append("stepped")
        yield [second]
        raise RuntimeError("boom")

    cohort = align_pallas._Cohort(steps())
    cohort.advance()                    # its own dispatch
    cohort.advance_if_ready()
    assert trail == ["dispatched"]      # `first` is still out
    first.back = True
    cohort.advance_if_ready()
    assert trail == ["dispatched", "stepped"] and cohort.error is None
    cohort.advance_if_ready()           # raises inside: kept, not raised
    assert isinstance(cohort.error, RuntimeError) and not cohort.done
    cohort.advance_if_ready()           # and never advanced again
    ops = align_pallas._HirschbergOps(None, {}, None, None, {"served": 0})
    with pytest.raises(RuntimeError, match="boom"):
        ops.unpack(None, "hirschberg", cohort)


# -- the host side works a launch at a time: parity with the per-task loops --

from tests import hirschberg_oracle as oracle  # noqa: E402


def _as_tasks(slots):
    """A launch's task table as the oracle's slot list."""
    return [None if r[0] < 0 else oracle.Task(*r) for r in slots.tolist()]


def _bands(state):
    return {p: (int(state.K[p]), int(state.gdmin[p]))
            for p in range(len(state.K))}


@functools.lru_cache(maxsize=1)
def _pack_pool():
    """Three pairs of one band bucket (K 256) and tasks of every kind a
    launch carries: roots (their halves' target windows are clipped to
    rcap + K on both sides), deeper tasks off the diagonal, a one-row
    task, and tasks against an empty target span (S = 0) at either end."""
    rng = random.Random(77)
    pairs = []
    for n in (1000, 940, 700):
        q = _rand(rng, n)
        pairs.append(_enc(q, mutate(q, 0.06, rng) + _rand(rng, 40)))
    m = [len(t) for _, t in pairs]
    tasks = np.array([
        [0, 0, 1000, 0, m[0]], [1, 0, 940, 0, m[1]], [2, 0, 700, 0, m[2]],
        [0, 0, 500, 0, 470], [0, 500, 1000, 470, m[0]],
        [1, 235, 470, 250, 520], [2, 350, 525, 330, 560],
        [2, 10, 11, 12, 14],
        [0, 100, 160, 130, 130], [1, 0, 40, 0, 0], [2, 600, 700, m[2], m[2]],
    ], np.int32)
    return pairs, tasks


@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "bwd"])
@pytest.mark.parametrize("case", ["halves", "whole-with-pads", "one-task",
                                  "pads-only"])
def test_launch_pack_equals_the_per_slot_loop(case, backward):
    """`_pack_launch` (one native call a launch) against `_task_arrays`
    as it was (a Python loop over the slots): scal, qs and ts are equal
    bit for bit, dtype and shape included — forward and backward, pad
    slots, target windows clipped to rcap + K, S = 0."""
    pairs, tasks = _pack_pool()
    state = _run_state(pairs)
    assert set(state.K.tolist()) == {256}
    K = 256
    if case == "halves":
        # what `_split_round` sends: each task's forward or backward half
        rcap, slots = 512, _slots(tasks, 16)
        slots[:len(tasks), 1 if backward else 2] = \
            (tasks[:, 1] + tasks[:, 2]) // 2
    elif case == "whole-with-pads":
        # what `_solve_base` sends, at the edge kernel's geometry: the
        # tasks as they are (R up to rcap), five pad slots behind them
        rcap, slots = 1024, _slots(tasks, 16)
    elif case == "one-task":
        rcap, slots = 512, _slots(tasks[7:8], 8)
    else:
        rcap, slots = 512, _slots(tasks[:0], 8)
    got = align_pallas._pack_launch(state, slots, rcap, K, backward)
    want = oracle.task_arrays(pairs, _as_tasks(slots), _bands(state), rcap,
                              K, backward)
    for g, w, name in zip(got, want, ("scal", "qs", "ts")):
        assert g.dtype == w.dtype and g.shape == w.shape, name
        np.testing.assert_array_equal(g, w, err_msg=name)
    if case == "halves":
        S = got[0][:len(tasks), 1]
        assert (S == 0).any()
        assert (S[:3] < tasks[:3, 4] - tasks[:3, 3]).all()     # clipped


def test_launch_pack_refuses_a_task_outside_its_pair():
    pairs, tasks = _pack_pool()
    state = _run_state(pairs)
    bad = tasks[:1].copy()
    bad[0, 2] = 5000                       # ib past the query's end
    with pytest.raises(ValueError, match="slot 0"):
        align_pallas._pack_launch(state, _slots(bad, 8), 8192, 256, False)


@pytest.mark.parametrize("shards", [1, 2, 4])
@pytest.mark.parametrize("n_tasks,B", [(11, 16), (5, 8), (40, 64)])
def test_slot_order_is_the_dealt_order(monkeypatch, shards, n_tasks, B):
    """`_deal_programs` as an index over the task table against the list
    it was: tasks in their order, pads last, the programs dealt round
    the shards of a mesh (2 and 4) — so the lock-step fill and the mesh
    pad counters read what they read.  The launch packed from it equals
    the per-slot loop's over the dealt list."""
    pairs, pool = _pack_pool()
    tasks = pool[np.arange(n_tasks) % len(pool)]
    monkeypatch.setattr(align_pallas, "_dispatch_shards", lambda b: shards)
    slots = align_pallas._deal_programs(tasks, B)
    want = oracle.deal_programs([oracle.Task(*r) for r in tasks.tolist()],
                                B, shards)
    assert [None if t is None else t.row() for t in want] == \
        [None if r[0] < 0 else r for r in slots.tolist()]
    state = _run_state(pairs)
    got = align_pallas._pack_launch(state, slots, 1024, 256, False)
    for g, w in zip(got, oracle.task_arrays(pairs, want, _bands(state),
                                            1024, 256, False)):
        np.testing.assert_array_equal(g, w)


def _select_case(name):
    """Crafted edge rows for one launch of K = 8 lanes: (state, slots, F,
    Bv, verify).  Pair p is a 40 x 40 pair whose tasks sit wherever the
    case needs them; lane o of a task is column imid + gdmin + o."""
    INF = align_pallas.INF
    K = 8
    pairs = [_enc(b"A" * 40, b"A" * 40) for _ in range(4)]
    state = _run_state(pairs, K=K, gdmin=-3)
    verify = {}
    F = np.full((8, K), 7, np.int32)
    Bv = np.full((8, K), 9, np.int32)
    if name == "ties":
        # imid 10 -> columns 7..14, all inside [ja, jb]; the least total
        # three times: the first of them wins, in every row
        tasks = [[0, 0, 20, 0, 40], [1, 4, 16, 2, 30], [2, 9, 11, 7, 14]]
        F[:3] = [[5, 4, 3, 9, 3, 3, 8, 9]] * 3
        Bv[:3] = [[5, 4, 3, 9, 3, 3, 8, 9]] * 3
    elif name == "span-cuts-the-lanes":
        # [ja, jb] leaves the lanes out on the left (columns under ja
        # hold the least totals and may not win), on the right, on both
        # sides down to one column, and not at all
        tasks = [[0, 0, 20, 10, 40], [1, 0, 20, 0, 9], [2, 0, 20, 11, 11],
                 [3, 0, 20, 0, 40]]
        F[:4] = [[0, 0, 0, 6, 5, 4, 3, 2]] * 4
        Bv[:4] = [[0, 0, 0, 1, 1, 1, 1, 1]] * 4
    elif name == "all-inf":
        # no finite crossing: the pair fails, its neighbour with one
        # finite lane does not; a row whose only finite lanes lie outside
        # its span fails too
        tasks = [[0, 0, 20, 0, 40], [1, 0, 20, 0, 40], [2, 0, 20, 12, 40]]
        F[0], Bv[0] = INF, INF
        F[1, :], Bv[1, :] = INF, 3
        F[1, 4] = 2
        F[2], Bv[2] = [1, 1, 1, 1, 1, INF, INF, INF], 1
    elif name in ("banded-root-certified", "banded-root-refused"):
        # pair 0 runs under a band override: its root task's total is the
        # pair's edit distance and carries the Ukkonen certificate; the
        # same task of pair 1 (no override) is selected like any other
        state.banded[0] = True
        verify[0] = (40, 40, K, -3)
        tasks = [[0, 0, 40, 0, 40], [1, 0, 40, 0, 40], [0, 0, 20, 0, 20]]
        d = 1 if name.endswith("certified") else 9
        F[:3], Bv[:3] = d, 0
    slots = _slots(np.array(tasks, np.int32), 8)
    if name == "ties":                     # pads between the tasks too
        slots = slots[[0, 7, 1, 6, 2, 5, 4, 3]]
        F[:5:2], Bv[:5:2] = F[:3].copy(), Bv[:3].copy()
    return state, slots, F, Bv, verify


@pytest.mark.parametrize("name", ["ties", "span-cuts-the-lanes", "all-inf",
                                  "banded-root-certified",
                                  "banded-root-refused"])
def test_select_equals_the_per_task_overlay(name):
    """`_select` (one native call a launch) against the per-task overlay
    it replaced: the same halves in the same order, the same pairs
    failed — ties go to the first minimal column, lanes outside a task's
    columns never win, a task with no finite crossing fails its pair, a
    banded pair's root answers to the Ukkonen certificate."""
    state, slots, F, Bv, verify = _select_case(name)
    got = align_pallas._select(state, slots, F, Bv)
    want, failed = [], set()
    oracle.select(_as_tasks(slots), F, Bv, _bands(state), verify, failed,
                  want)
    assert got.dtype == np.int32
    assert got.tolist() == [t.row() for t in want]
    assert set(np.flatnonzero(state.failed).tolist()) == failed
    n_failed = {"all-inf": 2, "banded-root-refused": 1}.get(name, 0)
    assert len(failed) == n_failed
    if name == "banded-root-certified":
        # the certificate was asked for: a distance of 9 is refused by it
        assert not align_pallas._band.ukkonen_ok(40, 40, 8, -3, 9)
        assert len(want) == 6
    if name == "span-cuts-the-lanes":
        assert [r[4] for r in got.tolist()[::2]] == [14, 7, 11, 7]


def _base_outs(rng, slots, OPS=384, not_ok=(), dist=None):
    """A base launch's outputs as the kernel shapes them: op codes back
    to front in the first cnt lanes, garbage behind them."""
    B = len(slots)
    ops = rng.integers(0, 3, (B, OPS)).astype(np.int32)
    cnt = rng.integers(0, OPS + 1, B).astype(np.int32)
    cnt[:3] = (0, 1, OPS)
    ok = np.ones(B, np.int32)
    ok[list(not_ok)] = 0
    return ops, cnt, ok, (np.zeros(B, np.int32) if dist is None else dist)


@pytest.mark.parametrize("banded", [False, True], ids=["flat", "banded"])
def test_collect_equals_the_per_segment_loop(banded):
    """`_collect_base` a launch and `_assemble` at the end (a native copy
    each) against a slice, a reverse and a list append per segment, then
    a sort and a concatenate per pair: the same op strings, int32, for
    the same pairs; a task the kernel did not finish fails its pair, a
    base-only banded pair answers to the certificate."""
    rng = np.random.default_rng(8)
    pairs = [_enc(b"A" * 300, b"A" * 300) for _ in range(5)]
    state = _run_state(pairs, K=256, gdmin=-3)
    verify = {}
    # two launches; a pair's segments arrive out of order and across both
    first = np.array([[0, 200, 300, 0, 0], [1, 0, 150, 0, 0],
                      [2, 0, 300, 0, 300], [0, 0, 100, 0, 0],
                      [3, 0, 150, 0, 0], [4, 0, 300, 0, 300]], np.int32)
    second = np.array([[1, 150, 300, 0, 0], [0, 100, 200, 0, 0],
                       [3, 150, 300, 0, 0]], np.int32)
    dist = np.zeros(8, np.int32)
    if banded:
        # pairs 2 and 4 are whole in one base task under an override:
        # 2's terminal distance is certified, 4's is not
        state.banded[[2, 4]] = True
        verify = {2: (300, 300, 256, -3), 4: (300, 300, 256, -3)}
        dist[2], dist[6] = 5, 200
    launches = [(_slots(first, 8)[[0, 1, 2, 6, 3, 4, 5, 7]],
                 _base_outs(rng, range(8), dist=dist)),
                (_slots(second, 8), _base_outs(rng, range(8), not_ok=[2]))]
    segments, failed = {}, set()
    for slots, outs in launches:
        align_pallas._collect_base(state, slots, outs)
        oracle.collect_base(_as_tasks(slots), outs, segments, verify, failed)
    got = align_pallas._assemble(state)
    want = oracle.assemble(segments, failed, len(pairs))
    assert failed == ({3, 4} if banded else {3})
    assert set(np.flatnonzero(state.failed).tolist()) == failed
    for g, w in zip(got, want):
        assert (g is None) == (w is None)
        if w is not None:
            assert g.dtype == w.dtype == np.int32
            np.testing.assert_array_equal(g, w)
    assert len(got[0]) > 0 and got[3] is None


def _op_strings():
    rng = np.random.default_rng(5)
    runs = rng.integers(1, 12, 1500)
    long = np.repeat(np.arange(1500) % 3, runs).astype(np.int32)
    return {"empty": np.zeros(0, np.int32),
            "one-run": np.full(8000, 2, np.int32),
            "one-op": np.array([1], np.int32),
            "1500-runs": long,
            "uint8": (np.arange(40) // 7 % 3).astype(np.uint8)}


@pytest.mark.parametrize("name", sorted(_op_strings()))
def test_ops_to_cigar_equals_the_per_run_loop(name):
    """The native run-length pass against the f-string a run it replaced,
    alone and as one of a cohort's strings."""
    strings = _op_strings()
    ops = strings[name]
    want = oracle.ops_to_cigar(ops)
    assert align_pallas.ops_to_cigar(ops) == want
    cohort = [strings[k] for k in sorted(strings)]
    assert align_pallas.ops_to_cigars(cohort) == \
        [oracle.ops_to_cigar(o) for o in cohort]
    if name == "1500-runs":
        assert sum(c in "MID" for c in want) == 1500
    with pytest.raises(ValueError):
        align_pallas.ops_to_cigar(np.array([0, 3], np.int32))


def test_host_task_counters_say_what_ran_per_launch():
    """``align.host.tasks.batched`` / ``.single``, once per launch beside
    ``align.tasks.real``: every task's pack, select or collect is a
    share of a per-launch call; a banded pair's root task also gets a
    step of its own (the Ukkonen certificate), and is counted for it —
    in the edge launches of its round, or in the base launch if the
    pair is a base case whole."""
    from racon_tpu import obs

    pairs = _mixed()

    def counters(**kwargs):
        obs.reset()
        obs.configure(metrics=True)
        try:
            res = align_pallas.align_pairs(pairs, interpret=True, **kwargs)
            return res, obs.snapshot()["counters"]
        finally:
            obs.reset()

    flat, c = counters()
    assert c["align.host.tasks.single"] == 0
    assert c["align.host.tasks.batched"] == c["align.tasks.real"] > 0
    # pair 0 (1400 rows: its root is split, forward + backward launch)
    # and pair 1 (200 rows: its root is a base task) under their own
    # flat band as an override narrower than... the flat bucket itself
    # is not narrower, so take half of it
    K0 = align_pallas.band_for(len(pairs[0][0]), len(pairs[0][1]))
    K1 = align_pallas.band_for(len(pairs[1][0]), len(pairs[1][1]))
    hits = set()
    banded, c = counters(band_overrides={0: K0 // 2, 1: K1 // 2}, hits=hits)
    assert c["align.host.tasks.single"] == 2 + 1
    assert c["align.host.tasks.single"] + c["align.host.tasks.batched"] \
        == c["align.tasks.real"]
    for i in (0, 1):
        if i not in hits:               # certified: the flat kernel's ops
            np.testing.assert_array_equal(banded[i], flat[i])
