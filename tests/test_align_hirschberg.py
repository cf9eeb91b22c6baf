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
