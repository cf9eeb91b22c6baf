"""The width of a lockstep consensus program (PR 34; thirty-two windows
since PR 44): how the driver derives it, what the launch counters say of
it, and the three per-layer metrics that read them.

`racon_poa_ls` runs U x 8 windows a grid program under one control flow.
U is a function of the window class, the per-shard batch, the VMEM sum
and the rows a launch really holds (`poa_driver._group_width`);
`poa_driver._count_launch` counts the programs as wide or narrow, the
launch's real windows under the width that ran them, and bills lock-step
what it costs (every window of a program runs the program's largest
layer count), once per launch from the packed `n_layers` row.
"""

import random

import numpy as np
import pytest

from benchmark import loader, reducers
from racon_tpu import obs
from racon_tpu.ops import poa_driver, poa_pallas_ls

SCORES = (5, -4, -8)
CELLS = ["ecoli-ont.sam", "ecoli-ont.paf", "chr20-sr.sam",
         "ecoli-ont-x4.sam", "ecoli-frag.paf", "ecoli-ont-x4.paf"]
ALL_CELLS = CELLS + ["ecoli-ont-deep.sam", "lambda-ont.paf",
                     "ecoli-ont-cap.sam", "lambda-ont-w1000.paf"]
#: the full-size four-chip cell (PR 50), appended to every list that
#: names ecoli-ont-x4.sam
FULL_CELL = "ecoli-ont-full-x4.sam"


@pytest.mark.parametrize("wl_class,shard_batch,want", [
    (512, 64, (4, 2)),   # one chip: two programs of thirty-two, or four
    (512, 32, (4, 2)),   # of sixteen where the last would be half empty
    (512, 16, (2,)),     # a shard's batch on four chips: one program
    (256, 64, (4, 2)), (128, 64, (4, 2)), (384, 64, (4, 2)),
    (768, 64, (4, 2)),   # 31.5 MiB of arrays under a limit of 63
    (896, 64, (2,)),     # 37.45 MiB would ask for 75 of the 64 allowed
    (1024, 64, (2,)),
    # past class 1024 the kernel serves since PR 47 (one rule for every
    # width: the limit a program needs may not pass the ceiling)
    (1152, 64, (2,)), (1536, 64, (2,)),   # 30.45 MiB under 61: the last
    (1664, 64, (1,)),    # sixteen would ask for 67; eight run under 34
    (3200, 64, (1,)),    # 31.41 MiB a group under 63: the last class
    (512, 8, (1,)),      # a batch of 8 somebody asked for
    (512, 24, (1,)),     # three programs of eight do not pair up
    (256, 40, (1,)),
    (512, 48, (2,)),     # three programs of sixteen, no thirty-two
], ids=lambda v: str(v))
def test_group_width_follows_class_batch_and_vmem(wl_class, shard_batch,
                                                  want):
    for depth in poa_driver.DEPTH_BUCKETS:
        cfg = poa_driver.make_config(wl_class, depth, *SCORES)
        assert poa_driver._group_widths(cfg, shard_batch) == want
        # a full batch, and a batch of pad rows alone, run at the widest
        assert poa_driver._group_width(cfg, shard_batch) == want[0]
        assert poa_driver._group_width(cfg, shard_batch,
                                       shard_batch) == want[0]
    # the device batch is a multiple of every program's width on every
    # shard, which is what make() asserts
    assert not [u for u in want if shard_batch % (u * poa_pallas_ls.G)]


#: the launch's real rows -> width at a shard batch of 64, 16 and 8: the
#: last program of thirty-two has to be more than half real
LAUNCH_RULE = [(1, 2, 2, 1), (8, 2, 2, 1), (16, 2, 2, 1), (17, 4, 2, 1),
               (32, 4, 2, 1), (33, 2, 2, 1), (46, 2, 2, 1), (48, 2, 2, 1),
               (49, 4, 2, 1), (64, 4, 2, 1)]


@pytest.mark.parametrize("wl_class,want", [
    (512, (4, 2)),       # 26.33 MiB under 53
    (640, (2,)), (768, (2,)),
    # sixteen windows of the upper rung past class 768 since PR 47: the
    # rung was the XLA twin's while a program of eight was held under
    # the default limit's line (11.39 / 12.64 MiB a group)
    (896, (2,)), (1024, (2,)),            # 25.27 MiB under 51
    (1280, (2,)),        # 31.33 MiB under 63: the last at sixteen
    (1408, (1,)), (2560, (1,)),           # 30.80 MiB a group under 62
], ids=lambda v: str(v))
def test_upper_rung_group_width_follows_the_same_rule(wl_class, want):
    cfg = poa_driver.make_config(wl_class, poa_driver.DEPTH_CAP, *SCORES, 1)
    assert cfg.max_nodes == 5 * wl_class
    assert poa_driver._group_widths(cfg, 64) == want
    assert poa_driver._group_widths(cfg, 32) == want
    assert poa_driver._group_widths(cfg, 16) == want[-1:]
    assert poa_driver._group_widths(cfg, 8) == (1,)
    for real in (1, 16, 17, 32, 47, 64):
        assert poa_driver._group_width(cfg, 64, real) == (
            want[0] if len(want) == 1 or real in (17, 32, 64)
            else want[1])


@pytest.mark.parametrize("real,at64,at16,at8", LAUNCH_RULE,
                         ids=[f"rows{r[0]}" for r in LAUNCH_RULE])
def test_launch_width_follows_the_rows_the_launch_holds(real, at64, at16,
                                                        at8):
    """What reaches the rule is the launch's real rows; the fullest
    shard holds min(rows, shard batch) of them (rows are packed real
    first).  Every geometry the one-chip cells run, both rungs."""
    for wl_class, depth, rung in ((512, 200, 0), (512, 200, 1),
                                  (512, 32, 0), (256, 200, 0),
                                  (128, 200, 0), (384, 8, 0)):
        cfg = poa_driver.make_config(wl_class, depth, *SCORES, rung)
        assert poa_driver._group_width(cfg, 64, real) == at64
        assert poa_driver._group_width(cfg, 16, real) == at16
        assert poa_driver._group_width(cfg, 8, real) == at8
    # a geometry VMEM holds at sixteen windows and no wider runs every
    # launch at sixteen, as before there was a wider program: class 1024
    # on both rungs (-w 1000: lambda-ont-w1000.paf)
    for rung in (0, 1):
        cfg = poa_driver.make_config(1024, 200, *SCORES, rung)
        assert poa_driver._group_width(cfg, 64, real) == 2


def test_a_program_of_thirty_two_never_replaces_fewer_than_two_of_sixteen():
    """The rule's ground: at four groups a launch runs at most half as
    many programs with a window in them as at two, plus none."""
    cfg = poa_driver.make_config(512, 200, *SCORES)
    for real in range(1, 65):
        live = {u: -(-real // (u * poa_pallas_ls.G)) for u in (2, 4)}
        width = poa_driver._group_width(cfg, 64, real)
        assert width in (2, 4)
        if width == 4:
            assert 2 * live[4] == live[2]
        else:
            assert 2 * live[4] > live[2]


def test_group_width_narrows_where_vmem_does_not_hold_the_wide_program(
        monkeypatch):
    """Up to class 1536 nothing is too large at two groups (class 1024
    asks for 42 MiB of the 64 a limit may reach; past 1536 the width
    falls to one group by the same rule), so the ceiling is lowered
    here: the width then falls to one group, the class stays on the
    kernel."""
    cfg = poa_driver.make_config(1024, 32, *SCORES)
    assert poa_pallas_ls.vmem_limit_bytes(cfg, 2) == 42 << 20
    assert poa_driver._group_width(cfg, 64) == 2
    monkeypatch.setattr(poa_pallas_ls, "VMEM_CEILING", 32 << 20)
    assert not poa_driver._fits_vmem(cfg, 2) and poa_driver._fits_vmem(cfg)
    assert poa_driver._group_width(cfg, 64) == 1
    assert poa_driver._pick_tier(cfg, True) == "ls"
    # class 512 is still wide, though the program of thirty-two (44 MiB
    # asked for) is out: every launch at sixteen
    at_512 = poa_driver.make_config(512, 32, *SCORES)
    assert poa_driver._group_widths(at_512, 64) == (2,)
    assert poa_driver._group_width(at_512, 64, 64) == 2


def test_scratch_sum_doubles_with_the_groups():
    cfg = poa_driver.make_config(512, 200, *SCORES)
    one = poa_pallas_ls.scratch_bytes(cfg)
    assert round(one / 2 ** 20, 2) == 5.43
    assert poa_pallas_ls.scratch_bytes(cfg, 2) == 2 * one
    assert poa_pallas_ls.scratch_bytes(cfg, 4) == 4 * one
    assert poa_pallas_ls.vmem_limit_bytes(cfg, 4) == 44 << 20


def _packed(n_layers):
    """_pack's tuple as far as _count_launch reads it."""
    n_layers = np.asarray(n_layers, np.int32)
    return (np.zeros((len(n_layers), 1), np.uint8), None, None, n_layers)


def _counted(n_real, n_layers, groups):
    obs.reset()
    obs.configure(metrics=True)
    try:
        poa_driver._count_launch(n_real, _packed(n_layers), groups)
        return {k: v for k, v in obs.snapshot()["counters"].items()
                if k.startswith("poa.")}
    finally:
        obs.reset()


def test_count_launch_full_batch_of_wide_programs():
    rng = random.Random(1)
    layers = sorted(rng.randrange(20, 46) for _ in range(64))
    c = _counted(64, layers, 2)
    by_hand = 16 * sum(max(layers[i:i + 16]) for i in range(0, 64, 16))
    assert c == {"poa.launches": 1, "poa.rows.real": 64, "poa.rows.pad": 0,
                 # the launch stream's own (PR 50; tests/test_full_cell.py)
                 "poa.launches.full": 1, "poa.queue.behind": 0,
                 "poa.queue.empty": 1,
                 "poa.programs.wide": 4, "poa.programs.narrow": 0,
                 "poa.width.windows.u1": 0, "poa.width.windows.u2": 64,
                 "poa.width.windows.u4": 0,
                 # programs compiled under a scoped-VMEM limit of their
                 # own (PR 47; tests/test_w1000_cell.py)
                 "poa.vmem.programs.raised": 0,
                 "poa.lockstep.layers.real": sum(layers),
                 "poa.lockstep.layers.slots": by_hand,
                 # the node rungs' counters (tests/test_deep_cell.py)
                 "poa.windows.rung.base": 64, "poa.windows.rung.upper": 0,
                 "poa.layers.admitted": sum(layers)}
    assert by_hand == 16 * (layers[15] + layers[31] + layers[47]
                            + layers[63])
    # the same batch as two programs of thirty-two: loop bounds are
    # maxima over 32 sorted windows, so lock-step bills a little more
    c4 = _counted(64, layers, 4)
    assert c4["poa.programs.wide"] == 2 and c4["poa.programs.narrow"] == 0
    assert [c4[f"poa.width.windows.u{u}"] for u in (1, 2, 4)] == [0, 0, 64]
    assert c4["poa.lockstep.layers.real"] == sum(layers)
    assert c4["poa.lockstep.layers.slots"] == 32 * (layers[31] + layers[63])
    assert c4["poa.lockstep.layers.slots"] >= by_hand


def test_count_launch_batch_with_pad_rows():
    # 21 windows and 43 pad rows: the second program is five windows and
    # eleven pad slots, the last two programs have no layers and cost a
    # lock-step program nothing
    layers = [30] * 16 + [12, 12, 40, 7, 9] + [0] * 43
    c = _counted(21, layers, 2)
    assert c["poa.rows.real"] == 21 and c["poa.rows.pad"] == 43
    assert c["poa.programs.wide"] == 4 and c["poa.programs.narrow"] == 0
    assert c["poa.lockstep.layers.real"] == 16 * 30 + 80
    assert c["poa.lockstep.layers.slots"] == 16 * 30 + 16 * 40
    # the launch's real windows under the one width that ran them
    assert [c[f"poa.width.windows.u{u}"] for u in (1, 2, 4)] == [0, 21, 0]
    # which is the width the rule gives 21 rows of 64: 4
    cfg = poa_driver.make_config(512, 200, *SCORES)
    c4 = _counted(21, layers, poa_driver._group_width(cfg, 64, 21))
    assert c4["poa.programs.wide"] == 2
    assert [c4[f"poa.width.windows.u{u}"] for u in (1, 2, 4)] == [0, 0, 21]
    assert c4["poa.lockstep.layers.slots"] == 32 * 40


def test_count_launch_per_shard_batch_of_sixteen():
    # four chips: 64 rows, 16 a shard, one program of sixteen a chip
    layers = [25] * 16 + [31] * 15 + [44] + [8] * 16 + [0] * 16
    cfg = poa_driver.make_config(512, 200, *SCORES)
    groups = poa_driver._group_width(cfg, 64 // 4, 48)
    c = _counted(48, layers, groups)
    assert groups == 2 and c["poa.programs.wide"] == 4
    assert [c[f"poa.width.windows.u{u}"] for u in (1, 2, 4)] == [0, 48, 0]
    assert c["poa.lockstep.layers.real"] == 16 * 25 + 15 * 31 + 44 + 16 * 8
    assert c["poa.lockstep.layers.slots"] == 16 * (25 + 44 + 8 + 0)


def test_count_launch_geometry_that_gets_one_group():
    cfg = poa_driver.make_config(512, 32, *SCORES)
    groups = poa_driver._group_width(cfg, 8, 4)
    layers = [3, 9, 9, 4, 0, 0, 0, 0]
    c = _counted(4, layers, groups)
    assert groups == 1
    # both keys at every launch, a zero too: a job served by narrow
    # programs alone reads 0 % wide, not nothing
    assert c["poa.programs.wide"] == 0 and c["poa.programs.narrow"] == 1
    assert [c[f"poa.width.windows.u{u}"] for u in (1, 2, 4)] == [4, 0, 0]
    assert c["poa.lockstep.layers.real"] == 25
    assert c["poa.lockstep.layers.slots"] == 8 * 9


def test_count_launch_of_the_xla_twin_has_no_programs():
    # nor windows under a width: the poa.width.* keys are the lockstep
    # kernel's, so their sum over a job is the windows it was given
    c = _counted(3, [5, 5, 5, 0], 0)
    assert c == {"poa.launches": 1, "poa.rows.real": 3, "poa.rows.pad": 1,
                 "poa.launches.full": 0, "poa.queue.behind": 0,
                 "poa.queue.empty": 1,
                 "poa.programs.wide": 0, "poa.programs.narrow": 0,
                 "poa.windows.rung.base": 3, "poa.windows.rung.upper": 0,
                 "poa.layers.admitted": 15}


def test_driver_counts_what_it_launches(tmp_path, monkeypatch):
    """Through the consensus driver (interpret mode), a batch of 16: the
    launch runs as one program of sixteen and is counted so."""
    from tests.test_pallas_ls import (_perfect_reads_dataset,
                                      _polish_perfect_reads)

    target = _perfect_reads_dataset(tmp_path)     # 240 bases, w = 100
    monkeypatch.setenv("RACON_TPU_PALLAS", "1")
    monkeypatch.setenv("RACON_TPU_SHARD", "0")
    monkeypatch.setenv("RACON_TPU_BATCH_WINDOWS", "16")
    counted = []
    real = poa_driver._count_launch

    def spy(n_real, packed, groups=0, *rung):
        counted.append((n_real, len(packed[0]), groups))
        real(n_real, packed, groups, *rung)

    monkeypatch.setattr(poa_driver, "_count_launch", spy)
    res, phase = _polish_perfect_reads(tmp_path)
    assert res[0][1] == target   # perfect reads -> perfect consensus
    assert phase["served"]["ls"] == 3
    assert counted == [(3, 16, 2)]


@pytest.mark.parametrize("window_length,want", [
    (10, (24, 32, 4)),      # 24 of 32 rows: one program of thirty-two
    (20, (12, 32, 2)),      # 12 of 32: two of sixteen, the second all pad
], ids=["rows24-u4", "rows12-u2"])
def test_driver_picks_the_width_launch_by_launch(tmp_path, monkeypatch,
                                                 window_length, want):
    """Through the consensus driver (interpret mode) at a batch of 32:
    the geometry holds a program of thirty-two and one of sixteen, a
    launch runs the one its real rows call for, counts its windows
    under that width, and the consensus is the same bytes either way."""
    import racon_tpu
    from tests.test_pallas_ls import _perfect_reads_dataset

    target = _perfect_reads_dataset(tmp_path)     # 240 bases
    monkeypatch.setenv("RACON_TPU_PALLAS", "1")
    monkeypatch.setenv("RACON_TPU_SHARD", "0")
    monkeypatch.setenv("RACON_TPU_BATCH_WINDOWS", "32")
    launched, ran = [], []
    real = poa_driver._count_launch
    submit = poa_driver._submit

    def spy(n_real, packed, groups=0, *rung):
        launched.append((n_real, len(packed[0]), groups))
        real(n_real, packed, groups, *rung)

    def spy_submit(kernel, *args, **kw):
        ran.append(kernel)
        return submit(kernel, *args, **kw)

    monkeypatch.setattr(poa_driver, "_count_launch", spy)
    monkeypatch.setattr(poa_driver, "_submit", spy_submit)
    monkeypatch.setenv("RACON_TPU_METRICS", "1")   # the polisher arms obs
    try:
        p = racon_tpu.TpuPolisher(
            str(tmp_path / "r.fasta"), str(tmp_path / "o.sam"),
            str(tmp_path / "t.fasta"), window_length=window_length,
            match=5, mismatch=-4, gap=-8)
        p.initialize()
        res = p.polish(True)
        counters = obs.snapshot()["counters"]
    finally:
        obs.reset()
    assert res[0][1] == target
    assert p.report.as_dict()["phases"]["consensus"]["served"]["ls"] == want[0]
    assert launched == [want]
    # the program that ran was built at that width (its key: name, cfg,
    # interpret, band, groups, batch)
    assert [k.key[4:] for k in ran] == [(want[2], 32)]
    widths = {u: counters[f"poa.width.windows.u{u}"] for u in (1, 2, 4)}
    assert widths == {1: 0, 2: 0, 4: 0, want[2]: want[0]}
    assert sum(widths.values()) == counters["poa.rows.real"]
    assert counters["kernel.builds.poa.ls"] == 1   # one build, two programs


def test_obs_report_lists_the_program_counters():
    """`python -m racon_tpu.obs <trace>` lists the four counters, and
    the kernel's own step counts (poa.ls.*), beside the mesh counters of
    alignment."""
    from racon_tpu.obs import __main__ as obs_cli

    counters = {"poa.programs.wide": 72, "poa.programs.narrow": 0,
                "poa.lockstep.layers.real": 36000,
                "poa.lockstep.layers.slots": 40000,
                "poa.width.windows.u1": 0, "poa.width.windows.u2": 40,
                "poa.width.windows.u4": 960,
                "poa.mesh.rows.real": 1000, "poa.mesh.fullest.slots": 1004,
                "poa.insert.slots.swept": 9000, "poa.insert.slots.all": 21600,
                "poa.ls.layers": 1800, "poa.ls.steps.dp": 1_400_000,
                "poa.ls.steps.traceback": 1_500_000,
                "poa.ls.steps.update": 900_000,
                "poa.ls.insert.firings": 1_500_000,
                "poa.ls.insert.shift_steps": 3_000_000,
                "align.mesh.launches.single": 380, "poa.launches": 18}
    text = obs_cli.render(
        {"traceEvents": [], "racon_tpu": {"metrics": {"counters": counters}}},
        "t.json")
    assert "-- consensus programs in lock-step" in text
    assert "-- alignment launches over the mesh" in text
    section = text.split("-- consensus programs in lock-step")[1]
    for name in counters:
        assert (name in section) == name.startswith(
            ("poa.programs.", "poa.lockstep.", "poa.width.", "poa.mesh.",
             "poa.insert.", "poa.ls."))


# -- the shape of a launch on a mesh (PR 45) --------------------------------

#: (real rows, shards, rows a shard): part-full and full launches of the
#: four-chip cells' batch (4 x 32), of 16 a shard, of two and eight
#: shards, and the degenerate ones (no real row, one, one fewer than full)
MESH_LAUNCHES = [(46, 4, 32), (76, 4, 32), (128, 4, 32), (28, 4, 32),
                 (1, 4, 32), (3, 4, 32), (127, 4, 32), (65, 4, 32),
                 (0, 4, 32), (46, 4, 16), (64, 4, 16), (33, 2, 32),
                 (64, 2, 32), (100, 8, 32), (7, 8, 8), (46, 1, 64),
                 (64, 1, 64), (5, 1, 8)]
MESH_IDS = [f"{n}rows-{m}x{b}" for n, m, b in MESH_LAUNCHES]


@pytest.mark.parametrize("n_real,m,shard_batch", MESH_LAUNCHES, ids=MESH_IDS)
def test_mesh_order_splits_the_real_rows_evenly(n_real, m, shard_batch):
    rows = m * shard_batch
    order = poa_driver._mesh_order(n_real, rows, m)
    if m == 1:
        assert order is None        # one chip: real first, nothing to undo
        return
    assert sorted(order) == list(range(rows))      # a permutation
    if n_real == rows:
        # a full launch on any mesh is laid out as it was: real first
        assert list(order) == list(range(rows))
    real, pad = order[:n_real], order[n_real:]
    held = np.bincount(real // shard_batch, minlength=m)
    assert held.sum() == n_real and held.max() - held.min() <= 1
    assert held.max() == -(-n_real // m)           # what _group_width hears
    assert list(held) == sorted(held, reverse=True)
    # each shard's share is a contiguous run of the chunk's order at the
    # head of the shard, so a program holds neighbours of the sort
    assert list(real) == sorted(real)
    at = 0
    for j in range(m):
        assert list(real[at:at + held[j]]) == list(
            range(j * shard_batch, j * shard_batch + held[j]))
        at += held[j]
    assert list(pad) == sorted(pad)


@pytest.mark.parametrize("n_real,m,shard_batch", MESH_LAUNCHES, ids=MESH_IDS)
def test_unpack_returns_results_in_chunk_order(n_real, m, shard_batch):
    """_pack puts chunk item p on row _mesh_order[p], a kernel answers
    row for row, _unpack hands row p back as item p's: whatever the
    mesh, the results index by chunk position."""
    from tests.test_pack import _random_export

    rows = m * shard_batch
    cfg = poa_driver.poa.PoaConfig(
        max_nodes=384, max_len=16, max_backbone=128, max_edges=12, depth=2,
        match=5, mismatch=-4, gap=-8)
    rng = random.Random(n_real * 31 + m)
    chunk = [(1000 + p, _random_export(rng, p, 2, 20 + p % 100, cfg.max_len),
              [0, 1]) for p in range(n_real)]
    flat = poa_driver._pack(chunk, cfg, rows)
    packed = poa_driver._pack(chunk, cfg, rows, None, m)
    order = poa_driver._mesh_order(n_real, rows, m)
    for a, b in zip(flat, packed):
        if m == 1 or n_real == rows:
            assert a.tobytes() == b.tobytes()      # byte for byte
        else:
            np.testing.assert_array_equal(a, b[order])
    bb, _, bb_len, n_layers = packed[:4]
    # a kernel that answers each row with what it was given
    outs = (bb.astype(np.int32), bb.astype(np.int32), bb_len[:, None],
            np.zeros((rows, 1), np.int32), n_layers[:, None])
    res = poa_driver._unpack(outs, True, order=order)
    cons_base, _, cons_len, failed = res
    assert len(cons_len) == rows and not failed.any()
    for p, (_, wx, keep) in enumerate(chunk):
        assert cons_len[p] == len(wx.backbone)
        assert res.nodes[p] == len(keep)
        np.testing.assert_array_equal(
            cons_base[p, :cons_len[p]], poa_driver.encode(wx.backbone))
    assert (cons_len[n_real:] == 1).all() and not res.nodes[n_real:].any()


@pytest.mark.parametrize("n_real,want", [(46, 2), (76, 4), (128, 4), (28, 2),
                                         (1, 2), (65, 4), (64, 2), (68, 4)],
                         ids=lambda v: str(v))
def test_launch_width_over_the_even_split(n_real, want):
    """Four shards of 32: the rule hears what the fullest shard holds,
    ceil(real rows / shards).  46 rows are 12 a shard (a program of
    sixteen a chip; real first they were 32 + 14 and a program of
    thirty-two on chip 0), 76 are 19 (thirty-two)."""
    for rung in (0, 1):
        cfg = poa_driver.make_config(512, 200, *SCORES, rung)
        assert poa_driver._group_widths(cfg, 32) == (4, 2)
        assert poa_driver._group_width(cfg, 32, -(-n_real // 4)) == want
    # where VMEM holds no program of thirty-two a shard runs two of
    # sixteen a launch, on either rung
    for rung in (0, 1):
        cfg = poa_driver.make_config(1024, 200, *SCORES, rung)
        assert poa_driver._group_widths(cfg, 32) == (2,)
        assert poa_driver._group_width(cfg, 32, -(-n_real // 4)) == 2


#: a program's cost on the chip in units of the one-group program's, by
#: its sublane groups (PERF.md section 6, PR 44)
PROGRAM_COST = {1: 1.0, 2: 1.38, 4: 2.01}


def _fullest_shard_cost(cfg, shard_batch, held):
    """What the launch's slowest chip runs: its programs with a window
    in them, at the width the rule picks for `held` rows."""
    groups = poa_driver._group_width(cfg, shard_batch, held)
    return -(-held // (groups * poa_pallas_ls.G)) * PROGRAM_COST[groups]


@pytest.mark.parametrize("m,shard_batch", [(4, 32), (4, 16), (2, 32),
                                           (8, 32), (8, 8)],
                         ids=lambda v: str(v))
def test_an_even_split_never_runs_a_wider_or_a_longer_program(m, shard_batch):
    """Against real-first packing into the same shards, at every count
    of real rows: the fullest shard of an even split costs no more, and
    strictly less for 17-32 rows over 4 x 32 (one program of sixteen
    where chip 0 ran one of thirty-two and three chips idled)."""
    cfg = poa_driver.make_config(512, 200, *SCORES)
    for n_real in range(1, m * shard_batch + 1):
        even = _fullest_shard_cost(cfg, shard_batch, -(-n_real // m))
        first = _fullest_shard_cost(cfg, shard_batch,
                                    min(n_real, shard_batch))
        assert even <= first, (n_real, even, first)
        if (m, shard_batch) == (4, 32) and 17 <= n_real <= 32:
            assert (even, first) == (1.38, 2.01)


@pytest.mark.parametrize("n_real,fullest", [(46, 12), (76, 19), (128, 32),
                                            (3, 1), (0, 0)],
                         ids=lambda v: str(v))
def test_count_launch_counts_the_balance_of_a_mesh_launch(n_real, fullest):
    layers = np.zeros(128, np.int32)
    layers[poa_driver._mesh_order(n_real, 128, 4)[:n_real]] = 30
    obs.reset()
    obs.configure(metrics=True)
    try:
        poa_driver._count_launch(n_real, _packed(layers), 2, "base", 4)
        c = dict(obs.snapshot()["counters"])
    finally:
        obs.reset()
    assert c["poa.mesh.rows.real"] == n_real
    assert c["poa.mesh.fullest.slots"] == 4 * fullest
    assert c["poa.rows.pad"] == 128 - n_real
    # programs are still consecutive runs of the packed rows, the pad
    # rows inside the batch bill nothing: a shard's programs of sixteen
    # with a window in them run 30 layers
    live = 4 * -(-fullest // 16) if n_real >= 4 else n_real
    assert c["poa.lockstep.layers.slots"] == 16 * 30 * live
    # one chip, and the XLA twin on a mesh, count neither key
    assert not [k for k in _counted(46, [30] * 46 + [0] * 18, 2)
                if k.startswith("poa.mesh.")]
    obs.configure(metrics=True)
    try:
        poa_driver._count_launch(n_real, _packed(layers), 0, "base", 4)
        assert not [k for k in obs.snapshot()["counters"]
                    if k.startswith("poa.mesh.")]
    finally:
        obs.reset()


def test_real_first_packing_into_wide_shards_would_read_36_percent():
    """What the counters' ratio says of the layout this PR replaces: 46
    rows packed real first into 4 x 32 fill shard 0."""
    cell = loader.load_cell("ecoli-ont-x4.sam")
    spec = {m["name"]: m for m in cell.per_layer}[
        "x4_poa_shard_balance_share"]
    read = reducers.registry()[spec["reducer"]]
    assert spec["layer"] == "drivers" and spec["better"] == "higher"
    assert spec["moves"] == "polished_mbp_per_s"
    assert spec["workloads"] == ["ecoli-ont-x4.sam", "ecoli-ont-x4.paf",
                                 FULL_CELL]
    real_first = {"poa.mesh.rows.real": 46, "poa.mesh.fullest.slots": 128}
    even = {"poa.mesh.rows.real": 46, "poa.mesh.fullest.slots": 48}
    assert read(_run(real_first), **spec["params"]) == pytest.approx(35.9375)
    assert read(_run(even, even), **spec["params"]) == pytest.approx(
        100 * 46 / 48)
    # the parent's program counts neither: nothing, and no error
    assert read(_run({"poa.rows.real": 46}), **spec["params"]) is None


# -- the three per-layer metrics ------------------------------------------

def _run(*job_counters):
    jobs = [{"counters": c, "spans": {}, "phases": {},
             "polished_bp": 500000, "wall_s": 9.0} for c in job_counters]
    return {"jobs": jobs, "facts": {}, "data": {}, "edits": {},
            "notes": {}, "trace": None, "device": None, "peaks": {}}


@pytest.mark.parametrize("cell_name", CELLS)
def test_wide_program_metrics_load_and_read_their_counters(cell_name):
    cell = loader.load_cell(cell_name)        # files agree with entries
    specs = {m["name"]: m for m in cell.per_layer}
    registry = reducers.registry()
    wide, fill = (specs["poa_wide_program_share"],
                  specs["poa_lockstep_fill_share"])
    for spec in (wide, fill):
        assert spec["workloads"] == CELLS + [FULL_CELL]
        assert spec["layer"] == "kernels"
        assert spec["moves"] == "polished_mbp_per_s"
        assert spec["reducer"] == "counter_share"

    def read(spec, *jobs):
        return registry[spec["reducer"]](_run(*jobs), **spec["params"])

    job = {"poa.programs.wide": 72, "poa.programs.narrow": 0,
           "poa.lockstep.layers.real": 36000,
           "poa.lockstep.layers.slots": 40000, "poa.launches": 18}
    mixed = dict(job, **{"poa.programs.wide": 36,
                         "poa.programs.narrow": 72})
    assert read(wide, job, job) == pytest.approx(100.0)
    assert read(wide, job, mixed) == pytest.approx(100 * 108 / 180)
    assert read(fill, job, job) == pytest.approx(90.0)
    # narrow programs alone: 0 %, because both keys are always counted
    assert read(wide, dict(job, **{"poa.programs.wide": 0,
                                   "poa.programs.narrow": 144})) == 0.0
    # a program without the counters (the parent under the driver's
    # check) reads nothing, and does not raise
    older = {"poa.launches": 18, "poa.rows.real": 1000, "poa.rows.pad": 152}
    assert read(wide, older, older) is None
    assert read(fill, older, older) is None


def _width_counters(u1, u2, u4):
    return {"poa.width.windows.u1": u1, "poa.width.windows.u2": u2,
            "poa.width.windows.u4": u4}


@pytest.mark.parametrize("cell_name", ALL_CELLS)
def test_program32_metric_loads_and_reads_its_counters(cell_name):
    cell = loader.load_cell(cell_name)        # the file agrees with its entry
    spec = {m["name"]: m for m in cell.per_layer}[
        "poa_program32_window_share"]
    assert spec["workloads"] == ALL_CELLS + [FULL_CELL]
    assert spec["layer"] == "kernels"
    assert spec["moves"] == "polished_mbp_per_s" and spec["unit"] == "%"
    assert spec["better"] == "higher"
    assert spec["reducer"] == "counter_share"
    registry = reducers.registry()

    def read(*jobs):
        return registry[spec["reducer"]](_run(*jobs), **spec["params"])

    # ecoli-ont.sam: fifteen full launches and three part-full ones
    job = dict(_width_counters(0, 40, 960), **{"poa.rows.real": 1000})
    assert read(job, job) == pytest.approx(96.0)
    # sixteen rows a shard: every key counted, the share reads 0
    assert read(dict(_width_counters(0, 1000, 0))) == 0.0
    assert read(dict(_width_counters(8, 0, 0))) == 0.0
    assert read(job, dict(_width_counters(0, 1000, 0))) == pytest.approx(48.0)
    # the parent's program counts none of them: nothing, and no raise
    older = {"poa.launches": 18, "poa.rows.real": 1000, "poa.rows.pad": 152,
             "poa.programs.wide": 72, "poa.programs.narrow": 0}
    assert read(older, older) is None


@pytest.mark.parametrize("cell_name", CELLS)
def test_older_program_metrics_read_past_the_width_counters(cell_name):
    """poa_wide_program_share and poa_lockstep_fill_share sum every
    counter under their prefixes: the width counters live under a prefix
    of their own, so a job's counters read what they read without them
    (a real job's counters: _count_launch over launches of every
    width)."""
    cell = loader.load_cell(cell_name)
    specs = {m["name"]: m for m in cell.per_layer}
    registry = reducers.registry()
    cfg = poa_driver.make_config(512, 200, *SCORES)
    rng = random.Random(7)
    obs.reset()
    obs.configure(metrics=True)
    try:
        for n_real in (64, 64, 46, 21, 8):
            layers = sorted(rng.randrange(20, 46) for _ in range(n_real))
            poa_driver._count_launch(
                n_real, _packed(layers + [0] * (64 - n_real)),
                poa_driver._group_width(cfg, 64, n_real))
        poa_driver._count_launch(5, _packed([9] * 5 + [0] * 3), 1)
        poa_driver._count_launch(3, _packed([5, 5, 5, 0]), 0)    # the twin
        counters = dict(obs.snapshot()["counters"])
    finally:
        obs.reset()
    widths = {k: v for k, v in counters.items()
              if k.startswith("poa.width.")}
    assert widths == _width_counters(5, 46 + 8, 64 + 64 + 21)
    # their sum is the windows the lockstep kernel was given: every real
    # row but the twin's three
    assert sum(widths.values()) == counters["poa.rows.real"] - 3
    without = {k: v for k, v in counters.items() if k not in widths}
    for name in ("poa_wide_program_share", "poa_lockstep_fill_share"):
        spec = specs[name]
        read = registry[spec["reducer"]]
        assert read(_run(counters, counters), **spec["params"]) == read(
            _run(without, without), **spec["params"]) is not None
    share = specs["poa_program32_window_share"]
    assert registry[share["reducer"]](_run(counters), **share["params"]) \
        == pytest.approx(100 * 149 / 208)
    # programs: 2 + 2 + 4 (46 rows at sixteen) + 2 (21 at thirty-two)
    # + 4 (8 at sixteen) wide, one narrow
    assert counters["poa.programs.wide"] == 14
    assert counters["poa.programs.narrow"] == 1
