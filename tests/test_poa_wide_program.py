"""The width of a lockstep consensus program (PR 34): how the driver
derives it, what the launch counters say of it, and the two per-layer
metrics that read them.

`racon_poa_ls` runs U x 8 windows a grid program under one control flow.
U is a function of the window class, the per-shard batch and the VMEM
sum (`poa_driver._group_width`); `poa_driver._count_launch` counts the
programs as wide or narrow and bills lock-step what it costs (every
window of a program runs the program's largest layer count), once per
launch from the packed `n_layers` row.
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


@pytest.mark.parametrize("wl_class,shard_batch,want", [
    (512, 64, 2),      # one chip: four programs of sixteen
    (512, 16, 2),      # a shard's batch on four chips: one program
    (256, 64, 2), (128, 64, 2), (384, 64, 2), (1024, 64, 2),
    (512, 8, 1),       # a batch of 8 somebody asked for
    (512, 24, 1),      # three programs of eight do not pair up
    (256, 40, 1),
], ids=lambda v: str(v))
def test_group_width_follows_class_batch_and_vmem(wl_class, shard_batch,
                                                  want):
    for depth in poa_driver.DEPTH_BUCKETS:
        cfg = poa_driver.make_config(wl_class, depth, *SCORES)
        assert poa_driver._group_width(cfg, shard_batch) == want
    # the device batch is a multiple of the program's width on every
    # shard, which is what make() asserts
    assert shard_batch % (want * poa_pallas_ls.G) == 0


def test_group_width_narrows_where_vmem_does_not_hold_the_wide_program(
        monkeypatch):
    """Nothing the driver admits today is too large at two groups (class
    1024 asks for 42 MiB of the 64 a limit may reach), so the ceiling is
    lowered here: the width then falls to one group, the class stays on
    the kernel."""
    cfg = poa_driver.make_config(1024, 32, *SCORES)
    assert poa_pallas_ls.vmem_limit_bytes(cfg, 2) == 42 << 20
    assert poa_driver._group_width(cfg, 64) == 2
    monkeypatch.setattr(poa_pallas_ls, "VMEM_CEILING", 32 << 20)
    assert not poa_driver._fits_vmem(cfg, 2) and poa_driver._fits_vmem(cfg)
    assert poa_driver._group_width(cfg, 64) == 1
    assert poa_driver._pick_tier(cfg, True) == "ls"
    assert poa_driver._group_width(          # class 512 is still wide
        poa_driver.make_config(512, 32, *SCORES), 64) == 2


def test_scratch_sum_doubles_with_the_groups():
    cfg = poa_driver.make_config(512, 200, *SCORES)
    one = poa_pallas_ls.scratch_bytes(cfg)
    assert round(one / 2 ** 20, 2) == 5.43
    assert poa_pallas_ls.scratch_bytes(cfg, 2) == 2 * one


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
                 "poa.programs.wide": 4, "poa.programs.narrow": 0,
                 "poa.lockstep.layers.real": sum(layers),
                 "poa.lockstep.layers.slots": by_hand,
                 # the node rungs' counters (tests/test_deep_cell.py)
                 "poa.windows.rung.base": 64, "poa.windows.rung.upper": 0,
                 "poa.layers.admitted": sum(layers)}
    assert by_hand == 16 * (layers[15] + layers[31] + layers[47]
                            + layers[63])


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


def test_count_launch_per_shard_batch_of_sixteen():
    # four chips: 64 rows, 16 a shard, one program of sixteen a chip
    layers = [25] * 16 + [31] * 15 + [44] + [8] * 16 + [0] * 16
    cfg = poa_driver.make_config(512, 200, *SCORES)
    groups = poa_driver._group_width(cfg, 64 // 4)
    c = _counted(48, layers, groups)
    assert groups == 2 and c["poa.programs.wide"] == 4
    assert c["poa.lockstep.layers.real"] == 16 * 25 + 15 * 31 + 44 + 16 * 8
    assert c["poa.lockstep.layers.slots"] == 16 * (25 + 44 + 8 + 0)


def test_count_launch_geometry_that_gets_one_group():
    cfg = poa_driver.make_config(512, 32, *SCORES)
    groups = poa_driver._group_width(cfg, 8)
    layers = [3, 9, 9, 4, 0, 0, 0, 0]
    c = _counted(4, layers, groups)
    assert groups == 1
    # both keys at every launch, a zero too: a job served by narrow
    # programs alone reads 0 % wide, not nothing
    assert c["poa.programs.wide"] == 0 and c["poa.programs.narrow"] == 1
    assert c["poa.lockstep.layers.real"] == 25
    assert c["poa.lockstep.layers.slots"] == 8 * 9


def test_count_launch_of_the_xla_twin_has_no_programs():
    c = _counted(3, [5, 5, 5, 0], 0)
    assert c == {"poa.launches": 1, "poa.rows.real": 3, "poa.rows.pad": 1,
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


def test_obs_report_lists_the_program_counters():
    """`python -m racon_tpu.obs <trace>` lists the four counters beside
    the mesh counters of alignment."""
    from racon_tpu.obs import __main__ as obs_cli

    counters = {"poa.programs.wide": 72, "poa.programs.narrow": 0,
                "poa.lockstep.layers.real": 36000,
                "poa.lockstep.layers.slots": 40000,
                "align.mesh.launches.single": 380, "poa.launches": 18}
    text = obs_cli.render(
        {"traceEvents": [], "racon_tpu": {"metrics": {"counters": counters}}},
        "t.json")
    assert "-- consensus programs in lock-step" in text
    assert "-- alignment launches over the mesh" in text
    section = text.split("-- consensus programs in lock-step")[1]
    for name in counters:
        assert (name in section) == name.startswith(("poa.programs.",
                                                     "poa.lockstep."))


# -- the two per-layer metrics --------------------------------------------

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
        assert spec["workloads"] == CELLS and spec["layer"] == "kernels"
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
