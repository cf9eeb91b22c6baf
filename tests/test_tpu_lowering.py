"""Lower and compile every production Pallas kernel for the TPU without one.

Two gates, at the shapes the defaults dispatch — the TPU batch (64), the
four-chip per-shard batch (32 since PR 45; 16 where 64 is asked for), the
three depth buckets:

* **lowering** — ``jax.export`` with ``platforms=['tpu']`` runs the full
  Pallas -> Mosaic lowering on the CPU backend.  Interpret-mode tests (the
  rest of the suite) execute kernels as plain XLA and silently accept
  constructs Mosaic cannot lower: a ``lax.dynamic_slice`` on a loaded
  value, or a ``(1, G)`` SMEM block that only passes while the grid has
  one program (the ``ls`` tier shipped that way: it lowered at B=8 and
  was refused at every batch the driver uses).
* **compile** — libtpu compiles ahead of time for a described topology
  (``jax.experimental.topologies``), so the Mosaic compile stage itself —
  layouts, VMEM, what the lowering accepts and the compiler then refuses,
  such as a loop that carries an i1 vector — runs here too.  Skipped when
  this installation's libtpu cannot describe a v5e.

What neither can catch is a miscompile or a runtime fault; that is
``chip_smoke.py``'s job, on the chip.

Reference analogue: building the CUDA kernels is part of the reference's
default build+test cycle (CMakeLists racon_enable_cuda), so a
non-compiling kernel cannot land there either.
"""

import functools
import re

import numpy as np
import pytest

import jax
import jax.export

from racon_tpu.ops import align_pallas, poa_driver, poa_pallas_ls
from racon_tpu.parallel import reset_partitioner

TPU_BATCH = 64          # poa_driver._batch_size() on a TPU
SHARD_BATCH = 16        # a batch of 64 asked for, over a four-chip host
SCORES = (5, -4, -8)
UNIT_SCORES = (1, -1, -1)   # upstream's fragment scenarios (-m 1 -x -1 -g -1)


def _export_tpu(fn, args):
    exp = jax.export.export(jax.jit(fn), platforms=["tpu"])(*args)
    assert len(exp.mlir_module_serialized) > 0


@functools.lru_cache(maxsize=1)
def _v5e_devices():
    """The four chips of a described (not attached) v5e host, or None."""
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc("v5e:2x2", "tpu").devices
    except Exception:  # noqa: BLE001 — no libtpu / no such topology
        return None


def _v5e():
    """Sharding on one described v5e chip, or None."""
    from jax.sharding import SingleDeviceSharding

    devices = _v5e_devices()
    return None if devices is None else SingleDeviceSharding(devices[0])


def _compile_v5e(fn, args):
    sharding = _v5e()
    if sharding is None:
        pytest.skip("this libtpu cannot describe a v5e topology")
    specs = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)
             for a in args]
    jax.jit(fn).lower(*specs).compile()


@pytest.fixture
def single_device(monkeypatch):
    """The Hirschberg builders shard over the (virtual CPU) mesh by
    themselves; these gates want the per-device kernel."""
    monkeypatch.setenv("RACON_TPU_SHARD", "0")
    reset_partitioner()
    yield
    reset_partitioner()


def _poa_args(cfg, B, band=False):
    import __graft_entry__ as g

    bb, bbw, bl, nl, seqs, ws, lens, bg, en = g._example_batch(
        cfg, B, np.random.default_rng(0))
    args = (bl.reshape(-1, 1), nl.reshape(-1, 1), lens, bg, en,
            bb.astype(np.int32), bbw, seqs.astype(np.int32), ws)
    return args + (np.zeros(B, np.int32),) if band else args


def _ls(window_length, depth, B, band=False, scores=SCORES, rung=0,
        groups=None):
    from racon_tpu.ops.poa_pallas_ls import build_lockstep_poa_kernel

    cfg = poa_driver.make_config(window_length, depth, *scores, rung)
    # the VMEM-fit model must agree: a geometry it approves has to build
    assert poa_driver._fits_vmem(cfg), "fit model rejects geometry"
    # at a group width the driver derives for this class and batch, and
    # so under the vmem_limit_bytes it ships with: by default the widest,
    # which is what a full batch runs as (thirty-two windows a program at
    # 64 where VMEM holds them, sixteen at 16 a shard, eight at a batch
    # of 8); `groups` asks for the geometry's other program, the one of
    # sixteen a part-full launch of 64 runs
    widths = poa_driver._group_widths(cfg, B)
    assert widths[0] == poa_driver._group_width(cfg, B)
    assert widths[-1] == (1 if B % 16 or not poa_driver._fits_vmem(cfg, 2)
                          else 2), (window_length, depth, B)
    groups = groups or widths[0]
    assert groups in widths, (window_length, depth, B, widths)
    fn = build_lockstep_poa_kernel(cfg, interpret=False, band=band,
                                   groups=groups)(B)
    return fn, _poa_args(cfg, B, band)


def _edge(rcap, K, backward, B):
    fn = align_pallas._build_edge_kernel(rcap, K, backward,
                                         interpret=False)(B)
    qin = max(128, align_pallas._round_up(rcap // align_pallas.PACK, 128))
    scal = np.zeros((B, 4), np.int32)
    scal[:, 0] = rcap
    scal[:, 1] = rcap + K
    return fn, (scal, np.zeros((B, qin), np.int32),
                np.full((B, rcap + K), 255, np.int32))


def _base(K, B):
    kern, _ops, qcap, tcap = align_pallas._build_base_kernel(
        K, interpret=False)
    scal = np.zeros((B, 4), np.int32)
    scal[:, 0] = 1
    return kern(B), (scal, np.zeros((B, qcap), np.int32),
                     np.full((B, tcap), 255, np.int32))


# -- lowering --------------------------------------------------------------

@pytest.mark.parametrize("window_length,depth,B,scores", [
    (100, 8, 8, SCORES),     # small-window datasets, one grid program
    (1000, 8, 8, SCORES),    # the paf_w1000 golden scenario
    (500, 8, SHARD_BATCH, SCORES), (500, 32, SHARD_BATCH, SCORES),
    (500, 200, SHARD_BATCH, SCORES),
    (500, 8, TPU_BATCH, SCORES), (500, 32, TPU_BATCH, SCORES),
    (500, 200, TPU_BATCH, SCORES),
] + [
    # fragment correction (ecoli-frag.paf): every read ends in a tail
    # window, so the classes under the nominal 512 fill at every depth
    (wl_class, depth, TPU_BATCH, UNIT_SCORES)
    for wl_class in (128, 256, 384) for depth in poa_driver.DEPTH_BUCKETS
])
def test_lockstep_poa_kernel_lowers_to_tpu(window_length, depth, B, scores):
    _export_tpu(*_ls(window_length, depth, B, scores=scores))


def test_banded_lockstep_poa_kernel_lowers_past_one_program():
    _export_tpu(*_ls(500, 32, SHARD_BATCH, band=True))


def test_lockstep_poa_kernel_lowers_at_node_factor_4(monkeypatch):
    """RACON_TPU_NODE_FACTOR=4 admits the repeat-dense windows factor 3
    rejects (interpret evidence: 96/96 λ windows device-served at ed
    1282)."""
    monkeypatch.setenv("RACON_TPU_NODE_FACTOR", "4")
    fn, args = _ls(500, 8, TPU_BATCH)
    assert poa_driver.make_config(500, 8, *SCORES).max_nodes == 2048
    _export_tpu(fn, args)


@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "bwd"])
# one grid program of GROUP tasks (a launch of eight or fewer), and eight
@pytest.mark.parametrize("B", [align_pallas.GROUP, TPU_BATCH])
@pytest.mark.parametrize("rcap,K", [
    (512, 256), (8192, 1024), (8192, 2048),
    # read-vs-read overlaps of 0.5-6 kb (ecoli-frag.paf): the low buckets
    (1024, 256), (2048, 256), (2048, 512),
])
def test_hirschberg_edge_kernels_lower_to_tpu(single_device, rcap, K, B,
                                              backward):
    _export_tpu(*_edge(rcap, K, backward, B))


@pytest.mark.parametrize("B", [align_pallas.GROUP, TPU_BATCH])
@pytest.mark.parametrize("K", [256, 512, 1024, 2048])
def test_hirschberg_base_kernel_lowers_to_tpu(single_device, K, B):
    _export_tpu(*_base(K, B))


@pytest.mark.parametrize("name,build", [
    ("racon_poa_ls", lambda: _ls(500, 8, SHARD_BATCH)),
    ("racon_hirschberg_edge_fwd", lambda: _edge(512, 256, False, 8)),
    ("racon_hirschberg_edge_bwd", lambda: _edge(512, 256, True, 8)),
    ("racon_hirschberg_base", lambda: _base(256, 8)),
])
def test_lowered_kernel_carries_its_stable_name(single_device, name, build):
    """One name per kernel kind, in both places a profile shows: the HLO
    module (``jit_<name>``, from the jitted wrapper) and the Mosaic
    custom call's ``kernel_name`` (from ``pallas_call(name=)``).  The
    call target stays ``tpu_custom_call``: the benchmark's rooflines
    find the kernels by it."""
    fn, args = build()
    assert fn.__name__ == name
    text = jax.export.export(jax.jit(fn), platforms=["tpu"])(
        *args).mlir_module()
    assert f"module @jit_{name} " in text
    assert f'kernel_name = "{name}"' in text
    assert "stablehlo.custom_call @tpu_custom_call" in text


@pytest.mark.parametrize("build", [
    lambda: _ls(500, 32, TPU_BATCH),
    lambda: _ls(500, 200, SHARD_BATCH, rung=1),
    lambda: _ls(500, 32, SHARD_BATCH, band=True),
], ids=["u4", "u2-upper-rung", "u2-banded"])
def test_lockstep_kernel_marks_its_phases_as_flat_regions(monkeypatch,
                                                          build):
    """The Mosaic module Pallas hands the TPU compiler holds one
    ``tpu.trace_start`` for each of poa_pallas_ls.REGIONS, in the
    kernel's order, each stopped before the next starts (none around a
    layer, none nested): the five of a layer side by side in the layer
    loop's body, one loop level under the consensus walk's, so none
    inside the rank, traceback, update or slot loops, whose bodies lie
    deeper still."""
    from jax._src.pallas.mosaic import lowering

    modules, real = [], lowering.lower_jaxpr_to_module

    def spy(*a, **kw):
        module = real(*a, **kw)
        modules.append(str(module))
        return module

    monkeypatch.setattr(lowering, "lower_jaxpr_to_module", spy)
    jax.clear_caches()       # a geometry lowered before is not lowered again
    _export_tpu(*build())
    assert len(modules) == 1
    marks = [(len(line) - len(line.lstrip()),
              re.search(r'message = "([^"]*)"', line))
             for line in modules[0].splitlines()
             if "tpu.trace_start" in line or "tpu.trace_stop" in line]
    names = [m.group(1) if m else None for _, m in marks]
    assert names[0::2] == list(poa_pallas_ls.REGIONS)
    assert names[1::2] == [None] * len(poa_pallas_ls.REGIONS)
    depths = [d for d, _ in marks]
    in_layer, in_program = depths[:10], depths[10:]
    assert len(set(in_layer)) == 1 and len(set(in_program)) == 1
    assert in_layer[0] == in_program[0] + 2    # one region level of MLIR


# -- Mosaic compile --------------------------------------------------------

@pytest.mark.parametrize("depth", poa_driver.DEPTH_BUCKETS)
def test_lockstep_poa_kernel_compiles_for_v5e(depth):
    _compile_v5e(*_ls(500, depth, TPU_BATCH))


@pytest.mark.parametrize("window_length,depth,B,scores", [
    # a shard's batch on four chips: one program of sixteen a chip
    (500, 8, SHARD_BATCH, SCORES), (500, 32, SHARD_BATCH, SCORES),
    (500, 200, SHARD_BATCH, SCORES),
    # chr20-sr.sam: class 256 at the deepest bucket
    (200, 200, TPU_BATCH, (3, -5, -4)),
    # ecoli-frag.paf: the tail classes at unit scores
    (128, 32, TPU_BATCH, UNIT_SCORES), (256, 32, TPU_BATCH, UNIT_SCORES),
    (384, 200, TPU_BATCH, UNIT_SCORES),
    # a batch of 8 somebody asked for: the program of eight, U = 1
    (500, 32, 8, SCORES),
])
def test_lockstep_program_compiles_for_v5e_at_the_cells_geometries(
        window_length, depth, B, scores):
    """The widest program of every geometry a benchmark cell runs beside
    class 512 at batch 64 above, with the limit it ships with: sixteen
    windows at 16 a shard (two sublane groups, 10.85 MiB of arrays at
    class 512 against the compiler's default 16 MB scoped limit),
    thirty-two at a batch of 64; and the program of eight a batch of 8
    narrows to."""
    _compile_v5e(*_ls(window_length, depth, B, scores=scores))


@pytest.mark.parametrize("window_length,rung,scores,groups,limit_mib", [
    # the program of thirty-two (four sublane groups) a launch of 64 runs
    # wherever its last program is more than half real: ecoli-ont.sam and
    # the PAF cells (class 512, 21.70 MiB of arrays), the deep and the cap
    # cell (class 512 on the upper rung, 26.33 MiB: never compiled before
    # PR 44), chr20-sr.sam (class 256 at -w 200, 11.33 MiB; 11.91 at
    # -w 256)
    (500, 0, SCORES, 4, 44), (500, 1, SCORES, 4, 53),
    (200, 0, (3, -5, -4), 4, 23),
    # the last class VMEM holds at four groups: 31.50 MiB under 63 of the
    # 64 a limit may ask for (class 896 would ask for 75)
    (768, 0, SCORES, 4, 63),
    # and the geometry's other program, of sixteen: what a launch of 64
    # runs where the last program of thirty-two would be half pad or more
    (500, 0, SCORES, 2, None), (500, 1, SCORES, 2, 27),
    (200, 0, (3, -5, -4), 2, None),
], ids=["w500-u4", "w500-upper-u4", "w200-u4", "w768-u4",
        "w500-u2", "w500-upper-u2", "w200-u2"])
def test_both_programs_of_a_batch_of_64_compile_for_v5e(
        window_length, rung, scores, groups, limit_mib):
    from racon_tpu.ops import poa_pallas_ls

    cfg = poa_driver.make_config(window_length, 200, *scores, rung)
    assert poa_driver._group_widths(cfg, TPU_BATCH) == (4, 2)
    assert not poa_driver._fits_vmem(
        poa_driver.make_config(896, 200, *SCORES), 4)
    limit = poa_pallas_ls.vmem_limit_bytes(cfg, groups)
    assert limit == (limit_mib and limit_mib << 20)
    fn, args = _ls(window_length, 200, TPU_BATCH, scores=scores, rung=rung,
                   groups=groups)
    _export_tpu(fn, args)
    _compile_v5e(fn, args)


def test_wide_program_past_class_512_needs_the_limit_it_ships_with(
        monkeypatch):
    """The program of sixteen compiles under the compiler's default
    scoped-VMEM limit up to class 512 (10.85 MiB of arrays) and ships
    with no limit there; at class 640 (13.83 MiB) the compiler refuses
    it without one, and takes it under the limit sized from the sum."""
    from racon_tpu.ops import poa_pallas_ls

    MiB = 1 << 20
    at_512 = poa_driver.make_config(500, 200, *SCORES)
    at_640 = poa_driver.make_config(640, 8, *SCORES)
    assert poa_pallas_ls.vmem_limit_bytes(at_512, 1) is None
    assert poa_pallas_ls.vmem_limit_bytes(at_512, 2) is None
    assert poa_pallas_ls.vmem_limit_bytes(at_640, 1) is None
    assert poa_pallas_ls.vmem_limit_bytes(at_640, 2) == 28 * MiB
    _compile_v5e(*_ls(640, 8, SHARD_BATCH))
    monkeypatch.setattr(poa_pallas_ls, "vmem_limit_bytes",
                        lambda cfg, groups: None)
    poa_pallas_ls.build_lockstep_poa_kernel.cache_clear()
    try:
        with pytest.raises(Exception, match="memory space vmem"):
            _compile_v5e(*_ls(640, 8, SHARD_BATCH))
    finally:
        poa_pallas_ls.build_lockstep_poa_kernel.cache_clear()


@pytest.mark.parametrize("window_length,B,limit_mib", [
    (500, 8, None), (500, SHARD_BATCH, 27),
    (768, TPU_BATCH, 39),    # the last class the upper rung was climbed at
    # class 1024 (-w 1000, lambda-ont-w1000.paf) since PR 47: the row the
    # table refused while a program of eight was held under the default
    # limit's line (12.64 MiB a group), at the batch the cell runs (64:
    # programs of sixteen, 25.27 MiB under 51), at 16 a shard and at a
    # batch of 8 (one group under a limit of 26)
    (1000, TPU_BATCH, 51), (1000, SHARD_BATCH, 51), (1000, 8, 26),
], ids=["w500-u1", "w500-u2", "w768-u2", "w1000-u2", "w1000-u2-shard",
        "w1000-u1"])
def test_upper_rung_program_compiles_for_v5e(window_length, B, limit_mib):
    """The deep cell's program (ecoli-ont-deep.sam): class 512 on the
    upper node rung, 2560 graph slots, node arrays of 20 lane-chunks
    where the base rung's are 12.  One group's arrays sum to 6.58 MiB,
    under the default scoped-VMEM limit; the program of sixteen's to
    13.16 MiB, which compiles under vmem_limit_bytes (27 MiB) and had
    not met the chip before PR 35 (here at 16 a shard; at a batch of 64
    it is the geometry's second program, beside the one of thirty-two:
    test_both_programs_of_a_batch_of_64_compile_for_v5e).  At class 768
    VMEM holds no more than sixteen windows of the rung.  Past class 768
    one group's arrays pass what the default limit holds; until PR 47
    poa_driver._rung_capacities left the rung out there, since then the
    program of eight ships with a limit of its own like the wider ones
    (class 1024: 5120 graph slots, node arrays of 40 lane-chunks;
    tests/test_deep_cell.py holds the table)."""
    from racon_tpu.ops import poa_pallas_ls

    cfg = poa_driver.make_config(window_length, 200, *SCORES, 1)
    groups = poa_driver._group_width(cfg, B)
    assert cfg.max_nodes == 5 * poa_driver.window_class(window_length)
    assert groups == (1 if B == 8 else 2)
    limit = poa_pallas_ls.vmem_limit_bytes(cfg, groups)
    assert limit == (limit_mib and limit_mib << 20)
    fn, args = _ls(window_length, 200, B, rung=1)
    _export_tpu(fn, args)
    _compile_v5e(fn, args)


@pytest.mark.parametrize("node_factor,window_length,rung,B,limit_mib", [
    # the last class of each width under the one rule (the limit a
    # program needs may not pass VMEM_CEILING), at the deepest bucket
    ("3", 1000, 0, TPU_BATCH, 42),    # upstream's largest documented -w
    ("3", 1536, 0, TPU_BATCH, 61),    # sixteen windows: the last class
    ("3", 1280, 1, TPU_BATCH, 63),    # and on the upper rung
    ("3", 3200, 0, 8, 63),            # eight windows: the last class
    ("3", 2560, 1, 8, 62),            # and on the upper rung
    ("4", 1408, 0, TPU_BATCH, 64),    # sixteen at NODE_FACTOR 4
], ids=["w1000", "w1536-u2", "w1280-upper-u2", "w3200-u1", "w2560-upper-u1",
        "nf4-w1408-u2"])
def test_lockstep_poa_kernel_compiles_at_its_largest_class(
        monkeypatch, node_factor, window_length, rung, B, limit_mib):
    """Until PR 47 the line was drawn where the compiler refuses a
    program of eight under its *default* 16 MB scoped limit (class 1152
    at NODE_FACTOR 3: 16.3 MB with Mosaic's temporaries); with a limit
    of its own every width compiles up to the class whose limit reaches
    the ceiling, and the next class up is not admitted at that width."""
    from racon_tpu.ops import poa_pallas_ls

    monkeypatch.setenv("RACON_TPU_NODE_FACTOR", node_factor)
    cfg = poa_driver.make_config(window_length, 200, *SCORES, rung)
    groups = poa_driver._group_width(cfg, B)
    assert poa_pallas_ls.vmem_limit_bytes(cfg, groups) == limit_mib << 20
    if window_length != 1000:
        nxt = poa_driver.make_config(window_length + 128, 200, *SCORES, rung)
        assert not poa_driver._fits_vmem(nxt, groups)
    fn, args = _ls(window_length, 200, B, rung=rung)
    _compile_v5e(fn, args)


def test_program_of_eight_past_the_default_limit_needs_the_one_it_ships_with(
        monkeypatch):
    """Class 1024's upper rung at one group, 12.64 MiB of arrays: the
    compiler refuses it under its default scoped-VMEM limit, which is
    why the rung was left out of the lockstep kernel until PR 47, and
    takes it under the 26 MiB that vmem_limit_bytes asks for."""
    from racon_tpu.ops import poa_pallas_ls

    cfg = poa_driver.make_config(1000, 200, *SCORES, 1)
    assert poa_pallas_ls.scratch_bytes(cfg) > poa_pallas_ls.DEFAULT_LIMIT_HOLDS
    assert poa_pallas_ls.vmem_limit_bytes(cfg, 1) == 26 << 20
    monkeypatch.setattr(poa_pallas_ls, "vmem_limit_bytes",
                        lambda cfg, groups: None)
    poa_pallas_ls.build_lockstep_poa_kernel.cache_clear()
    try:
        with pytest.raises(Exception, match="memory space vmem"):
            _compile_v5e(*_ls(1000, 200, 8, rung=1))
    finally:
        poa_pallas_ls.build_lockstep_poa_kernel.cache_clear()


def test_banded_lockstep_poa_kernel_compiles_for_v5e():
    _compile_v5e(*_ls(500, 32, SHARD_BATCH, band=True))


def test_hirschberg_kernels_compile_for_v5e(single_device):
    # the row bucket and band 8 kb ONT reads land in
    for backward in (False, True):
        _compile_v5e(*_edge(8192, 1024, backward, TPU_BATCH))
    _compile_v5e(*_base(1024, TPU_BATCH))


@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "bwd"])
@pytest.mark.parametrize("rcap,K", [(512, 256), (2048, 512), (8192, 2048)])
def test_lockstep_edge_kernels_compile_for_v5e(single_device, rcap, K,
                                               backward):
    # the ends of the bucket ladder the two PAF cells run: (8, w) tiles in
    # VMEM, the (8, 1) scalar columns and their lane broadcasts
    _compile_v5e(*_edge(rcap, K, backward, TPU_BATCH))


@pytest.mark.parametrize("K", [256, 512, 2048])
def test_lockstep_base_kernel_compiles_for_v5e(single_device, K):
    # the move matrix of eight tasks, a byte per row, a 128-lane chunk a
    # leading index (0.5 to 4 MB of VMEM), and the joint walk's dynamic
    # chunk loads and lane rotations; with K = 1024 above, every band in
    # BANDS
    _compile_v5e(*_base(K, TPU_BATCH))


def test_kernel_bundles_reads_the_base_kernels_two_loops(single_device,
                                                         capsys):
    """`racon_tpu/tools/kernel_bundles.py` compiles the base kernel for
    a described v5e with the LLO dumps on and finds the forward DP's
    loop and the joint walk's: a trip of the walk, eight steps, stays a
    few hundred bundles (it was 613-672 with a masked lane sum and a
    read-modify-write a step: scalar spills)."""
    from racon_tpu.tools import kernel_bundles

    kernel_bundles.main(["256"])
    out = capsys.readouterr().out
    if "no dump" in out:
        pytest.skip("this libtpu writes no LLO dumps")
    dp, walk = (int(n) for n in re.findall(r"(\d+) bundles a trip", out))
    assert dp > 0 and 0 < walk < 400


def test_kernel_bundles_reads_the_lockstep_kernels_loops_by_name(capsys):
    """`--kernel ls` finds `racon_poa_ls`'s loops by their place in the
    loop tree at class 512 in a program of thirty-two, and the two rank
    loops stay at what PR 53 shipped them with (+3 %): a DP rank pair
    3272 bundles (3619 while a rank read its twelve in-edge distances
    and their maximum as thirteen int32 lane sums, each two float
    reductions), a traceback rank 1048 (1473 with those and five reads
    at j_stop), by this libtpu's scheduler."""
    from racon_tpu.tools import kernel_bundles

    kernel_bundles.main(["--kernel", "ls", "--window", "500", "--depth",
                         "200", "--groups", "4"])
    out = capsys.readouterr().out
    if "no dump" in out:
        pytest.skip("this libtpu writes no LLO dumps")
    got = {name: tuple(int(n) for n in nums) for name, *nums in re.findall(
        r": (\w+) (\d+) bundles a trip, (\d+) ops, (\d+) cross-lane adds, "
        r"(\d+) bundles of at most one op", out)}
    assert list(got) == [name for name, _ in kernel_bundles.LS_LOOPS]
    assert 0 < got["dp_pair"][0] <= 3272 * 1.03
    assert 0 < got["tb_rank"][0] <= 1048 * 1.03
    # a rank's four record words and its base are one float reduction a
    # sublane group each, esc's H value two; a traceback rank's word at
    # j_stop one, its column key one
    assert got["dp_pair"][2] == 2 * (5 + 2) * 4
    assert got["tb_rank"][2] == (5 + 1 + 1) * 4
    for inner, outer in (("delta_scan", "dp_pair"), ("mscan", "tb_rank"),
                         ("insert_shift", "update_step")):
        assert 0 < got[inner][0] < got[outer][0]
        assert got[inner][2] == 0          # no reduction inside a scan
    assert all(ops >= bundles for bundles, ops, _, _ in got.values())


# -- Mosaic compile over the mesh ------------------------------------------

@pytest.fixture
def v5e_mesh(monkeypatch):
    """The partitioner over the (4, 1) mesh of a described v5e:2x2 host
    in place of the one over this process's CPU devices: the Hirschberg
    builders then wrap their kernels in shard_map for four chips."""
    from jax.sharding import Mesh

    from racon_tpu.parallel import axes, partitioner

    devices = _v5e_devices()
    if devices is None:
        pytest.skip("this libtpu cannot describe a v5e topology")
    mesh = Mesh(np.asarray(devices, dtype=object).reshape(4, 1),
                axes.MESH_AXES)
    part = partitioner.Partitioner(mesh, axes.rules_key())
    monkeypatch.setattr(partitioner, "get_partitioner", lambda: part)
    align_pallas._build_edge_kernel.cache_clear()
    align_pallas._build_base_kernel.cache_clear()
    yield part
    align_pallas._build_edge_kernel.cache_clear()
    align_pallas._build_base_kernel.cache_clear()


@pytest.mark.parametrize("B", [8, 16, 32, 64])
@pytest.mark.parametrize("kernel", ["edge_fwd", "edge_bwd", "base"])
def test_sharded_hirschberg_kernels_compile_for_a_v5e_host(v5e_mesh, kernel,
                                                           B):
    """The launches ``ecoli-ont-x4.paf`` ships, at the bucket 8 kb ONT
    reads land in: a shard's share of 2 or 4 rows (one program with idle
    sublanes, padded inside shard_map), 8 (one whole program) and 16
    (two).  One Mosaic kernel per chip and no collective: the batch is
    striped, nothing crosses chips."""
    assert align_pallas._dispatch_shards(B) == 4
    fn, args = (_base(1024, B) if kernel == "base"
                else _edge(8192, 1024, kernel == "edge_bwd", B))
    rows = v5e_mesh.sharding("windows")
    specs = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rows)
             for a in args]
    text = fn.lower(*specs).compile().as_text()
    assert "tpu_custom_call" in text
    assert not [c for c in ("all-gather", "all-reduce", "all-to-all",
                            "collective-permute") if c in text]


#: the batch a TPU's mesh of four gives itself (poa_driver._device_batch:
#: a widest program a shard)
MESH_BATCH = 4 * poa_driver.GROUP_WIDTHS[0] * poa_pallas_ls.G


@pytest.mark.parametrize("B,rung,want", [
    (MESH_BATCH, 0, [2, 4]),    # 32 a shard: what the driver builds
    (MESH_BATCH, 1, [2, 4]),    # the upper rung's [1,4,20,8,128] program
    (TPU_BATCH, 0, [2]),        # 16 a shard: a batch of 64 asked for
], ids=["128-base", "128-upper", "64-base"])
def test_sharded_lockstep_program_compiles_for_a_v5e_host(v5e_mesh, B, rung,
                                                          want):
    """The consensus launch of the four-chip cells, as the driver builds
    it: since PR 45 128 rows under shard_map over the (4, 1) mesh, 32 a
    shard, so a geometry holds a program of thirty-two a chip (four
    sublane groups) and one of sixteen, on both node rungs of class 512;
    a batch of 64 somebody asked for through the knob is still 16 a
    shard and one program of sixteen.  One Mosaic kernel per chip and no
    collective."""
    cfg = poa_driver.make_config(500, 200, *SCORES, rung)
    assert list(poa_driver._group_widths(cfg, B // 4)) == want[::-1]
    poa_driver._build_kernel_cached.cache_clear()
    try:
        handle = poa_driver._build_kernel_cached(cfg, B, True, 4, "tpu", 4,
                                                 False)
        assert sorted(handle.programs) == want    # built with the geometry
        rows = v5e_mesh.sharding("windows")
        specs = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rows)
                 for a in _poa_args(cfg, B)]
        texts = [handle.programs[u].lower(*specs).compile().as_text()
                 for u in want]
    finally:
        poa_driver._build_kernel_cached.cache_clear()
    for text in texts:
        assert "tpu_custom_call" in text
        assert not [c for c in ("all-gather", "all-reduce", "all-to-all",
                                "collective-permute") if c in text]


# -- what the program cache hands XLA ---------------------------------------

def _compile_exported(prog, args, sharding):
    """A process that found `prog` in the program cache
    (``ops/kernel_cache.Program``) runs ``jax.jit`` of the deserialized
    export's ``call`` under the program's shardings, not the body:
    compile that for the described chips."""
    exported = jax.export.deserialize(bytearray(jax.export.export(
        prog._plain, platforms=["tpu"])(
            *(jax.ShapeDtypeStruct(a.shape, a.dtype) for a in args)
        ).serialize()))
    specs = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)
             for a in args]
    text = prog.run_exported(exported).lower(*specs).compile().as_text()
    assert f"HloModule jit_{prog.__name__}" in text
    assert f"%{prog.__name__}" in text and "tpu_custom_call" in text
    return exported, text


@pytest.mark.parametrize("kernel", ["racon_poa_ls", "edge_fwd", "base"])
def test_exported_programs_compile_for_v5e(single_device, kernel):
    sharding = _v5e()
    if sharding is None:
        pytest.skip("this libtpu cannot describe a v5e topology")
    prog, args = {"racon_poa_ls": lambda: _ls(500, 32, TPU_BATCH),
                  "edge_fwd": lambda: _edge(8192, 1024, False, TPU_BATCH),
                  "base": lambda: _base(1024, TPU_BATCH)}[kernel]()
    exported, _ = _compile_exported(prog, args, sharding)
    assert exported.nr_devices == 1 and prog.shardings is None


@pytest.mark.parametrize("kernel", ["racon_poa_ls", "edge_bwd", "base"])
def test_exported_sharded_programs_compile_for_a_v5e_host(v5e_mesh, kernel):
    """The four-chip cells' programs as a warm process runs them: the
    four-device export under the mesh's NamedShardings, one Mosaic
    kernel per chip and no collective."""
    if kernel == "racon_poa_ls":
        cfg = poa_driver.make_config(500, 200, *SCORES)
        poa_driver._build_kernel_cached.cache_clear()
        prog = poa_driver._build_kernel_cached(
            cfg, MESH_BATCH, True, 4, "tpu", 4, False).programs[4]
        args = _poa_args(cfg, MESH_BATCH)
    else:
        prog, args = (_base(1024, 16) if kernel == "base"
                      else _edge(8192, 1024, True, 16))
    assert prog.key[0] == "shard_map"
    exported, text = _compile_exported(prog, args,
                                       v5e_mesh.sharding("windows"))
    assert exported.nr_devices == 4
    assert not [c for c in ("all-gather", "all-reduce", "all-to-all",
                            "collective-permute") if c in text]
