"""Deep coverage as a deployment: the consensus kernel on the upper node
rung (``poa_driver.NODE_RUNGS``) against the XLA twin and the host
engine, the driver's rung rule against the plain reference
``benchmark/reference_depth.py``, the counters that say what the rungs
did, and the files of the cell ``ecoli-ont-deep.sam``.

Everything runs at a small size on the CPU: windows of 128 bp (the
smallest window class, whose base rung is 384 graph slots and upper rung
640) at 16 to 100 layers of ``benchmark/generate.py``'s ONT error mix,
interpreted ``ls``.  Node growth per backbone base is the same at 128 bp
as at the cell's 500 (it is a per-column process), so the windows outgrow
the base rung where the cell's do, near 56 layers.  The cell's CPU
rehearsal (75 layers on two windows of 500 bp) takes minutes and is run
by hand, see the verify skill.
"""

import json
import random

import numpy as np
import pytest

from benchmark import (generate, judge, loader, prepare, reducers,
                       reference_depth, reference_layout)
from racon_tpu import native
from racon_tpu.ops import poa, poa_driver, poa_pallas_ls
from racon_tpu.ops.encoding import decode
from tests.test_pallas_ls import _alloc, _deal, _run_ls, _set_window

CELL = "ecoli-ont-deep.sam"
SCORES = (5, -4, -8)
NEW_METRICS = {
    "deep_poa_upper_rung_window_share", "deep_poa_overflow_window_share",
    "deep_poa_node_fill_share", "deep_poa_layers_per_window",
    "deep_poa_roofline", "deep_poa_wide_program_share",
    "deep_poa_lockstep_fill_share"}
LAYERS = (16, 40, 60, 66, 72, 80, 90, 100)      # one window each
BASE = poa_driver.make_config(128, poa_driver.DEPTH_CAP, *SCORES)
UPPER = poa_driver.make_config(128, poa_driver.DEPTH_CAP, *SCORES, 1)


def _ont(seq, rng):
    """configs/ecoli-ont-deep.json's read profile: 5 % substitutions,
    3 % insertions, 3 % deletions."""
    out = bytearray()
    for c in seq:
        if rng.random() < 0.03:
            continue
        out.append(rng.choice(b"ACGT") if rng.random() < 0.05 else c)
        if rng.random() < 0.03:
            out.append(rng.choice(b"ACGT"))
    return bytes(out)


def _strays(truth, layers):
    """What the native breaking-point walk counts for a window
    (``Pipeline.window_growth``), by the plain reference aligner."""
    t = np.frombuffer(truth, np.uint8)
    return sum(reference_layout.stray_bases(
        q, t, reference_layout.align(q, t)[1])
        for q in (np.frombuffer(layer, np.uint8) for layer in layers))


def _twin(a, cfg):
    fn = poa.build_poa_kernel(cfg)
    return tuple(np.asarray(x) for x in fn(
        a["bb"], a["bbw"], a["bb_len"], a["nl"], a["seqs"], a["ws"],
        a["lens"], a["bg"], a["en"]))


# -- (a) the kernels on the upper rung -------------------------------------

@pytest.fixture(scope="module")
def deep_windows():
    """Eight seeded windows of 128 bp, 16 to 100 layers, through ``ls``
    on the upper rung at one, two and four sublane groups, through the XLA
    twin on the upper rung, and through ``ls`` on the base rung."""
    rng = random.Random(35)
    cases, per_rung = [], {}
    for cfg in (BASE, UPPER):
        per_rung[cfg] = _alloc(len(LAYERS), cfg)
    for b, n in enumerate(LAYERS):
        truth = bytes(rng.choice(b"ACGT") for _ in range(128))
        layers = [_ont(truth, rng) for _ in range(n)]
        cases.append((truth, layers))
        for a in per_rung.values():
            _set_window(a, b, truth, layers)
    def dealt(groups):
        wide, pos = _deal(per_rung[UPPER], UPPER, groups)
        return tuple(x[pos] for x in _run_ls(wide, UPPER, groups))

    return {
        "cases": cases,
        "upper_u1": _run_ls(per_rung[UPPER], UPPER, 1),
        "upper_u2": dealt(2),
        "upper_u4": dealt(4),
        "upper_twin": _twin(per_rung[UPPER], UPPER),
        "base_u1": _run_ls(per_rung[BASE], BASE, 1),
    }


def test_ls_on_the_upper_rung_equals_the_twin_byte_for_byte(deep_windows):
    cb, cc, cl, fl, nn = deep_windows["upper_u1"]
    jb, jc, jl, jf, jn = deep_windows["upper_twin"]
    assert not fl.any() and not jf.any()
    np.testing.assert_array_equal(cl[:, 0], jl)
    np.testing.assert_array_equal(nn[:, 0], jn)
    for b in range(len(LAYERS)):
        n = int(jl[b])
        np.testing.assert_array_equal(cb[b, :n], jb[b, :n])
        np.testing.assert_array_equal(cc[b, :n], jc[b, :n])


def test_ls_on_the_upper_rung_against_the_host_engine(deep_windows):
    """As far as tests/test_pallas_ls.py holds the base rung to it, the
    consensus string, up to 40 layers.  Past that the device engines
    (``ls`` and the twin, byte for byte above) and the host engine part
    by an edit or two at a window's last columns, where many reads end
    and the heaviest path has ties the two break differently
    (benchmark/judge.py allows for it); neither is nearer the truth:
    over these windows the device leaves 5 edits and the host 8."""
    cb, _, cl, _, _ = deep_windows["upper_u1"]
    left = {"device": 0, "host": 0}
    for b, (truth, layers) in enumerate(deep_windows["cases"]):
        host, _ = native.window_consensus(truth, layers, trim=False)
        device = decode(cb[b, :cl[b, 0]])
        if LAYERS[b] <= 40:
            assert device == host, f"window {b}"
        assert native.edit_distance(device, host) <= 2, f"window {b}"
        left["device"] += native.edit_distance(device, truth)
        left["host"] += native.edit_distance(host, truth)
    assert left["device"] <= left["host"] + 2, left


@pytest.mark.parametrize("groups", [2, 4], ids=["u2", "u4"])
def test_one_and_two_sublane_groups_agree_on_the_upper_rung(deep_windows,
                                                            groups):
    """And one and four: the eight windows dealt over the groups of one
    program, pad windows in the other slots."""
    for one, wide in zip(deep_windows["upper_u1"],
                         deep_windows[f"upper_u{groups}"]):
        np.testing.assert_array_equal(one, wide)


def test_a_window_that_overflows_the_base_rung_fits_the_upper_one(
        deep_windows):
    """The base rung flags exactly the windows whose graph is larger
    than its 384 slots, with the cause; the upper rung serves them, at
    the twin's node count."""
    _, _, _, base_failed, base_nodes = deep_windows["base_u1"]
    _, _, _, failed, nodes = deep_windows["upper_u1"]
    too_large = nodes[:, 0] > BASE.max_nodes
    assert too_large.sum() >= 4 and not too_large[:2].any()
    np.testing.assert_array_equal(
        base_failed[:, 0], np.where(too_large, poa.FAIL_NODES, 0))
    # a window that fits is the same window on either rung
    np.testing.assert_array_equal(base_nodes[~too_large], nodes[~too_large])
    assert not failed.any() and nodes.max() <= UPPER.max_nodes
    np.testing.assert_array_equal(nodes[:, 0],
                                  deep_windows["upper_twin"][4])


def test_the_rung_rule_holds_what_the_kernel_built(deep_windows):
    """node_estimate (NODE_ENVELOPE, the most nodes the host engine's
    graphs held at a window's growth) against the kernel's node count on
    windows of 128: a graph's size is a sum over its columns, so a window
    a quarter as long scatters twice as widely around the same curve, and
    an envelope lies over the typical window: from 0.95 to 1.25 of the
    count here.  The rung the rule picks holds every window whose
    estimate is not within a tenth of a rung's capacity; one nearer than
    that can be misjudged, and then costs a host redo, which
    poa.windows.rung.miss.* counts."""
    nodes = deep_windows["upper_u1"][4][:, 0]
    caps = poa_driver._rung_capacities(128, True, *SCORES)
    assert caps == (BASE.max_nodes, UPPER.max_nodes)
    rungs = []
    for b, (truth, layers) in enumerate(deep_windows["cases"]):
        est = poa_driver.node_estimate(len(truth), sum(map(len, layers)),
                                       _strays(truth, layers))
        rungs.append(poa_driver._node_rung(est, caps))
        assert 0.95 * nodes[b] <= est <= 1.25 * nodes[b], (b, nodes[b], est)
        assert rungs[-1] == (1 if est > BASE.max_nodes else 0)
        if abs(est - BASE.max_nodes) > 0.1 * BASE.max_nodes:
            assert nodes[b] <= caps[rungs[-1]]
    assert rungs == [0, 0, 1, 1, 1, 1, 1, 1]
    # a window of any depth climbs, if what it tells of itself says so,
    # and one that tells nothing of its strays never does
    assert poa_driver._node_rung(10 ** 6, caps) == 1
    assert poa_driver.node_estimate(128, 128 * 100, 0) <= BASE.max_nodes


def test_the_kernels_name_the_same_cause():
    """Node slots and in-edge slots are counted apart, by ``ls`` and by
    the twin alike: a graph of 128 slots runs out of nodes, one of 2
    in-edge slots a node runs out of those."""
    rng = random.Random(7)
    for cfg, cause in (
            (poa.PoaConfig(128, 256, 128, 12, 8, *SCORES), poa.FAIL_NODES),
            (poa.PoaConfig(384, 256, 128, 2, 8, *SCORES), poa.FAIL_EDGES)):
        a = _alloc(8, cfg)
        for b in range(8):
            truth = bytes(rng.choice(b"ACGT") for _ in range(96))
            # windows 0-3 clean, 4-7 at 15 % errors
            layers = [truth if b < 4 else
                      _ont(_ont(_ont(truth, rng), rng), rng)
                      for _ in range(8)]
            _set_window(a, b, truth, layers)
        fl, jf = _run_ls(a, cfg, 1)[3][:, 0], _twin(a, cfg)[3]
        np.testing.assert_array_equal(fl, jf)
        assert not fl[:4].any() and set(fl[4:]) == {cause}, (cause, fl)


# -- (b) the capacity table -------------------------------------------------

@pytest.mark.parametrize("wl_class,base,upper,widest,mib_at_4", [
    # widest: the widest program VMEM holds on the two rungs; mib_at_4:
    # the VMEM arrays of a program of thirty-two there; a program runs
    # wherever twice its own arrays is 64 MiB or less, one rule for
    # every width
    (128, 384, 640, (4, 4), (8.06, 9.22)),
    (256, 768, 1280, (4, 4), (11.91, 14.22)),
    (384, 1152, 1920, (4, 4), (17.86, 21.33)),
    (512, 1536, 2560, (4, 4), (21.70, 26.33)),
    (640, 1920, 3200, (4, 2), (27.66, 33.44)),
    (768, 2304, 3840, (4, 2), (31.50, 38.44)),
    # until PR 47 the lockstep kernel stayed on the base rung from here
    # on (one group's upper-rung arrays, 11.39 and 12.64 MiB, pass what
    # the compiler's default limit holds, and a program of eight was
    # held under it); it climbs under a raised limit now, as sixteen and
    # thirty-two windows have since PR 34
    (896, 2688, 4480, (2, 2), (37.45, 45.55)),
    (1024, 3072, 5120, (2, 2), (41.30, 50.55)),
    # and past class 1024 (-w over 1000), where it did not serve at all
    (1152, 3456, 5760, (2, 2), (47.25, 57.66)),
    (1280, 3840, 6400, (2, 2), (51.09, 62.66)),
    (1536, 4608, 7680, (2, 1), (60.89, 74.77)),
    (2560, 7680, 12800, (1, 1), (100.08, 123.20)),
    # the upper rung's last class is 2560, the base rung's 3200
    (2688, 8064, 13440, (1, 0), (106.03, 130.31)),
    (3200, 9600, 16000, (1, 0), (125.62, 154.53)),
    (3328, 9984, 16640, (0, 0), (129.47, 159.53))])
def test_capacity_table_by_class_rung_and_group_width(wl_class, base, upper,
                                                      widest, mib_at_4):
    cfgs = [poa_driver.make_config(wl_class, poa_driver.DEPTH_CAP, *SCORES,
                                   rung) for rung in (0, 1)]
    assert [c.max_nodes for c in cfgs] == [base, upper]
    assert cfgs[0].max_edges == cfgs[1].max_edges == 12
    for cfg, mib, most in zip(cfgs, mib_at_4, widest):
        assert round(poa_pallas_ls.scratch_bytes(cfg, 4) / 2 ** 20,
                     2) == mib
        for groups in (1, 2, 4):
            assert poa_driver._fits_vmem(cfg, groups) == (
                2 * mib * groups / 4 <= 64) == (groups <= most)
    # thirty-two windows a full program up to class 768 on the base rung
    # and class 512 on the upper one, sixteen up to 1536 / 1280, eight
    # up to 3200 / 2560
    if widest[0]:
        assert [poa_driver._group_width(c, 64) for c in cfgs] == [
            widest[0], widest[1] or 1]
    ls_climbs = widest[1] > 0
    assert poa_driver._rung_capacities(wl_class, True, *SCORES) == (
        (base, upper) if ls_climbs else (base,))
    assert poa_driver._rung_capacities(wl_class, False, *SCORES) == (
        base, upper)
    assert poa_driver._pick_tier(cfgs[0], True) == (
        "ls" if widest[0] else "xla")


#: (window class, rung) -> widths at a batch of 64, 32, 16 and 8, and the
#: scoped-VMEM limit (MiB; None: the compiler's default) of the programs
#: of thirty-two, sixteen and eight, as the parent (PR 46) built them
PARENT_TABLE = {
    (128, 0): (((4, 2), (4, 2), (2,), (1,)), (None, None, None)),
    (128, 1): (((4, 2), (4, 2), (2,), (1,)), (None, None, None)),
    (256, 0): (((4, 2), (4, 2), (2,), (1,)), (24, None, None)),
    (256, 1): (((4, 2), (4, 2), (2,), (1,)), (29, None, None)),
    (384, 0): (((4, 2), (4, 2), (2,), (1,)), (36, None, None)),
    (384, 1): (((4, 2), (4, 2), (2,), (1,)), (43, None, None)),
    (512, 0): (((4, 2), (4, 2), (2,), (1,)), (44, None, None)),
    (512, 1): (((4, 2), (4, 2), (2,), (1,)), (53, 27, None)),
    (640, 0): (((4, 2), (4, 2), (2,), (1,)), (56, 28, None)),
    (640, 1): (((2,), (2,), (2,), (1,)), (67, 34, None)),
    (768, 0): (((4, 2), (4, 2), (2,), (1,)), (63, 32, None)),
    (768, 1): (((2,), (2,), (2,), (1,)), (77, 39, None)),
}


@pytest.mark.parametrize("wl_class,rung", sorted(PARENT_TABLE),
                         ids=lambda v: str(v))
def test_classes_up_to_768_keep_the_widths_and_limits_of_the_parent(
        wl_class, rung):
    """PR 47 made _fits_vmem one rule; every geometry of class 768 or
    less builds the programs it built before, under the limits it had
    (the table is the parent's, written down before the change)."""
    widths, limits = PARENT_TABLE[(wl_class, rung)]
    for depth in poa_driver.DEPTH_BUCKETS:
        cfg = poa_driver.make_config(wl_class, depth, *SCORES, rung)
        assert tuple(poa_driver._group_widths(cfg, b)
                     for b in (64, 32, 16, 8)) == widths
        assert tuple(poa_pallas_ls.vmem_limit_bytes(cfg, u)
                     for u in (4, 2, 1)) == tuple(
                         m and m << 20 for m in limits)
        # a limit past the ceiling is never asked for: the width is out
        for u, m in zip((4, 2, 1), limits):
            assert poa_driver._fits_vmem(cfg, u) == (m is None or m <= 64)
        assert poa_driver._pick_tier(cfg, True) == "ls"
    assert poa_driver._rung_capacities(wl_class, True, *SCORES) == (
        3 * wl_class, 5 * wl_class)


def test_the_program_of_sixteen_on_the_upper_rung_ships_with_a_limit():
    cfg = poa_driver.make_config(512, poa_driver.DEPTH_CAP, *SCORES, 1)
    MiB = 1 << 20
    assert poa_pallas_ls.scratch_bytes(cfg) < poa_pallas_ls.DEFAULT_LIMIT_HOLDS
    assert poa_pallas_ls.vmem_limit_bytes(cfg, 1) is None
    assert poa_pallas_ls.vmem_limit_bytes(cfg, 2) == 27 * MiB
    # and the program of thirty-two (26.33 MiB of arrays) with one of 53
    assert poa_pallas_ls.vmem_limit_bytes(cfg, 4) == 53 * MiB


def test_the_knob_is_the_base_rung(monkeypatch):
    monkeypatch.setenv("RACON_TPU_NODE_FACTOR", "4")
    assert poa_driver._rung_factors() == (4, poa_driver.UPPER_NODE_FACTOR)
    assert poa_driver.make_config(500, 200, *SCORES).max_nodes == 2048
    assert poa_driver.make_config(500, 200, *SCORES, 1).max_nodes == 2560
    monkeypatch.setenv("RACON_TPU_NODE_FACTOR", "6")   # past the upper rung
    assert poa_driver._rung_factors() == (6,)
    assert poa_driver._rung_capacities(512, True, *SCORES) == (3072,)


def test_the_envelope_is_sized_for_the_depth_cap():
    xs, ys = zip(*poa_driver.NODE_ENVELOPE)
    assert list(xs) == sorted(xs) and list(ys) == sorted(ys)
    # the ONT profile's layers stray by 7.25 % (5 % substitutions of which
    # a quarter draw the same base, 1 % in the draft, 3 % insertions)
    def est(layers, stray=0.0725):
        return poa_driver.node_estimate(500, 500 * layers,
                                        int(500 * layers * stray))
    assert est(poa_driver.DEPTH_CAP) <= poa_driver.make_config(
        500, 200, *SCORES, 1).max_nodes
    # the 30x cells' deepest windows (47 effective layers) stay on the
    # base rung, the deep cell's typical window (106) climbs
    base = poa_driver.make_config(500, 200, *SCORES).max_nodes
    assert est(47) <= base < est(106)
    # layers alone do not tell: 33 layers that stray by 18 % (a raw
    # layout under 17 % reads) need what 53 of the ONT profile do
    assert est(33) <= base < est(33, 0.18) == est(53)
    assert poa_driver.window_growth(500, 500 * 33, int(500 * 33 * 0.18)) \
        == pytest.approx(33 * 0.18 ** 0.5, rel=1e-3)


def test_audit_grid_names_every_program_and_no_more():
    grid = poa_driver.audit_grid()
    assert len(grid) == len(set(grid)) == 8
    assert {r for _, _, r in grid} == {0, 1}
    assert all(d == poa_driver.DEPTH_CAP for d, _, r in grid if r)
    # a geometry holds a program a width its launches choose between:
    # thirty-two and sixteen at class 512, sixteen at class 1024 on both
    # rungs (until PR 47 its upper rung was the XLA twin's one program;
    # the count is the same)
    widths = {(d, c, r): poa_driver.audit_widths(
        poa_driver.make_config(c, d, *SCORES, r)) for d, c, r in grid}
    assert {w for (_, c, _), w in widths.items() if c == 512} == {(4, 2)}
    assert {(r, w) for (_, c, r), w in widths.items() if c == 1024} == {
        (0, (2,)), (1, (2,))}
    assert sum(map(len, widths.values())) == \
        poa_driver.POA_RECOMPILE_BUDGET == 12


# -- (c) a served deep job against the reference ---------------------------

#: configs/ecoli-ont-deep.json's read profile with reads of ~1.2 kb (over
#: 1 kb on average, or the windows would be racon's short-read type, which
#: stays on the base rung)
PROFILE = dict(genome_mbp=0.00128, coverage=75, mean_read=1400, sub=0.05,
               ins=0.03, dele=0.03, draft_error=0.01, qual_phred=15,
               formats=("sam",), data_seed=2, layout_seed=22)
WINDOW = 128


def _serve(work, d, job_id):
    from racon_tpu.serve.session import JobSpec, PolishSession

    cell = loader.load_cell(CELL)
    args = dict(cell.config["polish_args"], window_length=WINDOW,
                num_threads=2)
    session = PolishSession(str(work), backend="tpu")
    res = session.run_job(JobSpec(
        str(d / "reads.fastq"), str(d / "overlaps.sam"),
        str(d / "draft.fasta"), args=args, job_id=job_id))
    with open(res["report"]) as f:
        report = json.load(f)
    with open(res["output"], "rb") as f:
        fasta = f.read()
    return args, res, report, fasta


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Ten windows of 128 bp at ~75x, served once as shipped and once
    with the upper rung taken away (the parent's capacity rule)."""
    d = tmp_path_factory.mktemp("deep-data")
    generate.mode_ont(str(d), 5, **PROFILE)
    work = tmp_path_factory.mktemp("deep-served")
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("RACON_TPU_PALLAS", "1")
        mp.setenv("RACON_TPU_SHARD", "0")
        mp.setenv("RACON_TPU_BATCH_WINDOWS", "8")
        args, res, report, fasta = _serve(work / "rungs", d, "rungs")
        mp.setattr(poa_driver, "UPPER_NODE_FACTOR", 3)
        _, _, base_report, _ = _serve(work / "base", d, "base")
    cell = loader.load_cell(CELL)
    demand = reference_depth.window_demand(
        str(d / "draft.fasta"), str(d / "reads.fastq"),
        str(d / "overlaps.sam"), window_length=WINDOW,
        quality_threshold=cell.config["polish_args"]["quality_threshold"],
        error_threshold=cell.config["polish_args"]["error_threshold"],
        depth_cap=poa_driver.DEPTH_CAP)
    return d, args, res, report, fasta, base_report, demand


def _counters(report):
    return report["obs"]["metrics"]["counters"]


def test_the_driver_admits_the_layers_the_reference_counts(served):
    _, _, _, report, _, _, demand = served
    c = _counters(report)
    kernel = demand["layers"] >= 2           # the rest pass the backbone on
    assert kernel.sum() == c["poa.rows.real"] == 10
    assert c["poa.layers.admitted"] == demand["layers"][kernel].sum()
    assert c["poa.layers.bases"] == demand["layer_bases"][kernel].sum()
    assert c["poa.layers.capped"] == demand["capped"].sum() == 0
    assert demand["layers"].max() >= 64 and demand["layers"].min() <= 48


def test_deep_windows_climb_and_the_device_serves_them(served):
    _, _, res, report, _, _, demand = served
    cons = report["phases"]["consensus"]
    c = _counters(report)
    # one window of the ten sits at the edge of the base rung (its
    # estimate says 3 x the backbone holds it, its graph is a few nodes
    # larger: the scatter of a 128 bp window) and is redone on the host
    assert cons["served"].get("ls") >= 9 and not cons["served"].get("xla")
    assert cons["served"].get("host", 0) == c["poa.windows.overflow.nodes"]
    assert not cons.get("degradations")
    assert cons["extra"]["rung_windows"] == {
        "base": c["poa.windows.rung.base"],
        "upper": c["poa.windows.rung.upper"]}
    assert c["poa.windows.rung.upper"] >= 6
    assert c["poa.windows.overflow.nodes"] <= 1
    assert all(c[f"poa.windows.overflow.{name}"] == 0
               for name in ("edges", "distance", "other"))
    # the exact graph of the same layers bounds the program's from above
    # (match + gap outscores a mismatch, so SPOA lands on fewer nodes)
    assert c["poa.nodes.used"] <= demand["nodes"].sum()
    assert c["poa.nodes.capacity"] == (
        c["poa.windows.rung.upper"] * UPPER.max_nodes
        + (cons["served"]["ls"] - c["poa.windows.rung.upper"])
        * BASE.max_nodes)
    assert 0.5 * c["poa.nodes.capacity"] < c["poa.nodes.used"] \
        < c["poa.nodes.capacity"]
    assert res["journal_replayed"] == 0


def test_the_base_rung_alone_sends_the_deep_windows_to_the_host(served):
    """The parent's rule, capacity blind to depth: the kernel flags the
    windows for node slots and the host redoes them.  It flags none that
    the reference says the base rung holds: its exact count is an upper
    bound of the program's graph (SPOA's alignment is not the truth's:
    it merges errors into nodes that are there, so its graphs reach the
    base rung's 3 x later than the exact ones; the tolerance is that one
    side)."""
    _, _, _, report, _, base_report, demand = served
    c = _counters(base_report)
    cons = base_report["phases"]["consensus"]
    says_overflow = int((demand["nodes"] > BASE.max_nodes).sum())
    assert c["poa.windows.rung.upper"] == 0
    assert 6 <= c["poa.windows.overflow.nodes"] <= says_overflow
    assert c["poa.windows.overflow.edges"] == 0
    assert cons["served"]["host"] == c["poa.windows.overflow.nodes"] \
        + c["poa.windows.overflow.distance"] \
        + c["poa.windows.overflow.other"]
    assert cons["extra"]["device_rejected"] == cons["served"]["host"]
    # the rule climbs with the windows the base rung loses, but for the
    # one at its edge
    assert _counters(report)["poa.windows.rung.upper"] \
        >= c["poa.windows.overflow.nodes"] - 1
    assert _counters(report)["poa.windows.overflow.nodes"] \
        < c["poa.windows.overflow.nodes"]


def test_the_served_deep_job_is_as_accurate_as_the_host(served):
    d, args, _, _, fasta, _, _ = served
    oracle, _ = prepare.ensure_oracle(str(d), {"overlaps": "sam"}, args,
                                      timed=False)
    truth = prepare.read_fasta(str(d / "genome.fasta"))
    edits = {name: native.edit_distance(seq, truth) for name, seq in (
        ("draft", prepare.read_fasta(str(d / "draft.fasta"))),
        ("host", prepare.read_fasta(oracle)),
        ("device", b"".join(fasta.split(b"\n")[1::2])))}
    at_most, _ = judge.accuracy_limits(edits["draft"], edits["host"],
                                       len(truth))
    assert edits["device"] <= at_most, edits


def test_dispatch_and_wait_spans_carry_the_rung(served):
    _, _, res, *_ = served
    with open(res["trace"]) as f:
        events = json.load(f)["traceEvents"]
    for name in ("poa.dispatch", "poa.wait"):
        rungs = {e["args"]["rung"] for e in events if e["name"] == name}
        assert rungs <= set(poa_driver.NODE_RUNGS) and "upper" in rungs
    buckets = [e["args"] for e in events if e["name"] == "poa.bucket"]
    assert {b["rung"] for b in buckets} >= {"upper"}


# -- (d) the cell's files ---------------------------------------------------

def test_the_cell_loads_and_is_the_deployment():
    cell = loader.load_cell(CELL)            # files agree with entries
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        "ecoli-ont-deep", "sam-0.15mbp", 1)
    shallow = loader.load_cell("ecoli-ont.sam")
    # ecoli-ont's deployment at another depth, and nothing else changed
    assert cell.config["polish_args"] == shallow.config["polish_args"]
    reads = dict(cell.config["reads"])
    assert reads.pop("coverage") == 100
    assert reads == {k: v for k, v in shallow.config["reads"].items()
                     if k != "coverage"}
    mine, theirs = (prepare.data_params(c, False) for c in (cell, shallow))
    assert mine.pop("genome_mbp") >= 0.1 and theirs.pop("genome_mbp") == 0.5
    assert mine.pop("coverage") == 100 and theirs.pop("coverage") == 30
    assert mine == theirs
    assert list(cell.config["reduced"]) == ["genome_mbp"]
    assert "coverage 100" in cell.config["assumed"]
    assert cell.workload["expect"]["consensus_min_share"] >= 0.90
    assert cell.workload["expect"]["consensus_tiers_at_zero"] == [
        "v2", "xla"]
    assert NEW_METRICS <= {m["name"] for m in cell.per_layer}
    bm = loader.load_benchmark()
    for m in bm["per_layer"]:
        if m["name"] in NEW_METRICS:
            # PR 43 appended the cell that runs the same layer at the cap
            assert m["workloads"] == [CELL, "ecoli-ont-cap.sam"]
    assert CELL in [w["name"] for w in bm["workloads"]]
    entry, = (c for c in bm["configs"] if c["name"] == "ecoli-ont-deep")
    assert entry["source"] == cell.config["source"]
    sources = [c["source"] for c in bm["configs"]]
    assert len(set(sources)) == len(sources)   # one source a deployment


def _run(job):
    return {"jobs": [job, dict(job)], "facts": {}, "data": {}, "edits": {},
            "notes": {}, "trace": None, "device": None, "peaks": {}}


def test_new_metrics_read_a_served_jobs_counters(served):
    from benchmark import run as bench_run

    _, _, res, *_ = served
    job = {"wall_s": 1.0, "polished_bp": res["polished_bp"],
           **bench_run.job_files(res)}
    cell = loader.load_cell(CELL)
    registry = reducers.registry()
    values = {m["name"]: registry[m["reducer"]](_run(job),
                                                **m.get("params", {}))
              for m in cell.per_layer if m["name"] in NEW_METRICS}
    assert values.pop("deep_poa_roofline") is None     # no device trace
    assert all(isinstance(v, float) for v in values.values()), values
    c = job["counters"]
    assert values["deep_poa_upper_rung_window_share"] == pytest.approx(
        100 * c["poa.windows.rung.upper"] / c["poa.rows.real"])
    assert values["deep_poa_overflow_window_share"] == pytest.approx(
        100 * c["poa.windows.overflow.nodes"] / c["poa.rows.real"])
    assert values["deep_poa_node_fill_share"] == pytest.approx(
        100 * c["poa.nodes.used"] / c["poa.nodes.capacity"])
    assert values["deep_poa_layers_per_window"] == pytest.approx(
        c["poa.layers.admitted"] / 10)
    assert values["deep_poa_wide_program_share"] == 0.0   # a batch of 8
    assert 0 < values["deep_poa_lockstep_fill_share"] <= 100
    # the cost function: operations of the graphs the job built
    from benchmark.reducers import deep
    served_ls = job["phases"]["consensus"]["served"]["ls"]
    ops, byts = deep.poa_ops_bytes(c, served_ls)
    mean_graph = (128 + c["poa.nodes.used"] / served_ls) / 2
    assert ops == pytest.approx(c["poa.layers.bases"] * mean_graph * 14)
    assert byts == pytest.approx(c["poa.layers.bases"] * 5
                                 + 2 * 10 * 128 * 5)


def test_new_metrics_read_nothing_from_an_older_program():
    """The parent under the driver's check has none of the rung
    counters: their readers return ``None``, none raises; the two that
    read PR 34's counters read them."""
    cell = loader.load_cell(CELL)
    registry = reducers.registry()
    job = {"counters": {"poa.launches": 6, "poa.rows.real": 300,
                        "poa.rows.pad": 84, "poa.programs.wide": 24,
                        "poa.programs.narrow": 0,
                        "poa.lockstep.layers.real": 30000,
                        "poa.lockstep.layers.slots": 32000},
           "spans": {}, "phases": {}, "polished_bp": 150000, "wall_s": 5.0}
    for m in cell.per_layer:
        if m["reducer"] == "setup_trace_lower_s":
            continue                     # reads the live process, not run
        value = registry[m["reducer"]](_run(job), **m.get("params", {}))
        assert value is None or isinstance(value, (int, float)), m["name"]
        if m["name"] in NEW_METRICS - {"deep_poa_wide_program_share",
                                       "deep_poa_lockstep_fill_share"}:
            assert value is None, m["name"]
    assert registry["counter_share"](
        _run(job), "poa.programs.wide", "poa.programs.") == 100.0
