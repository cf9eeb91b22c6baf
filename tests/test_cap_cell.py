"""The depth cap as a deployment: what ``poa_driver.DEPTH_CAP`` means
(which layers stay, both trim rules, the host redo), held to the plain
reference ``benchmark/reference_cap.py``; the thirteenth in-edge; the
counters that say what the cap did; the files of the cell
``ecoli-ont-cap.sam``.

Everything runs at a small size on the CPU: windows of 128 bp (the
smallest window class, upper rung 640 graph slots) through the XLA twin,
which the lockstep kernel equals node for node
(``tests/test_deep_cell.py``).  Two data sets: a **seeded** one
(``benchmark/generate.py``'s ONT profile at ~200x, ten windows of 151 to
217 layers, one of exactly 200) and a **crafted** one (error-free reads
laid so that the cap decides a base in one window and the trim a
window's length in another).
"""

import json
import random

import numpy as np
import pytest

from benchmark import (generate, generate_stretch, loader, prepare, reducers,
                       reference_cap)
from benchmark.tools import cap_capacity
from racon_tpu import native, obs
from racon_tpu.ops import poa, poa_driver
from racon_tpu.pipeline import Pipeline
from tests.test_pallas_ls import _alloc, _run_ls, _set_window

CELL = "ecoli-ont-cap.sam"
CAP = poa_driver.DEPTH_CAP
SCORES = dict(match=5, mismatch=-4, gap=-8)
WINDOW = 128
FILTERS = dict(window_length=WINDOW, quality_threshold=10.0,
               error_threshold=0.3)
NEW_METRICS = {
    "cap_poa_capped_window_share", "cap_poa_capped_layer_share",
    "cap_poa_edge_overflow_window_share"}
#: the deep cell's metrics, which read the same layer here: the cell is
#: appended to their lists
SHARED_METRICS = {
    "deep_poa_layers_per_window", "deep_poa_upper_rung_window_share",
    "deep_poa_overflow_window_share", "deep_poa_node_fill_share",
    "deep_poa_lockstep_fill_share", "deep_poa_wide_program_share",
    "deep_poa_roofline"}
#: configs/ecoli-ont-cap.json's read profile with reads of ~1.2 kb (over
#: 1 kb on average, or the windows would be racon's short-read type)
PROFILE = dict(genome_mbp=0.00128, coverage=240, mean_read=1400, sub=0.05,
               ins=0.03, dele=0.03, draft_error=0.01, qual_phred=15,
               formats=("sam",), data_seed=2, layout_seed=6)


def _files(d):
    return (str(d / "reads.fastq"), str(d / "overlaps.sam"),
            str(d / "draft.fasta"))


def _reference(d):
    reads, sam, draft = _files(d)
    return reference_cap.CapReference(draft, reads, sam, **FILTERS)


def _pipeline(d):
    pl = Pipeline(*_files(d), trim=True, num_threads=2, **FILTERS, **SCORES)
    pl.initialize()
    return pl


def _stats():
    return dict.fromkeys(("device", "failed", "host_fallback",
                          "layers_dropped", "layers_capped",
                          "windows_capped"), 0)


# -- (a) the order, and the set the cap admits ------------------------------

@pytest.fixture(scope="module")
def seeded(tmp_path_factory):
    d = tmp_path_factory.mktemp("cap-seeded")
    generate.mode_ont(str(d), 6, **PROFILE)
    return d, _reference(d), _pipeline(d)


def test_the_seeded_windows_lie_under_at_and_over_the_cap(seeded):
    _, ref, _ = seeded
    assert ref.offered.tolist() == [157, 187, 200, 207, 217, 214, 207, 194,
                                    177, 151]
    assert not ref.too_long.any()
    np.testing.assert_array_equal(ref.admitted, np.minimum(ref.offered, CAP))
    np.testing.assert_array_equal(ref.capped, ref.offered - ref.admitted)


@pytest.mark.parametrize("n", [0, 1, 2, 15, 16, 17, 40, 333])
def test_std_sort_order_sorts_and_is_not_stable(n):
    rng = random.Random(n)
    keys = [rng.choice((0, 0, 0, 3, 7, 90)) for _ in range(n)]
    order = reference_cap.std_sort_order(keys)
    assert sorted(order) == list(range(n))
    assert [keys[i] for i in order] == sorted(keys)
    if n > 16:      # past the insertion-sort threshold ties are permuted
        assert order != sorted(range(n), key=keys.__getitem__)


def test_the_heap_sort_of_the_depth_limit_sorts():
    rng = random.Random(3)
    keys = [rng.randrange(50) for _ in range(300)]
    a = list(range(300))
    reference_cap._introsort(a, keys.__getitem__, 0, 300, 0)
    assert [keys[i] for i in a] == sorted(keys)


def test_the_reference_recomputes_the_engines_order(seeded):
    """Layer for layer: begins, ends, lengths and bases of the export
    (``rt_capi.cpp``'s ``std::sort``, the host engine's own) against the
    reference's layers in :func:`reference_cap.std_sort_order`'s order."""
    _, ref, pl = seeded
    assert pl.num_windows() == len(ref.layers) == 10
    for i in range(pl.num_windows()):
        wx, lay, order = pl.export_window(i), ref.layers[i], ref.order[i]
        np.testing.assert_array_equal(wx.begins, lay[order, 0])
        np.testing.assert_array_equal(wx.ends, lay[order, 1])
        np.testing.assert_array_equal(wx.lens,
                                      lay[order, 4] - lay[order, 3] + 1)
        assert wx.bases.tobytes() == b"".join(
            ref.layer(i, int(k))[0] for k in order)
        assert pl.window_info(i)[0] - 1 == ref.offered[i]


def test_the_driver_admits_the_set_the_reference_admits(seeded):
    _, ref, pl = seeded
    cfg = poa_driver.make_config(WINDOW, CAP, 5, -4, -8, 1)
    assert cfg.max_len == reference_cap.max_layer_len(WINDOW)
    stats = _stats()
    obs.reset()
    obs.configure(metrics=True)
    try:
        chunk = poa_driver._export_chunk(pl, list(range(10)), cfg, [], stats)
        counters = obs.snapshot()["counters"]
    finally:
        obs.reset()
    for i, wx, keep in chunk:
        # the export is in consumption order, so a kept layer is named
        # by its place in it
        place = {int(k): at for at, k in enumerate(ref.order[i])}
        assert list(keep) == [place[int(k)] for k in ref.kept[i]]
        assert wx.capped == ref.capped[i]
        dropped = sorted(set(range(len(wx.lens))) - set(keep))
        if dropped:     # no kept layer begins after a dropped one
            assert wx.begins[keep].max() <= wx.begins[dropped].min()
        # rule (b), both thresholds
        assert len(keep) // 2 == ref.trim_accelerator[i]
        assert (pl.window_info(i)[0] - 1) // 2 == ref.trim_cpu[i]
    assert counters["poa.layers.capped"] == ref.capped.sum() == 45
    assert counters["poa.layers.capped.bases"] == ref.capped_bases.sum()
    assert counters["poa.windows.capped"] == (ref.capped > 0).sum() == 4
    assert counters["poa.layers.bases"] == ref.admitted_bases.sum()
    assert (stats["layers_capped"], stats["windows_capped"]) == (45, 4)


def test_a_layer_over_the_length_admission_is_not_the_caps():
    lens = np.array([100, 0, 300, 256, 257, 90], np.uint32)
    assert poa_driver.admit_layers(lens, 256) == [0, 3, 5]
    assert reference_cap.max_layer_len(500) == 768
    assert reference_cap.max_layer_len(128) == 256
    assert reference_cap.max_layer_len(100) == 256


# -- (b) a served job against the reference ---------------------------------

@pytest.fixture(scope="module")
def phase(seeded):
    """The seeded windows through ``run_consensus_phase`` (XLA twin,
    batches of 8): counters, report and each window's installed
    consensus."""
    d, ref, _ = seeded
    pl = _pipeline(d)
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("RACON_TPU_PALLAS", "0")
        mp.setenv("RACON_TPU_SHARD", "0")
        mp.setenv("RACON_TPU_BATCH_WINDOWS", "8")
        obs.reset()
        obs.configure(metrics=True)
        try:
            stats = poa_driver.run_consensus_phase(pl, trim=True, **SCORES)
            counters = obs.snapshot()["counters"]
        finally:
            obs.reset()
    return (ref, dict(stats), counters,
            [pl.get_consensus(i) for i in range(10)])


def test_the_phase_counts_what_the_reference_counts(phase):
    ref, stats, c, _ = phase
    report = stats["report"]
    assert c["poa.rows.real"] == 10
    assert c["poa.layers.admitted"] == ref.admitted.sum()
    assert c["poa.layers.capped"] == ref.capped.sum()
    assert c["poa.layers.capped.bases"] == ref.capped_bases.sum()
    assert c["poa.windows.capped"] == (ref.capped > 0).sum()
    assert report.extra["capped_windows"] == 4
    assert report.extra["capped_layers"] == 45
    # every window is trimmed under one rule or the other
    served = report.served
    assert c["poa.windows.trim.admitted"] == served.get("xla", 0)
    assert c["poa.windows.trim.full"] == served.get("host", 0)
    assert served.get("xla", 0) + served.get("host", 0) == 10
    assert c["poa.windows.capped.redone"] <= served.get("host", 0)
    assert c["poa.windows.rung.upper"] == 10
    assert served.get("xla", 0) >= 8


@pytest.mark.parametrize("window", [0, 3, 9])
def test_the_plain_engine_is_the_host_engines_algorithm(seeded, window):
    """``reference_cap.consensus`` shares no code with the program: the
    host engine's algorithm written again in numpy.  On a whole window
    the two agree byte for byte: the host engine given the layers in the
    order they were added (it sorts them itself) and the plain engine
    given them in ``std_sort_order``'s, every layer and trimmed by the
    full count."""
    _, ref, _ = seeded
    bases, quals, begins, ends = zip(*(
        ref.layer(window, k) for k in range(len(ref.layers[window]))))
    host, polished = native.window_consensus(
        ref.backbone(window), list(bases), quals=list(quals),
        begins=list(begins), ends=list(ends), tgs=True, trim=True, **SCORES)
    assert polished
    assert ref.capped_consensus(window, every_layer=True, **SCORES) == host
    # and untrimmed: the whole path, source to sink
    untrimmed, _ = native.window_consensus(
        ref.backbone(window), list(bases), quals=list(quals),
        begins=list(begins), ends=list(ends), tgs=True, trim=False, **SCORES)
    assert ref.capped_consensus(window, every_layer=True, trim=False,
                                **SCORES) == untrimmed


def test_the_installed_consensus_is_the_references(phase):
    """A window the device served holds the consensus of exactly the
    admitted layers, trimmed by the admitted count; one the kernel gave
    up holds the host engine's of every layer.  The kernel and the plain
    engine break score ties differently at a deep window's tail
    (tests/test_deep_cell.py): an edit or two on some windows, none on
    most."""
    ref, stats, c, installed = phase
    exact = redone = 0
    for i, got in enumerate(installed):
        capped = ref.capped_consensus(i, **SCORES)
        every = ref.capped_consensus(i, every_layer=True, **SCORES)
        if not ref.capped[i]:
            assert capped == every
        assert got == every or native.edit_distance(got, capped) <= 2, i
        exact += got == capped
        redone += got == every and got != capped
    assert exact >= 6, exact
    assert redone <= c["poa.windows.capped.redone"]


# -- (c) crafted windows: the cap decides a base, the trim a length ---------

A, B, COLUMN = 2, 17, 60


def _craft(d):
    """A genome of 20 windows of 128 bp and error-free reads: 105 span it
    all, 95 more span windows 0 to 14.  At one column of window A, 110 of
    those 200 carry another base than the genome's and 90 the genome's;
    30 late reads (begin 20 in window A) carry the genome's.  Window B
    gets 155 partial reads over its columns 2 to 99."""
    rng = np.random.default_rng(43)
    genome = generate.BASES[rng.integers(0, 4, 20 * WINDOW)]
    w = generate._Writer(str(d), genome, genome.copy(), ("sam",), 15)
    at = A * WINDOW + COLUMN
    other = generate.BASES[(list(generate.BASES).index(genome[at]) + 1) % 4]
    spans = [(0, len(genome) if k < 105 else 15 * WINDOW, k % 20 < 11)
             for k in range(200)]
    spans += [(A * WINDOW + 20, (A + 1) * WINDOW, False)] * 30
    spans += [(B * WINDOW + 2, B * WINDOW + 100, False)] * 155
    for n, (start, end, flip) in enumerate(spans):
        seg = genome[start:end].copy()
        if flip:
            seg[at - start] = other
        w.read(f"read{n}", start, end, bool(n % 2), seg,
               np.zeros(end - start, np.uint8))
    w.close()
    return bytes([genome[at]]), bytes([other])


@pytest.fixture(scope="module")
def crafted(tmp_path_factory):
    """Windows A and B through the driver's own export, pack, twin and
    install; then again with the kernel's `failed` flag raised, so the
    host redoes them."""
    d = tmp_path_factory.mktemp("cap-crafted")
    truth, other = _craft(d)
    ref, pl = _reference(d), _pipeline(d)
    cfg = poa_driver.make_config(WINDOW, CAP, 5, -4, -8)
    stats, fallback = _stats(), poa_driver._HostFallback(pl, True)
    obs.reset()
    obs.configure(metrics=True)
    try:
        chunk = poa_driver._export_chunk(pl, [A, B], cfg, fallback, stats)
        kernel = poa.build_poa_kernel(cfg)
        res = poa_driver._unpack(poa_driver._submit(
            kernel, poa_driver._pack(chunk, cfg, 4), False), False)
        assert not res[3].any()
        poa_driver._install(pl, chunk, res, True, stats, fallback,
                            tier="xla")
        device = {i: pl.get_consensus(i) for i in (A, B)}
        failed = res[3].copy()
        failed[:2] = poa.FAIL_NODES
        gave_up = poa_driver._Unpacked(tuple(res[:3]) + (failed,))
        gave_up.nodes = res.nodes
        poa_driver._install(pl, chunk, gave_up, True, stats, fallback,
                            tier="xla")
        fallback.join(None, stats)
        host = {i: pl.get_consensus(i) for i in (A, B)}
        counters = obs.snapshot()["counters"]
    finally:
        obs.reset()
    return ref, chunk, device, host, counters, stats, truth, other


def test_the_cap_drops_the_layers_that_begin_last(crafted):
    ref, chunk, *_ = crafted
    assert ref.offered[[A, B]].tolist() == [230, 260]
    assert ref.capped[[A, B]].tolist() == [30, 60]
    for (i, wx, keep), dropped_begin in zip(chunk, (20, 2)):
        assert len(keep) == CAP and wx.capped == ref.capped[i]
        rest = sorted(set(range(len(wx.lens))) - set(keep))
        assert wx.begins[keep].max() <= wx.begins[rest].min() \
            == dropped_begin


def test_a_capped_window_holds_the_consensus_of_the_admitted_layers(
        crafted):
    """Of the 200 admitted layers 110 carry the other base, so the device
    calls it; the 30 dropped layers make it 120 against 110 for the
    genome's, which the host path (every layer) calls."""
    ref, _, device, host, _, _, truth, other = crafted
    capped = ref.capped_consensus(A, **SCORES)
    every = ref.capped_consensus(A, every_layer=True, **SCORES)
    assert device[A] == capped and len(capped) == WINDOW
    assert capped[COLUMN:COLUMN + 1] == other
    assert every[COLUMN:COLUMN + 1] == truth
    assert capped[:COLUMN] + capped[COLUMN + 1:] \
        == every[:COLUMN] + every[COLUMN + 1:]


def test_each_trim_rule_where_it_applies(crafted):
    """Window B: 260 layers, 105 over the whole window, the rest over
    columns 2 to 99.  Admitted: the 105 and 95 of the rest, threshold
    100, and the tail's coverage of 106 stays.  The host path counts all
    260, threshold 130, and trims to the 98 columns the partial reads
    cover."""
    ref, _, device, host, *_ = crafted
    assert (ref.trim_accelerator[B], ref.trim_cpu[B]) == (100, 130)
    assert (ref.trim_accelerator[A], ref.trim_cpu[A]) == (100, 115)
    assert device[B] == ref.capped_consensus(B, **SCORES)
    assert len(device[B]) == WINDOW
    assert host[B] == ref.capped_consensus(B, every_layer=True, **SCORES)
    assert host[B] == device[B][2:100]
    # untrimmed, the two layer sets agree on this window
    assert ref.capped_consensus(B, trim=False, **SCORES) == \
        ref.capped_consensus(B, trim=False, every_layer=True, **SCORES)


def test_a_capped_window_the_kernel_gives_up_is_redone_from_every_layer(
        crafted):
    ref, _, device, host, c, stats, *_ = crafted
    for i in (A, B):
        assert host[i] == ref.capped_consensus(i, every_layer=True,
                                               **SCORES)
        assert host[i] != device[i]
    assert c["poa.windows.capped"] == 2
    assert c["poa.windows.capped.redone"] == 2
    assert c["poa.windows.overflow.nodes"] == 2
    assert c["poa.windows.trim.admitted"] == 2
    assert c["poa.windows.trim.full"] == 2
    assert c["poa.layers.capped"] == 90
    assert c["poa.layers.capped.bases"] == 30 * 108 + 60 * 98
    assert stats["host_fallback"] == stats["failed"] == 2


def test_the_sanitizer_counts_the_parity_samples_it_skips(monkeypatch,
                                                          tmp_path):
    """Host parity holds where nothing was dropped; a sampled window
    that lost layers to the cap is skipped, and counted."""
    d = tmp_path
    _craft(d)
    pl = _pipeline(d)
    monkeypatch.setenv("RACON_TPU_SANITIZE", "1")
    monkeypatch.setenv("RACON_TPU_SANITIZE_PARITY", "1")
    cfg = poa_driver.make_config(WINDOW, CAP, 5, -4, -8)
    stats, fallback = _stats(), poa_driver._HostFallback(pl, True)
    obs.reset()
    obs.configure(metrics=True)
    try:
        chunk = poa_driver._export_chunk(pl, [A, 5], cfg, fallback, stats)
        res = poa_driver._unpack(poa_driver._submit(
            poa.build_poa_kernel(cfg), poa_driver._pack(chunk, cfg, 4),
            False), False)
        poa_driver._install(pl, chunk, res, True, stats, fallback,
                            tier="xla")
        counters = obs.snapshot()["counters"]
    finally:
        obs.reset()
    assert counters["sanitize.parity.skipped.capped"] == 1   # A, not 5
    assert stats["device"] == 2


# -- (d) the thirteenth in-edge ---------------------------------------------

def _in_edge_layers(truth, c):
    """Layers that each bring the node at column c one more in-edge:
    another base at column c - 1, columns c - k to c - 1 deleted, a base
    inserted before column c."""
    out = [truth[:c - 1] + bytes([b]) + truth[c:]
           for b in b"ACGT" if b != truth[c - 1]]
    out += [truth[:c - k] + truth[c:] for k in range(1, 7)]
    out += [truth[:c] + bytes([b]) + truth[c:]
            for b in b"ACGT" if b not in (truth[c - 1], truth[c])]
    return out


def test_a_node_with_thirteen_in_edges_reports_edges():
    rng = random.Random(9)
    truth = bytes(rng.choice(b"ACGT") for _ in range(128))
    layers = _in_edge_layers(truth, 64)
    assert len(layers) == 12
    roomy = poa.PoaConfig(384, 256, 128, 24, 16, 5, -4, -8)
    tight = roomy._replace(max_edges=12)
    for n, most, cause in ((11, 12, 0), (12, 13, poa.FAIL_EDGES)):
        a = _alloc(8, roomy)
        _set_window(a, 0, truth, layers[:n])
        args = (a["bb"], a["bbw"], a["bb_len"], a["nl"], a["seqs"],
                a["ws"], a["lens"], a["bg"], a["en"])
        _, failed, edges = (np.asarray(x) for x in
                            cap_capacity._twin_graphs(roomy)(*args))
        assert (failed[0], edges[0]) == (0, most)
        twin = np.asarray(poa.build_poa_kernel(tight)(*args)[3])
        ls = _run_ls(a, tight, 1)[3][:, 0]
        assert twin[0] == ls[0] == cause, (n, twin, ls)
        assert not twin[1:].any() and not ls[1:].any()


# -- (e) the cell's data: a stretch of a longer contig ----------------------

@pytest.fixture(scope="module")
def stretch(tmp_path_factory):
    cell = loader.load_cell(CELL)
    params = dict(prepare.data_params(cell, False), genome_mbp=0.03)
    mode = generate.resolve(params.pop("generator"))
    d = tmp_path_factory.mktemp("cap-stretch")
    return d, params, mode(str(d), 0, **params)


def _depth(sam, n):
    """Reads over each base of the draft, from the SAM's positions and
    CIGARs alone."""
    edges = np.zeros(n + 1, np.int64)
    spans = []
    with open(sam, "rb") as f:
        for line in f:
            if line.startswith(b"@"):
                continue
            c = line.split(b"\t")
            ov = reference_cap.reference_depth.Overlap(
                c[0].decode(), int(c[1]), int(c[3]) - 1, c[5], c[9], 500)
            spans.append((int(ov.m_t[0]), int(ov.m_t[-1]) + 1,
                          len(ov.seq)))
            edges[spans[-1][0]] += 1
            edges[spans[-1][1]] -= 1
    return np.cumsum(edges)[:n], spans


def test_a_stretch_is_as_deep_at_its_ends_as_inside(stretch):
    """The ``ont`` mode's depth tapers to nothing over the last read
    length of a linear genome; the stretch mode's reads are clipped at
    the stretch's ends, so its first and last windows are as deep as the
    rest (what the windows of such a stretch hold in the full job)."""
    d, params, facts = stretch
    n = facts["truth_bp"]
    depth, spans = _depth(str(d / "overlaps.sam"), n)
    inside = depth[5000:-5000].mean()
    assert 170 < inside < 215              # 180x over the contig drawn from
    for end in (depth[:500], depth[-500:]):
        assert end.min() > 0.8 * inside
    assert all(0 <= b < e <= n and length >= 400 for b, e, length in spans)
    assert 0 < facts["reads_clipped"] < facts["reads"] < facts["reads_drawn"]
    # (a read whose first or last base at the cut is a deletion aligns
    # from a base further in)
    clipped = sum(b == 0 or e == n for b, e, _ in spans)
    assert 0.9 * facts["reads_clipped"] < clipped <= facts["reads_clipped"]

    # the ont mode on the same parameters: nothing at the ends
    ont = d / "ont"
    generate.mode_ont(str(ont), 0, **params)
    tapering, _ = _depth(str(ont / "overlaps.sam"), n)
    assert tapering[:500].max() < 0.2 * inside


def test_a_stretchs_seed_only_relabels_the_bases(stretch, tmp_path):
    d, params, facts = stretch
    again = generate_stretch.mode_ont_stretch(str(tmp_path), 5, **params)
    assert again == facts
    table = generate._relabel(5)
    for name in ("draft.fasta", "genome.fasta"):
        assert table[np.frombuffer(prepare.read_fasta(str(d / name)),
                                   np.uint8)].tobytes() \
            == prepare.read_fasta(str(tmp_path / name))
    with open(d / "overlaps.sam") as a, open(tmp_path / "overlaps.sam") as b:
        for x, y in zip(a, b):
            if x.startswith("@"):
                assert x == y
                continue
            x, y = x.split("\t"), y.split("\t")
            assert x[:9] == y[:9] and len(x[9]) == len(y[9])
    with pytest.raises(ValueError, match="generator_rev"):
        generate_stretch.mode_ont_stretch(
            str(tmp_path), 5, **dict(params, generator_rev=0))


# -- (f) the cell's files ---------------------------------------------------

def test_the_cell_loads_and_is_the_deployment():
    cell = loader.load_cell(CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        "ecoli-ont-cap", "sam-0.1mbp", 1)
    deep = loader.load_cell("ecoli-ont-deep.sam")
    # ecoli-ont-deep's deployment at another depth, cut as a stretch
    # from the inside of the contig (its ends clipped, not tapering:
    # benchmark/generate_stretch.py), nothing else changed
    assert cell.config["polish_args"] == deep.config["polish_args"]
    assert cell.config["guarantees"] == deep.config["guarantees"]
    reads = dict(cell.config["reads"], generator="ont")
    assert reads.pop("coverage") == 180
    assert reads == {k: v for k, v in deep.config["reads"].items()
                     if k != "coverage"}
    assert generate.resolve(cell.config["reads"]["generator"]) \
        is generate_stretch.mode_ont_stretch
    # ISSUE 43's traffic: 0.1 Mbp, the seeds every mix has
    data = cell.traffic["data"]
    assert (data["genome_mbp"], data["data_seed"], data["layout_seed"]) \
        == (0.1, 2, 22)
    assert data["generator_rev"] == generate_stretch.GENERATOR_REV
    assert cell.traffic["trace_jobs"] == 2
    assert list(cell.config["reduced"]) == ["genome_mbp"]
    assert "coverage 180" in cell.config["assumed"]
    expect = cell.workload["expect"]
    assert expect == deep.workload["expect"]
    assert expect["consensus_min_share"] == 0.95
    assert NEW_METRICS | SHARED_METRICS <= {m["name"]
                                            for m in cell.per_layer}
    bm = loader.load_benchmark()
    for m in bm["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL]
        if m["name"] in SHARED_METRICS:
            assert m["workloads"] == ["ecoli-ont-deep.sam", CELL]
    entry, = (c for c in bm["configs"] if c["name"] == "ecoli-ont-cap")
    assert entry["source"] == cell.config["source"]
    assert entry["reduced"] == ["genome_mbp"]
    sources = [c["source"] for c in bm["configs"]]
    assert len(set(sources)) == len(sources)   # one source a deployment
    counted = cell.config["layers_per_window"]
    assert counted["windows_capped"] > 0.3 * counted["windows"]
    assert counted["layers_capped"] > 0.03 * counted["layers_offered"]


def test_the_cells_rehearsal_reaches_the_cap(tmp_path):
    """The rehearsal's data (a 1.1 kb toy stretch under 242 clipped
    reads): the reference counts 237 and 242 layers on the two whole
    windows, 37 and 42 past the cap.  Then the job, served as the
    benchmark serves it
    (``PolishSession.run_job``) but through the XLA twin at windows of
    128 bp: interpreted ``ls`` at the cell's 500 takes minutes a job, the
    twin there twenty (the verify skill has the rehearsal by hand)."""
    from racon_tpu.serve.session import JobSpec, PolishSession

    cell = loader.load_cell(CELL)
    params = prepare.data_params(cell, True)
    mode = generate.resolve(params.pop("generator"))
    facts = mode(str(tmp_path), 1, **params)
    assert facts["reads"] == facts["reads_clipped"] == 242
    pa = cell.config["polish_args"]
    reads, sam, draft = _files(tmp_path)
    filters = {k: pa[k] for k in ("window_length", "quality_threshold",
                                  "error_threshold")}
    ref = reference_cap.CapReference(draft, reads, sam, **filters)
    assert ref.offered.tolist() == [237, 242, 224]
    assert ref.capped.tolist() == [37, 42, 24]
    assert ref.bb_len.tolist() == [500, 500, 100]

    small = reference_cap.CapReference(
        draft, reads, sam, **dict(filters, window_length=WINDOW))
    assert len(small.offered) == 9 and small.capped.min() >= 23
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("RACON_TPU_PALLAS", "0")
        mp.setenv("RACON_TPU_SHARD", "0")
        mp.setenv("RACON_TPU_BATCH_WINDOWS", "8")
        session = PolishSession(str(tmp_path / "work"), backend="tpu")
        res = session.run_job(JobSpec(
            reads, sam, draft, job_id="rehearsal",
            args=dict(pa, window_length=WINDOW, num_threads=2)))
    with open(res["report"]) as f:
        report = json.load(f)
    cons = report["phases"]["consensus"]
    c = report["obs"]["metrics"]["counters"]
    assert cons["extra"]["capped_windows"] == c["poa.windows.capped"] == 9
    assert cons["extra"]["capped_layers"] == c["poa.layers.capped"] \
        == small.capped.sum()
    assert c["poa.layers.admitted"] == 9 * CAP
    served = cons["served"]
    assert served.get("xla", 0) + served.get("host", 0) == 9
    assert c["poa.windows.trim.admitted"] == served.get("xla", 0) >= 7
    assert c["poa.windows.trim.full"] == served.get("host", 0)


def _run(job):
    return {"jobs": [job, dict(job)], "facts": {}, "data": {}, "edits": {},
            "notes": {}, "trace": None, "device": None, "peaks": {}}


def test_new_metrics_read_a_jobs_counters(phase):
    ref, stats, counters, _ = phase
    job = {"wall_s": 1.0, "polished_bp": 1280, "counters": counters,
           "spans": {}, "phases": {"consensus": {
               "served": dict(stats["report"].served)}}}
    cell = loader.load_cell(CELL)
    registry = reducers.registry()
    values = {m["name"]: registry[m["reducer"]](_run(job),
                                                **m.get("params", {}))
              for m in cell.per_layer
              if m["name"] in NEW_METRICS | SHARED_METRICS}
    assert values.pop("deep_poa_roofline") is None     # no device trace
    assert values["cap_poa_capped_window_share"] == pytest.approx(40.0)
    assert values["cap_poa_capped_layer_share"] == pytest.approx(
        100 * 45 / ref.offered.sum())
    assert values["cap_poa_edge_overflow_window_share"] == pytest.approx(
        10.0 * counters["poa.windows.overflow.edges"])
    # the deep cell's readers, on this cell's counters
    assert values["deep_poa_layers_per_window"] == pytest.approx(
        ref.admitted.sum() / 10)
    assert values["deep_poa_upper_rung_window_share"] == 100.0
    assert values["deep_poa_overflow_window_share"] == pytest.approx(
        10.0 * stats["failed"])
    assert 50 < values["deep_poa_node_fill_share"] < 100
    # the XLA twin has no grid programs: nothing to read
    assert values["deep_poa_lockstep_fill_share"] is None
    assert values["deep_poa_wide_program_share"] is None
