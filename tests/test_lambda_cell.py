"""Upstream's own suite scenario as a deployment (``lambda-ont``): a raw
layout as the draft, reads ~30 % from it, per-base qualities.

The plain reference ``benchmark/reference_layout.py`` against the host
path (overlap filter, admitted and quality-dropped layers per window),
the device tiers interpreted on the CPU (Hirschberg CIGARs against the
full-matrix aligner, ``ls`` against the host engine on weights that
differ), the driver's rung rule against the reference's exact graph, the
served path, and the files of the cell ``lambda-ont.paf``.

Small and seeded: a 6 kb genome, 30 reads of ~1.5 kb, the cell's error
mix and quality model; the device path at ``-w 200`` (window class 256),
whose interpreted programs build in seconds.
"""

import json

import numpy as np
import pytest

import racon_tpu
from benchmark import (generate_layout, loader, prepare, reducers,
                       reference_align, reference_layout)
from racon_tpu import native
from racon_tpu.ops import align_pallas, poa, poa_driver
from racon_tpu.ops.encoding import decode, encode
from racon_tpu.pipeline import Pipeline
from tests.test_pallas_ls import _alloc, _run_ls, _set_window

CELL = "lambda-ont.paf"
SMALL = dict(genome_mbp=0.006, reads=30, read_bases=45000, error_rate=0.17,
             qual_mean=13.0, qual_sd=2.0, qual_base_sd=3.0,
             qual_error_drop=5.0, data_seed=2, layout_seed=22)
ARGS = dict(quality_threshold=10.0, error_threshold=0.3, trim=True,
            match=5, mismatch=-4, gap=-8)
NEW_METRICS = {
    "raw_layers_quality_dropped_share", "raw_align_host_pair_share",
    "raw_align_top_bucket_pair_share", "raw_poa_overflow_window_share",
    "raw_poa_rung_miss_window_share", "raw_poa_nodes_per_backbone_base",
    "raw_job_boundary_share", "raw_align_roofline", "raw_poa_roofline"}


def _files(d):
    return (str(d / "reads.fastq"), str(d / "overlaps.paf"),
            str(d / "draft.fasta"))


def _counters(path):
    with open(path) as f:
        doc = json.load(f)
    return (doc.get("racon_tpu") or doc["obs"])["metrics"]["counters"]


@pytest.fixture(scope="module")
def small(tmp_path_factory):
    d = tmp_path_factory.mktemp("lambda30")
    facts = generate_layout.mode_layout(str(d), 0, **SMALL)
    # before the first read's line one the error threshold has to drop
    # (half the read span on the whole target span), after it one that is only the read's shorter: neither
    # may reach a window (a read's lines are consecutive, as racon's
    # per-query groups expect)
    with open(d / "overlaps.paf") as f:
        lines = f.readlines()
    first = lines[0].split("\t")
    q_begin, q_end, t_begin = int(first[2]), int(first[3]), int(first[7])
    half = (q_end - q_begin) // 2
    bad = list(first)
    bad[3] = str(q_begin + half)
    short = list(bad)
    short[8] = str(t_begin + half)
    with open(d / "overlaps.paf", "w") as f:
        f.writelines(["\t".join(bad), lines[0], "\t".join(short)]
                     + lines[1:])
    return d, facts


def _reference(d, w):
    return reference_layout.window_layers(
        str(d / "draft.fasta"), str(d / "reads.fastq"),
        str(d / "overlaps.paf"), window_length=w,
        quality_threshold=ARGS["quality_threshold"],
        error_threshold=ARGS["error_threshold"])


@pytest.fixture(scope="module")
def reference500(small):
    return _reference(small[0], 500)


# -- the reference against the host path ------------------------------------

def test_overlap_filter_and_layers_equal_the_reference(small, reference500,
                                                        tmp_path):
    d, facts = small
    ref = reference500
    rows = ref["overlaps"]
    assert len(rows) == facts["overlaps"] + 2
    assert sum(r["error"] > 0.3 for r in rows) == 1
    assert sum(r["kept"] for r in rows) == facts["overlaps"]

    pl = Pipeline(*_files(d), window_length=500, num_threads=4, **ARGS)
    pl.initialize()
    dropped_error, offered, short, quality = pl.filter_counts()
    assert dropped_error == 1
    assert offered == ref["offered"].sum()
    assert short == ref["dropped_short"].sum()
    assert quality == ref["dropped_quality"].sum() > 0
    # the share the cell's quality model is there for: some, not most
    assert 0.03 < quality / offered < 0.25
    admitted = [pl.window_info(i)[0] - 1 for i in range(pl.num_windows())]
    assert admitted == ref["admitted"].tolist()
    assert ref["depth_layers"].tolist() == admitted   # reference_depth's

    trace = tmp_path / "trace.json"
    p = racon_tpu.create_polisher(*_files(d), backend="cpu",
                                  window_length=500, num_threads=4,
                                  trace_path=str(trace), **ARGS)
    p.initialize()
    (name, _), = p.polish(True)
    assert f"RC:i:{facts['overlaps']}" in name
    c = _counters(trace)
    assert c["overlaps.parsed"] == len(rows)
    assert c["overlaps.kept"] == facts["overlaps"]
    assert c["overlaps.dropped.error"] == 1
    assert c["layers.offered"] == offered
    assert c["layers.dropped.quality"] == quality
    assert c["layers.dropped.short"] == short


def test_a_skipped_quality_filter_is_seen(small, reference500):
    """With ``-q 0`` the native filter drops nothing: the counts then
    part from the reference's at ``-q 10`` by exactly its drops."""
    d, _ = small
    pl = Pipeline(*_files(d), window_length=500, num_threads=4,
                  **dict(ARGS, quality_threshold=0.0))
    pl.initialize()
    _, offered, short, quality = pl.filter_counts()
    assert quality == 0 and offered == reference500["offered"].sum()
    admitted = sum(pl.window_info(i)[0] - 1
                   for i in range(pl.num_windows()))
    assert admitted - reference500["admitted"].sum() \
        == reference500["dropped_quality"].sum() > 0


def test_strays_and_host_graphs_under_the_exact_graph(small, reference500):
    """What the rung rule reads (``Pipeline.window_growth``): stray bases
    as the reference aligner counts them (an optimal alignment is not
    unique: within 3 %), and the host engine's graph under the exact
    graph of the same layers, which bounds the rule's estimate too."""
    d, _ = small
    pl = Pipeline(*_files(d), window_length=500, num_threads=4, **ARGS)
    pl.initialize()
    pl.consensus_cpu_all()
    growth = pl.window_growth()
    ref = reference500
    want = sum(reference_layout.stray_bases(
        q, t, reference_layout.align(q, t)[1])
        for q, t in _pairs(d, ref["overlaps"]))
    # the walk counts admitted and dropped pieces' strays per piece; the
    # reference here the whole overlaps': dropped layers make it more
    kept_share = ref["admitted"].sum() / ref["offered"].sum()
    assert growth[:, 0].sum() == pytest.approx(want * kept_share, rel=0.08)
    for i in range(pl.num_windows()):
        n, bb, _, _, layer_bytes, _ = pl.window_info(i)
        if n < 3:
            continue
        assert bb < growth[i, 1] <= ref["nodes"][i]
        est = poa_driver.node_estimate(bb, layer_bytes, int(growth[i, 0]))
        assert growth[i, 1] <= est <= 1.3 * ref["nodes"][i]


# -- the device tiers, interpreted ------------------------------------------

def _pairs(d, rows, n=None):
    """(read span on the target's strand, target span) of the kept
    overlaps, the first ``n``."""
    reads = reference_layout.read_fastq(str(d / "reads.fastq"))
    draft = np.frombuffer(prepare.read_fasta(str(d / "draft.fasta")),
                          np.uint8)
    out = []
    for row in [r for r in rows if r["kept"]][:n]:
        seq, _, lo, hi = reference_layout.on_target_strand(
            row, *reads[row["name"]])
        out.append((seq[lo:hi], draft[row["t_begin"]:row["t_end"]]))
    return out


def _cost(ops, q, t):
    cigar = reference_layout.cigar(np.asarray(ops, np.uint8))
    cost, qi, ti = reference_align.cigar_cost(cigar, q.tobytes(),
                                              t.tobytes())
    assert (qi, ti) == (len(q), len(t))
    return cost


def test_hirschberg_cigars_cost_what_the_full_matrix_costs(small,
                                                           reference500):
    """Raw-layout pairs (two noisy copies, ~30 % edits, ends a few bases
    off), one of them cut so that its spans differ by a tenth, and one
    whose spans differ by more than any band bucket holds: that one is
    refused (the host aligns it), never aligned under a band that cannot
    hold its diagonal."""
    d, _ = small
    pairs = _pairs(d, reference500["overlaps"], 5)
    q, t = pairs[0]
    pairs.append((q, t[:int(0.9 * len(t))]))            # unequal spans
    draft = np.frombuffer(prepare.read_fasta(str(d / "draft.fasta")),
                          np.uint8)
    pairs.append((draft[:3000], draft[:700]))            # no bucket
    assert align_pallas.band_for(3000, 700) == 0
    got = align_pallas.align_pairs(
        [(encode(a).astype(np.int32), encode(b).astype(np.int32))
         for a, b in pairs], interpret=True)
    shares = []
    for (a, b), ops in zip(pairs[:-1], got[:-1]):
        assert ops is not None
        best = reference_layout.align(a, b)[0]
        assert best == reference_align.edit_distance(a.tobytes(),
                                                     b.tobytes())
        assert _cost(ops, a, b) == best
        shares.append(best / len(a))
    # two noisy sides: ~30 % edits (less where the layout's piece is the
    # read's own)
    assert max(shares) < 0.45 and sorted(shares)[2] > 0.22, shares
    assert got[-1] is None


#: a column where three low-quality layers say T and two high-quality
#: ones say G: weighted, G wins; flattened to one weight, T does
_BB = b"ACGTTGCAAGCTTAGGCTAACGTAGCTAGGATCCATGCAAGTCCGATTACAGGCTTAACG" * 2
_COL = 50


def _weighted_window(flatten):
    layers, weights = [], []
    for base, w, n in ((b"T", 3, 3), (b"G", 30, 2)):
        for _ in range(n):
            layers.append(_BB[:_COL] + base + _BB[_COL + 1:])
            weights.append(np.full(len(_BB), 15 if flatten else w,
                                   np.int32))
    return layers, weights


@pytest.mark.parametrize("flatten", [False, True],
                         ids=["weights", "flattened"])
def test_ls_weighs_bases_as_the_host_engine_does(flatten):
    cfg = poa_driver.make_config(128, 8, 5, -4, -8)
    layers, weights = _weighted_window(flatten)
    a = _alloc(8, cfg)
    _set_window(a, 0, _BB, layers, weights)
    cb, _, cl, fl, _ = _run_ls(a, cfg, 1)
    assert not fl[0, 0]
    device = decode(cb[0, :cl[0, 0]])
    quals = [bytes((33 + w).astype(np.uint8)) for w in weights]
    host, _ = native.window_consensus(_BB, layers, quals=quals, trim=False)
    assert device == host
    assert chr(device[_COL]) == ("T" if flatten else "G")


@pytest.fixture(scope="module")
def served(small, tmp_path_factory):
    """Two served jobs with true weights, then one whose exported layer
    weights are flattened to a constant (the packer's input patched)."""
    from racon_tpu.serve.session import JobSpec, PolishSession

    d, _ = small
    mp = pytest.MonkeyPatch()
    mp.setenv("RACON_TPU_PALLAS", "1")
    mp.setenv("RACON_TPU_DEVICE_ALIGNER", "hirschberg")
    mp.setenv("RACON_TPU_BATCH_WINDOWS", "8")
    try:
        session = PolishSession(str(tmp_path_factory.mktemp("work")),
                                backend="tpu")
        args = dict(ARGS, window_length=200, num_threads=2)
        results = [session.run_job(JobSpec(*_files(d), args=args, job_id=j))
                   for j in ("first", "second")]
        export = Pipeline.export_window

        def flat(self, i):
            wx = export(self, i)
            wx.weights[:] = 15
            return wx

        mp.setattr(Pipeline, "export_window", flat)
        results.append(session.run_job(
            JobSpec(*_files(d), args=args, job_id="flat")))
    finally:
        mp.undo()
    out = []
    for res in results:
        with open(res["report"]) as f:
            report = json.load(f)
        with open(res["output"], "rb") as f:
            fasta = f.read()
        out.append(dict(res, report_doc=report, fasta=fasta,
                        counters=_counters(res["report"])))
    return out


def test_served_jobs_are_byte_identical_on_the_device_tiers(served, small):
    first, second, _ = served
    assert first["fasta"] == second["fasta"]
    assert second["kernel_builds"] == 0
    for res in (first, second):
        assert res["journal_replayed"] == 0
        phases = res["report_doc"]["phases"]
        ali, cons = phases["alignment"], phases["consensus"]
        assert ali["served"]["hirschberg"] == ali["total"] \
            == small[1]["overlaps"]
        assert cons["served"]["ls"] + cons["served"]["backbone"] \
            == cons["total"]
        assert cons["served"]["host"] == 0


def test_device_output_is_the_host_paths_within_the_judges_margin(
        served, small, tmp_path):
    d, _ = small
    p = racon_tpu.create_polisher(*_files(d), backend="cpu",
                                  window_length=200, num_threads=2, **ARGS)
    p.initialize()
    (_, host), = p.polish(True)
    truth = prepare.read_fasta(str(d / "genome.fasta"))
    device = b"".join(served[0]["fasta"].split(b"\n")[1:])
    edits = {"host": native.edit_distance(host.encode(), truth),
             "device": native.edit_distance(device, truth),
             "draft": native.edit_distance(
                 prepare.read_fasta(str(d / "draft.fasta")), truth)}
    from benchmark import judge
    at_most, _ = judge.accuracy_limits(edits["draft"], edits["host"],
                                       len(truth))
    assert edits["device"] <= at_most, edits
    assert edits["device"] < edits["draft"] - 300, edits


def test_flattened_weights_change_the_served_consensus(served):
    """The device path's weights are the reads' qualities all the way
    from the FASTQ through the packer into ``ls``: flatten them at the
    packer's input and the served bytes move."""
    assert served[2]["fasta"] != served[0]["fasta"]
    # nothing else moved: the same windows, layers and tiers
    for key in ("poa.layers.admitted", "poa.rows.real",
                "layers.dropped.quality"):
        assert served[2]["counters"][key] == served[0]["counters"][key]


def test_the_rung_rule_holds_every_window_and_the_counters_say_so(
        served, small):
    d, facts = small
    c = served[0]["counters"]
    ref = _reference(d, 200)
    # layers: what the reference admits, the driver packs
    assert c["layers.offered"] == ref["offered"].sum()
    assert c["layers.dropped.quality"] == ref["dropped_quality"].sum() > 0
    assert c["poa.layers.admitted"] == sum(
        n for n in ref["admitted"] if n >= 2)
    # pairs per band bucket; none refused, none escaped
    assert sum(c[f"align.pairs.band.k{k}"] for k in align_pallas.BANDS) \
        == facts["overlaps"] == c["align.cohorts.pairs"]
    assert c["align.pairs.host.refused"] == 0
    assert "align.pairs.host.escaped" not in c
    # no window outgrew its rung, by any cause or bucket
    assert not any(v for k, v in c.items()
                   if k.startswith(("poa.windows.overflow.",
                                    "poa.windows.rung.miss.")))
    assert {f"poa.windows.rung.miss.d{b}" for b in poa_driver.DEPTH_BUCKETS} \
        <= set(c)
    served_windows = served[0]["report_doc"]["phases"]["consensus"][
        "served"]["ls"]
    bb_len = np.minimum(200, facts["draft_bp"]
                        - 200 * np.arange(len(ref["admitted"])))
    assert c["poa.backbone.bases"] == bb_len[ref["admitted"] >= 2].sum()
    assert c["poa.rows.real"] == served_windows
    # the graphs the kernel built lie under the reference's exact graphs
    assert c["poa.backbone.bases"] < c["poa.nodes.used"] <= sum(
        n for n, k in zip(ref["nodes"], ref["admitted"]) if k >= 2)


# -- the cell's files ---------------------------------------------------------

def test_the_cell_loads_and_is_the_deployment():
    cell = loader.load_cell(CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        "lambda-ont", "paf-lambda", 1)
    bm = loader.load_benchmark()
    entry, = (c for c in bm["configs"] if c["name"] == "lambda-ont")
    assert entry["reduced"] == [] and cell.config["reduced"] == {}
    assert len(entry["source"]) <= 200
    ecoli = loader.load_cell("ecoli-ont.paf").config
    assert cell.config["polish_args"] == ecoli["polish_args"]
    assert cell.config["guarantees"] == ecoli["guarantees"]
    params = prepare.data_params(cell, rehearsal=False)
    assert params["generator"] == "generate_layout:mode_layout"
    assert params["generator_rev"] == generate_layout.GENERATOR_REV
    assert (params["genome_mbp"], params["reads"], params["read_bases"]) \
        == (0.048502, 236, 1658216)
    assert {k: params[k] for k in SMALL if k in params and k not in (
        "genome_mbp", "reads", "read_bases")} == {
            k: v for k, v in SMALL.items() if k not in (
                "genome_mbp", "reads", "read_bases")}
    toy = prepare.data_params(cell, rehearsal=True)
    assert {k: toy[k] for k in ("genome_mbp", "reads", "read_bases")} == {
        k: SMALL[k] for k in ("genome_mbp", "reads", "read_bases")}
    expect = cell.workload["expect"]
    assert expect["alignment"] and expect["alignment_tier"] == "hirschberg"
    assert expect["consensus_tier"] == "ls"
    assert set(expect["consensus_tiers_at_zero"]) == {"v2", "xla"}
    names = {m["name"] for m in cell.per_layer}
    assert NEW_METRICS <= names
    for m in bm["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL]
    assert sum(w["chips"] == 4 for w in bm["workloads"][:8]) == 2
    # the eight cells as PR 41 left them, this one the last: a later PR
    # appends its own and moves none
    assert [w["name"] for w in bm["workloads"]][:8] == [
        "ecoli-ont.sam", "ecoli-ont.paf", "chr20-sr.sam",
        "ecoli-ont-x4.sam", "ecoli-frag.paf", "ecoli-ont-x4.paf",
        "ecoli-ont-deep.sam", CELL]


def test_new_metrics_read_the_served_jobs_counters_and_spans(served):
    cell = loader.load_cell(CELL)
    registry = reducers.registry()
    jobs = []
    for res in served[:2]:
        with open(res["trace"]) as f:
            trace = json.load(f)
        spans = {}
        for e in trace["traceEvents"]:
            if e.get("ph") == "X":
                spans.setdefault(e["name"], []).append(
                    (e["ts"] * 1000, e["dur"] * 1000))
        jobs.append({"counters": res["counters"], "spans": spans,
                     "phases": res["report_doc"]["phases"],
                     "polished_bp": res["polished_bp"], "wall_s": 1.0})
    run = {"jobs": jobs, "facts": {}, "data": {}, "edits": {}, "notes": {},
           "trace": None, "device": None, "peaks": {}}
    values = {m["name"]: registry[m["reducer"]](run, **m.get("params", {}))
              for m in cell.per_layer if m["name"] in NEW_METRICS}
    c = served[0]["counters"]
    assert values["raw_layers_quality_dropped_share"] == pytest.approx(
        100 * c["layers.dropped.quality"] / c["layers.offered"])
    assert values["raw_align_host_pair_share"] == 0
    assert values["raw_align_top_bucket_pair_share"] == 0
    assert values["raw_poa_overflow_window_share"] == 0
    assert values["raw_poa_rung_miss_window_share"] == 0
    assert values["raw_poa_nodes_per_backbone_base"] == pytest.approx(
        c["poa.nodes.used"] / c["poa.backbone.bases"])
    assert 1.5 < values["raw_poa_nodes_per_backbone_base"] < 3.5
    assert 0 < values["raw_job_boundary_share"] < 50
    assert values["raw_align_roofline"] is None       # no device trace
    assert values["raw_poa_roofline"] is None


def test_every_metric_of_the_cell_reads_nothing_from_an_older_program():
    """On a program without this PR's counters (the parent under the
    driver's check) and without a trace, a reader returns ``None`` or a
    number; it does not raise."""
    cell = loader.load_cell(CELL)
    registry = reducers.registry()
    job = {"counters": {"poa.windows.d32.c512": 90, "poa.rows.real": 94,
                        "poa.nodes.used": 130000,
                        "poa.windows.overflow.nodes": 29,
                        "align.cohorts.pairs": 236},
           "spans": {}, "phases": {}, "polished_bp": 47300, "wall_s": 4.0}
    run = {"jobs": [job, dict(job)], "facts": {}, "data": {}, "edits": {},
           "notes": {}, "trace": None, "device": None, "peaks": {}}
    for m in cell.per_layer:
        if m["reducer"] == "setup_trace_lower_s":
            continue                     # reads the live process, not run
        value = registry[m["reducer"]](run, **m.get("params", {}))
        assert value is None or isinstance(value, (int, float)), m["name"]
        if m["name"] in NEW_METRICS - {"raw_poa_overflow_window_share"}:
            assert value is None, m["name"]
    assert registry["counter_family_share"](
        run, "poa.windows.overflow.", "poa.rows.real") == pytest.approx(
            100 * 29 / 94)
