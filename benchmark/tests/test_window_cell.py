"""The larger-window cell's benchmark files (``lambda-ont-w1000.paf``):
its metric files against ``BENCHMARK.json``'s entries, the plain
reference ``benchmark/reference_window.py`` on a crafted target, and the
roofline reader on a trace whose operations carry the class-1024 program
names of both node rungs.

    python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import json
import os

import numpy as np
import pytest

from benchmark import costs, loader, reducers, reference_window, xplane

CELL = "lambda-ont-w1000.paf"
HERE = os.path.dirname(os.path.abspath(__file__))
METRICS = {
    "w1000_poa_upper_rung_window_share": ("drivers", "program_counter",
                                          "counter_over_sum"),
    "w1000_poa_beyond_rung_window_share": ("drivers", "program_counter",
                                           "counter_over_sum"),
    "w1000_poa_overflow_window_share": ("drivers", "program_counter",
                                        "counter_family_share"),
    "w1000_poa_program16_window_share": ("kernels", "program_counter",
                                         "counter_share"),
    "w1000_poa_nodes_per_backbone_base": ("kernels", "program_counter",
                                          "counter_quotient"),
    "w1000_poa_job_share": ("phases", "program_span", "span_share"),
    "w1000_poa_roofline": ("kernels", "device_trace", "deep_roofline"),
}


# -- the files ---------------------------------------------------------------

def test_metric_files_agree_with_their_entries():
    bm = loader.load_benchmark()
    entries = {m["name"]: m for m in bm["per_layer"]}
    registry = reducers.registry()
    # the new entries are the last of the list, in the files' order
    assert [m["name"] for m in bm["per_layer"]][-len(METRICS):] \
        == list(METRICS)
    for name, (layer, source, reducer) in METRICS.items():
        with open(os.path.join(loader.BENCH_DIR, "layer_metrics",
                               name + ".json")) as f:
            spec = json.load(f)
        entry = entries[name]
        for key in ("name", "unit", "better", "source", "layer", "moves",
                    "workloads"):
            assert spec[key] == entry[key], (name, key)
        assert set(entry) == {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
        assert (spec["layer"], spec["source"], spec["reducer"]) == (
            layer, source, reducer)
        assert spec["workloads"] == [CELL]
        assert spec["moves"] == "polished_mbp_per_s"
        assert spec["reducer"] in registry
        assert name.endswith("_roofline") == (spec["unit"] == "%"
                                              and source == "device_trace")
    layers = {m["layer"] for m in bm["per_layer"] if m["name"] not in METRICS}
    assert {layer for layer, _, _ in METRICS.values()} <= layers


def test_the_cell_is_one_configuration_one_cell_on_one_chip():
    bm = loader.load_benchmark()
    cell = loader.load_cell(CELL)
    assert bm["workloads"][-1]["name"] == CELL
    assert bm["configs"][-1]["name"] == "lambda-ont-w1000"
    assert bm["configs"][-1]["file"] \
        == "benchmark/configs/lambda-ont-w1000.json"
    assert cell.chips == 1 and cell.traffic_name == "paf-lambda"
    assert cell.config["polish_args"]["window_length"] == 1000
    assert bm["run_seconds"] == 51
    for entry in bm["workloads"] + bm["configs"]:
        assert len(entry["why"]) <= 200
    # it reports every end-to-end metric, and a metric of every layer
    assert [m["name"] for m in cell.end_to_end] == [
        "polished_mbp_per_s", "err_removed_vs_host", "setup_s"]
    assert {m["layer"] for m in cell.per_layer} >= {
        "entry", "phases", "drivers", "kernels", "device"}
    # every accepted metric with no list of its own reads this cell too
    unlisted = [m["name"] for m in bm["per_layer"] if "workloads" not in m]
    assert set(unlisted) <= {m["name"] for m in cell.per_layer}


# -- the reference on a crafted target ---------------------------------------

def _craft(d):
    """A target of 2015 bases (windows of 1000, 1000 and a tail of 15,
    under the floor of 20) and four error-free reads: ``r19`` leaves 19
    bases in window 0 and ``r20`` 20; ``low`` is a copy of ``r20`` whose
    bases in window 0 have quality 5; ``tail`` ends in the tail."""
    rng = np.random.default_rng(7)
    target = bytes(rng.choice(np.frombuffer(b"ACGT", np.uint8), 2015))
    reads = {"r19": (981, 1700, None), "r20": (980, 1700, None),
             "low": (900, 1700, 100), "tail": (1200, 2015, None)}
    with open(d / "draft.fasta", "w") as f:
        f.write(f">t\n{target.decode()}\n")
    with open(d / "reads.fastq", "w") as fq, open(d / "ovl.paf", "w") as paf:
        for name, (lo, hi, n_low) in reads.items():
            seq = target[lo:hi].decode()
            qual = "I" * len(seq)
            if n_low:
                qual = "&" * n_low + qual[n_low:]      # '&' is Phred 5
            fq.write(f"@{name}\n{seq}\n+\n{qual}\n")
            paf.write("\t".join(map(str, (
                name, len(seq), 0, len(seq), "+", "t", len(target), lo, hi,
                len(seq), len(seq), 255))) + "\n")
    return (str(d / "draft.fasta"), str(d / "reads.fastq"),
            str(d / "ovl.paf"))


@pytest.fixture(scope="module")
def crafted(tmp_path_factory):
    return _craft(tmp_path_factory.mktemp("crafted"))


def test_windows_and_the_tail(crafted):
    draft = crafted[0]
    (name, lengths), = reference_window.windows(draft, 1000).items()
    assert name == "t" and lengths.tolist() == [1000, 1000, 15]
    assert reference_window.windows(draft, 500)["t"].tolist() \
        == [500] * 4 + [15]
    assert reference_window.windows(draft, 403)["t"].tolist() == [403] * 5
    assert reference_window.windows(draft, 5000)["t"].tolist() == [2015]


def test_a_piece_of_19_goes_and_one_of_20_stays_at_w1000(crafted):
    ref = reference_window.window_pieces(
        *crafted, window_length=1000, quality_threshold=10.0,
        error_threshold=0.3)
    assert ref["short_floor"] == 20 and ref["tail"] == 15
    assert ref["bb_len"].tolist() == [1000, 1000, 15]
    # window 0: r19 (19 bases: short), r20 (20: stays), low (100 bases of
    # quality 5: by quality); window 1: all four; the tail: `tail`'s 15
    # bases, under the floor
    assert ref["offered"].tolist() == [3, 4, 1]
    assert ref["dropped_short"].tolist() == [1, 0, 1]
    assert ref["dropped_quality"].tolist() == [1, 0, 0]
    assert ref["admitted"].tolist() == [1, 4, 0]
    assert ref["layer_bases"].tolist() == [20, 700 * 3 + 800, 0]
    # error-free layers add no node; a window without two layers builds
    # no graph: every window needs the base rung and no more
    assert ref["nodes"].tolist() == [1000, 1000, 15]
    assert ref["rung"].tolist() == [0, 0, 0]
    assert (ref["over_base"], ref["over_upper"]) == (0, 0)


def test_the_same_pieces_at_w500_where_the_floor_is_ten(crafted):
    ref = reference_window.window_pieces(
        *crafted, window_length=500, quality_threshold=10.0,
        error_threshold=0.3, nodes=False)
    assert ref["short_floor"] == 10 and ref["tail"] == 15
    # window 1 (500..999) holds r19's 19 bases and r20's 20: both stay
    assert ref["offered"].tolist() == [0, 3, 4, 4, 1]
    assert ref["dropped_short"].tolist() == [0, 0, 0, 0, 0]
    assert ref["dropped_quality"].tolist() == [0, 1, 0, 0, 0]
    assert ref["admitted"].tolist() == [0, 2, 4, 4, 1]


def test_rungs_by_the_class_of_the_backbone():
    assert reference_window.window_class(1000) == 1024
    assert reference_window.window_class(251) == 256
    assert reference_window.window_class(15) == 128
    assert reference_window.rung_capacities(1000) == (3072, 5120)
    assert reference_window.rung_capacities(500) == (1536, 2560)
    need = reference_window.rungs_needed(
        [1000, 3072, 3073, 5120, 5121, 700, 800], [1000] * 5 + [251] * 2)
    assert need.tolist() == [0, 0, 1, 1, 2, 0, 1]


# -- the roofline reader on class-1024 program names --------------------------

def _hlo(result, nb, groups, node_chunks, depth, j_chunks):
    """An ``ls`` operation's name in a device trace: its whole HLO text
    (``xplane.short_name`` keeps the dimensions)."""
    w = groups * 8
    n = f"s32[{nb},{groups},{node_chunks},8,128]"
    s = f"s32[{nb},1,{w}]"
    layer = f"s32[{nb},{w},{depth}]"
    seqs = f"s32[{nb},{depth},{groups},{j_chunks},8,128]"
    return (f"%{result} = ({n}{{4,3,2,1,0}}, {n}{{4,3,2,1,0}}) "
            f"custom-call({s}{{2,1,0}} %a, {s}{{2,1,0}} %b, {layer}{{2,1,0}} "
            f"%c, {layer}{{2,1,0}} %d, {layer}{{2,1,0}} %e, {n}{{4,3,2,1,0}} "
            f"%f, {n}{{4,3,2,1,0}} %g, {seqs}{{5,4,3,2,1,0}} %h, "
            f"{seqs}{{5,4,3,2,1,0}} %i), "
            'custom_call_target="tpu_custom_call", operand_layout_'
            "constraints={}")


def test_roofline_reads_the_class_1024_programs_of_both_rungs():
    base = _hlo("racon_poa_ls.1", 4, 2, 24, 200, 13)     # 3072 slots
    upper = _hlo("racon_poa_ls.2", 4, 2, 40, 200, 13)    # 5120 slots
    tail = _hlo("racon_poa_ls.3", 4, 2, 6, 32, 4)        # class 256
    assert xplane.short_name(base).startswith(
        "%racon_poa_ls.1 custom-call [4,1,16]x2 [4,16,200]x3 "
        "[4,2,24,8,128]x2")
    assert "[4,2,40,8,128]x2" in xplane.short_name(upper)
    hirschberg = ('%racon_hirschberg_base.1 = s32[8,8,128]{2,1,0} '
                  'custom-call(s32[8,1,1]{2,1,0} %a), '
                  'custom_call_target="tpu_custom_call"')
    s = 1_000_000_000
    trace = xplane.DeviceTrace(ops={0: [
        xplane.Event(hirschberg, 0.1 * s, 0.5 * s),      # phase.align
        xplane.Event(base, 1.0 * s, 0.6 * s),
        xplane.Event(upper, 1.7 * s, 1.1 * s),
        xplane.Event(tail, 2.9 * s, 0.05 * s),
        xplane.Event("%fusion.3 = s32[64]{0} fusion(s32[64]{0} %x)",
                     2.95 * s, 0.01 * s),
    ]})
    offset = 7 * s                 # program clock = profiler clock + 7 s
    counters = {"poa.layers.bases": 1_700_000, "poa.nodes.used": 140_000,
                "poa.windows.d32.c1024": 24, "poa.windows.d200.c1024": 23,
                "poa.windows.d32.c256": 1, "poa.rows.real": 48}
    job = {"id": "w0000", "clock_offset_ns": offset, "counters": counters,
           "spans": {"phase.align": [(offset + 0, 1 * s)],
                     "phase.poa": [(offset + 1 * s, 2 * s)]},
           "phases": {"consensus": {"served": {"ls": 48}}}}
    run = {"trace": trace, "jobs": [job], "notes": {}, "data": {},
           "facts": {"int32_ops_per_s": 1.0e12},
           "peaks": {"hbm_bytes_per_s": 8.19e11}}
    cell = loader.load_cell(CELL)
    spec = {m["name"]: m for m in cell.per_layer}["w1000_poa_roofline"]
    share = reducers.registry()[spec["reducer"]](run, **spec["params"])
    note = run["notes"]["deep_poa_roofline"]
    # the three ls programs inside phase.poa, not the aligner's kernel
    assert note["kernel_device_s"] == pytest.approx(0.6 + 1.1 + 0.05)
    mean_graph = ((47 * 1024 + 256) / 48 + 140_000 / 48) / 2
    ops = 1_700_000 * mean_graph * costs.POA_OPS_PER_CELL
    assert note["int_ops"] == pytest.approx(ops)
    assert note["binds"] == "int32 ops"
    assert share == pytest.approx(100 * ops / 1e12 / 1.75)
    assert 0 < share < 100
    # a pattern tied to class 512's node arrays would have read nothing
    assert xplane.kernel_seconds(
        trace, [r"racon_poa_ls.*\[\d+,\d+,(12|20),8,128\]"],
        [(1 * s, 3 * s)]) == 0
    assert xplane.kernel_seconds(
        trace, [r"racon_poa_ls.*\[\d+,\d+,(24|40),8,128\]"],
        [(1 * s, 3 * s)]) == pytest.approx(1.7)
    # no probe rate, no share: the metric is left out, not guessed
    run["facts"] = {}
    assert reducers.registry()[spec["reducer"]](
        run, **spec["params"]) is None
