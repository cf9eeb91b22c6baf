"""Upstream's larger-window scenario as a deployment (``lambda-ont-w1000``):
``lambda-ont``'s job at ``-w 1000``, window class 1024, both node rungs
inside the lockstep kernel.

The plain reference ``benchmark/reference_window.py`` against the host
path at ``-w 1000`` and, at ``-w 500``, against ``reference_layout.py``
(one rule, two lengths); the driver's rung rule against the reference's
exact graph; interpreted ``ls`` at class 1024 on both rungs against the
host engine on a crafted program that reaches each; a served
``-w 1000`` job; the files of the cell ``lambda-ont-w1000.paf``.

Small and seeded like ``tests/test_lambda_cell.py``: a 6 kb genome, 30
reads of ~1.5 kb, the cell's error mix and quality model, plus two
error-free reads laid so that one leaves 19 bases in a window of 1000 and
the other 20.  Interpreted class 1024 can be had in seconds (a program
of eight with a handful of layers: ~10 s the base rung, ~15 s the upper),
so the class is run as it is, not stood in for.  Every test has its own
time limit (``LIMITS``), none is marked slow.
"""

import contextlib
import json
import signal

import numpy as np
import pytest

from benchmark import (generate_layout, loader, prepare, reducers,
                       reference_layout, reference_window)
from racon_tpu import native
from racon_tpu.ops import poa, poa_driver, poa_pallas_ls
from racon_tpu.ops.encoding import decode
from racon_tpu.pipeline import Pipeline
from tests.test_pallas_ls import _alloc, _run_ls, _set_window

CELL = "lambda-ont-w1000.paf"
SMALL = dict(genome_mbp=0.006, reads=30, read_bases=45000, error_rate=0.17,
             qual_mean=13.0, qual_sd=2.0, qual_base_sd=3.0,
             qual_error_drop=5.0, data_seed=2, layout_seed=22)
ARGS = dict(quality_threshold=10.0, error_threshold=0.3, trim=True,
            match=5, mismatch=-4, gap=-8)
SCORES = (5, -4, -8)
W1000_METRICS = {
    "w1000_poa_upper_rung_window_share",
    "w1000_poa_beyond_rung_window_share", "w1000_poa_overflow_window_share",
    "w1000_poa_program16_window_share", "w1000_poa_nodes_per_backbone_base",
    "w1000_poa_job_share", "w1000_poa_roofline"}
#: metrics that listed all nine cells (or the rejected windows' three),
#: to whose lists this cell is appended
SHARED_METRICS = {
    "poa_host_fallback_s_per_mbp",
    "job_open_s_per_mbp", "job_close_s_per_mbp", "job_unattributed_share",
    "prepare_reads_s_per_mbp", "prepare_overlaps_s_per_mbp",
    "prepare_transmute_s_per_mbp", "prepare_unattributed_share",
    "window_assign_breaks_s_per_mbp", "window_assign_layers_s_per_mbp",
    "boundary_idle_s_per_job", "boundary_idle_unnamed_share",
    "native_pool_items_per_task", "program_cache_hit_share",
    "poa_program32_window_share"}

#: seconds a test may take, set-up of what it is the first to use included
LIMITS = {"default": 120, "served": 420, "interpreted": 240}


@contextlib.contextmanager
def _limit(seconds):
    """The caller's own time limit (the suite has no timeout plugin): a
    test that hangs fails here, not at the driver's limit on the run."""
    def over(signum, frame):
        raise TimeoutError(f"over its limit of {seconds} s")
    before = signal.signal(signal.SIGALRM, over)
    signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, before)


def limit(kind):
    """Marks a test with one of LIMITS (no pytest marker: the suite
    registers none for it)."""
    def mark(fn):
        fn.limit = kind
        return fn
    return mark


@pytest.fixture(autouse=True)
def _time_limit(request):
    with _limit(LIMITS[getattr(request.function, "limit", "default")]):
        yield


def _files(d):
    return (str(d / "reads.fastq"), str(d / "overlaps.paf"),
            str(d / "draft.fasta"))


def _counters(path):
    with open(path) as f:
        doc = json.load(f)
    return (doc.get("racon_tpu") or doc["obs"])["metrics"]["counters"]


#: where the two error-free reads begin on the draft: 19 bases of the
#: first and 20 of the second lie in the window of 1000 before the one
#: they cross into (981..999 and 1980..1999)
EXACT = {"exact19": 981, "exact20": 1980}
EXACT_LEN = 1200


@pytest.fixture(scope="module")
def small(tmp_path_factory):
    with _limit(LIMITS["default"]):
        d = tmp_path_factory.mktemp("lambda30w1000")
        facts = generate_layout.mode_layout(str(d), 0, **SMALL)
        draft = prepare.read_fasta(str(d / "draft.fasta"))
        with open(d / "draft.fasta") as f:
            target = f.readline()[1:].split()[0]
        with open(d / "reads.fastq", "a") as fq, \
                open(d / "overlaps.paf", "a") as paf:
            for name, at in EXACT.items():
                seq = draft[at:at + EXACT_LEN].decode()
                fq.write(f"@{name}\n{seq}\n+\n{'I' * len(seq)}\n")
                paf.write("\t".join(map(str, (
                    name, len(seq), 0, len(seq), "+", target, len(draft),
                    at, at + len(seq), len(seq), len(seq), 255))) + "\n")
        facts["overlaps"] += len(EXACT)
        return d, facts


def _reference(d, w, **kw):
    return reference_window.window_pieces(
        str(d / "draft.fasta"), str(d / "reads.fastq"),
        str(d / "overlaps.paf"), window_length=w,
        quality_threshold=ARGS["quality_threshold"],
        error_threshold=ARGS["error_threshold"], **kw)


@pytest.fixture(scope="module")
def reference1000(small):
    with _limit(LIMITS["default"]):
        return _reference(small[0], 1000)


def _host(d, w):
    pl = Pipeline(*_files(d), window_length=w, num_threads=4, **ARGS)
    pl.initialize()
    return pl


# -- the reference against the host path ------------------------------------

def test_windows_tail_and_layers_equal_the_reference_at_w1000(
        small, reference1000):
    d, facts = small
    ref = reference1000
    pl = _host(d, 1000)
    whole, tail = divmod(facts["draft_bp"], 1000)
    assert tail and ref["tail"] == tail
    (_, lengths), = reference_window.windows(str(d / "draft.fasta"),
                                             1000).items()
    assert lengths.tolist() == [1000] * whole + [tail]
    assert [pl.window_info(i)[1] for i in range(pl.num_windows())] \
        == ref["bb_len"].tolist() == lengths.tolist()
    dropped_error, offered, short, quality = pl.filter_counts()
    assert dropped_error == 0
    assert offered == ref["offered"].sum()
    assert short == ref["dropped_short"].sum() >= 1
    assert quality == ref["dropped_quality"].sum() > 0
    admitted = [pl.window_info(i)[0] - 1 for i in range(pl.num_windows())]
    assert admitted == ref["admitted"].tolist()


def _exact_pieces(w):
    """{read: {window: bases}} of the error-free reads, by the rule
    alone (their alignment is the identity)."""
    out = {}
    for name, at in EXACT.items():
        per = {}
        for pos in range(at, at + EXACT_LEN):
            per[pos // w] = per.get(pos // w, 0) + 1
        out[name] = per
    return out


@pytest.fixture(scope="module")
def bare(small, tmp_path_factory):
    """The set without the two error-free reads."""
    d, _ = small
    out = tmp_path_factory.mktemp("lambda30bare")
    for name, drop in (("reads.fastq", 4 * len(EXACT)),
                       ("overlaps.paf", len(EXACT)), ("draft.fasta", 0)):
        with open(d / name) as f:
            lines = f.readlines()
        with open(out / name, "w") as f:
            f.writelines(lines[:len(lines) - drop])
    return out


@pytest.mark.parametrize("w", [500, 1000])
def test_the_short_floor_is_two_percent_of_the_length_asked_for(
        small, bare, reference1000, w):
    """19 bases of a read in a window of 1000 are dropped and 20 stay;
    at ``-w 500`` the floor is 10 and both stay: one rule at two
    lengths, in the reference and in the host path alike.  Taking the
    two error-free reads away moves exactly their pieces."""
    d, _ = small
    floor = reference_window.short_floor(w)
    assert floor == {500: 10, 1000: 20}[w]
    pieces = _exact_pieces(w)
    assert pieces["exact19"][981 // w] == 19
    assert pieces["exact20"][1980 // w] == 20
    n_win = len(reference_window.windows(str(d / "draft.fasta"),
                                         w).popitem()[1])
    offered = np.zeros(n_win, np.int64)
    short = np.zeros(n_win, np.int64)
    for per in pieces.values():
        for k, n in per.items():
            offered[k] += 1
            short[k] += n < floor
    assert short.sum() == (1 if w == 1000 else 0)
    ref = reference1000 if w == 1000 else _reference(d, w, nodes=False)
    without = _reference(bare, w, nodes=False)
    assert (ref["offered"] - without["offered"]).tolist() \
        == offered.tolist()
    assert (ref["dropped_short"] - without["dropped_short"]).tolist() \
        == short.tolist()
    assert ref["dropped_quality"].tolist() \
        == without["dropped_quality"].tolist()
    assert (ref["admitted"] - without["admitted"]).tolist() \
        == (offered - short).tolist()
    # the host path says the same, window for window and in its totals
    full, base = _host(d, w), _host(bare, w)
    assert [full.window_info(i)[0] - base.window_info(i)[0]
            for i in range(n_win)] == (offered - short).tolist()
    f, b = full.filter_counts(), base.filter_counts()
    assert (f[1] - b[1], f[2] - b[2], f[3] - b[3]) == (
        offered.sum(), short.sum(), 0)
    assert [full.window_info(i)[0] - 1 for i in range(n_win)] \
        == ref["admitted"].tolist()
    if w == 500:
        # the rule at this length is reference_layout's, count for count
        layout = reference_layout.window_layers(
            str(d / "draft.fasta"), str(d / "reads.fastq"),
            str(d / "overlaps.paf"), window_length=500, nodes=False,
            quality_threshold=ARGS["quality_threshold"],
            error_threshold=ARGS["error_threshold"])
        for key in ("offered", "dropped_short", "dropped_quality",
                    "admitted", "layer_bases"):
            assert ref[key].tolist() == layout[key].tolist()
        assert ref["short_floor"] == 10 and ref["tail"] == ref["bb_len"][-1]


# -- the rung rule ----------------------------------------------------------

def test_class_1024_climbs_inside_the_kernel():
    caps = poa_driver._rung_capacities(1024, True, *SCORES)
    assert caps == (3072, 5120) == reference_window.rung_capacities(1000)
    assert caps == poa_driver._rung_capacities(1024, False, *SCORES)
    for bb in (1, 128, 129, 251, 500, 1000, 1024):
        assert reference_window.window_class(bb) \
            == poa_driver.window_class(bb)
        assert reference_window.rung_capacities(bb) \
            == poa_driver._rung_capacities(poa_driver.window_class(bb),
                                           True, *SCORES)
    # both rungs run sixteen windows a program at a TPU's batch, eight at
    # a batch of 8; the upper rung's under a limit of its own at any width
    for rung, mib in ((0, (None, 42)), (1, (26, 51))):
        cfg = poa_driver.make_config(1024, 200, *SCORES, rung)
        assert poa_driver._group_widths(cfg, 64) == (2,)
        assert poa_driver._group_widths(cfg, 8) == (1,)
        assert [poa_pallas_ls.vmem_limit_bytes(cfg, u) for u in (1, 2)] \
            == [m and m << 20 for m in mib]
        assert not poa_driver._fits_vmem(cfg, 4)


def test_the_rung_rule_holds_every_window_of_1000(small, reference1000):
    """No window is estimated under what its graph needs (the host
    engine's graph, which the exact graph bounds), none beyond the top
    rung, and the reference's rungs say the same."""
    d, _ = small
    pl = _host(d, 1000)
    pl.consensus_cpu_all()
    growth = pl.window_growth()
    ref = reference1000
    assert ref["over_upper"] == 0
    checked = 0
    for i in range(pl.num_windows()):
        n, bb, _, is_tgs, layer_bytes, _ = pl.window_info(i)
        if n < 3:
            continue
        assert is_tgs
        caps = poa_driver._rung_capacities(poa_driver.window_class(bb),
                                           True, *SCORES)
        est = poa_driver.node_estimate(bb, layer_bytes, int(growth[i, 0]))
        assert bb < growth[i, 1] <= ref["nodes"][i]
        assert growth[i, 1] <= est <= 1.3 * ref["nodes"][i]
        assert est <= caps[-1]
        rung = poa_driver._node_rung(est, caps)
        assert growth[i, 1] <= caps[rung]
        # the exact graph needs no more than the top rung either, and
        # never a lower rung than the engine's graph does
        assert ref["rung"][i] < len(caps)
        assert caps[ref["rung"][i]] >= growth[i, 1]
        checked += 1
    assert checked >= 4


# -- the kernel at class 1024, interpreted ------------------------------------

def _runs_window(rng, n_layers, sites_per_layer, run=3):
    """A backbone of A / C and layers that equal it but for runs of
    `run` G / T bases inserted at sites no other layer of the window
    uses: an inserted base matches no backbone base, so every run is
    `run` new nodes, and no edge spans more than a few ranks (the
    kernel's H ring holds 64)."""
    bb = rng.choice(np.frombuffer(b"AC", np.uint8), 1000)
    sites = rng.permutation(np.arange(4, 996))[
        :n_layers * sites_per_layer].reshape(n_layers, sites_per_layer)
    layers = []
    for mine in sites:
        parts, at = [], 0
        for s in sorted(mine):
            parts += [bb[at:s], rng.choice(np.frombuffer(b"GT", np.uint8),
                                            run)]
            at = s
        layers.append(np.concatenate(parts + [bb[at:]]).tobytes())
    return bb.tobytes(), layers


@limit("interpreted")
def test_ls_at_class_1024_on_both_rungs_equals_the_host_engine():
    """One program of eight on each rung, its windows the fewest layers
    that reach the rung: window 0 (6 layers of 140 inserted runs of 3
    each, and its first layer three times more so that some runs are
    the consensus) builds 3188 nodes: it outgrows the base rung's 3072
    slots (cause `nodes`) and is served on the upper one; window 1 (3
    layers of 70 runs, 1620 nodes) is served on both.  Byte for byte
    the host engine's consensus, which is not the backbone's."""
    rng = np.random.default_rng(47)
    heavy = _runs_window(rng, 6, 140)
    heavy = (heavy[0], heavy[1] + [heavy[1][0]] * 3)
    light = _runs_window(rng, 3, 70)
    assert max(map(len, heavy[1])) == 1420
    want = []
    for bb, layers in (heavy, light):
        cons, polished = native.window_consensus(bb, layers, trim=False)
        assert polished and len(cons) > 1100
        want.append(cons)
    for rung, slots in ((0, 3072), (1, 5120)):
        cfg = poa_driver.make_config(1024, 32, *SCORES, rung)
        assert (cfg.max_nodes, cfg.max_len, cfg.max_backbone) == (
            slots, 1536, 1024)
        a = _alloc(8, cfg)
        for b, (bb, layers) in enumerate((heavy, light)):
            _set_window(a, b, bb, layers)
        cb, _, cl, fl, nn = _run_ls(a, cfg, 1)
        assert not fl[1, 0] and 1500 < nn[1, 0] < 3072
        assert decode(cb[1, :cl[1, 0]]) == want[1]
        if rung == 0:
            assert fl[0, 0] == poa.FAIL_NODES
        else:
            assert not fl[0, 0] and 3072 < nn[0, 0] < 5120
            assert decode(cb[0, :cl[0, 0]]) == want[0]
        assert not fl[2:].any()             # the pad rows


# -- a served job -----------------------------------------------------------

@pytest.fixture(scope="module")
def served(small, tmp_path_factory):
    """Two served jobs at ``-w 1000`` as they come, then one with the
    base rung lowered to 2 x the class (``RACON_TPU_NODE_FACTOR``; 2048
    slots), at which this set's deeper windows climb: the toy's ~10
    layers a window reach 1900 nodes, not the 3072 of the default."""
    from racon_tpu.serve.session import JobSpec, PolishSession

    d, _ = small
    mp = pytest.MonkeyPatch()
    mp.setenv("RACON_TPU_PALLAS", "1")
    mp.setenv("RACON_TPU_DEVICE_ALIGNER", "hirschberg")
    mp.setenv("RACON_TPU_BATCH_WINDOWS", "8")
    with _limit(LIMITS["served"]):
        try:
            session = PolishSession(str(tmp_path_factory.mktemp("work")),
                                    backend="tpu")
            args = dict(ARGS, window_length=1000, num_threads=2)
            results = [session.run_job(JobSpec(*_files(d), args=args,
                                               job_id=j))
                       for j in ("first", "second")]
            mp.setenv("RACON_TPU_NODE_FACTOR", "2")
            results.append(session.run_job(
                JobSpec(*_files(d), args=args, job_id="climb")))
        finally:
            mp.undo()
    out = []
    for res in results:
        with open(res["report"]) as f:
            report = json.load(f)
        with open(res["output"], "rb") as f:
            fasta = f.read()
        with open(res["trace"]) as f:
            trace = json.load(f)
        out.append(dict(res, report_doc=report, fasta=fasta,
                        trace_doc=trace, counters=_counters(res["report"])))
    return out


@limit("served")
def test_served_job_is_byte_identical_and_its_counters_add_up(
        served, small, reference1000):
    d, facts = small
    first, second, _ = served
    assert first["fasta"] == second["fasta"]
    assert second["kernel_builds"] == 0
    ref = reference1000
    kernel_windows = int((ref["admitted"] >= 2).sum())
    for res in (first, second):
        c = res["counters"]
        phases = res["report_doc"]["phases"]
        ali, cons = phases["alignment"], phases["consensus"]
        assert ali["served"]["hirschberg"] == ali["total"] \
            == facts["overlaps"]
        assert cons["served"]["ls"] + cons["served"]["backbone"] \
            == cons["total"] == len(ref["bb_len"])
        assert cons["served"]["ls"] == kernel_windows == c["poa.rows.real"]
        # layers: what the reference admits, the driver packs
        assert c["layers.offered"] == ref["offered"].sum()
        assert c["layers.dropped.short"] == ref["dropped_short"].sum()
        assert c["layers.dropped.quality"] == ref["dropped_quality"].sum()
        assert c["poa.layers.admitted"] == sum(
            n for n in ref["admitted"] if n >= 2)
        # rungs, classes and widths each add up to the windows
        assert c["poa.windows.rung.base"] + c["poa.windows.rung.upper"] \
            == kernel_windows
        assert c["poa.windows.rung.upper"] == 0
        assert c["poa.windows.rung.beyond"] == 0
        by_class = {}
        for key, n in c.items():
            if key.startswith("poa.windows.d") and ".c" in key:
                cls = int(key.rsplit(".c", 1)[1])
                by_class[cls] = by_class.get(cls, 0) + n
        whole = int(((ref["bb_len"] == 1000) & (ref["admitted"] >= 2)).sum())
        tail_class = poa_driver.window_class(ref["tail"])
        assert by_class == {1024: whole, tail_class: kernel_windows - whole}
        assert c["poa.windows.tail"] == kernel_windows - whole
        assert [c[f"poa.width.windows.u{u}"] for u in (1, 2, 4)] == [
            kernel_windows, 0, 0]            # a batch of 8: one group
        assert c["poa.vmem.programs.raised"] == 0
        assert not any(v for k, v in c.items()
                       if k.startswith("poa.windows.overflow."))
        # the kernel's graphs lie under the reference's exact graphs
        assert c["poa.backbone.bases"] < c["poa.nodes.used"] <= sum(
            n for n, k in zip(ref["nodes"], ref["admitted"]) if k >= 2)


@limit("served")
def test_served_job_climbs_at_class_1024_under_a_raised_limit(served):
    """With the base rung at 2048 slots the same job runs class 1024 on
    both rungs; the upper rung's program of eight (12.64 MiB of arrays)
    is built under a limit of 26 MiB, which its kernel.build span says
    and its launches count."""
    first, _, climb = served
    c = climb["counters"]
    rows = c["poa.rows.real"]
    assert rows == first["counters"]["poa.rows.real"]
    assert c["poa.windows.rung.upper"] > 0 < c["poa.windows.rung.base"]
    assert c["poa.windows.rung.base"] + c["poa.windows.rung.upper"] == rows
    assert c["poa.windows.rung.beyond"] == 0
    assert c["poa.vmem.programs.raised"] >= 1
    assert climb["report_doc"]["phases"]["consensus"]["served"]["host"] == 0
    builds = [e["args"] for e in climb["trace_doc"]["traceEvents"]
              if e.get("name") == "kernel.build"
              and e["args"].get("builder") == "poa.ls"]
    upper = [b for b in builds if b["max_nodes"] == 5120]
    assert upper and all(b["vmem_limit"] == {"u1": 26 << 20}
                         for b in upper)
    assert all(b["vmem_limit"] == {"u1": 0} for b in builds
               if b["max_nodes"] < 5120)
    # the same windows, layers and tiers as the default job; the bytes
    # too, since no window outgrew a rung in either
    for key in ("poa.layers.admitted", "poa.rows.real",
                "layers.dropped.quality"):
        assert c[key] == first["counters"][key]
    assert climb["fasta"] == first["fasta"]


@limit("served")
def test_device_output_is_the_host_paths_within_the_judges_margin(
        served, small):
    import racon_tpu
    from benchmark import judge

    d, _ = small
    p = racon_tpu.create_polisher(*_files(d), backend="cpu",
                                  window_length=1000, num_threads=2, **ARGS)
    p.initialize()
    (_, host), = p.polish(True)
    truth = prepare.read_fasta(str(d / "genome.fasta"))
    device = b"".join(served[0]["fasta"].split(b"\n")[1:])
    edits = {"host": native.edit_distance(host.encode(), truth),
             "device": native.edit_distance(device, truth),
             "draft": native.edit_distance(
                 prepare.read_fasta(str(d / "draft.fasta")), truth)}
    at_most, _ = judge.accuracy_limits(edits["draft"], edits["host"],
                                       len(truth))
    assert edits["device"] <= at_most, edits
    assert edits["device"] < edits["draft"] - 300, edits


# -- the cell's files ---------------------------------------------------------

def test_the_cell_loads_and_is_the_deployment():
    cell = loader.load_cell(CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        "lambda-ont-w1000", "paf-lambda", 1)
    bm = loader.load_benchmark()
    entry, = (c for c in bm["configs"] if c["name"] == "lambda-ont-w1000")
    assert entry["reduced"] == [] and cell.config["reduced"] == {}
    assert len(entry["source"]) <= 200
    assert entry["source"] == cell.config["source"]
    assert "racon_test.cpp:197" in entry["source"]
    assert "-w 1000" in entry["source"]
    # lambda-ont with one argument changed
    control = loader.load_cell("lambda-ont.paf")
    assert cell.config["polish_args"] == dict(
        control.config["polish_args"], window_length=1000)
    for key in ("reads", "guarantees", "assumed", "scale"):
        assert cell.config[key] == control.config[key]
    assert cell.traffic == control.traffic
    assert prepare.data_params(cell, False) == prepare.data_params(
        control, False)
    assert cell.workload["expect"] == control.workload["expect"]
    expect = cell.workload["expect"]
    assert expect["consensus_tier"] == "ls"
    assert expect["consensus_min_share"] == 0.93
    assert set(expect["consensus_tiers_at_zero"]) == {"v2", "xla"}
    assert expect["alignment_min_share"] == 0.97
    names = {m["name"] for m in cell.per_layer}
    assert W1000_METRICS | SHARED_METRICS <= names
    for m in bm["per_layer"]:
        if m["name"] in W1000_METRICS:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "polished_mbp_per_s"
        if m["name"] in SHARED_METRICS:
            # the last until a later PR appended its own cell
            assert CELL in m["workloads"][-2:]
        # set once a job in which a window was rejected: this cell's jobs
        # reject none, so its reader finds nothing to read here
        if m["name"] == "poa_fallback_hidden_share":
            assert CELL not in m["workloads"]
    assert sum(w["chips"] == 4 for w in bm["workloads"][:10]) == 2
    # the nine cells as PR 43 left them, this one the last: a later PR
    # appends its own and moves none
    assert [w["name"] for w in bm["workloads"]][:10] == [
        "ecoli-ont.sam", "ecoli-ont.paf", "chr20-sr.sam",
        "ecoli-ont-x4.sam", "ecoli-frag.paf", "ecoli-ont-x4.paf",
        "ecoli-ont-deep.sam", "lambda-ont.paf", "ecoli-ont-cap.sam", CELL]
    assert [c["name"] for c in bm["configs"]][8] == "lambda-ont-w1000"


def _run_of(jobs):
    return {"jobs": jobs, "facts": {}, "data": {}, "edits": {}, "notes": {},
            "trace": None, "device": None, "peaks": {}}


@limit("served")
def test_every_w1000_metric_reads_a_served_job(served):
    cell = loader.load_cell(CELL)
    registry = reducers.registry()
    jobs = []
    for res in (served[0], served[2]):
        spans = {}
        for e in res["trace_doc"]["traceEvents"]:
            if e.get("ph") == "X":
                spans.setdefault(e["name"], []).append(
                    (e["ts"] * 1000, e["dur"] * 1000))
        jobs.append({"counters": res["counters"], "spans": spans,
                     "phases": res["report_doc"]["phases"],
                     "polished_bp": res["polished_bp"], "wall_s": 1.0})
    values = {m["name"]: registry[m["reducer"]](_run_of(jobs),
                                                **m.get("params", {}))
              for m in cell.per_layer if m["name"] in W1000_METRICS}
    a, b = (j["counters"] for j in jobs)
    rows = a["poa.rows.real"] + b["poa.rows.real"]
    assert values["w1000_poa_upper_rung_window_share"] == pytest.approx(
        100 * b["poa.windows.rung.upper"] / rows)
    assert 0 < values["w1000_poa_upper_rung_window_share"] < 100
    assert values["w1000_poa_beyond_rung_window_share"] == 0
    assert values["w1000_poa_overflow_window_share"] == 0
    assert values["w1000_poa_program16_window_share"] == 0   # a batch of 8
    assert values["w1000_poa_nodes_per_backbone_base"] == pytest.approx(
        a["poa.nodes.used"] / a["poa.backbone.bases"])
    assert 1.3 < values["w1000_poa_nodes_per_backbone_base"] < 3.5
    assert 0 < values["w1000_poa_job_share"] < 100
    assert values["w1000_poa_roofline"] is None         # no device trace


def test_every_metric_of_the_cell_reads_nothing_from_an_older_program():
    """On the parent's program (no `poa.windows.rung.beyond`, and here
    no rung, width or backbone counters and no spans either) a reader
    returns ``None`` or a number; it does not raise."""
    cell = loader.load_cell(CELL)
    registry = reducers.registry()
    job = {"counters": {"poa.windows.d32.c1024": 40, "poa.rows.real": 48,
                        "poa.nodes.used": 130000,
                        "poa.windows.overflow.nodes": 14,
                        "align.cohorts.pairs": 236},
           "spans": {}, "phases": {}, "polished_bp": 47300, "wall_s": 5.0}
    run = _run_of([job, dict(job)])
    for m in cell.per_layer:
        if m["reducer"] == "setup_trace_lower_s":
            continue                     # reads the live process, not run
        value = registry[m["reducer"]](run, **m.get("params", {}))
        assert value is None or isinstance(value, (int, float)), m["name"]
        if m["name"] in W1000_METRICS - {"w1000_poa_overflow_window_share"}:
            assert value is None, m["name"]
    assert registry["counter_family_share"](
        run, "poa.windows.overflow.", "poa.rows.real") == pytest.approx(
            100 * 14 / 48)
