"""The bacterial ONT polish at its source's own scale as a deployment:
the files of the cell ``ecoli-ont-full-x4.sam``, the plain reference
``benchmark/reference_full.py`` (groups, launch bounds, a window's
consensus), the consensus launch stream's own counters
(``poa.queue.behind`` / ``.empty``, ``poa.launches.full``,
``job.rss.peak_mb``) and the six ``full_*`` metrics, on a seeded SAM job
of two hundred windows served through ``PolishSession`` on a 4-way mesh
of the suite's virtual devices.

The data is ``benchmark/generate.py``'s ``ont`` mode with the cell's
error mix, data seed and layout seed at a size the XLA twin serves in
seconds (reads of ~0.6 kb, windows of 100 bp, 8 rows a shard); the
cell's own rehearsal (12 reads of ~8 kb, interpreted launches of 128
rows) takes minutes and is run by hand, see the verify skill.
"""

import itertools
import json

import pytest

from benchmark import generate, judge, loader, prepare, reducers
from benchmark import reference_full as rf
from racon_tpu import obs
from racon_tpu.ops import batch_exec, poa_driver, poa_pallas_ls
from racon_tpu.parallel import reset_partitioner

CELL = "ecoli-ont-full-x4.sam"
CONTROL = "ecoli-ont-x4.sam"
FULL_METRICS = ("full_poa_launch_ahead_share", "full_poa_full_launch_share",
                "full_poa_job_share", "full_job_boundary_share",
                "full_poa_roofline", "full_peak_rss_gb")
#: configs/ecoli-ont-full-x4.json's read profile, reads cut to ~0.6 kb
PROFILE = dict(coverage=12, mean_read=600, sub=0.05, ins=0.03, dele=0.03,
               draft_error=0.01, qual_phred=15, formats=("sam",),
               data_seed=2, layout_seed=22)
WINDOW, BATCH, SHARDS = 100, 32, 4
RULES = dict(window_length=WINDOW, quality_threshold=10.0,
             error_threshold=0.3)
SCORES = dict(match=5, mismatch=-4, gap=-8)


# -- the cell's files ------------------------------------------------------

def test_the_cell_loads_and_is_the_uncut_deployment():
    cell = loader.load_cell(CELL)            # files agree with entries
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        "ecoli-ont-full-x4", "sam-4.6mbp", 4)
    control = loader.load_cell(CONTROL)
    # ecoli-ont-x4 with its one cut undone
    for key in ("polish_args", "reads", "guarantees"):
        assert cell.config[key] == control.config[key], key
    assert cell.workload["expect"] == control.workload["expect"]
    assert cell.config["reduced"] == {}
    assert cell.config["genome_mbp"]["source"] == 4.6
    assert cell.config["layout"]["chips"] == 4
    assert "128 rows" in cell.config["layout"]["how"]
    data = prepare.data_params(cell, False)
    assert (data["genome_mbp"], data["data_seed"], data["layout_seed"],
            data["overlaps"], data["formats"]) == (4.6, 2, 22, "sam", ["sam"])
    small = prepare.data_params(control, False)
    assert {k: v for k, v in data.items() if k != "genome_mbp"} \
        == {k: v for k, v in small.items() if k != "genome_mbp"}
    assert prepare.data_params(cell, True) == prepare.data_params(
        control, True)                       # the same rehearsal
    assert cell.traffic["loop"] == "closed" and cell.traffic["clients"] == 1

    bm = loader.load_benchmark()
    entry = {c["name"]: c for c in bm["configs"]}["ecoli-ont-full-x4"]
    assert entry["reduced"] == [] and entry["source"] == cell.config["source"]
    sources = [c["source"] for c in bm["configs"]]
    assert len(set(sources)) == len(sources)   # one source a deployment
    cells = bm["workloads"]
    assert cells[-1]["name"] == CELL and cells[-1]["chips"] == 4
    assert sum(w["chips"] == 4 for w in cells) <= len(cells) // 2
    names = {m["name"] for m in cell.per_layer}
    assert set(FULL_METRICS) <= names
    for m in bm["per_layer"]:
        if m["name"] in FULL_METRICS:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "polished_mbp_per_s"
        elif CONTROL in m.get("workloads", ()):
            # appended to every list that names the control, moving none
            assert m["workloads"][-1] == CELL, m["name"]
    # the three metrics that count may be printed by a CPU rehearsal
    counted = {m["name"] for m in cell.per_layer if judge.is_a_count(m)}
    assert {"full_poa_launch_ahead_share", "full_poa_full_launch_share",
            "poa_pad_row_share", "shard_pad_share"} <= counted


# -- the plain reference: buckets, groups, launch bounds ------------------

def test_the_reference_restates_the_drivers_buckets_and_rows():
    assert rf.DEPTH_BUCKETS == tuple(poa_driver.DEPTH_BUCKETS)
    assert rf.DEPTH_BUCKETS[-1] == poa_driver.DEPTH_CAP
    assert rf.ROWS_PER_SHARD == poa_driver.GROUP_WIDTHS[0] * poa_pallas_ls.G
    assert [rf.depth_bucket(k) for k in (2, 8, 9, 32, 33, 200, 238)] \
        == [8, 8, 32, 32, 200, 200, 200]
    assert rf.groups([500, 500, 500, 260, 500], [1, 2, 30, 40, 400]) \
        == {(8, 512): 1, (32, 512): 1, (200, 384): 1, (200, 512): 1}
    assert rf.sampled_windows(9200, 64)[0] == 0
    assert rf.sampled_windows(9200, 64)[-1] == 9199
    assert len(rf.sampled_windows(9200, 64)) == 64
    assert rf.sampled_windows(3, 64) == [0, 1, 2]


@pytest.mark.parametrize("shards", [1, 4])
@pytest.mark.parametrize("n,launches,full,pad,most", [
    # one group of n windows in launches of 128 rows, nothing climbing;
    # `most`: the launches if some of them run on the upper rung
    (0, 0, 0, 0, 0), (1, 1, 0, 127, 1), (127, 1, 0, 1, 2),
    (128, 1, 1, 0, 2), (129, 2, 1, 127, 2)])
def test_launch_bounds_of_one_group_by_hand(n, shards, launches, full, pad,
                                            most):
    b = rf.launch_bounds({(32, 512): n}, shards, 128 // shards)
    assert b["rows"] == 128 and b["windows"] == n
    assert b["unsplit"] == {"launches": launches, "full": full,
                            "pad_rows": pad}
    assert b["launches"] == (launches, most)
    assert b["pad_rows"] == (launches * 128 - n, most * 128 - n)
    # 128 = 127 + 1 and 129 = 127 + 2 leave no launch full
    assert b["full"] == ((0, full) if n >= 128 else (0, 0))


@pytest.mark.parametrize("sizes", [(3, 5, 2), (1, 1, 1), (4, 8), (7,),
                                   (9, 1, 6)])
def test_launch_bounds_are_tight_over_every_split(sizes):
    """Every way of sending some windows of each depth bucket to the
    class's one upper-rung group, enumerated at 4 rows a launch: the
    bounds are reached and never passed."""
    rows = 4
    buckets = (8, 32, 200)[:len(sizes)]
    b = rf.launch_bounds({(d, 128): n for d, n in zip(buckets, sizes)},
                         2, rows // 2)
    seen = {"launches": set(), "full": set(), "pad_rows": set()}
    for climbers in itertools.product(*(range(n + 1) for n in sizes)):
        parts = [n - u for n, u in zip(sizes, climbers)] + [sum(climbers)]
        launches = sum(-(-p // rows) for p in parts)
        seen["launches"].add(launches)
        seen["full"].add(sum(p // rows for p in parts))
        seen["pad_rows"].add(launches * rows - sum(sizes))
    for key, values in seen.items():
        assert b[key] == (min(values), max(values)), key
    assert b["unsplit"]["launches"] == sum(-(-n // rows) for n in sizes)


def test_classes_do_not_pool():
    # a tail window's class has groups, and a climbers' group, of its own
    b = rf.launch_bounds({(32, 512): 200, (32, 256): 1}, 4)
    assert b["unsplit"] == {"launches": 3, "full": 1, "pad_rows": 183}
    assert b["launches"] == (3, 4)


# -- the executor says what a launch finds queued --------------------------

class _StubOps:
    """The executor's hooks, as far as a healthy chunk calls them."""

    span_name, pack_span, install_span = "t.chunk", "t.pack", "t.install"

    def __init__(self):
        self.seen, self.executor = [], None

    def live_tier(self, ctx, kind):
        return "xla"

    def export(self, ctx, idxs):
        return list(idxs)

    def pack(self, ctx, chunk):
        return (chunk,)

    def dispatch(self, ctx, kind, packed, chunk):
        self.seen.append(self.executor.in_flight())
        return chunk

    def unpack(self, ctx, kind, outs):
        return outs

    def span_args(self, ctx, chunk, pipelined):
        return {}

    def install(self, ctx, kind, sub, results):
        pass


def test_in_flight_is_what_a_dispatch_finds_queued():
    ops = _StubOps()
    ops.executor = ex = batch_exec.BatchExecutor(ops, depth=2)
    for i in range(4):
        ex.submit(None, [i])
    assert ops.seen == [0, 1, 1, 1]     # depth 2: one out while one packs
    assert ex.in_flight() == 1
    ex.flush()
    assert ex.in_flight() == 0
    ex.submit(None, [9])
    assert ops.seen[-1] == 0            # a drained queue is empty again


# -- a served job on the mesh ----------------------------------------------

@pytest.fixture(scope="module")
def dataset(tmp_path_factory):
    """A 20 kb draft at 12x: 200 windows of 100 bp, 3 to ~20 layers."""
    d = tmp_path_factory.mktemp("full")
    facts = generate.mode_ont(str(d), 5, genome_mbp=0.02, **PROFILE)
    return d, facts


def _files(d):
    return (str(d / "reads.fastq"), str(d / "overlaps.sam"),
            str(d / "draft.fasta"))


@pytest.fixture(scope="module")
def served(dataset, tmp_path_factory):
    """The set's job served twice on the 4-way mesh and once on one
    device (the XLA twin, 32 rows a launch), every window's installed
    consensus of the first job kept from before the stitch."""
    from racon_tpu.pipeline import Pipeline
    from racon_tpu.serve.session import JobSpec, PolishSession

    d, _ = dataset
    cell = loader.load_cell(CELL)
    args = dict(cell.config["polish_args"], window_length=WINDOW,
                num_threads=2)
    installed, stitch = [], Pipeline.stitch

    def keep(self, *a, **k):
        if not installed:
            installed.extend(self.get_consensus(i)
                             for i in range(self.num_windows()))
        return stitch(self, *a, **k)

    out = {}
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("RACON_TPU_BATCH_WINDOWS", str(BATCH))
        mp.setattr(Pipeline, "stitch", keep)
        try:
            for mesh, jobs in (("4", ("first", "again")), ("1", ("one",))):
                mp.setenv("RACON_TPU_MESH_SHAPE", mesh)
                reset_partitioner()
                session = PolishSession(
                    str(tmp_path_factory.mktemp(f"work{mesh}")),
                    backend="tpu")
                for job in jobs:
                    res = session.run_job(JobSpec(*_files(d), args=args,
                                                  job_id=job))
                    with open(res["report"]) as f:
                        report = json.load(f)
                    with open(res["output"], "rb") as f:
                        fasta = f.read()
                    out[job] = dict(res, report_doc=report, fasta=fasta)
        finally:
            reset_partitioner()
    return out, installed


def _counters(job):
    return job["report_doc"]["obs"]["metrics"]["counters"]


@pytest.fixture(scope="module")
def reference(dataset):
    d, _ = dataset
    reads, sam, draft = _files(d)
    offers = rf.window_offers(draft, reads, sam, **RULES)
    return offers, rf.groups(offers["bb_len"], offers["offered"])


def test_served_groups_equal_the_references(served, reference):
    jobs, _ = served
    offers, groups = reference
    assert len(offers["bb_len"]) == 200 and len(groups) >= 2
    for name in ("first", "again", "one"):
        c = _counters(jobs[name])
        assert c["poa.windows.rung.upper"] == 0      # nothing climbs here
        counted = {tuple(int(x) for x in k[len("poa.windows.d"):].split(".c")):
                   v for k, v in c.items()
                   if k.startswith("poa.windows.d") and ".c" in k}
        assert counted == groups, name
        cons = jobs[name]["report_doc"]["phases"]["consensus"]
        assert cons["served"]["backbone"] == int((offers["offered"] < 2).sum())
        assert c["poa.rows.real"] == sum(groups.values())


def test_served_launch_stream_lies_inside_the_bounds(served, reference):
    jobs, _ = served
    _, groups = reference
    bounds = rf.launch_bounds(groups, SHARDS, BATCH // SHARDS)
    assert bounds["rows"] == BATCH
    for name in ("first", "again", "one"):
        c = _counters(jobs[name])
        for key, counter in (("launches", "poa.launches"),
                             ("full", "poa.launches.full"),
                             ("pad_rows", "poa.rows.pad")):
            assert bounds[key][0] <= c[counter] <= bounds[key][1], (name, key)
            assert c[counter] == bounds["unsplit"][key], (name, key)
        assert c["poa.launches.full"] >= 2 and c["poa.rows.pad"] > 0
        # every launch went out behind another or found the queue empty,
        # and a job's first finds it empty
        assert c["poa.queue.behind"] + c["poa.queue.empty"] \
            == c["poa.launches"]
        assert c["poa.queue.empty"] >= 1 and c["poa.queue.behind"] >= 1
        assert c["job.rss.peak_mb"] > 50
    c4 = _counters(jobs["first"])
    assert [c4[f"shard.rows.d{i}"] for i in range(SHARDS)] \
        == [c4["poa.launches"] * BATCH // SHARDS] * SHARDS
    assert "shard.rows.d0" not in _counters(jobs["one"])


def test_served_bytes_equal_one_devices_and_a_repeated_jobs(served):
    jobs, _ = served
    assert jobs["first"]["fasta"] == jobs["again"]["fasta"] \
        == jobs["one"]["fasta"]
    assert jobs["again"]["kernel_builds"] == 0
    for job in jobs.values():
        assert job["journal_replayed"] == 0
        assert not job["report_doc"].get("degradations")


def test_sampled_windows_equal_the_plain_engines(served, dataset):
    """A sample spread over the contig, both end windows in it: what the
    device path installed is the plain engine's consensus of the same
    layers byte for byte, windows whose consensus hangs on a tie apart.
    At windows of 100 bp racon's 2 % rule admits layers of five bases,
    on which the plain engine parts from the host engine and the device
    (which agree) by a base at a window's end: one such window in this
    sample, none among the cell's windows of 500 bp (the builder's
    sample on the chip, PERF.md section 6)."""
    from racon_tpu import native

    _, installed = served
    d, _ = dataset
    reads, sam, draft = _files(d)
    windows = rf.sampled_windows(len(installed), 24)
    assert windows[0] == 0 and windows[-1] == len(installed) - 1
    ref = rf.sample_reference(draft, reads, sam, windows, **RULES)
    full = rf.window_offers(draft, reads, sam, **RULES)
    ties, equal, near = 0, 0, []
    for w in windows:
        assert ref.offered[w] == full["offered"][w]   # the sample is whole
        if not rf.tie_free(ref, w, **SCORES):
            ties += 1
            continue
        plain = ref.capped_consensus(w, **SCORES)
        if installed[w] == plain:
            equal += 1
        else:
            near.append(w)
            assert native.edit_distance(installed[w], plain) <= 2, w
    assert equal >= 16 and len(near) <= 2, (equal, ties, near)


# -- the counters on every configuration, and their listing ---------------

def test_the_host_path_counts_its_peak_rss_too(dataset, monkeypatch):
    import racon_tpu

    d, _ = dataset
    monkeypatch.setenv("RACON_TPU_METRICS", "1")
    try:
        p = racon_tpu.create_polisher(
            *_files(d), backend="cpu", window_length=WINDOW,
            quality_threshold=10.0, error_threshold=0.3, match=5,
            mismatch=-4, gap=-8, num_threads=2)
        p.initialize()
        p.polish(True)
        counters = dict(obs.snapshot()["counters"])
    finally:
        obs.reset()
    assert counters["job.rss.peak_mb"] > 50
    assert "poa.launches" not in counters     # no launch, no launch keys


def test_obs_lists_the_launch_stream(served):
    from racon_tpu.obs import __main__ as obs_cli

    jobs, _ = served
    with open(jobs["first"]["trace"]) as f:
        doc = json.load(f)
    text = obs_cli.render(doc, jobs["first"]["trace"])
    assert "the consensus launch stream" in text
    for key in ("poa.launches.full", "poa.queue.behind", "poa.queue.empty",
                "job.rss.peak_mb"):
        assert key in text, key


# -- the six metrics -------------------------------------------------------

def _run(*jobs):
    return {"jobs": list(jobs), "facts": {}, "data": {}, "edits": {},
            "notes": {}, "trace": None, "device": None, "peaks": {}}


def test_full_metrics_read_a_served_jobs_counters_and_spans(served):
    from benchmark import run as bench_run

    jobs, _ = served
    window = [{"wall_s": 1.0, "polished_bp": jobs[j]["polished_bp"],
               **bench_run.job_files(jobs[j])} for j in ("first", "again")]
    cell = loader.load_cell(CELL)
    registry = reducers.registry()
    values = {m["name"]: registry[m["reducer"]](_run(*window),
                                                **m.get("params", {}))
              for m in cell.per_layer if m["name"] in FULL_METRICS}
    assert values.pop("full_poa_roofline") is None     # no device trace
    assert all(isinstance(v, float) for v in values.values()), values
    c = window[0]["counters"]
    assert values["full_poa_launch_ahead_share"] == pytest.approx(
        100 * c["poa.queue.behind"] / c["poa.launches"])
    assert values["full_poa_full_launch_share"] == pytest.approx(
        100 * c["poa.launches.full"] / c["poa.launches"])
    assert 50 < values["full_poa_job_share"] < 100
    assert 0 < values["full_job_boundary_share"] < 50
    assert values["full_peak_rss_gb"] == pytest.approx(
        max(j["counters"]["job.rss.peak_mb"] for j in window) * 2 ** 20 / 1e9)
    # the lists the cell joined read the same job
    pad = {m["name"]: m for m in cell.per_layer}["shard_pad_share"]
    assert registry[pad["reducer"]](_run(*window), **pad["params"]) \
        == pytest.approx(100 * c["shard.pad_rows"] / (
            c["poa.launches"] * BATCH))


def test_full_metrics_read_nothing_from_an_older_program():
    """The parent under the driver's check has the spans and none of the
    three counters: the readers of those return ``None``, none raises."""
    cell = loader.load_cell(CELL)
    registry = reducers.registry()
    job = {"counters": {"poa.launches": 75, "poa.rows.real": 9172,
                        "poa.rows.pad": 428},
           "spans": {}, "phases": {}, "polished_bp": 4600000, "wall_s": 19.0}
    for m in cell.per_layer:
        if m["reducer"] in ("setup_trace_lower_s", "program_cache_hit_share"):
            continue                     # read the live process, not run
        value = registry[m["reducer"]](_run(job), **m.get("params", {}))
        assert value is None or isinstance(value, (int, float)), m["name"]
        if m["name"] in FULL_METRICS:
            assert value is None, m["name"]
