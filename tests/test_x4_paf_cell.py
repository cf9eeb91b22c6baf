"""Contig polishing from PAF on a four-chip host as a deployment: the
Hirschberg launches over a 4-way mesh (the suite's virtual devices,
``RACON_TPU_MESH_SHAPE=4``, interpreted kernels) against the plain
reference ``benchmark/reference_align.py``, against the same job on one
device and against the host oracle; the ``align.mesh.*`` counters; and
the files of the cell ``ecoli-ont-x4.paf``.

The data is ``benchmark/generate.py``'s ``ont`` mode with the cell's
error mix, data seed and layout seed, at a size the interpreted kernels
finish in seconds (reads of ~0.6 kb; the cell's rehearsal, 12 reads of
~8 kb, takes minutes and is run by hand, see the verify skill).
"""

import hashlib
import json

import numpy as np
import pytest

from benchmark import (generate, judge, loader, prepare, reducers,
                       reference_frag)
from benchmark import reference_align as ra
from racon_tpu import native, obs
from racon_tpu.ops import align_pallas
from racon_tpu.ops.encoding import encode
from racon_tpu.parallel import reset_partitioner
from tests.test_align_hirschberg import _FakePipe

CELL = "ecoli-ont-x4.paf"
#: configs/ecoli-ont-x4-paf.json's read profile, reads cut to ~0.6 kb
PROFILE = dict(coverage=10, mean_read=600, sub=0.05, ins=0.03, dele=0.03,
               draft_error=0.01, qual_phred=15, formats=("paf",),
               data_seed=2, layout_seed=22)
NEW_METRICS = {
    "x4_align_sharded_launch_share", "x4_align_mesh_pad_share",
    "x4_align_short_program_share", "x4_align_lockstep_fill_share",
    "x4_align_launch_ahead_share", "x4_align_device_wait_ms_per_pair",
    "x4_align_s_per_mbp", "x4_align_host_in_cohort_share",
    "x4_align_roofline"}
_COMP = bytes.maketrans(b"ACGT", b"TGCA")


def _fresh_builders():
    # the jitted kernels are memoized by batch and keep the mesh they
    # were built under
    align_pallas._build_edge_kernel.cache_clear()
    align_pallas._build_base_kernel.cache_clear()
    reset_partitioner()


@pytest.fixture
def mesh(monkeypatch):
    """``mesh(n)``: the partitioner over the first ``n`` virtual
    devices, the kernel builders fresh."""
    def set_mesh(n):
        monkeypatch.setenv("RACON_TPU_MESH_SHAPE", str(n))
        _fresh_builders()
    yield set_mesh
    _fresh_builders()


@pytest.fixture
def armed():
    obs.reset()
    obs.configure(metrics=True)
    yield
    obs.reset()


@pytest.fixture(scope="module")
def dataset(tmp_path_factory):
    """A 6 kb draft at 10x: ~100 read-to-contig pairs of 0.5-1.5 kb."""
    d = tmp_path_factory.mktemp("x4paf")
    facts = generate.mode_ont(str(d), 5, genome_mbp=0.006, **PROFILE)
    return d, facts


def _pairs(d):
    """(query, target span) byte pairs of the set's PAF, the query in
    the target's orientation: what ``Pipeline.align_job`` hands out."""
    reads = {name: bases.encode() for name, bases
             in reference_frag.read_sequences(str(d / "reads.fastq"))}
    draft = prepare.read_fasta(str(d / "draft.fasta"))
    out = []
    with open(d / "overlaps.paf") as f:
        for line in f:
            c = line.split("\t")
            q = reads[c[0]]
            if c[4] == "-":
                q = q.translate(_COMP)[::-1]
            out.append((q, draft[int(c[7]):int(c[8])]))
    return out


def _enc(pairs):
    return [tuple(encode(np.frombuffer(s, np.uint8)).astype(np.int32)
                  for s in pair) for pair in pairs]


def _launches():
    return [e["args"] for e in obs.tracer().events()
            if e["ph"] == "X" and e["name"] == "align.dispatch"]


# -- (a) mesh-path CIGARs against the plain reference ----------------------

def test_reference_distance_is_the_native_one(dataset):
    pairs = _pairs(dataset[0])[:12]
    assert [ra.edit_distance(q, t) for q, t in pairs] \
        == [native.edit_distance(q, t) for q, t in pairs]
    q, t = b"ACGTACGT", b"ACGACGTT"
    assert ra.edit_distance(q, t) == 2 and ra.edit_distance(b"", t) == 8
    assert ra.check_cigar("3M1I4M1D", q, t) == []
    assert "optimum" in ra.check_cigar("8M", q, t)[0]
    assert "consumes" in ra.check_cigar("7M", q, t)[0]
    with pytest.raises(ValueError):
        ra.cigar_cost("8Q", q, t)


@pytest.mark.parametrize("n_pairs,share", [
    (5, "short"), (20, "one_program"), (40, "several_programs")])
def test_mesh_cigars_are_valid_and_optimal(dataset, mesh, armed, n_pairs,
                                           share):
    """Launch sizes that give a shard fewer than GROUP rows (one program
    with idle sublanes), exactly GROUP, and several programs: every
    pair's CIGAR consumes both sequences and costs the reference's
    distance, wherever `_deal_programs` sent the pair."""
    mesh(4)
    pairs = _pairs(dataset[0])[:n_pairs]
    results = align_pallas.align_pairs(_enc(pairs), interpret=True)
    shares = {a["B"] // a["shards"] for a in _launches()}
    assert {a["shards"] for a in _launches()} == {4}
    G = align_pallas.GROUP
    assert {"short": min(shares) < G, "one_program": G in shares,
            "several_programs": max(shares) > G}[share], shares
    for (q, t), ops in zip(pairs, results):
        assert ops is not None
        assert ra.check_cigar(align_pallas.ops_to_cigar(ops), q, t) == []


# -- (c) two cohorts in flight on the mesh ---------------------------------

def test_two_cohorts_on_the_mesh_install_what_one_device_blocking_gave(
        dataset, mesh, armed):
    from racon_tpu.resilience.report import PhaseReport

    pairs = _pairs(dataset[0])[:18]
    mesh(1)
    want = [align_pallas.ops_to_cigar(r) for r in align_pallas.align_pairs(
        _enc(pairs), interpret=True)]
    assert obs.snapshot()["counters"].get(
        "align.mesh.launches.sharded", 0) == 0
    mesh(4)
    pipe = _FakePipe(pairs)
    rep = PhaseReport("alignment", ("hirschberg", "host"))
    served = align_pallas.run_jobs(pipe, list(range(len(pairs))), cohort=6,
                                   report=rep)
    assert served == len(pairs) and rep.retries == rep.bisections == 0
    assert [pipe.cigars[i] for i in range(len(pairs))] == want
    c = obs.snapshot()["counters"]
    assert c["align.cohorts"] >= 3 and c["align.queue.behind"] > 0
    # the report's tally is the counters'
    kern = rep.as_dict()["extra"]["kernels"]
    assert kern["launches_sharded"] == c["align.mesh.launches.sharded"] > 0
    assert kern["launches_single"] == 0


# -- (d) the counters add up -----------------------------------------------

def test_mesh_counters_add_up(dataset, mesh, armed):
    mesh(4)
    pairs = _pairs(dataset[0])[:12]
    align_pallas.align_pairs(_enc(pairs), interpret=True)
    c = obs.snapshot()["counters"]
    launches = _launches()
    assert c["align.mesh.launches.sharded"] \
        + c.get("align.mesh.launches.single", 0) \
        == c["align.launches.edge"] + c["align.launches.base"] \
        == len(launches)
    # alignment's own rows are the rows every shard got (nothing else
    # ran: shard.rows.d<i> hold consensus rows too in a job)
    rows = c["align.mesh.rows.real"] + c["align.mesh.rows.pad"]
    assert rows == 4 * c["shard.rows.d0"] == sum(a["B"] for a in launches)
    assert all(c[f"shard.rows.d{i}"] == c["shard.rows.d0"]
               for i in range(4))
    assert c["align.mesh.rows.real"] == c["align.tasks.real"]
    assert c["align.mesh.rows.pad"] == c["align.tasks.pad"] \
        == c["shard.pad_rows"]
    G = align_pallas.GROUP
    assert c["align.mesh.programs.whole"] == sum(
        a["B"] // G for a in launches if a["B"] // 4 >= G)
    assert c["align.mesh.programs.short"] == sum(
        4 for a in launches if a["B"] // 4 < G)


def test_one_device_counts_no_sharded_launch(dataset, mesh, armed):
    mesh(1)
    pairs = _pairs(dataset[0])[:5]
    align_pallas.align_pairs(_enc(pairs), interpret=True)
    c = obs.snapshot()["counters"]
    assert c["align.mesh.launches.single"] \
        == c["align.launches.edge"] + c["align.launches.base"]
    assert not [k for k in c if k.startswith(("align.mesh.rows.",
                                              "align.mesh.programs.",
                                              "shard."))]
    assert "align.mesh.launches.sharded" not in c
    assert {a["shards"] for a in _launches()} == {1}


# -- (b) a served PAF job: four shards against one device and the host -----

def _serve(work, d, job_id):
    from racon_tpu.serve.session import JobSpec, PolishSession

    cell = loader.load_cell(CELL)
    args = dict(cell.config["polish_args"], window_length=200,
                num_threads=2)
    session = PolishSession(str(work), backend="tpu")
    res = session.run_job(JobSpec(
        str(d / "reads.fastq"), str(d / "overlaps.paf"),
        str(d / "draft.fasta"), args=args, job_id=job_id))
    with open(res["report"]) as f:
        report = json.load(f)
    with open(res["output"], "rb") as f:
        fasta = f.read()
    return args, res, report, fasta


@pytest.fixture(scope="module")
def served_on_the_mesh(dataset, tmp_path_factory):
    """The set's PAF job served once on one device and once on the 4-way
    mesh, by the tiers the chip runs, interpreted."""
    d, _ = dataset
    work = tmp_path_factory.mktemp("served")
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("RACON_TPU_PALLAS", "1")
        mp.setenv("RACON_TPU_DEVICE_ALIGNER", "hirschberg")
        mp.setenv("RACON_TPU_BATCH_WINDOWS", "8")
        try:
            mp.setenv("RACON_TPU_MESH_SHAPE", "1")
            _fresh_builders()
            _, _, one_report, one_fasta = _serve(work / "one", d, "one")
            mp.setenv("RACON_TPU_MESH_SHAPE", "4")
            _fresh_builders()
            args, res, report, fasta = _serve(work / "four", d, "four")
        finally:
            _fresh_builders()
    return d, args, res, report, fasta, one_report, one_fasta


def test_served_job_on_the_mesh_equals_one_device_and_the_host(
        served_on_the_mesh):
    d, args, res, report, fasta, one_report, one_fasta = served_on_the_mesh
    assert hashlib.sha256(fasta).hexdigest() \
        == hashlib.sha256(one_fasta).hexdigest()
    four, one = (r["phases"]["alignment"] for r in (report, one_report))
    assert four["served"] == one["served"]
    assert four["served"]["hirschberg"] >= 0.97 * four["total"]
    assert four["extra"]["kernels"]["shards"] == 4
    assert four["extra"]["kernels"]["launches_sharded"] > 0
    assert four["extra"]["kernels"]["launches_single"] == 0
    assert one["extra"]["kernels"]["launches_sharded"] == 0
    assert one["extra"]["kernels"]["launches_single"] \
        == four["extra"]["kernels"]["launches_sharded"]
    assert not report.get("degradations") and res["journal_replayed"] == 0
    counters = report["obs"]["metrics"]["counters"]
    assert counters["align.tasks.real"] \
        == one_report["obs"]["metrics"]["counters"]["align.tasks.real"]

    # the cell's accuracy rule beside the host oracle, as run.py applies
    # it; at 6 kb the contig ends weigh too much for the quarter-of-the-
    # draft half of the rule (paf-0.1mbp's size_note), which is left out
    params = {"overlaps": "paf"}
    oracle, _ = prepare.ensure_oracle(str(d), params, args, timed=False)
    truth = prepare.read_fasta(str(d / "genome.fasta"))
    edits = {name: native.edit_distance(seq, truth) for name, seq in (
        ("draft", prepare.read_fasta(str(d / "draft.fasta"))),
        ("host", prepare.read_fasta(oracle)),
        ("device", b"".join(fasta.split(b"\n")[1::2])))}
    at_most, _ = judge.accuracy_limits(edits["draft"], edits["host"],
                                       len(truth))
    assert edits["device"] <= at_most, edits
    assert edits["device"] < edits["draft"], edits


# -- (e) the cell's files --------------------------------------------------

def test_the_cell_loads_and_is_the_deployment():
    cell = loader.load_cell(CELL)            # files agree with entries
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        "ecoli-ont-x4-paf", "paf-0.1mbp", 4)
    one = loader.load_cell("ecoli-ont.paf")
    # byte for byte the one-chip cell's input and arguments
    assert cell.config["polish_args"] == one.config["polish_args"]
    assert prepare.data_params(cell, False) == prepare.data_params(one, False)
    assert prepare.data_params(cell, True) == prepare.data_params(one, True)
    assert cell.workload["expect"] == one.workload["expect"]
    assert cell.config["layout"]["chips"] == 4
    assert list(cell.config["reduced"]) == ["genome_mbp"]
    assert any("byte for byte" in g for g in cell.config["guarantees"])
    assert NEW_METRICS <= {m["name"] for m in cell.per_layer}
    bm = loader.load_benchmark()
    for m in bm["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL]
    cells = {w["name"]: w for w in bm["workloads"]}
    assert cells[CELL]["chips"] == cells["ecoli-ont-x4.sam"]["chips"] == 4
    sources = [c["source"] for c in bm["configs"]]
    assert len(set(sources)) == len(sources)   # one source a deployment


def _run(job):
    return {"jobs": [job, dict(job)], "facts": {}, "data": {}, "edits": {},
            "notes": {}, "trace": None, "device": None, "peaks": {}}


def test_new_metrics_read_a_served_jobs_counters_and_spans(
        served_on_the_mesh):
    from benchmark import run as bench_run

    _, _, res, *_ = served_on_the_mesh
    job = {"wall_s": 1.0, "polished_bp": res["polished_bp"],
           **bench_run.job_files(res)}
    cell = loader.load_cell(CELL)
    registry = reducers.registry()
    values = {m["name"]: registry[m["reducer"]](_run(job),
                                                **m.get("params", {}))
              for m in cell.per_layer if m["name"] in NEW_METRICS}
    assert values.pop("x4_align_roofline") is None     # no device trace
    assert all(isinstance(v, float) for v in values.values()), values
    c = job["counters"]
    assert values["x4_align_sharded_launch_share"] == 100.0
    assert values["x4_align_mesh_pad_share"] == pytest.approx(
        100 * c["align.tasks.pad"]
        / (c["align.tasks.real"] + c["align.tasks.pad"]))
    assert 0 < values["x4_align_short_program_share"] < 100
    assert 0 < values["x4_align_lockstep_fill_share"] < 100


def test_new_metrics_read_nothing_from_an_older_program():
    """The parent under the driver's check has the spans and none of the
    ``align.mesh.*`` counters: the three readers of those return
    ``None``, none raises."""
    cell = loader.load_cell(CELL)
    registry = reducers.registry()
    job = {"counters": {"align.launches.edge": 260, "align.tasks.real": 9},
           "spans": {}, "phases": {}, "polished_bp": 100000, "wall_s": 5.0}
    for m in cell.per_layer:
        if m["reducer"] == "setup_trace_lower_s":
            continue                     # reads the live process, not run
        value = registry[m["reducer"]](_run(job), **m.get("params", {}))
        assert value is None or isinstance(value, (int, float)), m["name"]
        if m["name"] in {"x4_align_sharded_launch_share",
                         "x4_align_mesh_pad_share",
                         "x4_align_short_program_share"}:
            assert value is None, m["name"]
