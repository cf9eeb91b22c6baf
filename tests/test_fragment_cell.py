"""Fragment correction (``-f``, kF) as a deployment: the ``ecoli-frag``
generator, the independent reference of what kF must select
(``benchmark/reference_frag.py``) against the host path and against the
device path (interpreted Hirschberg + ``ls``), the served path with the
journal armed, and the cell's benchmark files."""

import hashlib
import json

import pytest

import racon_tpu
from benchmark import generate_frag, loader, prepare, reducers
from benchmark import reference_frag as rf
from racon_tpu import native
from racon_tpu.pipeline import Pipeline

#: the cell's read profile (configs/ecoli-frag.json) at the rehearsal's
#: 16 reads, and a set small enough for the interpreted kernels
PROFILE = dict(coverage=12, mean_read=2760, sub=0.05, ins=0.03, dele=0.03,
               min_overlap=500)
REHEARSAL = dict(PROFILE, reads=16, data_seed=2, layout_seed=22)
TINY = dict(reads=8, coverage=5, mean_read=450, sub=0.03, ins=0.02,
            dele=0.02, min_overlap=200)
KF = dict(fragment_correction=True, match=1, mismatch=-1, gap=-1,
          quality_threshold=10.0, error_threshold=0.3, trim=True)

#: sha256 prefixes of the rehearsal set at seed 0 (the identity
#: relabelling): an edit that moves them has to raise GENERATOR_REV
PINNED = {"reads.fastq": "eb1b3220071dcbaf",
          "draft.fasta": "b59fd5999db6b70b",
          "overlaps.paf": "28f6162a198f9a5e",
          "genome.fasta": "1ec9040475d2af66"}


def _files(d):
    return (str(d / "reads.fastq"), str(d / "overlaps.paf"),
            str(d / "draft.fasta"))


def _digest(path):
    with open(path, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()[:16]


def _counters(trace_path):
    with open(trace_path) as f:
        return json.load(f)["racon_tpu"]["metrics"]["counters"]


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    d = tmp_path_factory.mktemp("frag16")
    return d, generate_frag.mode_frag(str(d), 0, **REHEARSAL)


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    d = tmp_path_factory.mktemp("frag8")
    return d, generate_frag.mode_frag(str(d), 3, **TINY)


# -- the generator ---------------------------------------------------------

def test_generator_bytes_are_pinned(rehearsal):
    d, facts = rehearsal
    assert {f: _digest(d / f) for f in PINNED} == PINNED
    assert facts["reads"] == facts["targets"] == 16
    assert facts["genome_bp"] == 16 * 2760 // 12


def test_reads_and_targets_are_one_set_without_qualities(rehearsal):
    d, facts = rehearsal
    reads = rf.read_sequences(str(d / "reads.fastq"))
    assert reads == rf.read_sequences(str(d / "draft.fasta"))
    assert sum(len(b) for _, b in reads) == facts["read_bases"]
    with open(d / "reads.fastq") as f:
        quals = [line.strip() for i, line in enumerate(f) if i % 4 == 3]
    assert all(set(q) == {"!"} for q in quals)


def test_every_overlap_line_has_its_dual(rehearsal):
    d, facts = rehearsal
    overlaps = rf.read_paf(str(d / "overlaps.paf"))
    assert len(overlaps) == facts["pairs"] > 0
    assert rf.undualled(overlaps) == []
    assert all(o.q != o.t for o in overlaps)
    # grouped by query, as the native parser's per-query groups expect
    order = [o.q for o in overlaps]
    assert [q for i, q in enumerate(order) if i == 0 or order[i - 1] != q] \
        == sorted(set(order), key=order.index)


def test_coordinates_land_within_a_few_bases_of_a_realignment(rehearsal):
    """At the PAF's coordinates the two segments align at about the sum
    of the reads' error rates, and moving an end by a dozen bases either
    way finds nothing better by more than the shift explains."""
    d, _ = rehearsal
    reads = dict(rf.read_sequences(str(d / "reads.fastq")))
    comp = bytes.maketrans(b"ACGT", b"TGCA")
    overlaps = rf.read_paf(str(d / "overlaps.paf"))
    for o in overlaps[::max(1, len(overlaps) // 12)]:
        q = reads[o.q].encode()
        if o.strand:
            q = q.translate(comp)[::-1]
            qb, qe = o.q_len - o.q_end, o.q_len - o.q_begin
        else:
            qb, qe = o.q_begin, o.q_end
        t = reads[o.t].encode()

        def dist(db, de):
            tb, te = max(0, o.t_begin + db), min(o.t_len, o.t_end + de)
            return native.edit_distance(q[qb:qe], t[tb:te])

        here = dist(0, 0)
        assert here < 0.27 * (qe - qb)
        for shift in (-12, 12):
            assert here <= dist(shift, 0) + 4
            assert here <= dist(0, shift) + 4


def test_relabelling_keeps_the_work(rehearsal, tmp_path):
    d, facts = rehearsal
    other = generate_frag.mode_frag(str(tmp_path), 5, **REHEARSAL)
    assert other == facts
    assert _digest(tmp_path / "overlaps.paf") == PINNED["overlaps.paf"]
    a = rf.read_sequences(str(d / "reads.fastq"))
    b = rf.read_sequences(str(tmp_path / "reads.fastq"))
    assert a != b
    # one bijection of the letters per read (the relabelling acts in the
    # genome's orientation, so a reverse-strand read sees its conjugate)
    for (_, x), (_, y) in zip(a, b):
        assert len(x) == len(y)
        pairs = set(zip(x, y))
        assert len(pairs) == len(set(x)) == len({q for _, q in pairs})


def test_truth_records_match_the_targets(rehearsal):
    d, facts = rehearsal
    truth = rf.read_sequences(str(d / "genome.fasta"))
    targets = rf.read_sequences(str(d / "draft.fasta"))
    assert [n for n, _ in truth] == [n for n, _ in targets]
    assert sum(len(b) for _, b in truth) == facts["truth_bp"]
    raw = rf.edits_to_truth(targets, truth)
    for name, bases in targets:
        assert 0.05 * len(bases) < raw[name] < 0.16 * len(bases)
    # the harness's concatenated distance is the sum over reads
    assert native.edit_distance(
        prepare.read_fasta(str(d / "draft.fasta")),
        prepare.read_fasta(str(d / "genome.fasta"))) == sum(raw.values())


def test_generator_refuses_a_stale_revision_and_a_wrong_length(tmp_path):
    with pytest.raises(ValueError, match="generator_rev"):
        generate_frag.mode_frag(str(tmp_path), 0, generator_rev=0,
                                **REHEARSAL)
    with pytest.raises(ValueError, match="genome_mbp"):
        generate_frag.mode_frag(str(tmp_path), 0, genome_mbp=0.01,
                                **REHEARSAL)


# -- the reference against the two paths -----------------------------------

def _expected(d, window_length):
    return rf.expect(*_files(d), window_length=window_length,
                     error_threshold=KF["error_threshold"])


def _check_records(records, want, d):
    """Output records against the reference: which targets, their tags,
    and that correction brought the reads nearer their true bases."""
    tags = [rf.parse_record_name(name) for name, _ in records]
    assert [t["name"] for t in tags] == want.output_targets
    for t, (_, bases) in zip(tags, records):
        assert t["r"] and t["LN"] == len(bases)
        assert t["RC"] == want.coverage[t["name"]]
        assert 0 < t["XC"] <= 1
    truth = rf.read_sequences(str(d / "genome.fasta"))
    raw = rf.edits_to_truth(rf.read_sequences(str(d / "draft.fasta")),
                            truth)
    corrected = rf.edits_to_truth(records, truth)
    assert all(corrected[n] <= raw[n] for n in corrected)
    return sum(raw.values()), sum(corrected.values())


def test_reference_agrees_with_the_host_path(rehearsal, tmp_path):
    d, facts = rehearsal
    want = _expected(d, 500)
    assert want.parsed == len(want.kept) == facts["pairs"]
    assert len(want.targets) == 16

    pl = Pipeline(*_files(d), window_length=500, num_threads=4, **KF)
    pl.initialize()
    infos = [pl.window_info(i) for i in range(pl.num_windows())]
    assert [bb for _, bb, *_ in infos] == want.window_lengths
    # windows of a target are consecutive ranks 0..k-1, targets in order
    assert [rank for _, _, rank, *_ in infos] == [
        k for t in want.targets for k in range(len(want.windows[t]))]

    trace = tmp_path / "trace.json"
    p = racon_tpu.create_polisher(*_files(d), backend="cpu",
                                  window_length=500, num_threads=4,
                                  trace_path=str(trace), **KF)
    p.initialize()
    records = p.polish(True)
    raw, corrected = _check_records(records, want, d)
    assert corrected < 0.2 * raw          # judge.py's quarter rule has room
    c = _counters(trace)
    assert c["overlaps.parsed"] == want.parsed
    assert c["overlaps.kept"] == len(want.kept)
    assert c["polish.targets"] == 16
    assert c["polish.targets.dropped"] == 16 - len(records) == 0


def test_contig_mode_keeps_one_overlap_per_query_where_kf_keeps_all(
        rehearsal, tmp_path):
    """The filter kF skips: with the same files in kC every query keeps
    only its longest overlap."""
    d, _ = rehearsal
    want = _expected(d, 500)
    trace = tmp_path / "trace.json"
    p = racon_tpu.create_polisher(
        *_files(d), backend="cpu", window_length=500, num_threads=4,
        trace_path=str(trace), **dict(KF, fragment_correction=False))
    p.initialize()
    p.polish(False)
    c = _counters(trace)
    assert c["overlaps.parsed"] == want.parsed
    assert c["overlaps.kept"] == len({o.q for o in want.kept})


@pytest.fixture
def interpreted_device(monkeypatch):
    """The tiers the chip runs, interpreted on the CPU."""
    monkeypatch.setenv("RACON_TPU_PALLAS", "1")
    monkeypatch.setenv("RACON_TPU_DEVICE_ALIGNER", "hirschberg")
    monkeypatch.setenv("RACON_TPU_BATCH_WINDOWS", "8")


def test_reference_agrees_with_the_device_path(tiny, tmp_path,
                                               interpreted_device):
    d, facts = tiny
    want = _expected(d, 200)
    trace = tmp_path / "trace.json"
    p = racon_tpu.TpuPolisher(*_files(d), window_length=200, num_threads=2,
                              trace_path=str(trace), **KF)
    p.initialize()
    pl = p._pipeline
    assert [pl.window_info(i)[1] for i in range(pl.num_windows())] \
        == want.window_lengths
    records = p.polish(True)
    raw, corrected = _check_records(records, want, d)
    assert corrected < 0.8 * raw
    phases = p.report.summary()
    assert phases["alignment"]["served"]["hirschberg"] == len(want.kept) \
        == facts["pairs"]
    cons = phases["consensus"]["served"]
    assert cons["ls"] + cons["backbone"] == len(want.window_lengths)
    assert cons["ls"] > 0 and cons["host"] == 0

    c = _counters(trace)
    assert c["overlaps.kept"] == len(want.kept)
    assert c["polish.targets"] == 8 and c["polish.targets.dropped"] == 0
    # run_jobs' cohort accounting: every pair in a cohort, a bucket's
    # last cohort partial
    assert c["align.cohorts.pairs"] == len(want.kept)
    assert c["align.cohorts.capacity"] == 64 * c["align.cohorts"]
    assert 1 <= c["align.buckets"] <= c["align.cohorts"]
    assert 1 <= c["align.cohorts.partial"] <= c["align.buckets"]
    # every read ends in a tail window; those that reach a kernel in a
    # class under the largest are counted
    kernel = sum(v for k, v in c.items() if k.startswith("poa.windows.d"))
    assert kernel == cons["ls"]
    assert 0 < c["poa.windows.tail"] <= len(want.targets)


def test_served_fragment_jobs_are_byte_identical_and_replay_nothing(
        tiny, tmp_path, interpreted_device):
    from racon_tpu.serve.session import JobSpec, PolishSession

    d, _ = tiny
    session = PolishSession(str(tmp_path / "work"), backend="tpu")
    args = dict(KF, window_length=200, num_threads=2)
    results = [session.run_job(JobSpec(*_files(d), args=args, job_id=j))
               for j in ("first", "second")]
    fastas = []
    for res in results:
        assert res["journal_replayed"] == 0
        assert res["records"] == 8
        with open(res["report"]) as f:
            report = json.load(f)
        assert report["phases"]["alignment"]["served"]["hirschberg"] > 0
        assert report["phases"]["consensus"]["served"]["ls"] > 0
        with open(res["output"], "rb") as f:
            fastas.append(f.read())
    assert fastas[0] == fastas[1]
    assert results[1]["kernel_builds"] == 0
    assert all(line.split()[0].endswith("r")
               for line in fastas[0].decode().splitlines()
               if line.startswith(">"))


# -- the cell's files ------------------------------------------------------

NEW_METRICS = {
    "frag_pairs_per_target", "frag_cohort_fill_share",
    "frag_tail_window_share", "frag_stitch_us_per_target",
    "frag_align_device_wait_ms_per_pair", "frag_align_roofline",
    "frag_poa_host_fallback_s_per_mbp"}


def test_the_cell_loads_and_is_the_deployment():
    cell = loader.load_cell("ecoli-frag.paf")   # files agree with entries
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        "ecoli-frag", "ava-paf", 1)
    pa = cell.config["polish_args"]
    assert pa["fragment_correction"] is True and pa["window_length"] == 500
    assert (pa["match"], pa["mismatch"], pa["gap"]) == (1, -1, -1)
    params = prepare.data_params(cell, rehearsal=False)
    assert params["generator"] == "generate_frag:mode_frag"
    assert params["generator_rev"] == generate_frag.GENERATOR_REV
    assert (params["coverage"], params["mean_read"]) == (30, 2760)
    assert 64 <= params["reads"] <= 160
    assert round(params["genome_mbp"] * 1e6) == round(
        params["reads"] * 2760 / 30)
    assert prepare.data_params(cell, rehearsal=True)["reads"] == 16
    expect = cell.workload["expect"]
    assert expect["alignment"] and expect["alignment_tier"] == "hirschberg"
    assert expect["consensus_tier"] == "ls"
    names = {m["name"] for m in cell.per_layer}
    assert NEW_METRICS <= names
    # the new metrics are this cell's only
    bm = loader.load_benchmark()
    for m in bm["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == ["ecoli-frag.paf"]
    cells = {w["name"]: w for w in bm["workloads"]}
    assert cells["ecoli-frag.paf"]["chips"] == 1


def _run(counters, spans, phases):
    job = {"counters": counters, "spans": spans, "phases": phases,
           "polished_bp": 270000, "wall_s": 20.0}
    return {"jobs": [job, dict(job)], "facts": {}, "data": {}, "edits": {},
            "notes": {}, "trace": None, "device": None, "peaks": {}}


def test_new_metrics_read_the_counters_and_spans():
    cell = loader.load_cell("ecoli-frag.paf")
    registry = reducers.registry()
    run = _run(
        {"overlaps.kept": 4904, "polish.targets": 96,
         "align.cohorts.pairs": 4904, "align.cohorts.capacity": 5184,
         "poa.windows.tail": 96, "poa.windows.d32.c512": 489,
         "poa.windows.d32.c256": 96},
        {"phase.stitch": [(0, 4_800_000)], "align.wait": [(0, 9_808_000_000)],
         "poa.host_fallback": [(0, 27_000_000)]},
        {"alignment": {"served": {"hirschberg": 4904}}})
    values = {m["name"]: registry[m["reducer"]](run, **m.get("params", {}))
              for m in cell.per_layer if m["name"] in NEW_METRICS}
    assert values["frag_pairs_per_target"] == pytest.approx(4904 / 96)
    assert values["frag_cohort_fill_share"] == pytest.approx(
        100 * 4904 / 5184)
    assert values["frag_tail_window_share"] == pytest.approx(
        100 * 96 / 585)
    assert values["frag_stitch_us_per_target"] == pytest.approx(50.0)
    assert values["frag_align_device_wait_ms_per_pair"] == pytest.approx(
        2.0)
    assert values["frag_poa_host_fallback_s_per_mbp"] == pytest.approx(0.1)
    assert values["frag_align_roofline"] is None      # no device trace


def test_every_metric_of_the_cell_reads_nothing_from_an_older_program():
    """On a program without this PR's counters (the parent under the
    driver's check) and without a trace, a reader returns ``None`` or a
    number; it does not raise."""
    cell = loader.load_cell("ecoli-frag.paf")
    registry = reducers.registry()
    run = _run({"poa.windows.d32.c512": 320, "poa.rows.real": 383}, {}, {})
    for m in cell.per_layer:
        if m["reducer"] == "setup_trace_lower_s":
            continue                     # reads the live process, not run
        value = registry[m["reducer"]](run, **m.get("params", {}))
        assert value is None or isinstance(value, (int, float)), m["name"]
        if m["name"] in NEW_METRICS - {"frag_poa_host_fallback_s_per_mbp"}:
            assert value is None, m["name"]     # that span predates PR 28


@pytest.mark.parametrize("cell_name", ["ecoli-ont.paf", "ecoli-frag.paf"])
def test_lockstep_fill_share_reads_its_counter_pair(cell_name):
    """``align_lockstep_fill_share`` (PR 29) in both PAF cells: the rows
    the Hirschberg tasks asked for over the sublane-rows their programs
    ran; nothing on a program that does not count them (the parent)."""
    cell = loader.load_cell(cell_name)
    spec, = (m for m in cell.per_layer
             if m["name"] == "align_lockstep_fill_share")
    assert spec["workloads"] == ["ecoli-ont.paf", "ecoli-frag.paf"]
    read = reducers.registry()[spec["reducer"]]
    run = _run({"align.lockstep.rows.real": 9000,
                "align.lockstep.rows.slots": 10000}, {}, {})
    assert read(run, **spec["params"]) == pytest.approx(90.0)
    assert read(_run({"align.tasks.real": 5}, {}, {}),
                **spec["params"]) is None
