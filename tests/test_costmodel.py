"""Analytic cost model + roofline profiler + bench-history tracker.

Covers the contracts docs/benchmarks.md promises: the mirrored kernel
grid constants stay equal to the ops modules' (the stdlib-only obs
package must never drift from the kernels it models), roofline term
selection, the counter -> prediction pipeline (predict_from_counters /
validate_trace), the bench.py `cost_model` stamp, the bench-history
trend gate (including the synthetic-regression self-test CI runs), the
`span_us` histogram quantiles, and the obs CLI subcommand exit codes.
"""

import json

import pytest

from racon_tpu.obs import __main__ as obs_cli
from racon_tpu.obs import bench_track, costmodel
from racon_tpu.obs.metrics import hist_quantile

CPU = costmodel.PROFILES["cpu-host"]
TPU = costmodel.PROFILES["tpu-v5e"]


# -------------------------------------------- grid-constant parity (ops)

def test_grid_constants_match_ops_modules():
    """costmodel mirrors the kernel grid so it can stay stdlib-only;
    this pin is the only thing keeping the mirror honest."""
    from racon_tpu.ops import align_pallas, poa_driver
    from racon_tpu.ops import poa_pallas_ls

    assert costmodel.DEPTH_BUCKETS == poa_driver.DEPTH_BUCKETS
    assert costmodel.AUDIT_WINDOW_LENGTHS == poa_driver.AUDIT_WINDOW_LENGTHS
    assert costmodel.ALIGN_BUCKETS == tuple(
        (4 * band, band) for band in align_pallas.BANDS)
    assert costmodel.LS_GROUP == poa_pallas_ls.G
    from racon_tpu.ops import encoding
    assert costmodel.ALIGN_ROW_PACK == encoding.PACK
    from racon_tpu import config
    from racon_tpu.ops import band
    assert costmodel.BAND_BUCKETS == band.BAND_BUCKETS
    assert costmodel.BAND_SLACK == int(config.KNOBS[
        "RACON_TPU_BAND_SLACK"].default)
    for bb in (1, 100, 128, 129, 500, 1000, 1024):
        assert costmodel.window_class(bb) == poa_driver.window_class(bb)
    # band_need is the `need` inside align_pallas.band_for: the bucket
    # band_for returns is the smallest BANDS entry covering it (0 = host)
    for n, m in ((700, 660), (1000, 1000), (8000, 7000), (50000, 50000)):
        need = costmodel.band_need(n, m)
        expect = next((b for b in align_pallas.BANDS if need <= b), 0)
        assert align_pallas.band_for(n, m) == expect


# ------------------------------------------------------ closed forms

def test_roofline_picks_the_dominant_term():
    flops_heavy = costmodel.CostEstimate(1e12, 1.0, 1.0)
    s, verdict = costmodel.roofline(flops_heavy, CPU)
    assert verdict == "compute-bound" and s == 1e12 / CPU.peak_flops
    bw_heavy = costmodel.CostEstimate(1.0, 1e12, 1.0)
    assert costmodel.roofline(bw_heavy, CPU)[1] == "bandwidth-bound"
    serial_heavy = costmodel.CostEstimate(1.0, 1.0, 1e9)
    assert costmodel.roofline(serial_heavy, CPU)[1] == "serial-step-bound"


def test_ls_tier_divides_serial_steps_by_pair_and_group():
    """The lockstep kernel retires a rank pair per iteration for a group
    of windows at once; the XLA twin still retires one rank of one
    window per scan step.  The cell work is the same."""
    xla = costmodel.poa_window_cost(32, 512, "xla")
    ls = costmodel.poa_window_cost(32, 512, "ls")
    assert xla.serial_steps == (ls.serial_steps * costmodel.POA_RANK_PACK
                                * costmodel.LS_GROUP)
    assert xla.flops == ls.flops and xla.hbm_bytes == ls.hbm_bytes


def test_row_pack_divides_hirschberg_serial_steps():
    hs = costmodel.align_job_cost(1024, 256)
    assert hs.serial_steps == 4.0 * 1024 / costmodel.ALIGN_ROW_PACK


def test_banded_closed_forms_cut_cells_not_serial_steps():
    """Banding narrows each DP row's live lanes: the cell/FLOP bill
    divides by the band ratio, the latency-chained step count does not."""
    flat = costmodel.align_job_cost(1024, 256)
    nar = costmodel.banded_align_job_cost(1024, 128)
    assert nar.serial_steps == flat.serial_steps
    assert nar.flops * 2 == flat.flops
    assert costmodel.banded_cell_ratio("align", band=256, k=128) == 2.0

    pf = costmodel.poa_window_cost(8, 512, "ls")
    pb = costmodel.banded_poa_window_cost(8, 512, 8, "ls")
    assert pb.serial_steps == pf.serial_steps
    assert pb.hbm_bytes == pf.hbm_bytes      # layers stream in either way
    assert pb.flops == pf.flops * 17 / 512   # 2w+1 live columns
    assert costmodel.banded_cell_ratio("poa", wl_class=512, w=8) == 512 / 17
    # a band wider than the class floors at the flat bill
    wide = costmodel.banded_poa_window_cost(8, 512, 10_000, "ls")
    assert wide.flops == pf.flops
    assert costmodel.banded_cell_ratio("poa", wl_class=512, w=10_000) == 1.0


def test_predict_emits_banded_info_rows_without_double_count():
    counters = {"align.cells.hirschberg": 10_000_000,
                "align.cells.banded": 2_500_000,
                "align.cells.total": 10_000_000,
                "poa.cells.d8.c128": 1024,
                "poa.cells.banded": 400_000,
                "served.consensus.ls": 4}
    pred = costmodel.predict_from_counters(counters, CPU)
    banded = [b for b in pred["buckets"] if b["kind"] == "banded"]
    assert {b["phase"] for b in banded} == {"align", "poa"}
    # info rows only: phase totals must equal the banded-counter-free run
    bare = costmodel.predict_from_counters(
        {k: v for k, v in counters.items() if "banded" not in k}, CPU)
    assert pred["phases"] == bare["phases"]


def test_poa_window_cost_scales_with_depth_and_class():
    small = costmodel.poa_window_cost(8, 128, "ls")
    deep = costmodel.poa_window_cost(32, 128, "ls")
    assert deep.flops == pytest.approx(small.flops * 4)
    wide = costmodel.poa_window_cost(8, 256, "ls")
    assert wide.flops == pytest.approx(small.flops * 4)  # ranks x length


def test_tpu_poa_bucket_is_serial_step_bound():
    """The measured 0.188x story: the rank loop's latency chain, not
    FLOPs, dominates on the TPU profile — the prediction that justifies
    ROADMAP's next optimization target."""
    est = costmodel.poa_window_cost(32, 512, "ls")
    _, verdict = costmodel.roofline(est, TPU)
    assert verdict == "serial-step-bound"


def test_model_rows_cover_the_grid():
    rows = costmodel.model_rows(CPU)
    poa_rows = [r for r in rows if r["kind"] == "poa"]
    classes = {costmodel.window_class(w)
               for w in costmodel.AUDIT_WINDOW_LENGTHS}
    assert len(poa_rows) == (len(costmodel.POA_TIERS)
                             * len(costmodel.DEPTH_BUCKETS) * len(classes))
    align_rows = [r for r in rows if r["kind"] == "align"]
    assert len(align_rows) == len(costmodel.ALIGN_BUCKETS)
    for r in rows:
        assert r["predicted_s"] > 0.0 and r["verdict"].endswith("-bound")
        assert r["predicted_cycles"] == pytest.approx(
            r["predicted_s"] * CPU.clock_hz)


def test_profile_lookup_and_auto_resolution():
    assert costmodel.resolve_profile("auto", "tpu", "TPU v5 lite") is TPU
    assert costmodel.resolve_profile("auto", "cpu") is CPU
    assert costmodel.resolve_profile("auto", None) is CPU
    assert costmodel.resolve_profile("tpu-v5e", "cpu") is TPU
    with pytest.raises(KeyError):
        costmodel.profile("gpu-h100")


# ------------------------------------- counters -> per-phase prediction

def _counters(device=True):
    c = {
        "served.consensus.ls": 90, "served.consensus.host": 10,
        "poa.windows.d32.c512": 100,
        # 100 windows, ~30 admitted layers each, class 512
        "poa.cells.d32.c512": 100 * 30 * 512,
        "served.alignment.hirschberg": 40, "served.alignment.host": 10,
        "align.cells.hirschberg": 40 * 1024 * 256,
        "align.cells.total": 45 * 1024 * 256,
    }
    if not device:
        c["served.consensus.host"] = 100
        del c["served.consensus.ls"]
    return c


def test_predict_from_counters_builds_phases_and_buckets():
    pred = costmodel.predict_from_counters(_counters(), CPU)
    assert set(pred["phases"]) == {"poa", "align"}
    assert pred["phases"]["poa"]["tier"] == "ls"
    assert pred["phases"]["poa"]["predicted_s"] > 0.0
    kinds = {(b["kind"], b.get("tier")) for b in pred["buckets"]}
    assert ("poa", "ls") in kinds and ("align", "hirschberg") in kinds
    poa_b = next(b for b in pred["buckets"] if b["kind"] == "poa")
    # measured steps at growth 1, scaled by NODE_GROWTH ranks, x class
    assert poa_b["cells"] == pytest.approx(
        100 * 30 * 512 * costmodel.NODE_GROWTH * 512)


def test_predict_flags_host_served_alignment():
    c = _counters()
    del c["align.cells.hirschberg"]     # the device aligner served none
    c["align.cells.total"] = 10 ** 9
    pred = costmodel.predict_from_counters(c, CPU)
    assert pred["phases"]["align"]["verdict"] == "host-served"
    assert pred["phases"]["align"]["predicted_s"] == pytest.approx(
        10 ** 9 / CPU.host_align_cells_per_s)


# ------------------------------------------------ trace validation join

def _trace_doc(counters, phase_us, extra_events=(), dropped=0):
    events = [{"name": f"phase.{p}", "ph": "X", "ts": 0, "dur": us,
               "pid": 1, "tid": 1} for p, us in phase_us.items()]
    events += list(extra_events)
    return {"traceEvents": events,
            "otherData": {"dropped_events": dropped, "platform": "cpu"},
            "racon_tpu": {"metrics": {"counters": counters,
                                      "histograms": {}}}}


def test_validate_trace_ok_when_prediction_within_bound():
    pred = costmodel.predict_from_counters(_counters(), CPU)
    phase_us = {p: row["predicted_s"] * 1e6            # measured == predicted
                for p, row in pred["phases"].items()}
    v = costmodel.validate_trace(_trace_doc(_counters(), phase_us), CPU)
    assert v["ok"] is True
    for row in v["phases"].values():
        assert row["within_bound"] is True
        assert row["ratio"] == pytest.approx(1.0)


def test_validate_trace_fails_past_declared_bound():
    pred = costmodel.predict_from_counters(_counters(), CPU)
    wrong = {p: row["predicted_s"] * 1e6 * CPU.error_bound_ratio * 4
             for p, row in pred["phases"].items()}
    v = costmodel.validate_trace(_trace_doc(_counters(), wrong), CPU)
    assert v["ok"] is False
    assert any(r["within_bound"] is False for r in v["phases"].values())


def test_validate_trace_ungated_without_measured_walls():
    # counters but no phase spans: reported, not gated — and vice versa
    v = costmodel.validate_trace(_trace_doc(_counters(), {}), CPU)
    assert v["ok"] is True
    assert all(r["within_bound"] is None for r in v["phases"].values())


def test_validate_trace_joins_bucket_spans():
    ev = [{"name": "poa.bucket", "ph": "X", "ts": 0, "dur": 2_000_000,
           "pid": 1, "tid": 1, "args": {"depth": 32, "wl_class": 512,
                                        "windows": 100}}]
    v = costmodel.validate_trace(
        _trace_doc(_counters(), {}, extra_events=ev), CPU)
    poa_b = next(b for b in v["buckets"] if b["kind"] == "poa")
    assert poa_b["measured_s"] == pytest.approx(2.0)
    assert "error_pct" in poa_b


def test_validate_trace_reports_dropped_events():
    v = costmodel.validate_trace(
        _trace_doc(_counters(), {}, dropped=7), CPU)
    assert v["dropped_events"] == 7
    assert "WARNING" in costmodel.render_validation(v)


# ------------------------------------------------- bench.py cost stamp

def test_bench_cost_model_stamp_joins_report_phase_names():
    pred = costmodel.predict_from_counters(_counters(), CPU)
    pw = {"alignment": pred["phases"]["align"]["predicted_s"],
          "consensus": pred["phases"]["poa"]["predicted_s"],
          "stitch": 0.01}
    cm = costmodel.bench_cost_model({"counters": _counters()}, pw,
                                    "cpu-host")
    assert cm["profile"] == "cpu-host" and cm["ok"] is True
    assert set(cm["phases"]) == {"alignment", "consensus"}
    for row in cm["phases"].values():
        assert row["within_bound"] is True and "error_pct" in row


def test_bench_cost_model_none_when_metrics_disarmed():
    assert costmodel.bench_cost_model(None, {}) is None
    assert costmodel.bench_cost_model({}, {}) is None


# -------------------------------------------------- bench-history gate

def _entry(src, value, vs=0.2, pw=None, **kw):
    e = {"mbp": 0.5, "input": "paf", "profile": "ont", "unit": "Mbp/s",
         "value": value, "vs_baseline": vs, "kernel": "ls",
         "_source": src}
    if pw is not None:
        e["phase_wall"] = pw
    e.update(kw)
    return e


def test_trend_clean_series_has_no_regressions():
    r = bench_track.trend([_entry("a", 0.004), _entry("b", 0.0055)])
    assert r["regressions"] == []
    (s,) = r["series"]
    assert s["n"] == 2 and s["deltas"][0]["value_pct"] > 0


def test_trend_gates_value_drop_past_threshold():
    r = bench_track.trend([_entry("a", 0.01), _entry("b", 0.002)])
    assert len(r["regressions"]) == 1
    assert "value" in r["regressions"][0]
    assert "REGRESSION" in bench_track.render(r)


def test_trend_gates_vs_baseline_and_phase_wall():
    a = _entry("a", 0.01, vs=0.2, pw={"consensus": 1.0})
    b = _entry("b", 0.0099, vs=0.05, pw={"consensus": 2.0})
    r = bench_track.trend([a, b])
    kinds = "\n".join(r["regressions"])
    assert "vs_baseline" in kinds and "phase_wall.consensus" in kinds


def test_trend_min_delta_filters_tiny_phase_growth():
    a = _entry("a", 0.01, pw={"stitch": 0.001})
    b = _entry("b", 0.01, pw={"stitch": 0.010})   # +900% but 9 ms
    assert bench_track.trend([a, b])["regressions"] == []


def test_forced_and_device_entries_never_compared():
    dead = _entry("a", 0.03, vs=None, forced=True)   # CPU rehearsal
    dev = _entry("b", 0.004)            # device run at 13% of host: fine
    r = bench_track.trend([dead, dev])
    assert r["regressions"] == []
    assert len(r["series"]) == 2        # two distinct series


def test_load_history_reads_rounds_and_extras(tmp_path):
    with open(tmp_path / "BENCH_r01.json", "w") as f:
        json.dump({"n": 1, "parsed": _entry("x", 0.01)}, f)
    with open(tmp_path / "BENCH_r02.json", "w") as f:
        json.dump({"n": 2, "parsed": _entry("x", 0.011)}, f)
    extra = tmp_path / "inject.json"
    with open(extra, "w") as f:
        json.dump(_entry("x", 0.001), f)
    entries, problems = bench_track.load_history(str(tmp_path),
                                                 [str(extra)])
    assert problems == []
    # rounds (2) + the injected extra
    assert [e["value"] for e in entries] == [0.01, 0.011, 0.001]
    assert entries[0]["_source"] == "BENCH_r01.json"
    assert all("cost_model" in e for e in entries)   # normalized backfill
    r = bench_track.trend(entries)
    assert any("value" in s for s in r["regressions"])


def test_load_history_flags_unreadable_round(tmp_path):
    with open(tmp_path / "BENCH_r01.json", "w") as f:
        f.write("{broken")
    _, problems = bench_track.load_history(str(tmp_path))
    assert problems and "BENCH_r01.json" in problems[0]


def test_committed_history_is_clean():
    """The repo's own history must pass its own gate (CI runs this as
    `obs bench` too).  The checkout commits no bench rows — the driver's
    PERF_LEDGER.jsonl is the record — so empty is clean."""
    entries, problems = bench_track.load_history()
    assert problems == []
    assert bench_track.trend(entries)["regressions"] == []
    assert obs_cli.main(["bench"]) == 0


# --------------------------------------------------- histogram quantile

def test_hist_quantile_log2_buckets():
    h = {"count": 4, "sum": 1041.0, "max": 1000.0,
         "buckets": {"1": 1, "8": 2, "1024": 1}}
    # the crossing lands halfway into the (4, 8] bucket: interpolated
    # 6.0, where the old estimator snapped to the upper bound (8.0)
    assert hist_quantile(h, 0.5) == 6.0
    assert hist_quantile(h, 0.99) == 1000.0     # clamped to observed max
    assert hist_quantile({"count": 0, "buckets": {}}, 0.5) is None
    assert hist_quantile({}, 0.5) is None


# --------------------------------------------------------- CLI surface

def test_cli_model_json(capsys):
    assert obs_cli.main(["model", "--json", "--window-length", "500"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["profile"] == "cpu-host"
    assert all(r["class"] == 512 for r in out["rows"]
               if r["kind"] == "poa")


def test_cli_model_rejects_unknown_profile(capsys):
    assert obs_cli.main(["model", "--profile", "abacus"]) == 2


def test_cli_validate_exit_codes(tmp_path, capsys):
    assert obs_cli.main(["validate", str(tmp_path / "missing.json")]) == 2

    pred = costmodel.predict_from_counters(_counters(), CPU)
    good = _trace_doc(_counters(),
                      {p: r["predicted_s"] * 1e6
                       for p, r in pred["phases"].items()})
    p_good = tmp_path / "good.json"
    p_good.write_text(json.dumps(good))
    assert obs_cli.main(["validate", "--json", str(p_good)]) == 0
    v = json.loads(capsys.readouterr().out)
    assert v["ok"] is True and v["profile"] == "cpu-host"

    bad = _trace_doc(_counters(),
                     {p: r["predicted_s"] * 1e6 * 100
                      for p, r in pred["phases"].items()})
    p_bad = tmp_path / "bad.json"
    p_bad.write_text(json.dumps(bad))
    assert obs_cli.main(["validate", str(p_bad)]) == 3
    assert "PAST" in capsys.readouterr().out

    p_schema = tmp_path / "schema.json"
    p_schema.write_text(json.dumps(
        {"traceEvents": [{"name": "x", "ph": "QQ"}]}))
    assert obs_cli.main(["validate", str(p_schema)]) == 1


def test_cli_bench_regression_self_test(tmp_path, capsys):
    (tmp_path / "docs").mkdir()
    with open(tmp_path / "BENCH_r01.json", "w") as f:
        json.dump({"parsed": _entry("x", 0.01)}, f)
    with open(tmp_path / "BENCH_r02.json", "w") as f:
        json.dump({"parsed": _entry("x", 0.011)}, f)
    assert obs_cli.main(["bench", "--root", str(tmp_path)]) == 0
    capsys.readouterr()
    inject = tmp_path / "fake_regression.json"
    inject.write_text(json.dumps(_entry("z", 0.001)))
    assert obs_cli.main(["bench", "--root", str(tmp_path),
                         str(inject)]) == 3
    assert "REGRESSION" in capsys.readouterr().out
    assert obs_cli.main(["bench", "--root", str(tmp_path / "empty")]) == 2


def test_cli_legacy_flags_still_dispatch(tmp_path):
    # a trace file literally named "model" must not hijack the
    # subcommand path — subcommand words only dispatch at argv[0]
    p = tmp_path / "t.json"
    p.write_text(json.dumps({"traceEvents": []}))
    assert obs_cli.main(["--validate", str(p)]) == 0


# ------------------------------------------------ serial-step gate (CI)

def test_probe_serial_step_gate(capsys):
    """The dp_cost_probe gate: measured in-loop counts of the compressed
    modes vs their baselines must clear the floors (>= 1.5x serial steps
    for the lockstep POA shape, >= 2x for the packed aligner, >= 3x
    in-loop cells for the two banded pairs)."""
    from racon_tpu.tools import dp_cost_probe

    assert dp_cost_probe.gate()
    out = capsys.readouterr().out
    assert out.count("OK") == 4 and "FAIL" not in out
    assert "poa-ls rank-pair" in out
    assert out.count("in-loop cells") == 2
    assert "measured ratio" in out
