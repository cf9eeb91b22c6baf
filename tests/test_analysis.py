"""Tier-1 coverage for the static-analysis subsystem.

Three contracts:
* the analyzer keeps the real tree clean (this is the CI gate);
* each lint rule fires on its fixture snippet and nowhere else;
* the jaxpr audit enforces the declared recompile budgets — widening
  the audited grid must fail, the shipped grid must pass.
"""

import os
import subprocess
import sys

import pytest

from racon_tpu import config
from racon_tpu.analysis import jaxpr_audit, lint
from racon_tpu.analysis.__main__ import main as analysis_main
from racon_tpu.analysis.rules import ALL_RULES, RULES_BY_ID

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXROOT = os.path.join(REPO, "tests", "analysis_fixtures")

#: rule id -> fixture file carrying exactly that violation class
FIXTURES = {
    "tracer-leak": "racon_tpu/ops/tracer_leak.py",
    "kernel-cache-key": "racon_tpu/ops/cache_key.py",
    "env-registry": "racon_tpu/ops/env_read.py",
    "fault-point": "racon_tpu/ops/bad_fault_point.py",
    "device-except": "racon_tpu/ops/broad_except.py",
    "wall-clock": "racon_tpu/resilience/wall_clock.py",
    "thread-discipline": "racon_tpu/serve/bad_threads.py",
}

#: per-file rules (knob-docs is project-level; covered separately)
_FILE_RULES = [r for r in ALL_RULES if r.id != "knob-docs"]


# -------------------------------------------------------------------------
# AST lint: fixtures fire, real tree clean
# -------------------------------------------------------------------------

@pytest.mark.parametrize("rule_id,rel", sorted(FIXTURES.items()))
def test_each_rule_fires_exactly_on_its_fixture(rule_id, rel):
    vs = lint.run_lint(FIXROOT, paths=[rel], rules=_FILE_RULES)
    assert vs, f"{rule_id} did not fire on {rel}"
    assert {v.rule for v in vs} == {rule_id}, (
        f"unexpected rules on {rel}: {[v.render() for v in vs]}")
    assert all(v.path == rel for v in vs)


def test_tracer_leak_fixture_catches_every_flavor():
    vs = lint.run_lint(FIXROOT, paths=[FIXTURES["tracer-leak"]],
                       rules=[RULES_BY_ID["tracer-leak"]])
    text = " ".join(v.message for v in vs)
    for flavor in ("float()", ".item()", "np.asarray", "data-dependent"):
        assert flavor in text, f"missing {flavor}: {text}"


def test_device_except_fixture_catches_bare_and_broad():
    vs = lint.run_lint(FIXROOT, paths=[FIXTURES["device-except"]],
                       rules=[RULES_BY_ID["device-except"]])
    assert len(vs) == 2
    assert any("bare" in v.message for v in vs)
    assert any("BLE001" in v.message for v in vs)


def test_wall_clock_rule_scopes_obs_package():
    # the tracer's monotonic-clock contract: racon_tpu/obs/ is inside
    # the wall-clock scope, so a time.time() span there is a violation
    rel = "racon_tpu/obs/wall_clock_obs.py"
    vs = lint.run_lint(FIXROOT, paths=[rel],
                       rules=[RULES_BY_ID["wall-clock"]])
    assert vs and {v.rule for v in vs} == {"wall-clock"}
    assert all(v.path == rel for v in vs)


def test_knob_docs_rule_fires_when_readme_lacks_knobs():
    # The fixture root's README documents no knobs, so every registered
    # knob is reported undocumented.
    vs = lint.run_lint(FIXROOT, paths=[], rules=[RULES_BY_ID["knob-docs"]])
    assert {v.rule for v in vs} == {"knob-docs"}
    assert len(vs) == len(config.KNOBS)


def test_real_tree_is_clean():
    vs = lint.run_lint(REPO)
    assert vs == [], "\n".join(v.render() for v in vs)


def test_inline_suppression(tmp_path):
    (tmp_path / "snippet.py").write_text(
        "try:\n"
        "    pass\n"
        "except:  # lint: disable=device-except\n"
        "    pass\n")
    rule = [RULES_BY_ID["device-except"]]
    assert lint.run_lint(str(tmp_path), paths=["snippet.py"],
                         rules=rule) == []
    (tmp_path / "snippet.py").write_text(
        "try:\n    pass\nexcept:\n    pass\n")
    assert len(lint.run_lint(str(tmp_path), paths=["snippet.py"],
                             rules=rule)) == 1


# -------------------------------------------------------------------------
# CLI: exit codes + baseline round-trip
# -------------------------------------------------------------------------

def test_cli_exit_zero_on_repo():
    assert analysis_main(["--no-jaxpr", "--repo-root", REPO]) == 0


def test_cli_exit_nonzero_on_fixture_tree():
    assert analysis_main(["--no-jaxpr", "--repo-root", FIXROOT]) == 1


def test_cli_baseline_roundtrip(tmp_path):
    base = str(tmp_path / "baseline.json")
    # accept the fixture tree's violations, then a re-run is clean
    assert analysis_main(["--no-jaxpr", "--repo-root", FIXROOT,
                          "--baseline", base, "--write-baseline"]) == 0
    assert analysis_main(["--no-jaxpr", "--repo-root", FIXROOT,
                          "--baseline", base]) == 0


def test_cli_list_rules(capsys):
    assert analysis_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rid in list(FIXTURES) + ["knob-docs", "recompile-budget",
                                 "jaxpr-forbidden-primitive"]:
        assert rid in out


def test_cli_subprocess_full_run():
    """The acceptance gate: `python -m racon_tpu.analysis` (both
    engines) exits 0 on the repo."""
    proc = subprocess.run(
        [sys.executable, "-m", "racon_tpu.analysis"],
        cwd=REPO, capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stdout + proc.stderr


# -------------------------------------------------------------------------
# jaxpr audit: shipped grid within budget, widened grid rejected
# -------------------------------------------------------------------------

def test_audit_shipped_grids_pass():
    assert jaxpr_audit.run_audit() == []


def test_audit_fails_on_widened_poa_grid():
    vs = jaxpr_audit.audit_poa(window_lengths=(500, 1000, 1500))
    assert any(v.rule == "recompile-budget" for v in vs), \
        [v.render() for v in vs]


def test_audit_flags_forbidden_primitive():
    import jax

    def cb(x):
        return jax.pure_callback(
            lambda v: v, jax.ShapeDtypeStruct(x.shape, x.dtype), x)

    closed = jax.make_jaxpr(cb)(
        jax.ShapeDtypeStruct((4,), "float32"))
    vs = jaxpr_audit.check_jaxpr(closed, "x.py", "cb")
    assert any(v.rule == "jaxpr-forbidden-primitive" for v in vs)


def test_audit_flags_float64():
    import jax
    import jax.numpy as jnp

    def f64(x):
        return x.astype(jnp.float64) * 2

    with jax.enable_x64(True):
        closed = jax.make_jaxpr(f64)(
            jax.ShapeDtypeStruct((4,), "float32"))
    vs = jaxpr_audit.check_jaxpr(closed, "x.py", "f64")
    assert any(v.rule == "jaxpr-float64" for v in vs)


# -------------------------------------------------------------------------
# stale-knob surfacing (satellite: typo'd knobs must not vanish)
# -------------------------------------------------------------------------

def test_unknown_env_knobs_detects_typos():
    env = {"RACON_TPU_BOGUS_KNOB": "1", "RACON_TPU_PALLAS": "1",
           "HOME": "/root"}
    assert config.unknown_env_knobs(env) == ["RACON_TPU_BOGUS_KNOB"]
    assert config.unknown_env_knobs({"RACON_TPU_PALLAS": "1"}) == []


@pytest.mark.parametrize("name", [
    "RACON_TPU_POA_KERNEL", "RACON_TPU_POA_COLSTEP", "RACON_TPU_ALIGN_PACK",
    "RACON_TPU_COST_MODEL"])
def test_removed_knobs_are_reported(tmp_path, monkeypatch, capsys, name):
    """The knobs PR 31 removed (a selector with one tier left to select,
    two kill switches every use passed one value to, a stamp nothing
    read): one that is still set is reported as unknown, in the
    registry's check and on the CLI's warning line, and changes
    nothing."""
    from racon_tpu import cli

    assert name not in config.KNOBS
    assert config.unknown_env_knobs({name: "0", "RACON_TPU_PALLAS": "1"}) \
        == [name]

    target = "ACGT" * 30
    (tmp_path / "t.fasta").write_text(f">t\n{target}\n")
    (tmp_path / "r.fasta").write_text(
        "".join(f">r{i}\n{target}\n" for i in range(3)))
    (tmp_path / "o.sam").write_text("@HD\tVN:1.6\n" + "".join(
        f"r{i}\t0\tt\t1\t60\t{len(target)}M\t*\t0\t0\t{target}\t*\n"
        for i in range(3)))
    monkeypatch.setenv(name, "0")
    assert cli.main([str(tmp_path / "r.fasta"), str(tmp_path / "o.sam"),
                     str(tmp_path / "t.fasta")]) == 0
    out, err = capsys.readouterr()
    assert target in out
    assert (f"unknown RACON_TPU_* environment variable(s) ignored: {name} "
            in err)


def test_run_report_surfaces_stale_knobs(monkeypatch):
    from racon_tpu.resilience.report import RunReport

    monkeypatch.setenv("RACON_TPU_TYPOD_KNOB", "1")
    rep = RunReport().finalize()
    assert "RACON_TPU_TYPOD_KNOB" in rep.as_dict()["unknown_knobs"]
    assert "RACON_TPU_TYPOD_KNOB" in rep.summary()["unknown_knobs"]

    monkeypatch.delenv("RACON_TPU_TYPOD_KNOB")
    rep = RunReport().finalize()
    assert rep.as_dict()["unknown_knobs"] == []
    assert "unknown_knobs" not in rep.summary()
