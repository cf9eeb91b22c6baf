"""Bring-up contract: ``--tpu`` means a TPU, the report says where it ran,
there is one compile cache, and ``chip_smoke.py`` cannot pass off the chip.

The sandbox has libtpu but no chip, so a child started *without*
``JAX_PLATFORMS`` is the real thing these guard against: a JAX that falls
back to the CPU on its own (a log line, exit 0).
"""

import json
import os
import subprocess
import sys

import pytest

import racon_tpu
from racon_tpu import device
from racon_tpu.obs import costmodel

from tests.test_faults import _ARGS, _tpu_run, _write_dataset

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402 — repo-root script


def _child(argv, env_drop=(), env_set=None, cwd=ROOT, timeout=300):
    env = {k: v for k, v in os.environ.items() if k not in env_drop}
    env.pop("XLA_FLAGS", None)
    env.update(env_set or {})
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (ROOT, env.get("PYTHONPATH")) if p)
    return subprocess.run(argv, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=timeout)


# -- --tpu means a TPU -----------------------------------------------------

def test_tpu_backend_on_a_cpu_that_was_not_asked_for_is_an_error():
    r = _child([sys.executable, "-m", "racon_tpu.cli", "--tpu",
                "r.fastq", "o.paf", "t.fasta"], env_drop=("JAX_PLATFORMS",))
    assert r.returncode == 1 and r.stdout == ""
    last = r.stderr.strip().splitlines()[-1]
    assert "backend 'tpu' needs a TPU" in last and "Traceback" not in r.stderr


def test_tpu_backend_runs_on_a_cpu_asked_for_by_name():
    assert device.cpu_requested()
    assert device.require_tpu()["platform"] == "cpu"


def test_serve_daemon_and_workers_refuse_without_a_tpu(tmp_path,
                                                       monkeypatch):
    from racon_tpu.distrib import Coordinator
    from racon_tpu.fleet.plane import FleetPlane
    from racon_tpu.serve.server import ServeDaemon

    # two device workers on a CPU asked for by name hold no chip
    FleetPlane(str(tmp_path / "ok"), min_workers=0, max_workers=2,
               backend="tpu")
    monkeypatch.setattr(device, "cpu_requested", lambda: False)
    with pytest.raises(device.DeviceUnavailable, match="needs a TPU"):
        ServeDaemon(str(tmp_path / "serve"), backend="tpu", port=0)
    with pytest.raises(device.DeviceUnavailable, match="claims every"):
        FleetPlane(str(tmp_path / "plane"), min_workers=0, max_workers=2,
                   backend="tpu")
    with pytest.raises(device.DeviceUnavailable, match="claims every"):
        Coordinator("r.fastq", "o.paf", "t.fasta", str(tmp_path / "d"),
                    backend="tpu", workers=2)
    # one device worker, or any number of host workers, is fine
    device.check_device_workers(1, "x")
    Coordinator("r.fastq", "o.paf", "t.fasta", str(tmp_path / "h"),
                backend="cpu", workers=3)


def test_bench_exits_nonzero_without_a_chip():
    r = _child([sys.executable, "bench.py"])
    assert r.returncode != 0 and r.stdout.strip() == ""
    assert "no TPU" in r.stderr


# -- the report says where it ran ------------------------------------------

def test_report_carries_device_identity_and_kernel_geometry(tmp_path,
                                                            monkeypatch):
    _, p = _tpu_run(_write_dataset(tmp_path), monkeypatch, {})
    d = p.report.as_dict()
    assert d["device"] == {"platform": "cpu", "device_kind": "cpu",
                           "count": 8}
    assert d["jax_cache"]["dir"] == os.environ["JAX_COMPILATION_CACHE_DIR"]
    assert set(d["jax_cache"]) >= {"requests", "hits", "misses",
                                   "compile_s"}
    # the XLA twin: compiled, not interpreted Pallas
    assert d["phases"]["consensus"]["extra"]["kernels"] == {
        "interpreted": False, "batch": 8, "shards": 8}
    host = racon_tpu.create_polisher(*_write_dataset(tmp_path),
                                     backend="cpu", **_ARGS)
    host.initialize()
    host.polish(True)
    hd = host.report.as_dict()
    assert hd["device"] is None and "jax_cache" not in hd


def test_warm_up_demotions_land_in_the_run_report(tmp_path, monkeypatch):
    from racon_tpu.ops import poa_driver

    paths = _write_dataset(tmp_path)
    for k, v in {"RACON_TPU_PALLAS": "1",
                 "RACON_TPU_BATCH_WINDOWS": "8"}.items():
        monkeypatch.setenv(k, v)
    cfg = poa_driver.make_config(128, 8, 5, -4, -8)
    monkeypatch.setitem(poa_driver._WARM_DEAD, (cfg, "ls"),
                        RuntimeError("mosaic said no"))
    p = racon_tpu.create_polisher(*paths, backend="tpu", **_ARGS)
    p.initialize()
    p.polish(True)
    cons = p.report.as_dict()["phases"]["consensus"]
    assert {"from": "ls", "to": "xla",
            "error": "RuntimeError: mosaic said no"} in cons["degradations"]
    assert cons["served"]["ls"] == 0 and cons["served"]["xla"] > 0


# -- one compile cache -----------------------------------------------------

_PRINT_CACHE = ("import racon_tpu.ops, jax; "
                "print(jax.config.jax_compilation_cache_dir)")


def test_compile_cache_follows_the_environment():
    r = _child([sys.executable, "-c", _PRINT_CACHE],
               env_set={"JAX_COMPILATION_CACHE_DIR": "/x/placed/outside"})
    assert r.stdout.strip() == "/x/placed/outside", r.stderr[-500:]


def test_compile_cache_default_is_a_fixed_path_in_the_checkout():
    r = _child([sys.executable, "-c", "import jax\n" + _PRINT_CACHE],
               env_drop=("JAX_COMPILATION_CACHE_DIR",))
    assert r.stdout.strip() == os.path.join(ROOT, ".jax_cache"), \
        r.stderr[-500:]
    assert racon_tpu.JAX_CACHE_DIR == os.path.join(ROOT, ".jax_cache")


# -- machine profiles ------------------------------------------------------

def test_auto_profile_is_keyed_on_device_kind():
    v5e = costmodel.resolve_profile("auto", "tpu", "TPU v5 lite")
    assert v5e.name == "tpu-v5e"
    assert (v5e.peak_flops, v5e.hbm_bytes_per_s) == (1.97e14, 8.19e11)
    with pytest.raises(KeyError, match="no machine profile"):
        costmodel.resolve_profile("auto", "tpu", "TPU v9 imaginary")
    with pytest.raises(KeyError, match="no machine profile"):
        costmodel.resolve_profile("auto", "tpu", None)


# -- chip_smoke.py ---------------------------------------------------------

def test_chip_smoke_exits_nonzero_without_a_chip(tmp_path):
    r = _child([sys.executable, "chip_smoke.py", "--out",
                str(tmp_path / "out")])
    assert r.returncode != 0
    assert '"ok"' not in r.stdout and "no accelerator" in r.stdout


def test_chip_smoke_alone_in_a_directory_exits_nonzero(tmp_path):
    import shutil

    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       capture_output=True, text=True, timeout=60)
    assert r.returncode != 0 and r.stdout == ""


def _report(**over):
    rep = {
        "device": {"platform": "tpu", "device_kind": "TPU v5 lite",
                   "count": 1},
        "jax_cache": {"requests": 30, "hits": 30, "misses": 0},
        "phases": {
            "consensus": {
                "total": 1000, "retries": 0, "bisections": 0,
                "quarantined": [], "degradations": [],
                "served": {"ls": 990, "xla": 0, "host": 8,
                           "backbone": 2, "journal": 0},
                "extra": {"device_rejected": 8, "kernels": {
                    "interpreted": False, "batch": 64, "shards": 1}}},
            "alignment": {
                "total": 1900, "retries": 0, "bisections": 0,
                "quarantined": [], "degradations": [],
                "served": {"hirschberg": 1890, "host": 10,
                           "journal": 0},
                "extra": {"kernels": {"engine": "hirschberg",
                                      "interpreted": False, "batch": 64,
                                      "shards": 1}}}},
        "obs": {"metrics": {"counters": {"kernel.builds.poa.ls": 3}}},
    }
    for path, value in over.items():
        node = rep
        *keys, last = path.split("/")
        for k in keys:
            node = node[k]
        node[last] = value
    return rep


def test_judge_passes_a_clean_report():
    assert chip_smoke.judge_report(_report(), alignment=True,
                                   warm=True) == []


def test_judge_fails_ls_at_zero_with_xla_serving():
    """The failure the smoke exists to prevent: the run exits 0 with
    correct output while every geometry was demoted to the XLA twin."""
    bad = chip_smoke.judge_report(_report(**{
        "phases/consensus/served": {"ls": 0, "xla": 990, "host": 8,
                                    "backbone": 2},
        "phases/consensus/degradations": [
            {"from": "ls", "to": "xla", "error": "ValueError: smem"}],
        "obs/metrics/counters": {"kernel.builds.poa.xla": 3}}),
        alignment=True)
    text = json.dumps(bad)
    assert "ls served 0" in text and "tier xla served 990" in text
    assert "degraded" in text


@pytest.mark.parametrize("over,needle", [
    ({"device/platform": "cpu"}, "platform 'cpu'"),
    ({"phases/consensus/extra/kernels/interpreted": True}, "interpreted"),
    ({"phases/consensus/served": {"ls": 800, "host": 198, "backbone": 2}},
     "host served 198"),
    ({"phases/alignment/served": {"hirschberg": 900, "host": 1000}},
     "hirschberg served 900"),
    ({"phases/alignment/degradations": [
        {"from": "hirschberg", "to": "host", "error": "x"}]}, "degraded"),
    ({"phases/consensus/retries": 2}, "retries"),
    ({"phases/consensus/quarantined": [7]}, "quarantined"),
    ({"jax_cache": {"requests": 30, "hits": 12, "misses": 18}},
     "compile cache"),
    ({"obs/metrics/counters": {"shard.demotions": 1}}, "shard demotions"),
    ({"device/count": 4, "obs/metrics/counters": {
        "shard.rows.d0": 64, "shard.rows.d1": 0, "shard.rows.d2": 0,
        "shard.rows.d3": 0}}, "not spread"),
])
def test_judge_names_each_way_off_the_chip(over, needle):
    bad = chip_smoke.judge_report(_report(**over), alignment=True,
                                  warm=True)
    assert any(needle in b for b in bad), bad


def test_judge_sam_run_needs_no_alignment_phase():
    rep = _report()
    del rep["phases"]["alignment"]
    assert chip_smoke.judge_report(rep, alignment=False) == []
    assert chip_smoke.judge_report(rep, alignment=True) != []
