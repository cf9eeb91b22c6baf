"""Consensus driver over the 8-virtual-device mesh: both kernel flavors must
produce correct polished output with the batch sharded across devices, and
the batch is sized from the tier that serves."""

import random

import jax
import numpy as np
import pytest

import racon_tpu


def _make_dataset(tmp_path, n_targets=3):
    rng = random.Random(7)
    targets = []
    with open(tmp_path / "targets.fasta", "w") as tf, \
            open(tmp_path / "reads.fasta", "w") as rf, \
            open(tmp_path / "ovl.sam", "w") as of:
        of.write("@HD\tVN:1.6\n")
        for t in range(n_targets):
            seq = "".join(rng.choice("ACGT") for _ in range(200))
            targets.append(seq)
            tf.write(f">t{t}\n{seq}\n")
            for i in range(4):
                rf.write(f">t{t}r{i}\n{seq}\n")
                of.write(f"t{t}r{i}\t0\tt{t}\t1\t60\t200M\t*\t0\t0\t{seq}\t*\n")
    return targets


@pytest.mark.parametrize("pallas", ["0", "1"], ids=["0-xla", "1-ls"])
def test_sharded_driver(tmp_path, monkeypatch, capsys, pallas):
    assert len(jax.devices()) == 8
    targets = _make_dataset(tmp_path)
    monkeypatch.setenv("RACON_TPU_PALLAS", pallas)
    monkeypatch.setenv("RACON_TPU_BATCH_WINDOWS", "8")
    p = racon_tpu.TpuPolisher(str(tmp_path / "reads.fasta"),
                              str(tmp_path / "ovl.sam"),
                              str(tmp_path / "targets.fasta"),
                              window_length=100, quality_threshold=10,
                              error_threshold=0.3, match=5, mismatch=-4,
                              gap=-8, num_threads=1)
    from racon_tpu.ops import poa_driver

    captured = {}
    orig = poa_driver.run_consensus_phase

    def spy(*a, **k):
        stats = orig(*a, **k)
        captured.update(stats)
        return stats

    monkeypatch.setattr(poa_driver, "run_consensus_phase", spy)
    p.initialize()
    res = p.polish(True)
    assert len(res) == len(targets)
    for (name, data), truth in zip(res, targets):
        assert data == truth
    # Correct output via a degrade would mask a broken sharded pallas
    # path: no tier step-down warning, every window served by the
    # device, none re-polished on the host or failed.
    n_windows = 2 * len(targets)  # 200 bp targets, w=100 -> 2 each
    assert captured["device"] == n_windows
    assert captured["host_fallback"] == 0 and captured["failed"] == 0
    if pallas == "1":
        assert "falling back" not in capsys.readouterr().err


@pytest.mark.parametrize("pallas,env,want", [
    # Pallas on: the lockstep group G = 8 rounds the batch up per shard;
    # 64 is what a TPU asks for, on one chip and on four
    ("1", {"RACON_TPU_SHARD": "0"}, {4: 8, 64: 64}),
    ("1", {"RACON_TPU_MESH_SHAPE": "4"}, {8: 32, 64: 64}),
    # the XLA twin serves: the mesh round-up stays, the group's does not
    ("0", {"RACON_TPU_SHARD": "0"}, {4: 4, 12: 12}),
    ("0", {}, {8: 8, 12: 16}),
], ids=["pallas-1dev", "pallas-4shards", "twin-1dev", "twin-8dev"])
def test_device_batch_follows_serving_tier(monkeypatch, pallas, env, want):
    """The batch the warm-up builds and launches at, read where the
    driver hands it to the kernel builder: with Pallas off the twin must
    not inherit the lockstep kernel's G x shards round-up."""
    from racon_tpu.ops import poa_driver
    from racon_tpu.parallel import reset_partitioner

    assert len(jax.devices()) == 8
    monkeypatch.setenv("RACON_TPU_PALLAS", pallas)
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    reset_partitioner()
    built = []

    def fake_build(cfg, B, use_pallas):
        built.append((B, use_pallas))
        return lambda *args: tuple(np.zeros((B, 1), np.int32)
                                   for _ in range(5))

    monkeypatch.setattr(poa_driver, "_build_kernel", fake_build)
    monkeypatch.setattr(poa_driver, "_WARM_DEAD", {})
    for asked, batch in want.items():
        monkeypatch.setenv("RACON_TPU_BATCH_WINDOWS", str(asked))
        del built[:]
        poa_driver.warm_geometries(100, 5, -4, -8)
        assert built == [(batch, pallas == "1")] * len(
            poa_driver.DEPTH_BUCKETS), (asked, built)
