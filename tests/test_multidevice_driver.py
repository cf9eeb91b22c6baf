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


def _polish_counted(tmp_path, monkeypatch, env):
    """Polish _make_dataset's job on the device path (the interpreted
    lockstep kernel unless `env` says otherwise); returns (the polished
    records, obs counters, consensus stats)."""
    from racon_tpu import obs
    from racon_tpu.ops import poa_driver
    from racon_tpu.parallel import reset_partitioner

    monkeypatch.setenv("RACON_TPU_PALLAS", "1")
    monkeypatch.setenv("RACON_TPU_METRICS", "1")
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    reset_partitioner()
    captured = {}
    orig = poa_driver.run_consensus_phase

    def spy(*a, **k):
        captured.update(orig(*a, **k))
        return captured

    monkeypatch.setattr(poa_driver, "run_consensus_phase", spy)
    try:
        p = racon_tpu.TpuPolisher(str(tmp_path / "reads.fasta"),
                                  str(tmp_path / "ovl.sam"),
                                  str(tmp_path / "targets.fasta"),
                                  window_length=100, quality_threshold=10,
                                  error_threshold=0.3, match=5, mismatch=-4,
                                  gap=-8, num_threads=1)
        p.initialize()
        return p.polish(True), dict(obs.snapshot()["counters"]), captured
    finally:
        obs.reset()
        monkeypatch.setattr(poa_driver, "run_consensus_phase", orig)
        reset_partitioner()


@pytest.mark.parametrize("pallas", ["0", "1"], ids=["0-xla", "1-ls"])
def test_sharded_driver(tmp_path, monkeypatch, capsys, pallas):
    assert len(jax.devices()) == 8
    targets = _make_dataset(tmp_path)
    res, _, captured = _polish_counted(
        tmp_path, monkeypatch,
        {"RACON_TPU_PALLAS": pallas, "RACON_TPU_BATCH_WINDOWS": "8"})
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


@pytest.mark.parametrize("pallas,shards,want", [
    # the lockstep kernel on a TPU, nobody asked for a batch: every
    # shard gets at least one widest program (GROUP_WIDTHS[0] x G = 32)
    (True, 1, 64), (True, 2, 64), (True, 4, 128), (True, 8, 256),
    # the XLA twin has no programs to fill: its batch rule stays
    (False, 1, 64), (False, 4, 64), (False, 8, 64),
], ids=lambda v: str(v))
def test_tpu_batch_follows_the_mesh(monkeypatch, pallas, shards, want):
    """A rule of the mesh size and the kernel's own constants: 64 on one
    chip and on two (32 a shard), 128 on four, 256 on eight."""
    from racon_tpu.ops import poa_driver, poa_pallas_ls

    monkeypatch.delenv("RACON_TPU_BATCH_WINDOWS", raising=False)
    monkeypatch.setattr(poa_driver, "_platform", lambda: "tpu")
    monkeypatch.setattr(poa_driver, "_shard_n",
                        lambda B: shards if B >= shards else 1)
    assert poa_driver._device_batch(pallas) == want
    if pallas:
        assert want == max(poa_driver.TPU_BATCH,
                           shards * poa_driver.GROUP_WIDTHS[0]
                           * poa_pallas_ls.G)
        # so wherever VMEM holds it a shard's launches choose between a
        # program of thirty-two and one of sixteen, as one chip's do
        cfg = poa_driver.make_config(500, 200, 5, -4, -8)
        assert poa_driver._group_widths(cfg, want // shards) == (4, 2)


@pytest.mark.parametrize("asked,shards,want", [
    (64, 4, 64), (8, 4, 32), (100, 4, 128), (64, 8, 64), (16, 1, 16),
], ids=lambda v: str(v))
def test_a_batch_somebody_asked_for_stays(monkeypatch, asked, shards, want):
    """RACON_TPU_BATCH_WINDOWS on a TPU's mesh: rounded up to G x shards
    as before, never raised to the mesh's own batch (64 over four chips
    is still 16 a shard, one program of sixteen)."""
    from racon_tpu.ops import poa_driver

    monkeypatch.setenv("RACON_TPU_BATCH_WINDOWS", str(asked))
    monkeypatch.setattr(poa_driver, "_platform", lambda: "tpu")
    monkeypatch.setattr(poa_driver, "_shard_n",
                        lambda B: shards if B >= shards else 1)
    assert poa_driver._device_batch(True) == want


def test_demoted_partitioner_falls_back_to_the_one_chip_batch(monkeypatch):
    """_shard_n reads the partitioner: once it is demoted the next phase
    sizes its batch for one device."""
    from racon_tpu.ops import poa_driver
    from racon_tpu.parallel import get_partitioner, reset_partitioner

    monkeypatch.delenv("RACON_TPU_BATCH_WINDOWS", raising=False)
    monkeypatch.setenv("RACON_TPU_MESH_SHAPE", "4")
    monkeypatch.setattr(poa_driver, "_platform", lambda: "tpu")
    reset_partitioner()
    try:
        assert poa_driver._device_batch(True) == 128
        get_partitioner().demote("test")
        assert poa_driver._device_batch(True) == poa_driver.TPU_BATCH
    finally:
        reset_partitioner()


def test_mesh_launches_split_evenly_and_polish_the_same_bytes(
        tmp_path, monkeypatch):
    """The whole path over a (4, 1) mesh of the virtual devices, 8 rows
    a shard: 22 windows are a full launch of 32 and a part-full one of
    12, which lies 3 / 3 / 3 / 3 on the shards where real-first packing
    put 8 / 4 / 0 / 0.  Every window is another sequence, so a result
    handed back to the wrong chunk position would show in the bytes."""
    targets = _make_dataset(tmp_path, n_targets=22)
    one, c1, s1 = _polish_counted(
        tmp_path, monkeypatch,
        {"RACON_TPU_SHARD": "0", "RACON_TPU_BATCH_WINDOWS": "32"})
    mesh, c4, s4 = _polish_counted(
        tmp_path, monkeypatch,
        {"RACON_TPU_SHARD": "1", "RACON_TPU_MESH_SHAPE": "4",
         "RACON_TPU_BATCH_WINDOWS": "32"})
    assert mesh == one                  # names and bases, record by record
    assert [data for _, data in mesh] == targets
    for stats in (s1, s4):
        assert stats["device"] == 44 and stats["host_fallback"] == 0
        assert stats["failed"] == 0
    assert c4["poa.launches"] == 2 and c4["poa.rows.real"] == 44
    assert c4["poa.mesh.rows.real"] == 44
    assert c4["poa.mesh.fullest.slots"] == 32 + 4 * 3
    assert [c4[f"shard.rows.d{i}"] for i in range(4)] == [16] * 4
    assert c4["shard.pad_rows"] == 20 == c4["poa.rows.pad"]
    # one device counts neither mesh key, and no shard rows
    assert not [k for k in c1 if k.startswith(("poa.mesh.", "shard."))]
    assert c1["poa.rows.real"] == 44
