"""The partitioning subsystem: mesh discovery, logical-axis rules, pad
accounting, the will_shard gate and the sharded->single-device lattice
edge — all on the 8-virtual-device mesh the conftest forces."""

import random

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec

from racon_tpu import obs
from racon_tpu.parallel import axes, divisible_batch
from racon_tpu.parallel.partitioner import (Partitioner, build_mesh,
                                            get_partitioner, mesh_shape)
from racon_tpu.resilience import lattice as rl
from racon_tpu.resilience.report import PhaseReport


# -- shard_map wrap -------------------------------------------------------

def test_shard_build_runs_on_the_real_mesh():
    """The partitioner's shard_map wrap must execute a trivial per-shard
    kernel over the real device mesh (a jax bump that moves or renames
    shard_map must fail a test, not silently kill the sharded tier)."""
    part = get_partitioner()
    fn = part.shard_build(lambda b: (lambda x: x * 2), 8, 1, 1)
    x = np.arange(16, dtype=np.int32).reshape(8, 2)
    np.testing.assert_array_equal(np.asarray(fn(x)), x * 2)


# -- logical axis rules ----------------------------------------------------

def test_resolve_spec_default_rules():
    spec = axes.resolve_spec(("windows", "depth", "lane"),
                             axes.DEFAULT_RULES, axes.MESH_AXES)
    assert spec == PartitionSpec("data", "model", None)
    assert axes.resolve_spec((), axes.DEFAULT_RULES,
                             axes.MESH_AXES) == PartitionSpec()
    # None entries and lane dims replicate
    assert axes.resolve_spec(("query", None, "lane"), axes.DEFAULT_RULES,
                             axes.MESH_AXES) == \
        PartitionSpec("data", None, None)


def test_resolve_spec_rejects_unknown_axis():
    with pytest.raises(ValueError, match="unknown logical axis"):
        axes.resolve_spec(("windoes",), axes.DEFAULT_RULES, axes.MESH_AXES)


def test_resolve_spec_rejects_absent_mesh_axis():
    rules = (("windows", "expert"),)
    with pytest.raises(ValueError, match="absent from this mesh"):
        axes.resolve_spec(("windows",), rules, ("data",))


def test_validate_rules_errors():
    with pytest.raises(ValueError, match="unknown logical axis"):
        axes.validate_rules((("bogus", "data"),), axes.MESH_AXES)
    with pytest.raises(ValueError, match="duplicate rule"):
        axes.validate_rules((("windows", "data"), ("windows", None)),
                            axes.MESH_AXES)
    with pytest.raises(ValueError, match="no such axis"):
        axes.validate_rules((("windows", "expert"),), axes.MESH_AXES)


def test_rules_registry_roundtrip(monkeypatch):
    """set_rules changes what get_partitioner memoizes on (rules_key is
    part of the cache key), and a depth-replicated override resolves."""
    override = (("windows", "data"), ("query", "data"),
                ("depth", None), ("lane", None))
    monkeypatch.setattr(axes, "_RULES", override)
    assert axes.get_rules() == override
    assert axes.rules_key() == override
    part = get_partitioner()
    assert part.spec("windows", "depth") == PartitionSpec("data", None)


# -- mesh discovery --------------------------------------------------------

def test_mesh_shape_spellings(monkeypatch):
    monkeypatch.delenv("RACON_TPU_MESH_SHAPE", raising=False)
    assert mesh_shape(8) == (8, 1)
    monkeypatch.setenv("RACON_TPU_MESH_SHAPE", "8")
    assert mesh_shape(8) == (8, 1)
    monkeypatch.setenv("RACON_TPU_MESH_SHAPE", "4,2")
    assert mesh_shape(8) == (4, 2)
    monkeypatch.setenv("RACON_TPU_MESH_SHAPE", "4x2")
    assert mesh_shape(8) == (4, 2)
    monkeypatch.setenv("RACON_TPU_MESH_SHAPE", "2")
    assert mesh_shape(8) == (2, 1)


def test_mesh_shape_invalid_degrades_with_warning(monkeypatch, capsys):
    """Mis-set knobs degrade to the all-devices default, never fail."""
    for bad in ("garbage", "16", "0,4", "2,2,2"):
        monkeypatch.setenv("RACON_TPU_MESH_SHAPE", bad)
        assert mesh_shape(8) == (8, 1)
        assert "RACON_TPU_MESH_SHAPE" in capsys.readouterr().err


def test_build_mesh_flat_and_undersubscribed():
    assert len(jax.devices()) == 8
    full = build_mesh((8, 1))
    assert dict(full.shape) == {"data": 8, "model": 1}
    sub = build_mesh((2, 1))
    assert dict(sub.shape) == {"data": 2, "model": 1}
    assert list(sub.devices.ravel()) == jax.devices()[:2]
    two_d = build_mesh((4, 2))
    assert dict(two_d.shape) == {"data": 4, "model": 2}


# -- pad accounting --------------------------------------------------------

def test_pad_rows_rounds_up():
    part = get_partitioner()
    assert part.batch_axis_size == 8
    assert part.pad_rows(13) == 16
    assert part.pad_rows(8) == 8
    assert part.pad_rows(1) == 8
    assert part.pad_rows(17) == 24


def test_divisible_batch_round_down_regression_pin():
    """The legacy helper rounds DOWN (remainder windows spilled to the
    slow path); the partitioner rounds UP and accounts the pad — the
    satellite this PR fixes, pinned as a visible difference."""
    assert divisible_batch(8, 13) == 8          # 5 windows spilled
    assert get_partitioner().pad_rows(13) == 16  # 3 pad rows, none spilled


def test_pad_packed_repeats_final_row():
    part = get_partitioner()
    a = np.arange(26, dtype=np.int32).reshape(13, 2)
    b = np.arange(13, dtype=np.int32)
    (pa, pb), pad = part.pad_packed((a, b))
    assert pad == 3 and pa.shape == (16, 2) and pb.shape == (16,)
    np.testing.assert_array_equal(pa[13:], np.repeat(a[-1:], 3, axis=0))
    np.testing.assert_array_equal(pb[13:], [12, 12, 12])
    same, none = part.pad_packed((np.zeros((8, 2)),))
    assert none == 0 and same[0].shape == (8, 2)


def test_pad_to_multiple_and_balanced_counters():
    """The executor's one-place pad seam + the balance evidence: after
    padding, every device position counts the same row total (balanced
    to within one batch per device, per the acceptance criterion)."""
    from racon_tpu.ops.batch_exec import count_shard_rows, pad_to_multiple

    obs.configure(metrics=True)
    packed = (np.arange(26, dtype=np.int32).reshape(13, 2),)
    padded, pad = pad_to_multiple(packed, 8)
    assert pad == 3 and padded[0].shape == (16, 2)
    assert count_shard_rows(13, 16, 8) == 3
    snap = obs.snapshot()["counters"]
    per_dev = [snap[f"shard.rows.d{i}"] for i in range(8)]
    assert per_dev == [2] * 8          # balanced: 16 rows / 8 devices
    assert snap["shard.pad_rows"] == 3
    assert snap["shard.chunks"] == 1


# -- the will_shard gate ---------------------------------------------------

def test_will_shard_gating(monkeypatch):
    part = get_partitioner()
    assert part.will_shard(8) and part.will_shard(64)
    assert not part.will_shard(7)     # below one row per shard
    monkeypatch.setenv("RACON_TPU_SHARD_MIN_BATCH", "4")
    assert part.will_shard(4) and not part.will_shard(3)
    monkeypatch.setenv("RACON_TPU_SHARD", "0")
    assert not part.will_shard(64)    # kill switch wins


def test_demote_is_sticky_and_reported_once():
    part = get_partitioner()
    assert part.disabled is None
    assert part.demote("boom") is True     # first demotion: record it
    assert part.demote("again") is False   # sticky: already single-device
    assert not part.will_shard(64)
    assert part.shard_build(lambda b: (lambda x: x), 64, 1, 1) is None
    # the process-wide singleton carries the state
    assert get_partitioner().disabled is not None


def test_record_shard_demotion_lattice_edge():
    """The edge is orthogonal to tier demotion: degradation list shows
    `<tier>+sharded -> <tier>` and the shard.demotions counter ticks."""
    obs.configure(metrics=True)
    rep = PhaseReport("consensus", rl.CONSENSUS_TIERS)
    rl.record_shard_demotion(rep, "ls", RuntimeError("device lost"))
    assert rep.degradations == [{"from": "ls+sharded", "to": "ls",
                                 "error": "RuntimeError: device lost"}]
    assert obs.snapshot()["counters"]["shard.demotions"] == 1
    rl.record_shard_demotion(None, "xla", "compile failed")  # no report
    assert obs.snapshot()["counters"]["shard.demotions"] == 2


# -- kernel wrapping -------------------------------------------------------

def test_partition_pjit_path_executes():
    part = get_partitioner()
    fn = part.partition(lambda x, y: x + y,
                        in_axes=[("windows", "lane"), ("windows", "lane")],
                        out_axes=("windows", "lane"))
    x = np.arange(32, dtype=np.int32).reshape(16, 2)
    np.testing.assert_array_equal(np.asarray(fn(x, x)), x + x)


def test_shard_build_traces_local_batch():
    """The shard_map path hands each device a kernel built for the LOCAL
    batch size and reassembles the global batch."""
    part = get_partitioner()
    seen = []

    def build_local(b):
        seen.append(b)
        return lambda x: x * 3

    kern = part.shard_build(build_local, 16, 1, 1)
    assert kern is not None and seen == [2]    # 16 rows / 8 shards
    x = np.arange(16, dtype=np.int32).reshape(16, 1)
    np.testing.assert_array_equal(np.asarray(kern(x)), x * 3)


def test_shard_build_declines_bad_batches():
    part = get_partitioner()
    build = lambda b: (lambda x: x)  # noqa: E731
    assert part.shard_build(build, 10, 1, 1) is None   # 10 % 8 != 0
    assert part.shard_build(build, 4, 1, 1) is None    # fewer than shards


# -- end-to-end: byte identity + the demotion edge -------------------------

def _dataset(tmp_path, n_targets=3):
    rng = random.Random(11)
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
                of.write(f"t{t}r{i}\t0\tt{t}\t1\t60\t200M\t*\t0\t0\t"
                         f"{seq}\t*\n")
    return targets


def _polish(tmp_path):
    import racon_tpu

    p = racon_tpu.TpuPolisher(str(tmp_path / "reads.fasta"),
                              str(tmp_path / "ovl.sam"),
                              str(tmp_path / "targets.fasta"),
                              window_length=100, quality_threshold=10,
                              error_threshold=0.3, match=5, mismatch=-4,
                              gap=-8, num_threads=1)
    p.initialize()
    return p.polish(True), p


def test_sharded_polish_byte_identical_to_single_device(tmp_path,
                                                        monkeypatch):
    """Sharding changes where rows compute, never what: the same polish
    with the mesh on vs RACON_TPU_SHARD=0 must be byte-identical, and the
    sharded run's obs counters must show balanced per-device rows."""
    targets = _dataset(tmp_path)
    monkeypatch.setenv("RACON_TPU_PALLAS", "0")
    monkeypatch.setenv("RACON_TPU_BATCH_WINDOWS", "8")
    monkeypatch.setenv("RACON_TPU_METRICS", "1")
    sharded, _ = _polish(tmp_path)
    snap = obs.snapshot()["counters"]
    assert snap.get("shard.chunks", 0) >= 1
    rows = [v for k, v in snap.items() if k.startswith("shard.rows.d")]
    assert len(rows) == 8 and max(rows) - min(rows) == 0
    monkeypatch.setenv("RACON_TPU_SHARD", "0")
    single, _ = _polish(tmp_path)
    assert sharded == single
    for (_, got), want in zip(single, targets):
        assert got == want


def test_sharded_build_failure_demotes_never_fails(tmp_path, monkeypatch,
                                                   capsys):
    """The lattice edge end-to-end: a sharded build that dies drops to
    single-device dispatch at the SAME tier, output still correct, the
    demotion recorded (sticky) — the polish never fails."""
    targets = _dataset(tmp_path)
    monkeypatch.setenv("RACON_TPU_PALLAS", "0")
    monkeypatch.setenv("RACON_TPU_BATCH_WINDOWS", "8")
    monkeypatch.setenv("RACON_TPU_METRICS", "1")

    def broken_partition(self, fn, in_axes, out_axes):
        raise RuntimeError("forced sharded build failure")

    monkeypatch.setattr(Partitioner, "partition", broken_partition)
    monkeypatch.setattr(Partitioner, "shard_build",
                        lambda self, *a, **k: (_ for _ in ()).throw(
                            RuntimeError("forced sharded build failure")))
    res, p = _polish(tmp_path)
    for (_, got), want in zip(res, targets):
        assert got == want
    assert get_partitioner().disabled is not None
    assert obs.snapshot()["counters"].get("shard.demotions", 0) >= 1
    assert "demoting to single-device dispatch" in capsys.readouterr().err
