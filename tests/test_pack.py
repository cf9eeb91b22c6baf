"""Feeder tests: the vectorized _pack and the >=2-deep in-flight queue.

The reference fills accelerator batches continuously in C++ while kernels
execute (/root/reference/src/cuda/cudapolisher.cpp:83-145); this driver's
analogue is a numpy gather/scatter pack plus a configurable-depth queue of
async-dispatched chunks. These tests pin the pack against a plain
per-slice loop (the shape the reference's add_window marshalling takes,
src/cuda/cudabatch.cpp:141-198) and run the polisher end-to-end at a
deeper queue setting.
"""

import random

import numpy as np
import pytest

from racon_tpu.ops import poa, poa_driver
from racon_tpu.ops.encoding import encode
from racon_tpu.pipeline import WindowExport


def _naive_pack(chunk, cfg, pad_to):
    """The original per-layer-slice packing loop, kept as the oracle."""
    B = pad_to
    bb = np.zeros((B, cfg.max_backbone), dtype=np.uint8)
    bbw = np.zeros((B, cfg.max_backbone), dtype=np.int32)
    bb_len = np.ones(B, dtype=np.int32)
    n_layers = np.zeros(B, dtype=np.int32)
    seqs = np.zeros((B, cfg.depth, cfg.max_len), dtype=np.uint8)
    ws = np.zeros((B, cfg.depth, cfg.max_len), dtype=np.int32)
    lens = np.zeros((B, cfg.depth), dtype=np.int32)
    begins = np.zeros((B, cfg.depth), dtype=np.int32)
    ends = np.zeros((B, cfg.depth), dtype=np.int32)
    for bi, (i, wx, keep) in enumerate(chunk):
        L = len(wx.backbone)
        bb[bi, :L] = encode(wx.backbone)
        bbw[bi, :L] = wx.backbone_weights
        bb_len[bi] = L
        n_layers[bi] = len(keep)
        offsets = np.concatenate([[0], np.cumsum(wx.lens)]).astype(np.int64)
        for li, j in enumerate(keep):
            ll = int(wx.lens[j])
            seqs[bi, li, :ll] = encode(wx.bases[offsets[j]:offsets[j] + ll])
            ws[bi, li, :ll] = wx.weights[offsets[j]:offsets[j] + ll]
            lens[bi, li] = ll
            begins[bi, li] = wx.begins[j]
            ends[bi, li] = wx.ends[j]
    return (bb, bbw, bb_len, n_layers, seqs, ws, lens, begins, ends)


def _random_export(rng, index, n_layers, bb_len, max_len):
    lens = np.array([rng.randrange(1, max_len + 1) for _ in range(n_layers)],
                    dtype=np.uint32)
    total = int(lens.sum())
    bases = np.frombuffer(
        bytes(rng.choice(b"ACGTN") for _ in range(total)),
        dtype=np.uint8).copy()
    weights = np.array([rng.randrange(0, 60) for _ in range(total)],
                       dtype=np.uint8)
    backbone = np.frombuffer(
        bytes(rng.choice(b"ACGT") for _ in range(bb_len)),
        dtype=np.uint8).copy()
    return WindowExport(
        index=index, rank=0, target_id=0, is_tgs=True,
        backbone=backbone,
        backbone_weights=np.zeros(bb_len, np.uint8),
        lens=lens,
        begins=np.array([rng.randrange(0, bb_len) for _ in range(n_layers)],
                        dtype=np.uint32),
        ends=np.array([bb_len - 1] * n_layers, dtype=np.uint32),
        bases=bases, weights=weights)


def test_vectorized_pack_matches_naive_loop():
    """Mixed chunk: full keeps, dropped (oversized) layers, truncated-at-
    DEPTH_CAP keeps, an empty-keep window, and padding rows."""
    rng = random.Random(13)
    cfg = poa.PoaConfig(max_nodes=384, max_len=64, max_backbone=128,
                        max_edges=12, depth=6, match=5, mismatch=-4, gap=-8)
    chunk = []
    # window 0: all layers kept
    wx = _random_export(rng, 0, 4, 100, cfg.max_len)
    chunk.append((0, wx, list(range(4))))
    # window 1: layer 1 dropped (as if oversized) -> ragged keep indices
    wx = _random_export(rng, 1, 5, 90, cfg.max_len)
    chunk.append((1, wx, [0, 2, 3, 4]))
    # window 2: keep truncated below the layer count (depth cap analogue)
    wx = _random_export(rng, 2, 6, 80, cfg.max_len)
    chunk.append((2, wx, [0, 1, 2, 3, 4, 5][:cfg.depth - 2]))
    # window 3: nothing kept
    wx = _random_export(rng, 3, 3, 70, cfg.max_len)
    chunk.append((3, wx, []))

    got = poa_driver._pack(chunk, cfg, 6)     # 2 padding rows
    want = _naive_pack(chunk, cfg, 6)
    names = ("bb", "bbw", "bb_len", "n_layers", "seqs", "ws", "lens",
             "begins", "ends")
    for name, g, w in zip(names, got, want):
        np.testing.assert_array_equal(g, w, err_msg=name)


@pytest.mark.parametrize("depth", ["1", "3"])
def test_polish_correct_at_any_pipeline_depth(tmp_path, monkeypatch, depth):
    """End-to-end polish with the queue at depth 1 and 3 — results must be
    identical to the single-slot behavior (ordering-independent install).

    The target is long enough (30 windows vs the mesh-rounded batch of 8)
    that the bucket splits into several chunks, so the deque really holds
    multiple in-flight entries at depth 3 — asserted via a dispatch
    counter, not assumed."""
    import racon_tpu

    rng = random.Random(5)
    target = "".join(rng.choice("ACGT") for _ in range(3000))
    with open(tmp_path / "t.fasta", "w") as f:
        f.write(f">t\n{target}\n")
    with open(tmp_path / "r.fasta", "w") as f:
        for i in range(4):
            f.write(f">r{i}\n{target}\n")
    with open(tmp_path / "o.sam", "w") as f:
        f.write("@HD\tVN:1.6\n")
        for i in range(4):
            f.write(f"r{i}\t0\tt\t1\t60\t{len(target)}M\t*\t0\t0\t{target}"
                    f"\t*\n")

    submits = []
    real_submit = poa_driver._submit

    def counting_submit(*args, **kw):
        submits.append(1)
        return real_submit(*args, **kw)

    monkeypatch.setenv("RACON_TPU_PALLAS", "0")
    monkeypatch.setenv("RACON_TPU_PIPELINE_DEPTH", depth)
    monkeypatch.setenv("RACON_TPU_BATCH_WINDOWS", "1")  # several chunks
    monkeypatch.setattr(poa_driver, "_submit", counting_submit)
    p = racon_tpu.TpuPolisher(str(tmp_path / "r.fasta"),
                              str(tmp_path / "o.sam"),
                              str(tmp_path / "t.fasta"),
                              window_length=100, match=5, mismatch=-4,
                              gap=-8)
    p.initialize()
    res = p.polish(True)
    assert len(submits) > int(depth), \
        "scenario too small to exercise the in-flight queue"
    assert len(res) == 1
    assert res[0][1] == target
