"""Device kernel tests (JAX CPU backend, 8 virtual devices): differential
against the host oracle, the way the reference pins GPU results against CPU
results (/root/reference/test/racon_test.cpp:297-507)."""

import random

import numpy as np
import pytest

from racon_tpu import native
from racon_tpu.ops import align_pallas, encoding, poa
from racon_tpu.ops.encoding import decode, encode


def mutate(seq, rate, rng):
    out = bytearray()
    for c in seq:
        r = rng.random()
        if r < rate / 3:
            out.append(rng.choice(b"ACGT"))
        elif r < 2 * rate / 3:
            pass
        elif r < rate:
            out.append(c)
            out.append(rng.choice(b"ACGT"))
        else:
            out.append(c)
    return bytes(out)


@pytest.fixture(scope="module")
def poa_kernel():
    cfg = poa.PoaConfig(max_nodes=768, max_len=384, max_backbone=256,
                        max_edges=12, depth=16, match=5, mismatch=-4, gap=-8)
    return cfg, poa.build_poa_kernel(cfg)


def run_device_window(cfg, kernel, backbone, layers, begins, ends,
                      quals=None):
    B = 1
    bl = len(backbone)
    bb = np.zeros((B, cfg.max_backbone), np.uint8)
    bb[0, :bl] = encode(np.frombuffer(backbone, np.uint8))
    bbw = np.zeros((B, cfg.max_backbone), np.int32)
    bb_len = np.array([bl], np.int32)
    nl = np.array([len(layers)], np.int32)
    seqs = np.zeros((B, cfg.depth, cfg.max_len), np.uint8)
    ws = np.zeros((B, cfg.depth, cfg.max_len), np.int32)
    lens = np.zeros((B, cfg.depth), np.int32)
    bg = np.zeros((B, cfg.depth), np.int32)
    en = np.zeros((B, cfg.depth), np.int32)
    for i, l in enumerate(layers):
        seqs[0, i, :len(l)] = encode(np.frombuffer(l, np.uint8))
        if quals is not None:
            ws[0, i, :len(l)] = (
                np.frombuffer(quals[i], np.uint8).astype(np.int32) - 33)
        else:
            ws[0, i, :len(l)] = 1
        lens[0, i] = len(l)
        bg[0, i] = begins[i]
        en[0, i] = ends[i]
    cb, cc, cl, failed, _ = (np.asarray(x)
                             for x in kernel(bb, bbw, bb_len, nl, seqs, ws,
                                             lens, bg, en))
    assert not failed[0]
    return decode(cb[0, :cl[0]]), cc[0, :cl[0]]


@pytest.mark.parametrize("seed", [0, 2, 3])
def test_device_poa_matches_host(poa_kernel, seed):
    cfg, kernel = poa_kernel
    rng = random.Random(seed)
    L = 200
    truth = bytes(rng.choice(b"ACGT") for _ in range(L))
    backbone = mutate(truth, 0.1, rng)
    bl = len(backbone)
    layers, begins, ends = [], [], []
    for _ in range(10):
        layers.append(mutate(truth, 0.12, rng))
        begins.append(0)
        ends.append(bl - 1)
    dev, _ = run_device_window(cfg, kernel, backbone, layers, begins, ends)
    host, _ = native.window_consensus(backbone, layers, begins=begins,
                                      ends=ends, trim=False)
    # Exact match on most seeds; tie-breaks may differ by a base or two the
    # way the reference's CUDA path diverges from its CPU path.
    assert native.edit_distance(dev, host) <= 2
    assert native.edit_distance(dev, truth) <= native.edit_distance(
        host, truth) + 2


def test_device_poa_partial_layers_and_quality(poa_kernel):
    cfg, kernel = poa_kernel
    rng = random.Random(42)
    L = 200
    truth = bytes(rng.choice(b"ACGT") for _ in range(L))
    backbone = mutate(truth, 0.08, rng)
    bl = len(backbone)
    layers, begins, ends, quals = [], [], [], []
    for _ in range(12):
        if rng.random() < 0.6:
            b = rng.randint(0, L // 2)
            e = rng.randint(b + L // 4, L - 1)
        else:
            b, e = 0, L - 1
        seg = mutate(truth[b:e + 1], 0.12, rng)
        layers.append(seg)
        begins.append(min(b, bl - 1))
        ends.append(min(e, bl - 1))
        quals.append(bytes(33 + rng.randint(5, 40) for _ in seg))
    order = sorted(range(len(layers)), key=lambda i: begins[i])
    layers = [layers[i] for i in order]
    begins = [begins[i] for i in order]
    ends = [ends[i] for i in order]
    quals = [quals[i] for i in order]

    dev, cov = run_device_window(cfg, kernel, backbone, layers, begins, ends,
                                 quals=quals)
    host, _ = native.window_consensus(backbone, layers, quals=quals,
                                      begins=begins, ends=ends, trim=False)
    assert native.edit_distance(dev, host) <= 2
    assert len(cov) == len(dev)


def test_ops_to_cigar():
    assert align_pallas.ops_to_cigar(np.array([], np.uint8)) == ""
    assert align_pallas.ops_to_cigar(
        np.array([0, 0, 1, 2, 2], np.uint8)) == "2M1I2D"


# --------------------------------------------------- packed encoding
# (the Hirschberg kernels read the query PACK codes to an int32 word)

def test_pack_bases_round_trip():
    rng = np.random.default_rng(3)
    for n in (0, 1, 3, 4, 5, 127, 128, 1000):
        codes = rng.integers(0, 5, size=n).astype(np.int32)
        words = encoding.pack_bases(codes)
        assert words.shape[-1] == (n + encoding.PACK - 1) // encoding.PACK
        np.testing.assert_array_equal(encoding.unpack_bases(words, n),
                                      codes)


def test_pack_bases_width_and_batch():
    codes = (np.arange(10, dtype=np.int32) % 5).reshape(2, 5)
    words = encoding.pack_bases(codes, width=128)
    assert words.shape == (2, 128)
    np.testing.assert_array_equal(encoding.unpack_bases(words, 5), codes)


def test_pack_bases_is_lossless_for_code4():
    # why packing is byte-per-code, not 2-bit: code 4 (N) must survive
    codes = np.full(9, 4, np.int32)
    np.testing.assert_array_equal(
        encoding.unpack_bases(encoding.pack_bases(codes), 9), codes)
