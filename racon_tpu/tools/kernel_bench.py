"""Micro-benchmark the fused Pallas POA kernel at production geometry on
the current JAX backend (meant for the real TPU; refuses nothing, but
prints the platform so a CPU number can't masquerade as a chip number).

Synthesizes ONT-like windows: 500 bp backbone, `depth` layers at ~11%
error (mix of substitutions/insertions/deletions), which grows the graph
the way real data does — unlike a substitution-only batch, which never
allocates insertion columns.

Usage: python racon_tpu/tools/kernel_bench.py [batch] [depth] [iters]
(batch: a multiple of the lockstep group, 8)
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import numpy as np


def make_batch(cfg, B, rng, err=0.11):
    bb = np.zeros((B, cfg.max_backbone), dtype=np.uint8)
    bbw = np.zeros((B, cfg.max_backbone), dtype=np.int32)
    bb_len = np.zeros(B, dtype=np.int32)
    n_layers = np.zeros(B, dtype=np.int32)
    seqs = np.zeros((B, cfg.depth, cfg.max_len), dtype=np.uint8)
    ws = np.zeros((B, cfg.depth, cfg.max_len), dtype=np.int32)
    lens = np.zeros((B, cfg.depth), dtype=np.int32)
    begins = np.zeros((B, cfg.depth), dtype=np.int32)
    ends = np.zeros((B, cfg.depth), dtype=np.int32)

    W = 500
    for b in range(B):
        truth = rng.integers(0, 4, W).astype(np.uint8)
        draft = mutate(truth, err, rng)[:min(cfg.max_backbone, W)]
        bb[b, :len(draft)] = draft
        bb_len[b] = len(draft)
        n_layers[b] = cfg.depth
        for li in range(cfg.depth):
            layer = mutate(truth, err, rng)[:cfg.max_len]
            seqs[b, li, :len(layer)] = layer
            ws[b, li, :len(layer)] = rng.integers(1, 30, len(layer))
            lens[b, li] = len(layer)
            begins[b, li] = 0
            ends[b, li] = len(draft) - 1
    return (bb, bbw, bb_len, n_layers, seqs, ws, lens, begins, ends)


def mutate(seq, rate, rng):
    r = rng.random(len(seq))
    out = []
    for i, c in enumerate(seq):
        if r[i] < rate / 3:
            out.append(rng.integers(0, 4))          # substitution
        elif r[i] < 2 * rate / 3:
            pass                                    # deletion
        elif r[i] < rate:
            out.append(c)
            out.append(rng.integers(0, 4))          # insertion
        else:
            out.append(c)
    return np.array(out, dtype=np.uint8)


def main():
    B = int(sys.argv[1]) if len(sys.argv) > 1 else 64
    depth = int(sys.argv[2]) if len(sys.argv) > 2 else 32
    iters = int(sys.argv[3]) if len(sys.argv) > 3 else 3

    import jax

    from racon_tpu.ops import poa_driver, poa_pallas_ls

    platform = jax.devices()[0].platform
    cfg = poa_driver.make_config(500, depth, 5, -4, -8)
    interp = platform != "tpu"
    # the program width the driver would ship this batch at when full
    fn = poa_pallas_ls.build_lockstep_poa_kernel(
        cfg, interpret=interp, groups=poa_driver._group_width(cfg, B))(B)

    rng = np.random.default_rng(0)
    bb, bbw, bl, nl, seqs, ws, lens, bg, en = make_batch(cfg, B, rng)
    args = (bl.reshape(-1, 1), nl.reshape(-1, 1), lens, bg, en,
            bb.astype(np.int32), bbw, seqs.astype(np.int32), ws)

    t0 = time.time()
    out = fn(*args)
    jax.block_until_ready(out)
    compile_and_first = time.time() - t0
    failed = int(np.asarray(out[3]).sum())
    nmax = int(np.asarray(out[4]).max())

    times = []
    for _ in range(iters):
        t0 = time.time()
        out = fn(*args)
        jax.block_until_ready(out)
        times.append(time.time() - t0)
    best = min(times)
    print(f"platform={platform} B={B} depth={depth} "
          f"first={compile_and_first:.2f}s warm={best:.3f}s "
          f"per_window={best / B * 1e3:.2f}ms failed={failed} "
          f"max_nodes_used={nmax}")


if __name__ == "__main__":
    main()
