#!/usr/bin/env python3
"""Graph capacity at the depth cap, by count: what 160 / 200 / 240 / 300
layers of the ONT profile ask of a window graph's node slots and in-edge
slots.

    JAX_PLATFORMS=cpu python3 benchmark/tools/cap_capacity.py [--twin N]

Counts only, so a CPU run may make them (PERF.md section 6, PR 43).  Two
engines on ``benchmark/generate.py``'s ``ont`` mode at
``configs/ecoli-ont-cap.json``'s profile, 0.03 Mbp a coverage:

* the **host** engine's graphs (``Pipeline.initialize`` +
  ``consensus_cpu_all``; it takes every layer a window has), read through
  ``Pipeline.window_growth``: nodes and the most in-edges of one node,
  at the cell's windows of 500 bp and at windows of 128 bp;
* the **kernels'** graphs: the XLA twin ``racon_tpu/ops/poa.py``, which
  the lockstep kernel equals node for node (``tests/test_deep_cell.py``),
  at windows of 128 bp (the smallest class: at 500 bp a window of 200
  layers takes the CPU five minutes), run with room to spare (8 x the
  class in nodes, 24 in-edge slots, every layer the window has up to
  256) so that what it *would* use is seen, on ``--twin`` whole windows
  from the middle of each coverage's contig (default 32).

Printed per window length and bin of layers a window: windows, nodes per
backbone base (median, most), most in-edges (median, most), windows past
the upper rung's 5 x the class in nodes and past 12 in-edges
(``PoaConfig.max_edges``); then the twin against the host engine on the
same windows.
"""

from __future__ import annotations

import argparse
import os
import shutil
import sys
import tempfile

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

#: ~160 / 200 / 240 / 300 layers a whole window (the last past what the
#: twin is given room for here: the host engine alone)
COVERAGES = (148, 185, 222, 278)
BINS = ((150, 170), (190, 210), (230, 250), (290, 310))
ROOMY_DEPTH, ROOMY_NODES, ROOMY_EDGES = 256, 8, 24


def _twin_graphs(cfg):
    """jit(vmap) of the twin's graph construction alone: (nodes, failed,
    most in-edges a node) of each window."""
    import jax

    from racon_tpu.ops import poa

    def one(bb, bbw, bb_len, n_layers, seqs, ws, lens, begins, ends):
        g = poa._init_graph(cfg, bb, bbw, bb_len)

        def body(c):
            g, li = c
            g = jax.lax.cond(
                (lens[li] > 0) & (g.failed == 0),
                lambda g: poa._add_layer(cfg, g, seqs[li], ws[li], lens[li],
                                         begins[li], ends[li], bb_len),
                lambda g: g, g)
            return g, li + 1

        g = jax.lax.while_loop(lambda c: c[1] < n_layers, body,
                               (g, jax.numpy.int32(0)))[0]
        return g.n, g.failed, (g.in_src >= 0).sum(axis=1).max()

    return jax.jit(jax.vmap(one))


def study(coverage: int, w: int, n_twin: int, profile: dict,
          polish_args: dict):
    """Rows (window length, layers, host nodes, host in-edges, twin
    nodes, twin in-edges) of the whole windows of length `w` at one
    coverage; the twin's columns are -1 where it was not run."""
    from benchmark import generate
    from racon_tpu.ops import poa, poa_driver
    from racon_tpu.pipeline import Pipeline

    d = tempfile.mkdtemp(prefix="cap-capacity-")
    try:
        generate.mode_ont(d, 0, **{**profile, "coverage": coverage,
                                   "genome_mbp": 0.03, "formats": ("sam",),
                                   "data_seed": 2, "layout_seed": 22})
        pl = Pipeline(os.path.join(d, "reads.fastq"),
                      os.path.join(d, "overlaps.sam"),
                      os.path.join(d, "draft.fasta"),
                      **dict(polish_args, window_length=w,
                             num_threads=os.cpu_count() or 1))
        pl.initialize()
        cfg = poa.PoaConfig(
            max_nodes=ROOMY_NODES * poa_driver.window_class(w),
            max_len=poa_driver.make_config(w, 8, 5, -4, -8).max_len,
            max_backbone=poa_driver.window_class(w), max_edges=ROOMY_EDGES,
            depth=ROOMY_DEPTH, match=polish_args["match"],
            mismatch=polish_args["mismatch"], gap=polish_args["gap"])
        info = [pl.window_info(i) for i in range(pl.num_windows())]
        whole = [i for i, (n_seqs, bb, *_) in enumerate(info)
                 if bb == w and n_seqs >= 3]
        # the middle of the contig: the ends are shallow
        fits = [i for i in whole if info[i][0] <= ROOMY_DEPTH + 1]
        mid = fits[max(len(fits) // 2 - n_twin // 2, 0):][:n_twin]
        twin = {}
        for off in range(0, len(mid), 8):
            chunk = []
            for i in mid[off:off + 8]:
                wx = pl.export_window(i)
                chunk.append((i, wx, poa_driver.admit_layers(
                    wx.lens, cfg.max_len)))
            packed = poa_driver._pack(chunk, cfg, 8)
            n, failed, edges = (np.asarray(x) for x in _twin_graphs(cfg)(
                *packed[:9]))
            assert not failed.any(), failed
            twin.update({i: (int(n[b]), int(edges[b]))
                         for b, (i, _, _) in enumerate(chunk)})
        pl.consensus_cpu_all()
        grown = pl.window_growth()
        return [(w, info[i][0] - 1, int(grown[i, 1]), int(grown[i, 2]),
                 *twin.get(i, (-1, -1))) for i in whole]
    finally:
        shutil.rmtree(d, ignore_errors=True)


def main(argv=None) -> int:
    import json

    from benchmark import loader

    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--twin", type=int, default=32)
    args = p.parse_args(argv)
    with open(os.path.join(loader.BENCH_DIR, "configs",
                           "ecoli-ont-cap.json")) as f:
        config = json.load(f)
    profile = {k: v for k, v in config["reads"].items() if k != "generator"}
    rows = np.array([r for c in COVERAGES for w, n_twin in (
        (config["polish_args"]["window_length"], 0), (128, args.twin))
        for r in study(c, w, n_twin, profile, config["polish_args"])])
    print("window  layers    engine  windows  nodes/base med  most   "
          "in-edges med  most  >5x nodes  >12 in-edges")
    for w in sorted(set(rows[:, 0]), reverse=True):
        for lo, hi in BINS:
            at = rows[(rows[:, 0] == w) & (rows[:, 1] >= lo)
                      & (rows[:, 1] <= hi)]
            for name, nodes, edges in (("host", 2, 3), ("twin", 4, 5)):
                got = at[at[:, nodes] > 0]
                if not len(got):
                    continue
                per = got[:, nodes] / w
                room = 5 * ((w + 127) // 128 * 128)
                print(f"{w:>6d}  {lo:>3d}-{hi:<3d}   {name:<6s} "
                      f"{len(got):>7d}  {np.median(per):>13.2f}  "
                      f"{per.max():>5.2f}  "
                      f"{np.median(got[:, edges]):>11.0f}  "
                      f"{got[:, edges].max():>4d}  "
                      f"{int((got[:, nodes] > room).sum()):>9d}  "
                      f"{int((got[:, edges] > 12).sum()):>12d}")
    both = rows[rows[:, 4] > 0]
    if len(both):
        ratio = both[:, 4] / both[:, 2]
        print(f"twin against host on the same {len(both)} windows of 128: "
              f"nodes x{np.median(ratio):.3f} (median), x{ratio.min():.3f} "
              f"to x{ratio.max():.3f}; in-edges "
              f"{int((both[:, 5] - both[:, 3]).min()):+d} to "
              f"{int((both[:, 5] - both[:, 3]).max()):+d}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
