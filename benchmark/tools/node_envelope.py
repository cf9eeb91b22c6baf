#!/usr/bin/env python3
"""Reads ``poa_driver.NODE_ENVELOPE`` off the host engine's graphs.

    python3 benchmark/tools/node_envelope.py <cell> [<cell> ...]

For each cell: its data at seed 0 (the full size, on the CPU), the host
path's windows (``Pipeline.initialize`` + ``consensus_cpu_all``), and per
long-read window of a whole class the growth the driver's rung rule reads
(``poa_driver.window_growth``: sqrt(layer bases x stray bases) / backbone)
beside the nodes the host engine's graph held
(``Pipeline.window_growth``'s second column).  Prints, per growth key of
the table, the most nodes per backbone base among the windows under the
next key, by cell and over all: the table's definition.  PR 41's table is
this over the four one-chip ONT cells, the fragment cell and
``lambda-ont.paf``, plus the ONT profile at 60x (counts, no timing: a CPU
run may make them).
"""

from __future__ import annotations

import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def windows(cell_name: str) -> np.ndarray:
    """(growth, nodes per backbone base) of the cell's long-read windows
    the host engine polished."""
    from benchmark import loader, prepare
    from racon_tpu.ops import poa_driver
    from racon_tpu.pipeline import Pipeline

    cell = loader.load_cell(cell_name)
    d, data = prepare.ensure_data(cell, 0, rehearsal=False)
    pl = Pipeline(*prepare.inputs(d, data["params"]),
                  **dict(cell.config["polish_args"],
                         num_threads=os.cpu_count() or 1))
    pl.initialize()
    pl.consensus_cpu_all()
    grown = pl.window_growth()
    rows = []
    for i in range(pl.num_windows()):
        _, bb, _, is_tgs, layer_bytes, _ = pl.window_info(i)
        if is_tgs and grown[i, 1] and bb >= 128:
            rows.append((poa_driver.window_growth(bb, layer_bytes,
                                                  int(grown[i, 0])),
                         grown[i, 1] / bb))
    return np.array(rows)


def main(cells) -> int:
    from racon_tpu.ops import poa_driver

    keys = [k for k, _ in poa_driver.NODE_ENVELOPE]
    per_cell = {c: windows(c) for c in cells}
    per_cell["all"] = np.concatenate(list(per_cell.values()))
    print("growth  table  " + "  ".join(f"{c:>18}" for c in per_cell))
    for (k, value), nxt in zip(poa_driver.NODE_ENVELOPE,
                               keys[1:] + [float("inf")]):
        cols = []
        for rows in per_cell.values():
            under = rows[rows[:, 0] < nxt]
            here = rows[(rows[:, 0] >= k) & (rows[:, 0] < nxt)]
            cols.append(f"{under[:, 1].max():.2f} (n {len(here)})"
                        if len(under) else "-")
        print(f"{k:6d}  {value:5.2f}  " + "  ".join(f"{c:>18}" for c in cols))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:] or ["lambda-ont.paf", "ecoli-ont.paf"]))
