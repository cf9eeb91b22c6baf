"""Operations and bytes the DP kernels need, from shapes and counters.

The closed forms are a copy of ``racon_tpu/obs/costmodel.py`` (PR 22):
``poa_window_cost`` and ``align_job_cost(tier="hirschberg")``.  Only the
operation and byte counts are taken; the model's ``serial_step_s`` and
every predicted wall are not (never measured on this device).  The
counters are the run's own: ``poa.cells.d<D>.c<C>`` (sum of admitted
depth x window class over a bucket's windows), ``poa.windows.d<D>.c<C>``
and ``align.cells.hirschberg`` (2 x max(n, m) x band per job: forward
and backward distance passes).
"""

from __future__ import annotations

import re

#: graph ranks per backbone position (costmodel.NODE_GROWTH)
NODE_GROWTH = 2.0
#: vector ops per POA DP cell: sub/ins/del merge, weight add, move
#: select, cummax contribution
POA_OPS_PER_CELL = 14.0
#: HBM bytes per admitted layer base (u8 code + i32 weight streamed in)
POA_LAYER_BYTES = 5.0
#: aligner DP: add/min/select + move byte per cell
ALIGN_OPS_PER_CELL = 10.0

_POA_CELLS = re.compile(r"^poa\.cells\.d(\d+)\.c(\d+)$")
_POA_WINDOWS = re.compile(r"^poa\.windows\.d(\d+)\.c(\d+)$")


def poa_ops_bytes(counters: dict) -> tuple:
    """(integer ops, HBM bytes) of the consensus DP a job's counters
    describe.  Per window: cells = depth x (NODE_GROWTH x class) x class;
    bytes = depth x class x 5 streamed in + 2 x class x 5 out."""
    ops = byts = 0.0
    for key, val in counters.items():
        m = _POA_CELLS.match(key)
        if m:
            wl_class = int(m.group(2))
            ops += val * NODE_GROWTH * wl_class * POA_OPS_PER_CELL
            byts += val * POA_LAYER_BYTES
            continue
        m = _POA_WINDOWS.match(key)
        if m:
            byts += val * 2 * int(m.group(2)) * 5
    return ops, byts


def align_ops_bytes(counters: dict, pair_bases: int) -> tuple:
    """(integer ops, HBM bytes) of the Hirschberg passes.  No moves
    matrix crosses HBM: the least the chip must read is each pair's two
    sequences once, ``pair_bases`` bytes (from the benchmark's own
    data: query length + target span over the job's overlaps)."""
    cells = counters.get("align.cells.hirschberg", 0)
    return cells * ALIGN_OPS_PER_CELL, float(pair_bases)
