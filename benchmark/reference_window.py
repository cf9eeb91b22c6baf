"""What a window length asks of the program, recomputed from the cell's
files alone (draft FASTA, reads FASTQ, PAF) and the length: numpy only,
nothing of the program.

``lambda-ont-w1000`` is ``lambda-ont`` with one argument changed, ``-w``,
so everything that argument decides is said here once, for any length:

1. The windows of each target (:func:`windows`): fixed chunks of ``w``
   bases from the target's first base, the remainder a window of its own
   (the **tail**), however short; a target of 47 251 bp is 47 windows of
   1000 and a tail of 251 at ``-w 1000``, 94 of 500 and a tail of 251 at
   ``-w 500`` (``rt_pipeline.cpp`` ``build_windows``, upstream
   ``src/polisher.cpp:388-403``).
2. Per window the pieces racon's rules offer, drop and admit
   (:func:`window_pieces`, through ``reference_layout.window_layers``:
   one piece per overlap and window, from the first to the last aligned
   pair inside the window).  A piece of fewer read bases than 2 % of the
   window **length asked for** is dropped as short (``:415``): under 20
   bases at ``-w 1000``, under 10 at ``-w 500``, in a tail as in a whole
   window (:func:`short_floor`; a tail shorter than that floor admits
   nothing); then one whose mean base quality is under ``-q``.
3. The nodes an exact partial-order graph of the admitted layers holds
   (``reference_depth.window_demand``), hence the node rung each window
   needs (:func:`rungs_needed`) under the capacities its class gets
   (:func:`rung_capacities`: 3 x and 5 x the backbone's 128-lane class,
   the driver's ``NODE_RUNGS`` restated), and how many windows need more
   than the base rung and more than the upper one.  The exact graph
   bounds the program's from above (``reference_depth``'s docstring), so
   a rung that holds this many nodes holds the window, and a window the
   program served held no more nodes than this.

An optimal alignment is not unique, so a piece's first or last base can
differ by a few from the program's: counts per window are what is
compared, never paths.
"""

from __future__ import annotations

import numpy as np

from . import reference_depth, reference_layout

#: racon drops a piece of fewer read bases than this share of ``-w``
SHORT_SHARE = 0.02
#: graph slots of a rung over the backbone's class, smallest rung first
RUNG_FACTORS = (3, 5)


def windows(draft_path: str, window_length: int) -> dict:
    """Target name -> int64 array of its windows' backbone lengths, in
    order: ``w`` for every whole chunk, then the remainder if there is
    one (the tail)."""
    w = int(window_length)
    out = {}
    for name, bases in reference_depth.read_fasta(draft_path).items():
        whole, tail = divmod(len(bases), w)
        out[name] = np.array([w] * whole + ([tail] if tail else []),
                             np.int64)
    return out


def short_floor(window_length: int) -> float:
    """The fewest read bases a piece may hold and stay: 2 % of the
    window length asked for, whatever the window's own length."""
    return SHORT_SHARE * int(window_length)


def window_class(bb_len: int) -> int:
    """A backbone's kernel-geometry class: its length on the 128-lane
    grid, 128 at least."""
    return max(128, -(-int(bb_len) // 128) * 128)


def rung_capacities(bb_len: int, factors=RUNG_FACTORS) -> tuple:
    """Graph slots of each node rung of a backbone's class."""
    return tuple(f * window_class(bb_len) for f in factors)


def rungs_needed(nodes, bb_lens, factors=RUNG_FACTORS) -> np.ndarray:
    """Per window the index of the smallest rung that holds ``nodes``
    graph nodes; ``len(factors)`` where none does (beyond the top)."""
    return np.array([
        next((r for r, cap in enumerate(rung_capacities(bb, factors))
              if n <= cap), len(factors))
        for n, bb in zip(nodes, bb_lens)], np.int64)


def window_pieces(draft_path: str, reads_path: str, paf_path: str, *,
                  window_length: int, quality_threshold: float,
                  error_threshold: float, nodes: bool = True,
                  factors=RUNG_FACTORS) -> dict:
    """The first target's windows at this length: ``bb_len``, and per
    window ``offered``, ``dropped_short``, ``dropped_quality``,
    ``admitted``, ``layer_bases`` (``reference_layout.window_layers``)
    and, with ``nodes``: ``nodes`` (the exact graph's count), ``rung``
    (:func:`rungs_needed`), ``over_base`` / ``over_upper`` (how many
    windows need more than the first and more than the last rung).
    ``tail`` is the last window's length where it is a remainder, else
    0; ``short_floor`` the fewest bases of a piece that stays."""
    w = int(window_length)
    ref = reference_layout.window_layers(
        draft_path, reads_path, paf_path, window_length=w,
        quality_threshold=quality_threshold,
        error_threshold=error_threshold, nodes=nodes)
    bb_len = next(iter(windows(draft_path, w).values()))
    assert len(bb_len) == len(ref["offered"])
    out = {k: ref[k] for k in ("offered", "dropped_short",
                               "dropped_quality", "admitted",
                               "layer_bases", "overlaps")}
    out.update(bb_len=bb_len, short_floor=short_floor(w),
               tail=int(bb_len[-1]) if bb_len[-1] < w else 0)
    if nodes:
        # a window of fewer than two layers is the backbone passed
        # through: no graph is built for it
        need = np.where(ref["admitted"] >= 2, ref["nodes"], bb_len)
        rung = rungs_needed(need, bb_len, factors)
        out.update(nodes=ref["nodes"], rung=rung,
                   over_base=int((rung > 0).sum()),
                   over_upper=int((rung >= len(factors)).sum()))
    return out
