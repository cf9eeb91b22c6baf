"""What a whole-draft SAM job asks of the consensus launch stream,
recomputed from the cell's files alone (draft FASTA, reads FASTQ, SAM):
numpy only, nothing of the program.

``ecoli-ont-full-x4`` is ``ecoli-ont-x4`` with its one cut undone, so
what the size decides is said here once, for any size:

1. Per window the layers racon's rules offer it (:func:`window_offers`:
   ``reference_depth``'s rules, one SAM record at a time so that 17 250
   overlaps are never held together), hence its **depth bucket**
   (:func:`depth_bucket`: the driver's ``DEPTH_BUCKETS`` 8 / 32 / 200,
   restated; by the layers offered, cut at the depth cap, as the
   driver's metadata pass buckets a window before any layer is packed)
   and its class (``reference_window.window_class``).  A window of
   fewer than two layers is the backbone passed through and joins no
   group.
2. The windows each (bucket, class) **group** holds (:func:`groups`).
   The program counts the same as ``poa.windows.d<bucket>.c<class>``,
   except that a window it sends to the upper node rung runs in the
   ``DEPTH_CAP`` bucket's program of its class whatever its depth and is
   counted there: with ``u`` windows on the upper rung, up to ``u`` of a
   class's shallower windows may read under ``d200``; with none the
   counts are equal.
3. From the group sizes and a launch's rows (shards x rows a shard,
   :data:`ROWS_PER_SHARD` = one program of thirty-two windows a chip)
   the launches, full launches and pad rows of the job
   (:func:`launch_bounds`).  A group is served in launches of ``rows``
   windows, the last one part full.  Which windows climb is the
   program's rung rule, not restated here, so the bounds hold for
   **any** split of a class's groups over the node rungs: every climber
   of a class joins one more group (its ``DEPTH_CAP`` bucket on the
   upper rung).  ``unsplit`` is what the job takes if nothing climbs.
4. The consensus of a window by ``reference_cap``'s plain engine
   (:func:`sample_reference`: the records that touch the sampled
   windows, through ``reference_cap.CapReference``), for the builder's
   window-for-window comparison with what the chip installed, and
   whether that consensus hangs on a tie (:func:`tie_free`).

The output's reference is the host path, as in every cell; this file
holds the launch stream's counters and a sample of windows to it.
"""

from __future__ import annotations

import os
import re
import tempfile

import numpy as np

from . import reference_cap, reference_depth, reference_window

#: the driver's depth buckets (``poa_driver.DEPTH_BUCKETS``), restated
DEPTH_BUCKETS = (8, 32, 200)
#: windows a shard of a mesh launch holds: one program of thirty-two
ROWS_PER_SHARD = 32

_REF_SPAN = re.compile(rb"(\d+)[MDN=X]")


def depth_bucket(layers: int, buckets=DEPTH_BUCKETS) -> int:
    """The smallest bucket that holds a window's layers, cut at the
    last (the depth cap)."""
    return next(b for b in buckets if min(int(layers), buckets[-1]) <= b)


def window_offers(draft_path: str, reads_path: str, sam_path: str, *,
                  window_length: int, quality_threshold: float,
                  error_threshold: float) -> dict:
    """Per window of the first (only) draft contig, int64 arrays of one
    entry a window: ``bb_len`` and ``offered``, the pieces racon's rules
    leave it (``reference_depth``'s docstring: one piece per overlap and
    window, 2 % of the window length at least, mean quality ``-q`` at
    least, the overlap's error ``-e`` at most, a read's longest overlap
    only)."""
    (contig, draft), = list(reference_depth.read_fasta(draft_path).items())[:1]
    quals = reference_depth.read_fastq_qualities(reads_path)
    w = int(window_length)
    n_win = (len(draft) + w - 1) // w
    best = {}                     # read -> (span, windows of its pieces)
    with open(sam_path, "rb") as f:
        for line in f:
            if line.startswith(b"@"):
                continue
            c = line.rstrip(b"\n").split(b"\t")
            if c[2].decode() != contig or c[5] == b"*":
                continue
            ov = reference_depth.Overlap(c[0].decode(), int(c[1]),
                                         int(c[3]) - 1, c[5], c[9], w)
            if not ov.q_span or ov.error() > error_threshold:
                continue
            if ov.name in best and ov.q_span <= best[ov.name][0]:
                continue
            win, _begin, q0, q1 = ov.pieces()
            n_bases = q1 - q0 + 1
            ok = n_bases >= reference_window.short_floor(w)
            qual = quals.get(ov.name)
            if qual is not None and len(qual):
                qual = (qual[::-1] if ov.reverse else qual).astype(
                    np.int64) - 33
                total = np.concatenate([[0], np.cumsum(qual)])
                ok &= ((total[q1 + 1] - total[q0]) / n_bases
                       >= quality_threshold)
            best[ov.name] = (ov.q_span, win[ok])
    offered = np.zeros(n_win, np.int64)
    for _span, win in best.values():
        np.add.at(offered, win, 1)
    bb_len = np.minimum(w, len(draft) - np.arange(n_win) * w)
    return {"bb_len": bb_len.astype(np.int64), "offered": offered}


def groups(bb_len, offered, buckets=DEPTH_BUCKETS) -> dict:
    """(depth bucket, window class) -> windows, over the windows that
    reach a kernel (two layers at least)."""
    out = {}
    for bb, k in zip(bb_len, offered):
        if k >= 2:
            key = (depth_bucket(k, buckets), reference_window.window_class(bb))
            out[key] = out.get(key, 0) + 1
    return out


def _class_bounds(sizes: list, rows: int) -> dict:
    """Bounds for one class whose depth-bucket groups hold ``sizes``
    windows, over every way of sending some of them to one more group.
    With ``N`` windows in ``G`` groups of at least one window each, the
    launches are sum ceil(n / rows) = G + sum floor((n - 1) / rows):
    least with everything in one group, most with every group but one
    at a single window; full launches sum floor(n / rows) are most in
    one group and least where every group's last launch holds as much
    as it can short of full: ``rows - 1`` windows, or all the group's
    bucket has."""
    sizes = [n for n in sizes if n]
    N = sum(sizes)
    if not N:
        return {k: (0, 0) for k in ("launches", "full", "pad_rows")} | {
            "unsplit": {"launches": 0, "full": 0, "pad_rows": 0}}
    G = min(len(sizes) + 1, N)      # one more group for the climbers
    least = -(-N // rows)
    most = G + (N - G) // rows
    unsplit = sum(-(-n // rows) for n in sizes)
    part_full = sum(min(rows - 1, n) for n in sizes) + (
        min(rows - 1, N) if G > len(sizes) else 0)
    return {"launches": (least, most),
            "full": (max(0, -(-(N - part_full) // rows)), N // rows),
            "pad_rows": (least * rows - N, most * rows - N),
            "unsplit": {"launches": unsplit,
                        "full": sum(n // rows for n in sizes),
                        "pad_rows": unsplit * rows - N}}


def launch_bounds(group_sizes: dict, shards: int,
                  rows_per_shard: int = ROWS_PER_SHARD) -> dict:
    """``launches``, ``full`` (launches whose every row is a window) and
    ``pad_rows`` of a job whose (bucket, class) groups hold
    ``group_sizes`` windows, each as (least, most) over any split of the
    groups over node rungs, at ``shards x rows_per_shard`` rows a
    launch; ``unsplit``: the three if no window climbs; ``rows``: a
    launch's rows; ``windows``: the job's."""
    rows = int(shards) * int(rows_per_shard)
    total = {"launches": [0, 0], "full": [0, 0], "pad_rows": [0, 0]}
    unsplit = {"launches": 0, "full": 0, "pad_rows": 0}
    for wl_class in sorted({c for _, c in group_sizes}):
        b = _class_bounds([n for (_, c), n in sorted(group_sizes.items())
                           if c == wl_class], rows)
        for key, pair in total.items():
            pair[0] += b[key][0]
            pair[1] += b[key][1]
            unsplit[key] += b["unsplit"][key]
    return {**{k: tuple(v) for k, v in total.items()}, "unsplit": unsplit,
            "rows": rows, "windows": sum(group_sizes.values())}


def sampled_windows(n_windows: int, count: int) -> list:
    """``count`` windows spread evenly over a contig of ``n_windows``,
    both end windows among them."""
    return sorted({int(round(x)) for x in
                   np.linspace(0, n_windows - 1, min(count, n_windows))})


def sample_reference(draft_path: str, reads_path: str, sam_path: str,
                     windows, *, window_length: int,
                     quality_threshold: float, error_threshold: float):
    """``reference_cap.CapReference`` over the SAM records that touch
    one of ``windows`` (in the file's order, which is the order the
    consumption order starts from): complete for those windows, light
    enough for a 4.6 Mbp job.  Every other window's arrays count only
    the records that happened to be kept."""
    w = int(window_length)
    wanted = np.zeros(max(windows) + 2, bool)
    wanted[list(windows)] = True
    with tempfile.TemporaryDirectory() as tmp:
        part = os.path.join(tmp, "sampled.sam")
        with open(sam_path, "rb") as src, open(part, "wb") as dst:
            for line in src:
                if not line.startswith(b"@"):
                    c = line.split(b"\t", 6)
                    lo = (int(c[3]) - 1) // w
                    hi = (int(c[3]) - 1 + sum(
                        int(n) for n in _REF_SPAN.findall(c[5]))) // w
                    if not wanted[lo:hi + 1].any():
                        continue
                dst.write(line)
        return reference_cap.CapReference(
            draft_path, reads_path, part, window_length=w,
            quality_threshold=quality_threshold,
            error_threshold=error_threshold)


class _LastOfEquals(reference_cap._Graph):
    """The plain engine's graph, reading the heaviest path the other
    way wherever two edges weigh the same on equal scores: the last of
    equals where the engine takes the first."""

    def heaviest_path(self) -> tuple:
        n = len(self.base)
        order = sorted(range(n), key=lambda u: (self.key[self.col[u]], u))
        score, pred = [0] * n, [-1] * n
        top = order[0]
        for u in order:
            best = (-1, -1)
            for e in self.into[u]:
                cand = (self.weight[e], score[self.src[e]])
                if cand >= best:
                    best, pred[u] = cand, self.src[e]
            if pred[u] != -1:
                score[u] = best[0] + best[1]
            if score[u] >= score[top]:
                top = u
        path, u = [], top
        while u != -1:
            path.append(u)
            u = pred[u]
        path.reverse()
        u = top
        while self.out[u]:
            best, nxt = (-1, -1), -1
            for e in self.out[u]:
                cand = (self.weight[e], score[self.dst[e]])
                if cand >= best:
                    best, nxt = cand, self.dst[e]
            u = nxt
            path.append(u)
        return (bytes(self.base[v] for v in path),
                [self.cover[v] for v in path])


def tie_free(ref, window: int, *, match: int, mismatch: int, gap: int,
             trim: bool = True) -> bool:
    """Whether a window's consensus is the same whichever of equal
    edges the heaviest path takes: ``reference_cap.consensus`` written
    out over :class:`_LastOfEquals`, against the plain engine's own
    answer.  Where the two differ, the engines (the host's, the
    kernel's, the plain one) may each be right with another answer
    (``reference_cap``'s docstring), and a byte-for-byte comparison of
    that window says nothing."""
    backbone = ref.backbone(window)
    layers = [ref.layer(window, int(k)) for k in ref.kept[window]]
    if len(layers) < 2:
        return True
    graph = _LastOfEquals()
    bb = np.frombuffer(backbone, np.uint8)
    graph.add([], bb, np.zeros(len(bb), np.int64))
    edge = int(0.01 * len(bb))
    for bases, qual, begin, end in layers:
        seq = np.frombuffer(bases, np.uint8)
        whole = begin < edge and end > len(bb) - edge
        lo, hi = (-np.inf, np.inf) if whole else (float(begin), float(end))
        weights = np.ones(len(seq), np.int64) if qual is None else \
            np.frombuffer(qual, np.uint8).astype(np.int64) - 33
        graph.add(graph.align(seq, lo, hi, match, mismatch, gap), seq,
                  weights)
    last, cover = graph.heaviest_path()
    if trim:
        ok = [k for k, c in enumerate(cover) if c >= len(layers) // 2]
        if ok and ok[0] < ok[-1]:
            last = last[ok[0]:ok[-1] + 1]
    return last == reference_cap.consensus(
        backbone, layers, match=match, mismatch=mismatch, gap=gap, trim=trim)
