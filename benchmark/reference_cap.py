"""What the depth cap means, recomputed from a cell's files alone (draft
FASTA, reads FASTQ, SAM): numpy only, nothing of the consensus driver.

The rule this file restates (``racon_tpu/ops/poa_driver.py``, docstring
at ``DEPTH_CAP``; PERF.md section 3):

(a) A window's layers are consumed in the order of their begin on the
    backbone, as ``std::sort`` leaves them: libstdc++'s introsort over
    the layers in the order ``build_windows`` added them (the overlaps'
    order in the file), comparing begins alone.  It is not stable, so
    among the window-spanning reads that all begin at 0 the permutation
    decides who comes first; :func:`std_sort_order` is that algorithm,
    transcribed, so the permutation can be recomputed.  The device path
    admits the first ``DEPTH_CAP`` of the layers that pass its length
    admission (1 to ``max_layer_len`` bases), in that order.  Hence: no
    kept layer begins after a dropped one.
(b) The device path trims a long-read consensus by the sequences it
    **admitted** (backbone + packed layers: coverage under
    ``admitted // 2`` goes, at both ends); the host path, and every
    window the host redoes, by the window's **full** count
    (``offered // 2``).  The two agree wherever nothing was dropped.
(c) The host path takes every layer, as upstream's CPU path does.

Per window: the layers offered (what racon's rules leave, as
``reference_depth.py`` counts them), the ones over the length admission,
the set admitted under the cap in consumption order, the layers and
bases the cap dropped, both trim thresholds, and on request the capped
consensus: the plain partial-order consensus of exactly the admitted
layers, by the plain engine at the end of this file (numpy, none of the
program's code).

Departures from upstream, and what is from memory (``assumed``;
upstream's files are not on this machine, the lines are SURVEY.md's and
the code's own comments'):

* upstream's accelerator fills a window's group with the layers in the
  order ``Window::sequences_`` holds them, stopping at
  ``MAX_DEPTH_PER_WINDOW`` = 200 (``cudapolisher.cpp:226``,
  ``cudabatch.cpp:139-163``); whether it sorts by begin first, as its
  CPU path does (``window.cpp:85-86``), is from memory: assumed it does
  not.  This program sorts on both paths and caps in the sorted order,
  so that the layers dropped are those that begin last;
* upstream rejects a layer longer than its batch's maximal sequence
  size (``cudabatch.cpp:141-160``); here the bound is the window
  class's ``max_len`` (1.5 x the 128-grid class of the backbone);
* upstream's trim by ``seqs_added_per_window_`` (``cudabatch.cpp:233``)
  and the CPU rule (``window.cpp:125-146``) are as the driver's comment
  in ``_install`` cites them;
* the plain engine (:func:`consensus`) is the program's host engine's
  algorithm, written a second time: upstream's is spoa, whose graph and
  ties this does not claim (the host engine's own departures from spoa
  are in ``rt_poa.hpp``).  The device's kernels are a third writing:
  on windows whose layers agree on the consensus all are equal; at the
  tail of a noisy window, where many reads end and the heaviest path
  has score ties, the kernels may part from the other two by an edit,
  as they do on uncapped windows (``tests/test_deep_cell.py``);
* one SAM record a read (the generator's); with several, the order of a
  read's layers would be upstream's filter's, which is not restated
  here.
"""

from __future__ import annotations

import numpy as np

from . import reference_depth

DEPTH_CAP = 200       # upstream: MAX_DEPTH_PER_WINDOW, cudapolisher.cpp:226

_THRESHOLD = 16       # libstdc++ std::sort: _S_threshold


def _insert_unguarded(a, key, last):
    val, nxt = a[last], last - 1
    while key(val) < key(a[nxt]):
        a[last] = a[nxt]
        last, nxt = nxt, nxt - 1
    a[last] = val


def _insertion(a, key, first, last):
    for i in range(first + 1, last):
        if key(a[i]) < key(a[first]):
            a[first:i + 1] = [a[i]] + a[first:i]
        else:
            _insert_unguarded(a, key, i)


def _adjust_heap(a, key, first, hole, length, value):
    top, child = hole, hole
    while child < (length - 1) // 2:
        child = 2 * (child + 1)
        if key(a[first + child]) < key(a[first + child - 1]):
            child -= 1
        a[first + hole] = a[first + child]
        hole = child
    if length % 2 == 0 and child == (length - 2) // 2:
        child = 2 * (child + 1)
        a[first + hole] = a[first + child - 1]
        hole = child - 1
    parent = (hole - 1) // 2
    while hole > top and key(a[first + parent]) < key(value):
        a[first + hole] = a[first + parent]
        hole, parent = parent, (parent - 1) // 2
    a[first + hole] = value


def _heap_sort(a, key, first, last):
    length = last - first
    for parent in range((length - 2) // 2, -1, -1):
        _adjust_heap(a, key, first, parent, length, a[first + parent])
    while last - first > 1:
        last -= 1
        value, a[last] = a[last], a[first]
        _adjust_heap(a, key, first, 0, last - first, value)


def _introsort(a, key, first, last, depth_limit):
    while last - first > _THRESHOLD:
        if depth_limit == 0:
            _heap_sort(a, key, first, last)
            return
        depth_limit -= 1
        # median of first + 1, the middle and last - 1 to first ...
        x, y, z = first + 1, first + (last - first) // 2, last - 1
        if key(a[x]) < key(a[y]):
            m = y if key(a[y]) < key(a[z]) else \
                z if key(a[x]) < key(a[z]) else x
        else:
            m = x if key(a[x]) < key(a[z]) else \
                z if key(a[y]) < key(a[z]) else y
        a[first], a[m] = a[m], a[first]
        # ... and the unguarded partition about it
        lo, hi, pivot = first + 1, last, key(a[first])
        while True:
            while key(a[lo]) < pivot:
                lo += 1
            hi -= 1
            while pivot < key(a[hi]):
                hi -= 1
            if not lo < hi:
                break
            a[lo], a[hi] = a[hi], a[lo]
            lo += 1
        _introsort(a, key, lo, last, depth_limit)
        last = lo


def std_sort_order(keys) -> list:
    """The permutation libstdc++'s ``std::sort`` leaves of ``0 .. n-1``
    compared by ``keys`` alone (``bits/stl_algo.h``: introsort to runs of
    16 with the median of three moved to the front, heap sort past
    2 log2 n levels, then one insertion pass, the first 16 guarded).  The
    native engine sorts a window's layers so on both paths
    (``rt_window.cpp``, ``rt_capi.cpp``); transcribed from memory of that
    header, and held to the engine's own order by
    ``tests/test_cap_cell.py``."""
    keys = [int(k) for k in keys]
    a, n = list(range(len(keys))), len(keys)
    if n:
        _introsort(a, keys.__getitem__, 0, n, 2 * (n.bit_length() - 1))
        if n > _THRESHOLD:
            _insertion(a, keys.__getitem__, 0, _THRESHOLD)
            for i in range(_THRESHOLD, n):
                _insert_unguarded(a, keys.__getitem__, i)
        else:
            _insertion(a, keys.__getitem__, 0, n)
    return a


def max_layer_len(bb_len: int) -> int:
    """The device path's length admission for a window: its class's
    ``max_len``, 1.5 x the backbone's 128-grid class, on the 128 grid."""
    wl_class = max(128, (int(bb_len) + 127) // 128 * 128)
    return (wl_class + wl_class // 2 + 127) // 128 * 128


class CapReference:
    """A cell's windows under the cap.  Arrays of one entry a window:
    ``bb_len``, ``offered`` (layers the window holds), ``too_long`` (of
    them, over the length admission), ``admitted``, ``capped`` (layers
    the cap dropped), ``admitted_bases``, ``capped_bases``,
    ``trim_accelerator`` (``admitted // 2``) and ``trim_cpu``
    (``offered // 2``).  ``layers[w]`` is the window's offered layers in
    the order they were added, as rows (begin, end, overlap, first read
    index, last read index); ``order[w]`` their consumption order and
    ``kept[w]`` the admitted ones, in that order, both as indices into
    ``layers[w]``."""

    def __init__(self, draft_path: str, reads_path: str, sam_path: str, *,
                 window_length: int, quality_threshold: float,
                 error_threshold: float, depth_cap: int = DEPTH_CAP):
        (contig, draft), = list(
            reference_depth.read_fasta(draft_path).items())[:1]
        quals = reference_depth.read_fastq_qualities(reads_path)
        w = self.window_length = int(window_length)
        self.draft = draft
        n_win = (len(draft) + w - 1) // w
        self.bb_len = np.minimum(
            w, len(draft) - np.arange(n_win) * w).astype(np.int64)

        best = {}                             # read -> its longest overlap
        with open(sam_path, "rb") as f:
            for line in f:
                if line.startswith(b"@"):
                    continue
                c = line.rstrip(b"\n").split(b"\t")
                if c[2].decode() != contig or c[5] == b"*":
                    continue
                ov = reference_depth.Overlap(
                    c[0].decode(), int(c[1]), int(c[3]) - 1, c[5], c[9], w)
                if not ov.q_span or ov.error() > error_threshold:
                    continue
                if ov.name not in best or ov.q_span > best[ov.name].q_span:
                    best[ov.name] = ov
        self.overlaps = list(best.values())
        self.quals = []
        rows = [[] for _ in range(n_win)]
        for no, ov in enumerate(self.overlaps):
            qual = quals.get(ov.name)
            qual = None if qual is None or not len(qual) else \
                (qual[::-1] if ov.reverse else qual)
            self.quals.append(qual)
            win = ov.m_t // w
            edge = np.flatnonzero(np.diff(win)) + 1
            lo = np.concatenate([[0], edge])
            hi = np.concatenate([edge, [len(win)]]) - 1
            q0, q1 = ov.m_q[lo], ov.m_q[hi]
            ok = q1 - q0 + 1 >= 0.02 * w
            if qual is not None:
                total = np.concatenate([[0], np.cumsum(
                    qual.astype(np.int64) - 33)])
                ok &= ((total[q1 + 1] - total[q0]) / (q1 - q0 + 1)
                       >= quality_threshold)
            for k in np.flatnonzero(ok):
                at = int(win[lo[k]])
                rows[at].append((int(ov.m_t[lo[k]]) - at * w,
                                 int(ov.m_t[hi[k]]) - at * w, no,
                                 int(q0[k]), int(q1[k])))
        self.layers = [np.array(r, np.int64).reshape(-1, 5) for r in rows]

        self.order, self.kept = [], []
        cols = {k: np.zeros(n_win, np.int64) for k in (
            "offered", "too_long", "admitted", "capped", "admitted_bases",
            "capped_bases")}
        for at, lay in enumerate(self.layers):
            order = np.array(std_sort_order(lay[:, 0]), np.int64)
            length = lay[order, 4] - lay[order, 3] + 1
            fits = (length > 0) & (length <= max_layer_len(self.bb_len[at]))
            kept, dropped = order[fits][:depth_cap], order[fits][depth_cap:]
            self.order.append(order)
            self.kept.append(kept)
            cols["offered"][at] = len(lay)
            cols["too_long"][at] = int((~fits).sum())
            cols["admitted"][at] = len(kept)
            cols["capped"][at] = len(dropped)
            cols["admitted_bases"][at] = int(
                (lay[kept, 4] - lay[kept, 3] + 1).sum())
            cols["capped_bases"][at] = int(
                (lay[dropped, 4] - lay[dropped, 3] + 1).sum())
        for name, col in cols.items():
            setattr(self, name, col)
        self.trim_accelerator = self.admitted // 2
        self.trim_cpu = self.offered // 2

    def layer(self, window: int, k: int) -> tuple:
        """(bases, PHRED + 33 qualities or None, begin, end) of a
        window's k-th offered layer, on the draft's strand."""
        begin, end, no, q0, q1 = (int(x) for x in self.layers[window][k])
        qual = self.quals[no]
        return (self.overlaps[no].seq[q0:q1 + 1].tobytes(),
                None if qual is None else qual[q0:q1 + 1].tobytes(),
                begin, end)

    def backbone(self, window: int) -> bytes:
        w = self.window_length
        return self.draft[window * w:(window + 1) * w].tobytes()

    def capped_consensus(self, window: int, *, match: int, mismatch: int,
                         gap: int, trim: bool = True,
                         every_layer: bool = False) -> bytes:
        """The plain partial-order consensus (:func:`consensus`) of
        exactly the admitted layers, in consumption order (of every
        offered layer with ``every_layer``: what the host path makes of
        the window), trimmed by the count of the layers it was given:
        the accelerator's rule for the admitted set, the CPU's for the
        full one."""
        ks = self.order[window] if every_layer else self.kept[window]
        return consensus(self.backbone(window),
                         [self.layer(window, int(k)) for k in ks],
                         match=match, mismatch=mismatch, gap=gap, trim=trim)


# -- the plain engine -------------------------------------------------------
#
# Partial-order alignment as racon runs it on a window, in numpy: a graph
# whose nodes live in columns (one node a distinct base, a column a place
# of the alignment, ordered by a real key: backbone column i has key i,
# an inserted column a key between its neighbours'), a layer aligned to
# the graph end to end by dynamic programming with a linear gap cost
# (spoa's kNW) over the columns its span on the backbone names, added
# along the alignment with its base qualities as edge weights, and the
# heaviest path read off.  The algorithm and every tie (which of several
# equal predecessors a traceback takes, which of equal end nodes, which
# of equal edges the heaviest path) are those of the program's host
# engine (``racon_tpu/native/src/rt_poa.cpp``, ``rt_window.cpp``), of
# which this is a second writing in another language with none of its
# code: ``tests/test_cap_cell.py`` holds the two to each other on whole
# windows, so an error in either shows.


class _Graph:
    def __init__(self):
        self.base, self.col, self.cover = [], [], []
        self.into, self.out = [], []          # edge ids a node
        self.src, self.dst, self.weight = [], [], []
        self.key, self.members = [], []       # a column

    def _column(self, key: float) -> int:
        self.key.append(key)
        self.members.append([])
        return len(self.key) - 1

    def _node(self, base: int, col: int) -> int:
        self.base.append(base)
        self.col.append(col)
        self.cover.append(0)
        self.into.append([])
        self.out.append([])
        self.members[col].append(len(self.base) - 1)
        return len(self.base) - 1

    def _edge(self, a: int, b: int, w: int) -> None:
        for e in self.out[a]:
            if self.dst[e] == b:
                self.weight[e] += w
                return
        self.src.append(a)
        self.dst.append(b)
        self.weight.append(w)
        self.out[a].append(len(self.src) - 1)
        self.into[b].append(len(self.src) - 1)

    def add(self, alignment, seq, weights) -> None:
        """A sequence along ``alignment``, pairs (node or -1, position
        or -1); none: a fresh chain, the backbone."""
        n = len(seq)
        at = [-1] * n
        for node, pos in alignment:
            if node != -1 and pos != -1:
                at[pos] = node
        first_key = float(np.floor(max(self.key, default=-1.0)) + 1.0)
        prev = prev_pos = -1
        pos = 0
        while pos < n:
            b = int(seq[pos])
            if not alignment:
                node = self._node(b, self._column(first_key + pos))
            elif at[pos] != -1:
                col = self.col[at[pos]]
                node = next((m for m in self.members[col]
                             if self.base[m] == b), -1)
                if node == -1:
                    node = self._node(b, col)
            else:
                # an inserted run: each base a column of its own, its
                # key dividing what is left between the column before
                # and the next matched one
                end = pos
                while end < n and at[end] == -1:
                    end += 1
                run = end - pos
                if end < n:
                    hi = self.key[self.col[at[end]]]
                elif prev != -1:
                    hi = self.key[self.col[prev]] + 1.0
                else:
                    hi = max(self.key) + float(run) + 1.0
                lo = self.key[self.col[prev]] if prev != -1 \
                    else hi - run - 1.0
                node = self._node(b, self._column(
                    lo + (hi - lo) / (run + 1.0)))
            self.cover[node] += 1
            if prev != -1:
                self._edge(prev, node,
                           int(weights[prev_pos]) + int(weights[pos]))
            prev, prev_pos = node, pos
            pos += 1

    def align(self, seq, lo: float, hi: float, match: int, mismatch: int,
              gap: int) -> list:
        """``seq`` against the nodes whose column key lies in [lo, hi],
        end to end; pairs (node or -1, position or -1) from the start."""
        key = np.array(self.key)[np.array(self.col)]
        sub = np.flatnonzero((key >= lo) & (key <= hi))
        sub = sub[np.lexsort((sub, key[sub]))]
        S, L = len(sub), len(seq)
        if not S or not L:
            return []
        rank = np.zeros(len(self.base), np.int64)
        rank[sub] = np.arange(1, S + 1)
        preds = [[int(rank[self.src[e]]) for e in self.into[u]
                  if rank[self.src[e]]] for u in sub.tolist()]
        ramp = np.arange(L + 1, dtype=np.int64) * gap
        H = np.empty((S + 1, L + 1), np.int64)
        H[0] = ramp
        profile = {}
        for r in range(1, S + 1):
            b = self.base[sub[r - 1]]
            if b not in profile:
                profile[b] = np.where(seq == b, match, mismatch)
            pf, row = profile[b], H[r]
            for n, p in enumerate(preds[r - 1] or [0]):
                prow = H[p]
                cand = np.maximum(prow[:-1] + pf, prow[1:] + gap)
                if n:
                    np.maximum(row[1:], cand, out=row[1:])
                    row[0] = max(row[0], prow[0] + gap)
                else:
                    row[1:] = cand
                    row[0] = prow[0] + gap
            # a run of gaps along the row: a running maximum, less the
            # ramp
            row[:] = np.maximum.accumulate(row - ramp) + ramp

        ends = [r for r in range(1, S + 1)
                if not any(rank[self.dst[e]] for e in self.out[sub[r - 1]])]
        r = ends[int(np.argmax(H[ends, L]))]       # the first of equals
        j, back = L, []
        while r or j:
            if not r:
                back.append((-1, j - 1))
                j -= 1
                continue
            u = int(sub[r - 1])
            cur = H[r, j]
            sc = (match if seq[j - 1] == self.base[u] else mismatch) \
                if j else 0
            ps = preds[r - 1] or [0]
            p = next((p for p in ps if j and H[p, j - 1] + sc == cur), None)
            if p is not None:
                back.append((u, j - 1))
                r, j = p, j - 1
                continue
            p = next((p for p in ps if H[p, j] + gap == cur), None)
            if p is not None:
                back.append((u, -1))
                r = p
                continue
            back.append((-1, j - 1))
            j -= 1
        return back[::-1]

    def heaviest_path(self) -> tuple:
        """(bases, coverage of each chosen node) of the heaviest path,
        source to sink."""
        n = len(self.base)
        order = sorted(range(n), key=lambda u: (self.key[self.col[u]], u))
        score, pred = [0] * n, [-1] * n
        top = order[0]
        for u in order:
            best = (-1, -1)
            for e in self.into[u]:
                cand = (self.weight[e], score[self.src[e]])
                if cand > best:
                    best, pred[u] = cand, self.src[e]
            if pred[u] != -1:
                score[u] = best[0] + best[1]
            if score[u] > score[top]:
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
                if cand > best:
                    best, nxt = cand, self.dst[e]
            u = nxt
            path.append(u)
        return (bytes(self.base[v] for v in path),
                [self.cover[v] for v in path])


def consensus(backbone: bytes, layers, *, match: int, mismatch: int,
              gap: int, trim: bool = True) -> bytes:
    """The consensus of a long-read window: ``layers`` are (bases,
    PHRED + 33 qualities or None, begin, end on the backbone), consumed
    in the order given.  A layer that spans the window to within 1 % of
    its length at both ends is aligned to the whole graph, any other to
    the columns of its span.  The backbone weighs nothing (a draft has
    no qualities), a layer's base its quality, or 1 without.  Under two
    layers the backbone comes back.  With ``trim`` the ends go where
    fewer than half as many sequences as the layers given pass through
    the chosen node (racon's rule for long reads, ``window.cpp:125-146``)."""
    if len(layers) < 2:
        return backbone
    graph = _Graph()
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
    out, cover = graph.heaviest_path()
    if trim:
        least = len(layers) // 2
        ok = [k for k, c in enumerate(cover) if c >= least]
        if ok and ok[0] < ok[-1]:
            out = out[ok[0]:ok[-1] + 1]
    return out
