"""Plain reference for the alignment mechanism: global alignment of one
pair at unit costs, the whole matrix, nothing else.

What the device's Hirschberg engine must agree with, written without
any of what makes it fast: no band, no midpoint splitting, no batching,
no kernels.  ``edit_distance`` is the optimal cost of turning the query
into the target (match 0; mismatch, insertion and deletion 1 each: the
reference racon's edlib NW configuration), and ``check_cigar`` says what
is wrong with a CIGAR for the pair: it has to consume both sequences
exactly and cost that distance.  An optimal alignment is not unique, so
paths are compared by cost, never op by op.

O(n x m) time, O(m) memory per row in NumPy: meant for the test suite's
pairs of a few hundred to a few thousand bases.
"""

from __future__ import annotations

import re

import numpy as np

_CIGAR = re.compile(r"(\d+)([MIDX=])")


def edit_distance(q: bytes, t: bytes) -> int:
    """Optimal unit-cost global alignment cost of ``q`` against ``t``."""
    qa, ta = np.frombuffer(q, np.uint8), np.frombuffer(t, np.uint8)
    cols = np.arange(len(ta) + 1, dtype=np.int64)
    row = cols.copy()                      # row 0: j insertions of target
    for i, c in enumerate(qa, 1):
        # diagonal and vertical moves are elementwise on the row above;
        # the horizontal chain new[j] = min(new[j], new[j-1] + 1) is a
        # running minimum of new[j] - j
        new = np.empty_like(row)
        new[0] = i
        np.minimum(row[:-1] + (ta != c), row[1:] + 1, out=new[1:])
        row = np.minimum.accumulate(new - cols) + cols
    return int(row[-1])


def cigar_cost(cigar: str, q: bytes, t: bytes) -> tuple:
    """(cost, query bases consumed, target bases consumed) of a CIGAR
    walked over the pair: M / = / X consume both (a differing pair costs
    1), I consumes the query, D the target (1 per base each)."""
    if cigar and _CIGAR.sub("", cigar):
        raise ValueError(f"not a CIGAR: {cigar[:60]!r}")
    cost = qi = ti = 0
    for n, op in _CIGAR.findall(cigar):
        n = int(n)
        if op == "I":
            cost, qi = cost + n, qi + n
        elif op == "D":
            cost, ti = cost + n, ti + n
        else:
            if qi + n > len(q) or ti + n > len(t):
                return cost, qi + n, ti + n       # runs past an end
            a = np.frombuffer(q, np.uint8)[qi:qi + n]
            b = np.frombuffer(t, np.uint8)[ti:ti + n]
            cost, qi, ti = cost + int((a != b).sum()), qi + n, ti + n
    return cost, qi, ti


def check_cigar(cigar: str, q: bytes, t: bytes) -> list:
    """Problems with ``cigar`` as a global alignment of ``q`` to ``t``;
    an empty list means valid and optimal."""
    cost, qi, ti = cigar_cost(cigar, q, t)
    problems = []
    if (qi, ti) != (len(q), len(t)):
        problems.append(f"consumes {qi} of {len(q)} query and {ti} of "
                        f"{len(t)} target bases")
        return problems
    best = edit_distance(q, t)
    if cost != best:
        problems.append(f"costs {cost}, the optimum is {best}")
    return problems
