"""Data for a stretch of a longer contig: the ``ont`` mode's reads,
clipped at the stretch's ends.

A module beside ``generate.py`` (which later PRs may not edit), named by
a configuration as ``"generator": "generate_stretch:mode_ont_stretch"``.
It reuses ``generate._mutate`` (the ONT error draw), ``_rngs``,
``_genome_and_draft`` and ``_Writer``.

Why.  ``generate.mode_ont`` draws a read's start uniformly over the
places where the whole read fits a *linear* genome of ``genome_mbp``, so
depth tapers to nothing over the last read length at either end.  On the
source's 4.6 Mbp that taper is 16 kb of 4 600 (0.35 % of the windows);
on a job cut to 0.1 Mbp it is 16 kb of 100 (16 %), and at 180x racon's
own trim (coverage under half the window's sequences goes) eats into the
few windows at each end that are polished from staggered layers: the
same ~180 to ~580 bases a job whatever its length, which a 4.6 Mbp job
does not notice and a 0.1 Mbp job cannot pay (``judge.py`` wants under a
quarter of the draft's edits).  A cut in scale should not change the
shape, so this mode cuts the job the other way: the draft is a stretch
of ``genome_mbp`` out of the *inside* of a longer contig, and a read
that crosses one of its ends is clipped there, which is what the windows
of that stretch hold of it in the full job (racon cuts every read into
pieces at window boundaries anyway).  Every window then has the depth
the configuration states, the two at the ends included.

Reads fall as in ``mode_ont``: gamma(4) lengths around ``mean_read``
clipped to [min(500, mean), 40000], ``coverage`` / ``mean_read`` reads
a base, a start uniform over a contig that runs ``FLANK`` = 40 000
bases (the longest read) past the stretch on either side.  A read that
keeps fewer bases of the stretch than the shortest read the mode draws
(min(500, mean)) is left out, as a mapper reports no alignment that
short; one that misses the stretch is never written.  Only the bases of
the stretch are drawn (the flanks' bases are never needed).  The seeds
mean what they mean in ``generate.py``: ``layout_seed`` fixes lengths,
places and strands, ``data_seed`` the bases and every error, and
``seed`` then only relabels A, C, G, T.
"""

from __future__ import annotations

import numpy as np

from . import generate

#: the traffic file's ``data.generator_rev`` must equal this.  The data
#: cache's key hashes ``generate.py`` and the parameters only, so an edit
#: here that changes the bytes raises both numbers and so makes new data.
GENERATOR_REV = 1

#: how far the contig runs past the stretch on either side: the longest
#: read, so that every read that can reach the stretch may be drawn
FLANK = 40000


def mode_ont_stretch(outdir: str, seed: int, *, genome_mbp: float,
                     coverage: int, mean_read: int, sub: float, ins: float,
                     dele: float, draft_error: float,
                     formats=("paf", "sam"), qual_phred: int = 15,
                     generator_rev: int = GENERATOR_REV, layout_seed=None,
                     data_seed=None, **_ignored) -> dict:
    if generator_rev != GENERATOR_REV:
        raise ValueError(f"traffic asks for generator_rev {generator_rev}, "
                         f"generate_stretch.py is at {GENERATOR_REV}")
    rng, lrng, relabel = generate._rngs(seed, data_seed, layout_seed)
    g_len = int(genome_mbp * 1e6)
    genome, draft = generate._genome_and_draft(rng, g_len, draft_error)
    w = generate._Writer(outdir, genome, draft, formats, qual_phred,
                         relabel=relabel)
    lo = min(500, int(mean_read))
    drawn = max(1, int((g_len + 2 * FLANK) * coverage / mean_read))
    clipped = 0
    for i in range(drawn):
        length = int(np.clip(lrng.gamma(4.0, mean_read / 4.0), lo, 40000))
        start = int(lrng.integers(-FLANK, g_len + FLANK - length + 1))
        strand = bool(lrng.integers(0, 2))
        begin, end = max(start, 0), min(start + length, g_len)
        if end - begin < min(lo, g_len):
            continue
        clipped += end - begin < length
        fwd, ops = generate._mutate(genome[begin:end], rng, sub, ins, dele)
        w.read(f"read{i}", begin, end, strand, fwd, ops)
    out = w.close()
    out.update(reads_drawn=drawn, reads_clipped=clipped)
    return out
