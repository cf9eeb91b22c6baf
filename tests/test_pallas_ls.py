"""Lane-lockstep Pallas POA kernel differential tests (interpret mode on
the CPU backend; on TPU hardware the same kernel runs compiled — the bench
exercises that).

The kernel (racon_tpu/ops/poa_pallas_ls.py) runs U x 8 windows per grid
step in sublane lock-step (U sublane groups under one control flow); these
tests assert lockstep == XLA twin == host oracle on one mixed batch covering
varying lengths/depths, quality weights, partial spans, padding windows,
and the DMAX rank-distance cap (which must fail the window to the host
path, reproducing the reference's accelerator->CPU fallback lattice,
/root/reference/src/cuda/cudapolisher.cpp:354-378), each at one group a
program and at two.
"""

import random

import numpy as np
import pytest

from racon_tpu import native
from racon_tpu.ops import poa, poa_driver, poa_pallas_ls
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


CFG = poa.PoaConfig(max_nodes=384, max_len=256, max_backbone=128,
                    max_edges=12, depth=8, match=5, mismatch=-4, gap=-8)
#: node arrays of four lane-chunks, windows of up to 384 bases: graphs
#: whose insertions land past rank 128 and whose shifts cross chunks
WIDE_CFG = CFG._replace(max_nodes=512, max_len=384, max_backbone=384)


def _alloc(B, cfg):
    return dict(
        bb=np.zeros((B, cfg.max_backbone), np.uint8),
        bbw=np.zeros((B, cfg.max_backbone), np.int32),
        bb_len=np.ones(B, np.int32),
        nl=np.zeros(B, np.int32),
        seqs=np.zeros((B, cfg.depth, cfg.max_len), np.uint8),
        ws=np.zeros((B, cfg.depth, cfg.max_len), np.int32),
        lens=np.zeros((B, cfg.depth), np.int32),
        bg=np.zeros((B, cfg.depth), np.int32),
        en=np.zeros((B, cfg.depth), np.int32),
    )


def _set_window(a, b, backbone, layers, weights=None, begins=None,
                ends=None):
    a["bb"][b, :len(backbone)] = encode(np.frombuffer(backbone, np.uint8))
    a["bb_len"][b] = len(backbone)
    a["nl"][b] = len(layers)
    for i, l in enumerate(layers):
        a["seqs"][b, i, :len(l)] = encode(np.frombuffer(l, np.uint8))
        a["ws"][b, i, :len(l)] = 1 if weights is None else weights[i]
        a["lens"][b, i] = len(l)
        a["bg"][b, i] = 0 if begins is None else begins[i]
        a["en"][b, i] = (len(backbone) - 1) if ends is None else ends[i]


GROUPS = pytest.mark.parametrize("groups", [1, 2, 4],
                                 ids=["u1", "u2", "u4"])


def _run_ls(a, cfg, groups=1, swept=False, counts=False):
    """The kernel's five per-window outputs; with `swept`, beside them
    the in-edge slots each program's node insertions swept (slot 0 of
    its last output); with `counts`, every slot of it, a row a program
    (poa_pallas_ls.PROGRAM_COUNTS)."""
    B = len(a["bb"])
    ls_fn = poa_pallas_ls.build_lockstep_poa_kernel(
        cfg, interpret=True, groups=groups)(B)
    outs = tuple(np.asarray(x) for x in ls_fn(
        a["bb_len"][:, None], a["nl"][:, None], a["lens"], a["bg"],
        a["en"], a["bb"].astype(np.int32), a["bbw"],
        a["seqs"].astype(np.int32), a["ws"]))
    assert outs[5].shape == (B // (8 * groups),
                             len(poa_pallas_ls.PROGRAM_COUNTS))
    assert poa_pallas_ls.PROGRAM_COUNTS[0] == "slots_swept"
    if counts:
        return outs[:5], outs[5]
    return (outs[:5], outs[5][:, 0]) if swept else outs[:5]


def _deal(a, cfg, groups):
    """The batch's windows dealt round-robin over the sublane groups of
    programs `groups` wide (window b of a program of eight lands in
    group b % groups), pad windows filling the other slots; returns the
    wide batch and where each window went."""
    B = len(a["bb"])
    pos = np.array([(b // 8) * 8 * groups + (b % groups) * 8 +
                    (b % 8) // groups for b in range(B)])
    wide = _alloc(B * groups, cfg)
    for k, v in a.items():
        wide[k][pos] = v
    return wide, pos


def _run_both(a, cfg, B, groups=1):
    """ls at `groups` sublane groups a program and the XLA twin, on the
    same B windows; at groups > 1 the ls batch is the dealt one and its
    outputs come back in the windows' own order."""
    wide, pos = _deal(a, cfg, groups)
    ls = tuple(x[pos] for x in _run_ls(wide, cfg, groups))
    jax_fn = poa.build_poa_kernel(cfg)
    jb, jc, jl, jf, jn = (np.asarray(x) for x in jax_fn(
        a["bb"], a["bbw"], a["bb_len"], a["nl"], a["seqs"], a["ws"],
        a["lens"], a["bg"], a["en"]))
    return ls, (jb, jc, jl, jf, jn)


@GROUPS
def test_lockstep_matches_host_and_jax(groups):
    """One mixed 8-window batch: perfect reads, rising mutation/depth,
    quality weights, partial spans, and a 1-base padding window — each
    asserted against both the XLA twin and the host oracle (consensus,
    coverage, and node count)."""
    rng = random.Random(7)
    B = 8
    a = _alloc(B, CFG)
    cases = {}

    # w0: perfect reads
    truth0 = bytes(rng.choice(b"ACGT") for _ in range(90))
    _set_window(a, 0, truth0, [truth0] * 4)
    cases[0] = (truth0, [truth0] * 4, None, None, None)

    # w1..w4: rising mutation rate and depth, varying lengths
    for b in range(1, 5):
        truth = bytes(rng.choice(b"ACGT") for _ in range(60 + 15 * b))
        backbone = mutate(truth, 0.05 * b, rng)
        layers = [mutate(truth, 0.05 * b, rng) for _ in range(2 + b)]
        _set_window(a, b, backbone, layers)
        cases[b] = (backbone, layers, None, None, None)

    # w5: per-base quality weights (not all-1) — exercises edge-weight
    # accumulation and heaviest-bundle scoring with real magnitudes
    truth5 = bytes(rng.choice(b"ACGT") for _ in range(80))
    backbone5 = mutate(truth5, 0.1, rng)
    layers5 = [mutate(truth5, 0.1, rng) for _ in range(5)]
    w5 = [np.array([rng.randrange(1, 50) for _ in range(len(l))],
                   np.int32) for l in layers5]
    _set_window(a, 5, backbone5, layers5, weights=w5)
    cases[5] = (backbone5, layers5, w5, None, None)

    # w6: partial spans — layers cover only part of the backbone, so the
    # subgraph rule (reference src/window.cpp:88-97) kicks in
    truth6 = bytes(rng.choice(b"ACGT") for _ in range(120))
    backbone6 = mutate(truth6, 0.08, rng)
    half = len(backbone6) // 2
    lay_a = mutate(truth6[:len(truth6) // 2], 0.08, rng)
    lay_b = mutate(truth6[len(truth6) // 2:], 0.08, rng)
    lay_c = mutate(truth6, 0.08, rng)
    layers6 = [lay_c, lay_a, lay_b]
    begins6 = [0, 0, half]
    ends6 = [len(backbone6) - 1, half - 1, len(backbone6) - 1]
    _set_window(a, 6, backbone6, layers6, begins=begins6, ends=ends6)
    cases[6] = (backbone6, layers6, None, begins6, ends6)

    # w7: padding window (1-base backbone, zero layers) — must not crash
    # or flag failure, like the driver's pad-to-B windows

    (cb, cc, cl, fl, nn), (jb, jc, jl, jf, jn) = _run_both(a, CFG, B,
                                                           groups)

    assert not fl.any(), f"unexpected device failures: {fl[:, 0]}"
    assert not jf.any()
    for b, (backbone, layers, weights, begins, ends) in cases.items():
        ls_cons = decode(cb[b, :cl[b, 0]])
        jax_cons = decode(jb[b, :jl[b]])
        quals = None
        if weights is not None:
            quals = [bytes((w + 33).astype(np.uint8)) for w in weights]
        host_cons, _ = native.window_consensus(
            backbone, [bytes(l) for l in layers], quals=quals,
            begins=begins, ends=ends, trim=False)
        assert ls_cons == jax_cons == host_cons, f"window {b}"
        assert int(nn[b, 0]) == int(jn[b]), f"window {b} node count"
        np.testing.assert_array_equal(cc[b, :cl[b, 0]], jc[b, :jl[b]],
                                      err_msg=f"window {b} coverage")


def _fuzz_batch(seed):
    """test_lockstep_differential_fuzz's eight windows of a seed: the
    batch, its geometry, and each window's own inputs."""
    rng = random.Random(seed)
    B = 8
    cfg, lengths = ((WIDE_CFG._replace(depth=4), (260, 330)) if seed == 404
                    else (CFG, (40, 110)))
    a = _alloc(B, cfg)
    cases = {}
    for b in range(B):
        L = rng.randrange(*lengths)
        truth = bytes(rng.choice(b"ACGT") for _ in range(L))
        backbone = mutate(truth, rng.uniform(0.02, 0.12), rng)
        nl = rng.randrange(2, cfg.depth + 1)
        layers = [mutate(truth, rng.uniform(0.02, 0.12), rng)
                  for _ in range(nl)]
        bq = np.array([rng.randrange(0, 60) for _ in range(len(backbone))],
                      np.int32)
        w = [np.array([rng.randrange(1, 60) for _ in range(len(l))],
                      np.int32) for l in layers]
        begins = [0] * nl
        ends = [len(backbone) - 1] * nl
        if nl >= 3:  # one partial-span layer per window when depth allows
            begins[nl - 1] = len(backbone) // 3
            ends[nl - 1] = 2 * len(backbone) // 3
            layers[nl - 1] = layers[nl - 1][:max(
                1, len(layers[nl - 1]) // 3)]
            w[nl - 1] = w[nl - 1][:len(layers[nl - 1])]
        _set_window(a, b, backbone, layers, weights=w, begins=begins,
                    ends=ends)
        a["bbw"][b, :len(backbone)] = bq
        cases[b] = (backbone, layers, w, bq, begins, ends)
    return a, cfg, cases


@GROUPS
@pytest.mark.parametrize("seed", [101, 202, 303, 404])
def test_lockstep_differential_fuzz(seed, groups):
    """Seeded random windows — lengths, depths, mutation rates, partial
    spans, per-base layer weights AND backbone weights (the product
    exports PHRED-33 backbone weights, dummy '!' = 0 when the target has
    no quality; rt_capi.cpp rt_pipeline_window_export) — asserted
    lockstep == XLA twin == host oracle.  Seed 404 runs windows of
    260-330 bases at four layers on WIDE_CFG (graphs of 300-450 nodes),
    the others 40-110 on CFG."""
    a, cfg, cases = _fuzz_batch(seed)
    B = len(cases)

    (cb, cc, cl, fl, nn), (jb, jc, jl, jf, jn) = _run_both(a, cfg, B,
                                                           groups)

    assert not fl.any() and not jf.any()
    np.testing.assert_array_equal(nn[:, 0], jn)
    for b, (backbone, layers, w, bq, begins, ends) in cases.items():
        quals = [bytes((x + 33).astype(np.uint8)) for x in w]
        host, _ = native.window_consensus(
            backbone, [bytes(l) for l in layers],
            backbone_qual=bytes((bq + 33).astype(np.uint8)),
            quals=quals, begins=begins, ends=ends, trim=False)
        ls = decode(cb[b, :cl[b, 0]])
        jx = decode(jb[b, :jl[b]])
        assert ls == jx == host, f"seed {seed} window {b}"
    if seed == 404 and groups == 1:
        # the data, not the kernel: several chunks, and most insertions
        # past the first
        walks = [_twin_insertions(cfg, a, b) for b in range(B)]
        assert max(w[0][-1] for w in walks) > 3 * 128
        ranks = [p for w in walks for lay in w[1] for _, p, _ in lay]
        assert sum(p >= 128 for p in ranks) > len(ranks) // 2


@GROUPS
def test_lockstep_ring_spill_at_large_geometry(groups):
    """Windows of 420+ ranks force the 128-row H ring to wrap multiple
    times: DP chunks are DMA'd to the HBM spill buffer under compute and
    streamed back block-descending during traceback (poa_pallas_ls.py
    flush_chunk/tb_load). The small-geometry tests never leave the ring;
    this one crosses ~7 traceback blocks and must still match both the
    XLA twin and the host oracle exactly."""
    rng = random.Random(21)
    big = poa.PoaConfig(max_nodes=768, max_len=640, max_backbone=512,
                        max_edges=12, depth=4, match=5, mismatch=-4,
                        gap=-8)
    B = 8
    a = _alloc(B, big)
    cases = {}
    for b in range(B):
        truth = bytes(rng.choice(b"ACGT") for _ in range(420 + 10 * b))
        backbone = mutate(truth, 0.1, rng)
        layers = [mutate(truth, 0.1, rng) for _ in range(3)]
        _set_window(a, b, backbone, layers)
        cases[b] = (backbone, layers)

    (cb, cc, cl, fl, nn), (jb, jc, jl, jf, jn) = _run_both(a, big, B,
                                                           groups)

    assert not fl.any() and not jf.any()
    for b, (backbone, layers) in cases.items():
        host, _ = native.window_consensus(
            backbone, [bytes(l) for l in layers], trim=False)
        ls = decode(cb[b, :cl[b, 0]])
        jx = decode(jb[b, :jl[b]])
        assert ls == jx == host, f"window {b}"
        assert int(nn[b, 0]) == int(jn[b]), f"window {b} node count"


@GROUPS
def test_lockstep_dmax_cap_fails_window_to_host(groups):
    """A window whose graph grows an in-subgraph edge with rank distance
    beyond DMAX must raise its failed flag (-> driver host fallback), and
    must not poison its batch-mates.

    A long random *insertion* does not produce a long edge — spurious
    matches fragment it during alignment (host telemetry: a 104-base
    insert yields max distance 9). A deletion that CANNOT fragment does:
    the backbone carries a 74-base all-A block while the layers contain
    no A, so the DP is forced into one contiguous deletion and layer 1's
    incorporation adds a single rank-distance-75 edge (> DMAX=64), which
    layer 2's pre-DP distance check must trip."""
    rng = random.Random(11)
    B = 8
    a = _alloc(B, CFG)

    truth = bytes(rng.choice(b"CGT") for _ in range(50))
    backbone = truth[:25] + b"A" * (poa_pallas_ls.DMAX + 10) + truth[25:]
    _set_window(a, 0, backbone, [truth, truth])

    # a healthy batch-mate in another sublane
    mate = mutate(truth, 0.1, rng)
    _set_window(a, 1, truth, [mate, mutate(truth, 0.1, rng)])

    (cb, cc, cl, fl, nn), (jb, jc, jl, jf, jn) = _run_both(a, CFG, B,
                                                           groups)

    assert fl[0, 0] == poa.FAIL_DISTANCE, (
        "DMAX overflow must fail the window, and say why")
    assert not jf[0], "the XLA twin has no DMAX cap and must succeed"
    assert fl[1, 0] == 0, "batch-mate must be unaffected"
    ls_cons = decode(cb[1, :cl[1, 0]])
    jax_cons = decode(jb[1, :jl[1]])
    assert ls_cons == jax_cons


@GROUPS
@pytest.mark.parametrize("tail", ["odd", "even"])
def test_ls_pair_step_tail(tail, groups):
    """The rank loop retires two ranks per iteration; the second is
    guarded by `r + 1 < r_end`.  One batch whose largest rank count is
    odd (the guard skips a rank past the end) and one where it is even
    (it never fires on the last pair), each against the XLA twin.
    Perfect layers add no node, so a window has as many ranks as its
    backbone has bases; the count is read back from the kernel."""
    B = 8
    a = _alloc(B, CFG)
    rng = random.Random(3)
    truth = bytes(rng.choice(b"ACGT") for _ in range(61))
    backbone = truth if tail == "odd" else truth[:60]
    # perfect layers add no node: rank count = backbone length
    _set_window(a, 0, backbone, [backbone] * 3)
    # a shorter batch-mate, so the longest window alone sets the tail
    _set_window(a, 1, backbone[:40], [backbone[:40]] * 2)

    (cb, cc, cl, fl, nn), (jb, jc, jl, jf, jn) = _run_both(a, CFG, B,
                                                           groups)

    assert int(nn[:, 0].max()) == len(backbone)
    assert (int(nn[:, 0].max()) % 2 == 1) == (tail == "odd")
    assert not fl.any() and not jf.any()
    for b, want in ((0, backbone), (1, backbone[:40])):
        assert decode(cb[b, :cl[b, 0]]) == decode(jb[b, :jl[b]]) == want
        assert int(nn[b, 0]) == int(jn[b])
        np.testing.assert_array_equal(cc[b, :cl[b, 0]], jc[b, :jl[b]])


def _one_program(fill, groups):
    """The windows of one program of `groups` sublane groups.  `mixed`:
    unequal layer counts and lengths across the groups (short and
    shallow in the first half of them, long and deep in the second, so
    every loop bound is set by one group and masked in another), one
    window that trips the DMAX cap beside the others that do not, one
    pad slot in a group of each half.  `pad-group`: the second half of
    the groups is pad windows only (a batch's last program)."""
    rng = random.Random(34)
    n = 8 * groups
    a = _alloc(n, CFG)
    for b in range(n):
        if fill == "pad-group" and b >= n // 2:
            continue
        if fill == "mixed" and b in (3, n - 4):
            continue
        deep = fill == "mixed" and b >= n // 2
        L = rng.randrange(85, 115) if deep else rng.randrange(30, 70)
        truth = bytes(rng.choice(b"ACGT") for _ in range(L))
        rate = rng.uniform(0.04, 0.15)
        nl = rng.randrange(5, CFG.depth + 1) if deep else rng.randrange(2, 5)
        layers = [mutate(truth, rate, rng) for _ in range(nl)]
        w = [np.array([rng.randrange(1, 60) for _ in range(len(l))],
                      np.int32) for l in layers]
        _set_window(a, b, mutate(truth, rate, rng), layers, weights=w)
    if fill == "mixed":
        truth = bytes(rng.choice(b"CGT") for _ in range(50))
        far = truth[:25] + b"A" * (poa_pallas_ls.DMAX + 10) + truth[25:]
        _set_window(a, n // 2 + 1, far, [truth, truth])
    return a


@pytest.mark.parametrize("groups", [2, 4], ids=["u2", "u4"])
@pytest.mark.parametrize("fill", ["mixed", "pad-group"])
def test_wide_program_equals_programs_of_eight(fill, groups):
    """One program of two or four sublane groups gives every window what
    the same window gets in a program of eight: consensus, coverage,
    length, `failed` and node count, byte for byte — the groups share the
    control flow (loop bounds are maxima over the program's windows) and
    nothing else."""
    a = _one_program(fill, groups)
    n = 8 * groups
    narrow = _run_ls(a, CFG, groups=1)
    wide = _run_ls(a, CFG, groups=groups)
    assert int((narrow[3] != 0).sum()) == (1 if fill == "mixed" else 0)
    if fill == "mixed":
        assert narrow[3][n // 2 + 1, 0] == poa.FAIL_DISTANCE
        # the later groups set every loop bound, the earlier ones are
        # masked under them
        assert a["nl"][:n // 2].max() < a["nl"][n // 2:].max()
        assert a["bb_len"][:n // 2].max() < a["bb_len"][n // 2:].max()
    for name, x, y in zip(("consensus", "coverage", "length", "failed",
                           "nodes"), narrow, wide):
        np.testing.assert_array_equal(x, y, err_msg=f"{fill}: {name}")
    jf = np.asarray(poa.build_poa_kernel(CFG)(
        a["bb"], a["bbw"], a["bb_len"], a["nl"], a["seqs"], a["ws"],
        a["lens"], a["bg"], a["en"])[3])
    assert not jf.any()


# -- the node-insertion block sweeps the in-edge slots its group uses -------

EDGE_CFG = poa.PoaConfig(max_nodes=384, max_len=256, max_backbone=128,
                         max_edges=12, depth=16, match=5, mismatch=-4,
                         gap=-8)
E = EDGE_CFG.max_edges
HUB = 40                         # the backbone position _fan_in feeds


def _fan_in(n_layers, tail=0):
    """A backbone and `n_layers` layers, each of which gives the node of
    backbone position HUB one more in-edge from a node of its own, so the
    hub holds 1 + n_layers in-edges after them (layer 12 asks for a
    thirteenth): three substitutions of the base before the hub, then
    insertions before it, two bases a depth, down five depths.  No
    inserted base equals a neighbour, so every alignment is the only
    best one.  `tail` perfect layers follow."""
    rng = random.Random(5)
    bb = bytearray(rng.choice(b"ACGT") for _ in range(70))
    bb[HUB - 2:HUB + 1] = b"CGT"
    bb = bytes(bb)
    layers = [bb[:HUB - 1] + s + bb[HUB:] for s in (b"A", b"C", b"T")]
    chain = b""
    for pair in (b"AC", b"CG", b"AG", b"CG", b"AG"):
        layers += [bb[:HUB] + chain + bytes([b]) + bb[HUB:] for b in pair]
        chain += pair[:1]
    return bb, layers[:n_layers] + [bb] * tail


def _twin_graph_walk(cfg, a, b):
    """Window b's graph as the XLA twin builds it, layer by layer: the
    most in-edges of one node and the node count before each layer and
    after the last, and the cause it ended on.  The twin keeps in-edges
    as source ids, not as slots by rank distance: an oracle of its own
    for what the lockstep kernel reads out of rk_cnt."""
    import functools

    import jax
    import jax.numpy as jnp

    add = jax.jit(functools.partial(poa._add_layer, cfg))
    g = poa._init_graph(cfg, *(jnp.asarray(a[k][b])
                               for k in ("bb", "bbw", "bb_len")))
    edges, nodes = [], []
    for li in range(int(a["nl"][b]) + 1):
        edges.append(int((g.in_src >= 0).sum(axis=1).max()))
        nodes.append(int(g.n))
        if li < a["nl"][b] and int(g.failed) == 0:
            g = add(g, *(jnp.asarray(a[k][b, li])
                         for k in ("seqs", "ws", "lens", "bg", "en")),
                    jnp.asarray(a["bb_len"][b]))
    return edges, nodes, int(g.failed)


def _expected_sweep(cfg, a, groups):
    """What one program of `groups` sublane groups reports for the batch
    `a`, a group: over the program's layers, min(E, 1 + the most
    in-edges a node of the group's windows holds before the layer), from
    the twin's graphs.  A window past its last layer keeps its graph."""
    walks = [_twin_graph_walk(cfg, a, b)[0] for b in range(8 * groups)]
    return [sum(min(cfg.max_edges,
                    1 + max(w[min(li, len(w) - 1)]
                            for w in walks[8 * u:8 * u + 8]))
                for li in range(int(a["nl"].max())))
            for u in range(groups)]


def _check_against_twin_and_host(a, cfg, ls, cases):
    """ls's outputs for the windows of `cases` (index -> backbone, layers)
    against the XLA twin (coverage, node count, failure cause) and the
    host engine (consensus; it takes the layers' spans as the batch
    holds them)."""
    cb, cc, cl, fl, nn = ls
    jb, jc, jl, jf, jn = (np.asarray(x) for x in poa.build_poa_kernel(cfg)(
        a["bb"], a["bbw"], a["bb_len"], a["nl"], a["seqs"], a["ws"],
        a["lens"], a["bg"], a["en"]))
    np.testing.assert_array_equal(fl[:, 0], jf, err_msg="failure cause")
    for b, (backbone, layers) in cases.items():
        if fl[b, 0]:
            continue
        host, _ = native.window_consensus(
            backbone, list(layers), trim=False,
            begins=a["bg"][b, :len(layers)].tolist(),
            ends=a["en"][b, :len(layers)].tolist())
        assert decode(cb[b, :cl[b, 0]]) == decode(jb[b, :jl[b]]) == host, b
        assert int(nn[b, 0]) == int(jn[b]), f"window {b} node count"
        np.testing.assert_array_equal(cc[b, :cl[b, 0]], jc[b, :jl[b]],
                                      err_msg=f"window {b} coverage")


@pytest.mark.parametrize("n_layers,ends_at", [
    (0, 1), (2, 3), (E - 2, E - 1), (E - 1, E), (E, "past E"),
], ids=["1", "3", "E-1", "E", "past-E"])
def test_insert_sweep_follows_the_groups_largest_in_edge_count(n_layers,
                                                               ends_at):
    """One window whose hub node ends at 1, 3, E - 1 and E in-edges, and
    one that asks for E + 1 (FAIL_EDGES, the host's from there), beside a
    noisy mate in a program of eight: consensus, coverage, node count and
    failure cause are the twin's and the host's, and the program swept,
    layer by layer, one slot more than the most in-edges a node held."""
    rng = random.Random(48)
    a = _alloc(8, EDGE_CFG)
    hub_bb, hub_layers = _fan_in(n_layers, tail=0 if n_layers else 3)
    truth = bytes(rng.choice(b"ACGT") for _ in range(90))
    mate = (mutate(truth, 0.1, rng), [mutate(truth, 0.1, rng)
                                      for _ in range(4)])
    cases = {0: (hub_bb, hub_layers), 1: mate}
    for b, (backbone, layers) in cases.items():
        _set_window(a, b, backbone, layers)

    edges, _, cause = _twin_graph_walk(EDGE_CFG, a, 0)
    if ends_at == "past E":
        assert edges[-2:] == [E, E] and cause == poa.FAIL_EDGES
    else:
        assert edges[-1] == ends_at and cause == 0
        assert edges[:n_layers + 1] == list(range(1, n_layers + 2))
    mate_most = max(_twin_graph_walk(EDGE_CFG, a, 1)[0])
    assert 1 < mate_most < E - 2

    ls, swept = _run_ls(a, EDGE_CFG, swept=True)
    assert ls[3][0, 0] == (poa.FAIL_EDGES if ends_at == "past E" else 0)
    _check_against_twin_and_host(a, EDGE_CFG, ls, cases)
    assert swept.tolist() == _expected_sweep(EDGE_CFG, a, 1)
    slots_all = poa_driver._insert_slots_all(a["nl"], 1, E)
    assert slots_all == E * int(a["nl"].max())
    assert 2 * int(a["nl"].max()) <= swept[0] < slots_all


def test_edge_written_at_the_bound_then_nodes_inserted_in_the_same_layer():
    """The `+ 1` of the bound.  The hub holds the group's most in-edges,
    3; one layer gives it a fourth (written at slot 3, the last the
    layer's bound of 4 covers) and further along inserts two nodes more;
    the next layer inserts a node below the hub, so the hub's row moves
    with all four edges.  (Inside one layer a row cannot move after it
    gained its edge: the path climbs in rank, and a node is inserted
    above every row the layer has touched.)"""
    a = _alloc(8, EDGE_CFG)
    bb, layers = _fan_in(2)
    far = HUB + 12
    other = bytes([next(c for c in b"ACGT"
                        if c not in (bb[far], bb[far - 1], bb[far + 1]))])
    both = bb[:HUB] + b"A" + bb[HUB:far] + other + bb[far:]
    sub = next(bytes([c]) for c in b"ACGT" if c != bb[HUB - 8])
    below = bb[:HUB - 8] + sub + bb[HUB - 7:]
    layers = layers + [both, below, bb]
    _set_window(a, 0, bb, layers)

    edges, nodes, cause = _twin_graph_walk(EDGE_CFG, a, 0)
    assert cause == 0
    assert edges == [1, 2, 3, 4, 4, 4]          # `both` is layer 2
    assert nodes[3] - nodes[2] == 2              # hub's source + `other`
    assert nodes[4] - nodes[3] == 1              # `below`
    ls, swept = _run_ls(a, EDGE_CFG, swept=True)
    _check_against_twin_and_host(a, EDGE_CFG, ls, {0: (bb, layers)})
    assert swept.tolist() == [2 + 3 + 4 + 5 + 5]


def _pad_beside_deep():
    """A program of thirty-two: group 0 holds the window whose hub
    reaches E in-edges and noisy mates, group 1 perfect reads alone,
    group 2 noisy windows, group 3 pad rows alone."""
    rng = random.Random(32)
    a = _alloc(32, EDGE_CFG)
    cases = {0: _fan_in(E - 1, tail=2)}
    for b in (1, 2, 5, 16, 17, 20):
        truth = bytes(rng.choice(b"ACGT") for _ in range(rng.randrange(50,
                                                                       100)))
        cases[b] = (mutate(truth, 0.1, rng),
                    [mutate(truth, 0.12, rng)
                     for _ in range(rng.randrange(3, 9))])
    for b in (8, 9, 13):
        truth = bytes(rng.choice(b"ACGT") for _ in range(60 + b))
        cases[b] = (truth, [truth] * (b - 4))
    for b, (backbone, layers) in cases.items():
        _set_window(a, b, backbone, layers)
    return a, cases


def test_pad_group_beside_a_deep_one_each_sweeps_its_own_slots():
    """The bound is a group's, not the program's: in one program of
    thirty-two the deep group climbs to all E slots while the group of
    perfect reads stays at 2 and the pad group at 1, and every window
    gets what it gets in a program of eight."""
    a, cases = _pad_beside_deep()
    wide, swept = _run_ls(a, EDGE_CFG, groups=4, swept=True)
    narrow, swept8 = _run_ls(a, EDGE_CFG, groups=1, swept=True)
    for name, x, y in zip(("consensus", "coverage", "length", "failed",
                           "nodes"), narrow, wide):
        np.testing.assert_array_equal(x, y, err_msg=name)
    assert not wide[3].any()
    _check_against_twin_and_host(a, EDGE_CFG, wide, cases)

    layers = int(a["nl"].max())
    assert layers == E + 1
    by_group = _expected_sweep(EDGE_CFG, a, 4)
    assert by_group[1] == 2 * layers and by_group[3] == layers
    assert by_group[0] > by_group[2] > by_group[1]
    assert by_group[0] == sum(range(2, E + 1)) + 2 * E
    assert swept.tolist() == [sum(by_group)]
    assert swept[0] < poa_driver._insert_slots_all(a["nl"], 4, E) \
        == E * 4 * layers
    # a program of eight runs its own layer count: the pad program none
    assert swept8[3] == 0 and swept8[1] == 2 * int(a["nl"][8:16].max())


# -- poa.insert.slots.swept / .all and poa_insert_slot_sweep_share ----------

def test_sweep_equals_all_from_the_layer_a_window_holds_E_in_edges():
    """Once a node of the group holds E - 1 in-edges the bound is E:
    every further layer sweeps every slot, and swept grows as all does."""
    runs = {}
    for tail in (1, 4):
        a = _alloc(8, EDGE_CFG)
        _set_window(a, 0, *_fan_in(E - 1, tail=tail))
        runs[tail] = (int(_run_ls(a, EDGE_CFG, swept=True)[1][0]),
                      poa_driver._insert_slots_all(a["nl"], 1, E))
    (swept1, all1), (swept4, all4) = runs[1], runs[4]
    assert swept4 - swept1 == all4 - all1 == 3 * E
    assert swept1 == sum(range(2, E + 1)) + E < all1 == E * E


def test_driver_counts_two_slots_of_twelve_on_reads_equal_to_the_backbone(
        tmp_path, monkeypatch):
    """Through the consensus driver: reads identical to the backbone add
    no edge, so every layer's bound is the chain's one in-edge plus one,
    2 of 12, and the driver counts it at install beside all twelve."""
    from racon_tpu import obs

    target = _perfect_reads_dataset(tmp_path)
    monkeypatch.setenv("RACON_TPU_PALLAS", "1")
    monkeypatch.setenv("RACON_TPU_SHARD", "0")
    monkeypatch.setenv("RACON_TPU_BATCH_WINDOWS", "8")
    monkeypatch.setenv("RACON_TPU_METRICS", "1")   # the polisher arms obs
    try:
        res, phase = _polish_perfect_reads(tmp_path)
        counters = obs.snapshot()["counters"]
    finally:
        obs.reset()
    assert res[0][1] == target and phase["served"]["ls"] == 3
    # three windows in one program of eight, four layers each
    assert counters["poa.insert.slots.all"] == 12 * 1 * 4
    assert counters["poa.insert.slots.swept"] == 2 * 1 * 4
    assert counters["poa.launches"] == 1


def test_driver_counts_the_bound_not_the_blocks_the_loop_rounds_it_to(
        tmp_path, monkeypatch):
    """Through the consensus driver: every read carries one base the
    target lacks, at position 130, so the second of three windows
    inserts one node, whose successor then holds two in-edges: the
    bound is 2 at the first layer and 3 at the three after it, and that
    is what the driver counts, though the loop shifts whole blocks of
    SLOT_BLOCK slots."""
    from racon_tpu import obs

    target = _perfect_reads_dataset(tmp_path, insert_at=130)
    monkeypatch.setenv("RACON_TPU_PALLAS", "1")
    monkeypatch.setenv("RACON_TPU_SHARD", "0")
    monkeypatch.setenv("RACON_TPU_BATCH_WINDOWS", "8")
    monkeypatch.setenv("RACON_TPU_METRICS", "1")
    try:
        res, phase = _polish_perfect_reads(tmp_path)
        counters = obs.snapshot()["counters"]
    finally:
        obs.reset()
    assert res[0][1] == target[:130] + "T" + target[130:]
    assert phase["served"]["ls"] == 3 and counters["poa.launches"] == 1
    assert poa_pallas_ls.SLOT_BLOCK > 3
    assert counters["poa.insert.slots.swept"] == 2 + 3 * 3
    assert counters["poa.insert.slots.all"] == 12 * 1 * 4


def test_xla_twin_counts_no_insert_slots(tmp_path, monkeypatch):
    """The twin has no slot sweep to bound: it reports nothing, and the
    driver counts nothing (the metric reads nothing, as on a program
    that predates the counters)."""
    from racon_tpu import obs

    _perfect_reads_dataset(tmp_path)
    monkeypatch.setenv("RACON_TPU_PALLAS", "0")
    monkeypatch.setenv("RACON_TPU_SHARD", "0")
    monkeypatch.setenv("RACON_TPU_METRICS", "1")
    try:
        _, phase = _polish_perfect_reads(tmp_path)
        counters = obs.snapshot()["counters"]
    finally:
        obs.reset()
    assert phase["served"]["xla"] == 3 and counters["poa.launches"] >= 1
    assert not [k for k in counters if k.startswith("poa.insert.")]


# -- poa.ls.*: the trips of the kernel's own loops ---------------------------

def _check_step_counts(a, cfg, groups, nn, counts):
    """A program's STEP_COUNTERS against what its windows' inputs and the
    node counts it returned say alone, over the layers of its deepest
    window (the driver's _program_layers, which counts poa.ls.layers):
    a layer's longest admitted read of update steps; a group's node
    insertions fire once a step in which any of its eight windows
    inserts a node (a window inserts at most one a step), so at least
    what its busiest window inserted and at most what all eight did,
    each firing running one to E / SLOT_BLOCK blocks of slots; a pair
    of rank slots a trip of the DP loop, under the program's largest
    graph a layer; whole blocks of BLK traceback ranks.  The two scans
    (PR 53): a rank's DP scan runs to its largest valid in-edge
    distance, so at least once for every backbone column of a layer's
    span past the first (its chain edge lies inside the subgraph) of the
    layer's widest-spanning window, and at most DMAX a rank slot; the
    traceback's runs at a rank to the largest such distance of the
    windows that stand there, a rank at most once a layer: never past
    the DP's."""
    W = 8 * groups
    blocks = -(-cfg.max_edges // poa_pallas_ls.SLOT_BLOCK)
    for p, row in enumerate(counts.tolist()):
        got = dict(zip(poa_pallas_ls.PROGRAM_COUNTS, row))
        rows = slice(p * W, (p + 1) * W)
        nl = a["nl"][rows]
        layers = poa_driver._program_layers(nl, W)
        assert layers == int(nl.max())
        live = np.arange(cfg.depth)[None, :] < nl[:, None]
        inserted = (nn[rows, 0] - a["bb_len"][rows]).reshape(groups, 8)
        assert got["steps.update"] == int(
            np.where(live, a["lens"][rows], 0).max(axis=0).sum()), p
        assert (inserted.max(axis=1).sum() <= got["insert.firings"]
                <= inserted.sum()), (p, got, inserted)
        assert (got["insert.firings"] <= got["insert.shift_steps"]
                <= blocks * got["insert.firings"]), (p, got)
        most = int(nn[rows, 0].max())
        assert got["steps.dp"] % 2 == 0 and (
            2 * layers <= got["steps.dp"] <= layers * (most + 1)), (p, got)
        assert got["steps.traceback"] % poa_pallas_ls.BLK == 0 and (
            layers <= got["steps.traceback"] // poa_pallas_ls.BLK
            <= layers * -(-most // poa_pallas_ls.BLK)), (p, got)
        chain = (np.minimum(a["en"][rows], a["bb_len"][rows, None] - 1)
                 - np.maximum(a["bg"][rows], 0))
        chain = np.where(live & (a["lens"][rows] > 0), chain, 0)
        assert (chain.max(axis=0).sum() <= got["steps.dp_scan"]
                <= poa_pallas_ls.DMAX * got["steps.dp"]), (p, got)
        assert 0 <= got["steps.tb_scan"] <= got["steps.dp_scan"], (p, got)
        assert (got["steps.tb_scan"] > 0) == (got["steps.dp_scan"] > 0)


@GROUPS
@pytest.mark.parametrize("seed", [101, 202, 303, 404])
def test_step_counters_against_plain_counts_on_the_fuzz(seed, groups):
    """The differential fuzz's windows, dealt over the sublane groups of
    one program (pad windows fill the other slots): the kernel's last
    output counts its own loops as the inputs and outputs say it must."""
    a, cfg, _ = _fuzz_batch(seed)
    wide, _ = _deal(a, cfg, groups)
    (cb, cc, cl, fl, nn), counts = _run_ls(wide, cfg, groups, counts=True)
    assert not fl.any()
    assert counts[:, 1:].all(), "noisy reads: every loop ran"
    _check_step_counts(wide, cfg, groups, nn, counts)


@pytest.mark.parametrize("groups", [4, 1], ids=["u4", "u1"])
def test_step_counters_with_a_pad_group_and_a_pad_program(groups):
    """A group of pad rows beside a deep one counts nothing of its own
    (the program's layers are the deep group's), and as a program of
    eight by itself it counts nothing at all; the group of perfect reads
    fires no insertion."""
    a, _ = _pad_beside_deep()
    (cb, cc, cl, fl, nn), counts = _run_ls(a, EDGE_CFG, groups, counts=True)
    assert not fl.any()
    _check_step_counts(a, EDGE_CFG, groups, nn, counts)
    if groups == 1:
        assert not counts[3].any(), "the pad program ran no layer"
        firings = counts[:, poa_pallas_ls.PROGRAM_COUNTS.index(
            "insert.firings")]
        assert firings[1] == 0 and firings[0] > 0 and firings[2] > 0
    else:
        assert poa_driver._program_layers(a["nl"], 8 * groups) == E + 1


@pytest.mark.parametrize("pallas,insert_at,want", [
    # reads equal to the backbone: every rank but the first has its
    # chain edge, at distance 1, and every window walks every rank back
    ("1", None, {"layers": 4, "steps.update": 4 * 100,
                 "insert.firings": 0, "insert.shift_steps": 0,
                 "steps.dp_scan": 4 * 99, "steps.tb_scan": 4 * 99}),
    # the first layer inserts a node before rank 30 of the second window:
    # from the next layer on 101 ranks, and the chain edge that spans
    # the new node has distance 2 (one trip more at its rank)
    ("1", 130, {"layers": 4, "steps.update": 4 * 101,
                "insert.firings": 1, "insert.shift_steps": 1,
                "steps.dp_scan": 99 + 3 * 101,
                "steps.tb_scan": 99 + 3 * 101}),
    ("0", None, None),
], ids=["ls", "ls-one-insertion", "xla"])
def test_install_counts_every_step_counter_or_none(tmp_path, monkeypatch,
                                                   pallas, insert_at, want):
    """Through the consensus driver: an installed lockstep launch counts
    all eight poa.ls.* keys, a zero too (reads equal to the backbone fire
    no insertion; one base the target lacks fires one, in one block of
    slots), beside poa.insert.slots.*; the XLA twin counts none."""
    from racon_tpu import obs

    _perfect_reads_dataset(tmp_path, insert_at=insert_at)
    monkeypatch.setenv("RACON_TPU_PALLAS", pallas)
    monkeypatch.setenv("RACON_TPU_SHARD", "0")
    monkeypatch.setenv("RACON_TPU_BATCH_WINDOWS", "8")
    monkeypatch.setenv("RACON_TPU_METRICS", "1")
    try:
        _, phase = _polish_perfect_reads(tmp_path)
        counters = obs.snapshot()["counters"]
    finally:
        obs.reset()
    got = {k[len("poa.ls."):]: v for k, v in counters.items()
           if k.startswith("poa.ls.")}
    if want is None:
        assert phase["served"]["xla"] == 3 and got == {}
        return
    assert phase["served"]["ls"] == 3 and counters["poa.launches"] == 1
    assert sorted(got) == sorted(("layers",) + poa_pallas_ls.STEP_COUNTERS)
    # three windows of 100 bases in one program of eight: a backbone of
    # ranks in one block, walked down from its top a layer
    assert got.pop("steps.traceback") == 4 * 2 * poa_pallas_ls.BLK
    assert 4 * 100 <= got.pop("steps.dp") <= 4 * 102
    assert got == want


# -- packed words: what a rank step reads at one index, reduced once ---------

REC_VALUES = (0, 1, 63, 64, 65, 255, 256, 5000)


def test_in_edge_record_round_trips_every_tuple_of_distances():
    """Every E-tuple over REC_VALUES: a word holds REC_SLOTS distances
    and is packed by itself, so every tuple of a word at every word, the
    other words holding one of the values each, is every E-tuple.  A
    distance comes back capped at REC_MAX, in its own slot: what is
    valid (0 < d <= DMAX) stays valid, what is past DMAX stays past it;
    a word fits NARROW_BITS and one float reduction returns it."""
    import itertools

    ls = poa_pallas_ls
    assert (ls.REC_SLOTS, ls.REC_MAX, ls.record_words(E)) == (3, 255, 4)
    assert ls.record_words(13) == 5 and ls.record_words(8) == 3
    word_tuples = np.array(list(itertools.product(REC_VALUES,
                                                  repeat=ls.REC_SLOTS)))
    for w in range(ls.record_words(E)):
        for other in REC_VALUES:
            d = np.full((len(word_tuples), E), other, np.int32)
            d[:, w * ls.REC_SLOTS:(w + 1) * ls.REC_SLOTS] = word_tuples
            deltas = [d[:, e] for e in range(E)]
            words = ls.pack_record(deltas)
            assert len(words) == ls.record_words(E)
            for word in words:
                word = np.asarray(word)
                assert ((0 <= word) & (word < 1 << ls.NARROW_BITS)).all()
                np.testing.assert_array_equal(
                    np.asarray(ls.lane_sum(word[:, None], narrow=True))[:, 0],
                    word)
            back = [np.asarray(x) for x in ls.unpack_record(words, E)]
            for e in range(E):
                np.testing.assert_array_equal(
                    back[e], np.minimum(deltas[e], ls.REC_MAX), err_msg=e)
                np.testing.assert_array_equal(
                    (back[e] > 0) & (back[e] <= ls.DMAX),
                    (deltas[e] > 0) & (deltas[e] <= ls.DMAX))
    # a last word with slots to spare (E = 8: two of three)
    odd = [np.array([v], np.int32) for v in REC_VALUES]
    back = ls.unpack_record(ls.pack_record(odd), len(odd))
    assert [int(x[0]) for x in back] == [min(v, ls.REC_MAX)
                                         for v in REC_VALUES]


def test_narrow_lane_sum_equals_the_int_one_at_every_lane():
    """The one float reduction against the int32 one (two float
    reductions of 16-bit halves on the chip) for a row with one nonzero
    term: the ends of (-2**24, 2**24), 0 and -1, at every lane; and a
    count of ones."""
    ls = poa_pallas_ls
    top = (1 << ls.NARROW_BITS) - 1
    values = np.array([top, -top, 0, -1, 1, 1 << 16, -(1 << 16) - 1],
                      np.int32)
    rows = np.zeros((len(values), 128, 128), np.int32)
    for lane in range(128):
        rows[:, lane, lane] = values
    narrow = np.asarray(ls.lane_sum(rows, narrow=True))
    wide = np.asarray(ls.lane_sum(rows))
    assert narrow.dtype == wide.dtype == np.int32
    assert narrow.shape == wide.shape == (len(values), 128, 1)
    np.testing.assert_array_equal(narrow, wide)
    np.testing.assert_array_equal(narrow[:, :, 0],
                                  np.repeat(values[:, None], 128, axis=1))
    ones = (np.arange(128)[None, :] < np.arange(129)[:, None]).astype(
        np.int32)
    np.testing.assert_array_equal(
        np.asarray(ls.lane_sum(ones, narrow=True))[:, 0], np.arange(129))


@pytest.mark.parametrize("edges", [E, 8, 16], ids=["E12", "E8", "E16"])
def test_move_word_decodes_to_the_five_reads(edges):
    """The traceback's word at a cell against the five values its reads
    at j_stop gave (diag_ok, wdiag or 0, wdiag == WNONE, wup or 0,
    wup == WNONE), for a real, a virtual and no predecessor on both
    moves: every packed slot * 256 + distance on one move beside each
    case of the other, and the virtual row's four cases (it needs a rank
    with no valid in-edge, so neither move has a real predecessor
    there).  A cell is explained unless its word is no_move's."""
    ls = poa_pallas_ls
    bits = ls.move_bits(edges)
    assert bits == (12 if edges < 16 else 13)
    assert (2 * bits <= ls.NARROW_BITS) == (edges < 16)
    real = [s * 256 + d for s in range(edges)
            for d in (1, 2, 63, ls.DMAX)]
    cases = [(wd, wu, False, False) for wd in real + [ls.WNONE]
             for wu in (real[0], real[-1], real[len(real) // 2], ls.WNONE)]
    cases += [(wd, wu, False, False) for wu in real
              for wd in (real[0], real[-1], ls.WNONE)]
    cases += [(ls.WNONE, ls.WNONE, vd, vu) for vd in (False, True)
              for vu in (False, True)]
    wdiag, wup, vdiag, vup = (np.array(c) for c in zip(*cases))
    wdiag, wup = wdiag.astype(np.int32), wup.astype(np.int32)
    word = np.asarray(ls.pack_moves(wdiag, wup, vdiag, vup, bits))
    assert ((0 <= word) & (word < 1 << 2 * bits)).all()
    diag_ok, wd, wd_virt, wu, wu_virt = (
        np.asarray(x) for x in ls.unpack_moves(word, bits))
    np.testing.assert_array_equal(diag_ok, (wdiag < ls.WNONE) | vdiag)
    np.testing.assert_array_equal(wd, np.where(wdiag == ls.WNONE, 0, wdiag))
    np.testing.assert_array_equal(wd_virt, wdiag == ls.WNONE)
    np.testing.assert_array_equal(wu, np.where(wup == ls.WNONE, 0, wup))
    np.testing.assert_array_equal(wu_virt, wup == ls.WNONE)
    np.testing.assert_array_equal(
        word != ls.no_move(bits),
        (wdiag < ls.WNONE) | vdiag | (wup < ls.WNONE) | vup)
    if 2 * bits <= ls.NARROW_BITS:
        np.testing.assert_array_equal(
            np.asarray(ls.lane_sum(word[:, None], narrow=True))[:, 0], word)


def test_scratch_holds_the_record_where_the_consensus_arrays_were():
    """The record's words live in the rows the layer loop leaves idle
    (score, spred, revbuf and what was rk_dmax): at E = 12 the VMEM sum
    is what it was before the record, (9 + 2 E) node rows beside the
    ring, the j rows and the I/O blocks; a geometry with fewer slots
    needs the consensus walk's three."""
    cfg = poa_driver.make_config(500, 200, 5, -4, -8)
    assert cfg.max_edges == 12
    NC, JC = cfg.max_nodes // 128, -(-(cfg.max_len + 1) // 128)
    for groups in (1, 2, 4):
        lane_bytes = groups * 8 * 128 * 4
        assert poa_pallas_ls.scratch_bytes(cfg, groups) == lane_bytes * (
            poa_pallas_ls.RING * JC + 7 * JC + (9 + 2 * 12) * NC + 4 * NC)
    small = cfg._replace(max_edges=6)
    assert (poa_pallas_ls.scratch_bytes(cfg) - poa_pallas_ls.scratch_bytes(
        small)) == (12 + 1) * NC * 8 * 128 * 4


# -- node insertions at the ranks and chunk boundaries a shift can miss ------

def _twin_insertions(cfg, a, b):
    """Window b's node insertions as the XLA twin's graphs tell them,
    layer by layer: the node count before each layer (and after the
    last), a layer's insertions as (step j, rank, nodes before), and the
    cause the window ended on.  A step inserts where the path's column
    holds no node of the layer's base (the twin's `has`); the j-th new
    node takes id n, and its rank is the count of older nodes whose key
    is not above its own (the kernel's p_ins).  A window the twin fails
    for nodes stops where it overflows, as the kernel's does; no other
    cause is walked."""
    import functools

    import jax
    import jax.numpy as jnp

    def path(g, seq, L, begin, end, bb_len):     # poa._add_layer's head
        offset = (0.01 * bb_len.astype(jnp.float32)).astype(jnp.int32)
        full = (begin < offset) & (end > bb_len - offset)
        lo = jnp.where(full, -jnp.inf, begin.astype(jnp.float32))
        hi = jnp.where(full, jnp.inf, end.astype(jnp.float32))
        sub = (g.key >= lo) & (g.key <= hi)
        order = jnp.argsort(jnp.where(sub, g.key, poa.KEY_INF)).astype(
            jnp.int32)
        n_sub = sub.sum().astype(jnp.int32)
        H = poa._dp_matrix(cfg, g, seq, sub, order, n_sub)
        return poa._traceback(cfg, g, H, seq, sub, order, n_sub, L)[0]

    path = jax.jit(path)
    add = jax.jit(functools.partial(poa._add_layer, cfg))
    g = poa._init_graph(cfg, *(jnp.asarray(a[k][b])
                               for k in ("bb", "bbw", "bb_len")))
    starts, layers = [int(g.n)], []
    for li in range(int(a["nl"][b])):
        seq, w, L, bg, en = (jnp.asarray(a[k][b, li])
                             for k in ("seqs", "ws", "lens", "bg", "en"))
        bb_len = jnp.asarray(a["bb_len"][b])
        pos = np.asarray(path(g, seq, L, bg, en, bb_len))
        key0, base0, n0 = np.asarray(g.key), np.asarray(g.base), int(g.n)
        g = add(g, seq, w, L, bg, en, bb_len)
        assert int(g.failed) in (0, poa.FAIL_NODES), int(g.failed)
        key2 = np.asarray(g.key)
        steps = [j for j in range(int(L)) if not (
            pos[j] >= 0 and ((key0[:n0] == key0[pos[j]]) &
                             (base0[:n0] == int(seq[j]))).any())]
        room = cfg.max_nodes - n0
        assert int(g.n) == n0 + min(len(steps), room)
        assert (len(steps) > room) == (int(g.failed) == poa.FAIL_NODES)
        layers.append([(j, int((key2[:n0 + i] <= key2[n0 + i]).sum()),
                        n0 + i) for i, j in enumerate(steps[:room])])
        starts.append(int(g.n))
        if int(g.failed):
            break
    return starts, layers, int(g.failed)


def _other(*bases):
    return bytes([next(c for c in b"ACGT" if c not in bases)])


def _bases(rng, n):
    return bytes(rng.choice(b"ACGT") for _ in range(n))


def _crafted_insertion(name):
    """A crafted window, (backbone, layers, the layers' end or None for
    the backbone's last base), and what its insertions have to be:
    [(rank, nodes before)] in order.  Every layer comes three times (the
    host engine wants three to vote; a repeat inserts nothing)."""
    rng = random.Random(49)
    if name == "rank-0":
        # a base before the backbone's first: key -1, below every node;
        # the next layer puts one more before that
        bb = _bases(rng, 300)
        x = _other(bb[0])
        y = _other(bb[0], x[0])
        return bb, [x + bb] * 3 + [y + x + bb] * 3, None, [(0, 300),
                                                           (0, 301)]
    if name in ("rank-128", "rank-256"):
        # a base between backbone positions 127 | 128 (255 | 256): lane
        # 0 of a chunk is the new row, and the chunk below gives nothing
        at = int(name[5:])
        bb = _bases(rng, 300)
        x = _other(bb[at - 1], bb[at])
        return bb, [bb[:at] + x + bb[at:]] * 3, None, [(at, 300)]
    if name.startswith("append-"):
        # a base past the backbone's last, then one more: rows n and
        # n + 1, the second of them (n = 127, 255) or both (n = 128) in
        # a chunk that held only fill.  The layers end at the
        # backbone's length: the full-graph rule, or the next layer
        # would not see the node the one before appended.
        n = int(name[7:])
        bb = _bases(rng, n)
        x = _other(bb[-1])
        y = _other(x[0])
        return bb, [bb + x] * 3 + [bb + x + y] * 3, n, [(n, n),
                                                        (n + 1, n + 1)]
    assert name == "full"
    # substitutions until the graph holds N - 1 nodes, one more (the
    # last row is written, nothing falls off), then one the graph has
    # no room for: FAIL_NODES, nothing shifted.  A substitute differs
    # from its neighbours too, so no alignment slides.
    N = WIDE_CFG.max_nodes
    bb = _bases(rng, 384)
    spots = rng.sample(range(4, 380, 2), N - 384 + 1)
    cuts = [0, 60, N - 384 - 1, N - 384, N - 384 + 1]
    layers = []
    for lo, hi in zip(cuts, cuts[1:]):
        lay = bytearray(bb)
        for at in spots[lo:hi]:
            lay[at] = _other(*bb[at - 1:at + 2])[0]
        layers.append(bytes(lay))
    return bb, layers, None, None


@pytest.mark.parametrize("name", ["rank-0", "rank-128", "rank-256",
                                  "append-127", "append-128", "append-255",
                                  "full"])
def test_insertion_at_a_chunk_boundary(name):
    """One crafted window beside a noisy mate in a program of eight, on
    a geometry of four lane-chunks: an insertion at rank 0, at a rank
    that is a multiple of 128, at rank n (append) where the new row is
    the last lane of a chunk, the first of one that held only fill, or
    crosses into it, and into a graph of N - 1 nodes, then one more.
    The twin's graphs, walked layer by layer, say the window inserts
    where it was crafted to; consensus, coverage, node count and cause
    are the twin's and the host's."""
    rng = random.Random(149)
    a = _alloc(8, WIDE_CFG)
    bb, layers, end, want = _crafted_insertion(name)
    truth = _bases(rng, 200)
    mate = (mutate(truth, 0.08, rng), [mutate(truth, 0.08, rng)
                                       for _ in range(3)])
    cases = {0: (bb, layers), 1: mate}
    _set_window(a, 0, bb, layers, ends=end and [end] * len(layers))
    _set_window(a, 1, *mate)

    starts, ins, cause = _twin_insertions(WIDE_CFG, a, 0)
    if want is None:
        N = WIDE_CFG.max_nodes
        assert starts == [384, 444, N - 1, N, N] and cause == poa.FAIL_NODES
        assert [n for _, _, n in ins[2]] == [N - 1] and ins[3] == []
    else:
        assert cause == 0
        assert [(p, n) for lay in ins for _, p, n in lay] == want

    ls = _run_ls(a, WIDE_CFG)
    assert ls[3][0, 0] == cause
    _check_against_twin_and_host(a, WIDE_CFG, ls, cases)


@GROUPS
def test_insertions_far_apart_in_one_step(groups):
    """Two windows of one group insert in the same steps, one near rank
    10 and one near rank 290, beside a window that inserts nothing and
    holds the group's largest graph, and the second inserts once alone:
    each window's rows move from its own insertion rank up, and the
    bystander's rows read as the twin's.  At two and four groups the
    others hold noisy windows and a pad group."""
    rng = random.Random(249)
    B = 8 * groups
    a = _alloc(B, WIDE_CFG)
    near = _bases(rng, 330)
    far = _bases(rng, 330)
    wide = _bases(rng, 380)
    x, y = _other(near[9], near[10]), _other(far[289], far[290])
    z = _other(far[299], far[300])
    cases = {
        0: (near, [near[:10] + x + near[10:]] * 3),
        # a layer over the backbone's tail: its step 10 is rank 290
        3: (far, [far[280:290] + y + far[290:300] + z + far[300:]] * 3),
        5: (wide, [wide] * 3),
    }
    begins = {3: [280] * 3}
    if groups > 1:
        truth = _bases(rng, 150)
        for b in (8, 9, 12):
            cases[b] = (mutate(truth, 0.1, rng),
                        [mutate(truth, 0.1, rng) for _ in range(3)])
    for b, (backbone, lays) in cases.items():
        _set_window(a, b, backbone, lays, begins=begins.get(b))

    for b, want in ((0, [(10, 10, 330)]), (5, []),
                    (3, [(10, 290, 330), (21, 301, 331)])):
        assert _twin_insertions(WIDE_CFG, a, b)[1] == [want, [], []]
    ls = _run_ls(a, WIDE_CFG, groups=groups)
    assert not ls[3].any()
    _check_against_twin_and_host(a, WIDE_CFG, ls, cases)


def _bench_cells():
    import json
    import os

    from benchmark import loader
    with open(os.path.join(loader.ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


@pytest.mark.parametrize("cell_name", _bench_cells())
def test_sweep_share_metric_loads_and_reads_its_counters(cell_name):
    from benchmark import loader, reducers

    cell = loader.load_cell(cell_name)        # the file agrees with its entry
    spec = {m["name"]: m for m in cell.per_layer}[
        "poa_insert_slot_sweep_share"]
    assert spec["workloads"] == _bench_cells() and len(spec["workloads"]) >= 10
    assert (spec["layer"], spec["moves"], spec["unit"], spec["better"],
            spec["source"]) == ("kernels", "polished_mbp_per_s", "%",
                                "lower", "program_counter")
    assert spec["reducer"] == "counter_share" and spec["what"]
    read = reducers.registry()[spec["reducer"]]

    def run(*job_counters):
        return {"jobs": [{"counters": c, "spans": {}, "phases": {}}
                         for c in job_counters]}

    job = {"poa.insert.slots.swept": 8, "poa.insert.slots.all": 48,
           "poa.launches": 1}
    deep = {"poa.insert.slots.swept": 96, "poa.insert.slots.all": 96}
    assert read(run(job), **spec["params"]) == pytest.approx(100 * 2 / 12)
    assert read(run(job, deep), **spec["params"]) == pytest.approx(
        100 * 104 / 144)
    assert read(run(deep), **spec["params"]) == 100.0
    # the parent's program counts neither: nothing, and no error
    older = {"poa.launches": 18, "poa.rows.real": 1000}
    assert read(run(older, older), **spec["params"]) is None


def test_lockstep_production_geometry_real_window():
    """Production-size config (N=1536, L=768, BB=512) on a real lambda
    window: catches geometry-dependent bugs the small-config differentials
    can't (tiling, padding, rank insertion at scale)."""
    import os

    from tests.conftest import DATA
    if not os.path.isdir(DATA):
        pytest.skip(f"lambda test data not found at {DATA} "
                    "(set RACON_TPU_TEST_DATA)")

    import racon_tpu
    from racon_tpu.ops import poa_driver

    pl = racon_tpu.Pipeline(DATA + "sample_reads.fastq.gz",
                            DATA + "sample_overlaps.sam.gz",
                            DATA + "sample_layout.fasta.gz",
                            match=5, mismatch=-4, gap=-8, trim=False)
    pl.initialize()
    target = next((i for i in range(pl.num_windows())
                   if 20 <= pl.window_info(i)[0] - 1 <= 32), None)
    if target is None:
        pytest.skip("no window with 21-32 layers in this dataset")
    wx = pl.export_window(target)

    cfg = poa_driver.make_config(512, 32, 5, -4, -8)
    keep = [j for j in range(len(wx.lens))
            if 0 < wx.lens[j] <= cfg.max_len][:cfg.depth]
    B = 8
    packed = poa_driver._pack([(target, wx, keep)], cfg, B)
    kern = poa_pallas_ls.build_lockstep_poa_kernel(cfg, interpret=True)(B)
    cb, cc, cl, fl = poa_driver._unpack(
        poa_driver._submit(kern, packed, True), True)
    assert not fl[0]
    # Compare against the pipeline's own host consensus for the same
    # window: the export is already layer-sorted, and re-sorting through
    # the one-shot hook would permute equal begin keys differently
    # (std::sort is not idempotent on ties).
    pl.consensus_cpu_one(target)
    assert decode(cb[0, :cl[0]]) == pl.get_consensus(target)


@pytest.mark.parametrize("length,pallas,tier,groups", [
    (200, True, "ls", 4), (500, True, "ls", 4), (1000, True, "ls", 2),
    (1152, True, "ls", 2), (1408, True, "ls", 2), (2000, True, "ls", 1),
    (3200, True, "ls", 1), (3300, True, "xla", 0), (500, False, "xla", 0)],
    ids=["200", "500", "1000", "1152", "1408", "2000", "3200", "3300",
         "pallas-off"])
def test_entry_tier_by_window_length(length, pallas, tier, groups):
    """Which tier a window length enters at, in every depth bucket and at
    the score sets the deployments use, and how wide its programs are at
    a TPU's batch (64, or 16 a shard): the lockstep kernel's scratch
    fits VMEM up to class 3200 under the one rule that the limit a
    program needs may not pass half the chip's VMEM (so -w 200, -w 500
    and upstream's largest documented -w 1000 are served by it, and
    since PR 47 what is longer too: thirty-two windows a full program
    up to class 768, sixteen up to 1536, eight up to 3200; until then a
    program of eight was held under the compiler's default limit, which
    stopped at class 1024), the XLA twin takes what is longer still and
    everything when Pallas is off.  A change to RING, NODE_FACTOR or the
    ceiling that drops a documented window length off the kernel fails
    here, not on the chip."""
    from racon_tpu.ops import poa_driver

    for depth in poa_driver.DEPTH_BUCKETS:
        for scores in ((5, -4, -8), (3, -5, -4), (1, -1, -1)):
            cfg = poa_driver.make_config(poa_driver.window_class(length),
                                         depth, *scores)
            assert poa_driver._pick_tier(cfg, pallas) == tier
            if pallas:
                fits = [u for u in (1, 2, 4)
                        if poa_driver._fits_vmem(cfg, u)]
                assert fits == [u for u in (1, 2, 4) if u <= groups]
            if groups:
                assert poa_driver._group_width(cfg, 64) == groups
                assert poa_driver._group_width(cfg, 16) == min(groups, 2)
                assert poa_driver._group_width(cfg, 8) == 1
    assert poa_driver._next_tier("ls") == "xla"
    assert poa_driver._next_tier("xla") == "host"


def test_lockstep_driver_path_end_to_end(tmp_path, monkeypatch):
    """Full TpuPolisher flow with the lockstep branch of the consensus
    driver (interpret mode): exercises the Pallas dispatch, G-multiple
    batching, padding, marshalling, and unpacking."""
    import random as _r

    import racon_tpu

    rng = _r.Random(5)
    target = "".join(rng.choice("ACGT") for _ in range(240))
    with open(tmp_path / "target.fasta", "w") as f:
        f.write(f">tgt\n{target}\n")
    with open(tmp_path / "reads.fasta", "w") as f:
        for i in range(4):
            f.write(f">r{i}\n{target}\n")
    with open(tmp_path / "ovl.sam", "w") as f:
        f.write("@HD\tVN:1.6\n")
        for i in range(4):
            f.write(f"r{i}\t0\ttgt\t1\t60\t240M\t*\t0\t0\t{target}\t*\n")

    monkeypatch.setenv("RACON_TPU_PALLAS", "1")
    monkeypatch.setenv("RACON_TPU_BATCH_WINDOWS", "4")  # rounds up to G=8
    p = racon_tpu.TpuPolisher(str(tmp_path / "reads.fasta"),
                              str(tmp_path / "ovl.sam"),
                              str(tmp_path / "target.fasta"),
                              window_length=80, quality_threshold=10,
                              error_threshold=0.3, match=5, mismatch=-4,
                              gap=-8, num_threads=1)
    p.initialize()
    res = p.polish(True)
    assert len(res) == 1
    assert res[0][1] == target  # perfect reads -> perfect consensus


def _perfect_reads_dataset(tmp_path, insert_at=None):
    """Four reads equal to the target; with `insert_at`, each with a T
    the target lacks before that position."""
    target = "ACGT" * 60
    read, cigar = target, f"{len(target)}M"
    if insert_at is not None:
        read = target[:insert_at] + "T" + target[insert_at:]
        cigar = f"{insert_at}M1I{len(target) - insert_at}M"
    with open(tmp_path / "t.fasta", "w") as f:
        f.write(f">t\n{target}\n")
    with open(tmp_path / "r.fasta", "w") as f:
        for i in range(4):
            f.write(f">r{i}\n{read}\n")
    with open(tmp_path / "o.sam", "w") as f:
        f.write("@HD\tVN:1.6\n")
        for i in range(4):
            f.write(f"r{i}\t0\tt\t1\t60\t{cigar}\t*\t0\t0\t{read}"
                    f"\t*\n")
    return target


def _polish_perfect_reads(tmp_path):
    import racon_tpu

    p = racon_tpu.TpuPolisher(str(tmp_path / "r.fasta"),
                              str(tmp_path / "o.sam"),
                              str(tmp_path / "t.fasta"),
                              window_length=100, match=5, mismatch=-4,
                              gap=-8)
    p.initialize()
    return p.polish(True), p.report.as_dict()["phases"]["consensus"]


@pytest.mark.parametrize("seam", ["compile", "run"])
def test_lockstep_failure_degrades_to_xla_kernel(tmp_path, monkeypatch,
                                                 capsys, seam):
    """A Mosaic failure of the lockstep kernel, at its build or at its
    call, must degrade to the XLA kernel, not crash the polish."""
    target = _perfect_reads_dataset(tmp_path)

    def broken_ls(cfg, **kw):
        if seam == "compile":
            raise RuntimeError("synthetic mosaic failure")

        def make(batch):
            def call(*args):
                raise RuntimeError("synthetic mosaic failure")
            return call
        return make

    monkeypatch.setenv("RACON_TPU_PALLAS", "1")
    monkeypatch.setenv("RACON_TPU_SHARD", "0")
    monkeypatch.setattr(
        "racon_tpu.ops.poa_pallas_ls.build_lockstep_poa_kernel", broken_ls)
    res, cons = _polish_perfect_reads(tmp_path)
    assert len(res) == 1
    assert res[0][1] == target
    assert "falling back to the XLA kernel" in capsys.readouterr().err
    assert [(d["from"], d["to"]) for d in cons["degradations"]] == \
        [("ls", "xla")]
    assert cons["served"]["ls"] == 0
    assert cons["served"]["xla"] == cons["total"]


def test_lockstep_runtime_failure_at_drain_degrades(tmp_path, monkeypatch,
                                                    capsys):
    """JAX async dispatch surfaces Mosaic runtime failures at the blocking
    transfer, not at the kernel call — the drain-time recovery must re-run
    the retained packed chunk through the XLA kernel and mark the geometry
    dead."""
    target = _perfect_reads_dataset(tmp_path)

    class _LazyFail:
        """Stands in for a device future whose error surfaces on transfer."""

        def __array__(self, *a, **k):
            raise RuntimeError("synthetic async mosaic failure")

    def async_broken_ls(cfg, **kw):
        def make(batch):
            def call(*args):
                return tuple(_LazyFail() for _ in range(5))
            return call
        return make

    monkeypatch.setenv("RACON_TPU_PALLAS", "1")
    # single-device dispatch: under shard_map the stand-in would fail
    # where it is traced, which is the call seam, not the drain
    monkeypatch.setenv("RACON_TPU_SHARD", "0")
    monkeypatch.setattr(
        "racon_tpu.ops.poa_pallas_ls.build_lockstep_poa_kernel",
        async_broken_ls)
    res, cons = _polish_perfect_reads(tmp_path)
    assert len(res) == 1
    assert res[0][1] == target
    assert "falling back to the XLA kernel" in capsys.readouterr().err
    assert cons["served"]["xla"] == cons["total"]
