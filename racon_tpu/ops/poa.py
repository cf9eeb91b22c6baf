"""Batched partial-order alignment (POA) on device.

TPU-native replacement for the reference's per-window SPOA consensus
(/root/reference/src/window.cpp:65-149) and its CUDA batch analogue
(/root/reference/src/cuda/cudabatch.cpp): one jitted program consumes a
padded batch of windows and emits consensus strings + per-node coverages.

Design (mirrors the host engine in racon_tpu/native/src/rt_poa.cpp, which is
the correctness oracle):

* The graph lives in fixed-size arrays per window. Every node belongs to a
  *column* identified by a strictly ordered fractional key (f32). Backbone
  column i has key exactly i; insertion columns take keys strictly between
  their neighbours. All edges increase the key, so topological order is a
  sort by key and the classic aligned-node ring is just "same key".
* Per layer (sequential, as POA fundamentally is): a global (kNW) sequence-
  to-graph DP over nodes in key order — the linear-gap horizontal pass is a
  cummax after the affine transform H[j] = j*g + cummax(V[j] - j*g) — then a
  device traceback (transition re-checking against exact maxima; no move
  matrix is stored), then a graph update scan that merges matched bases into
  columns, allocates insertion columns, and bumps edge weights by
  w[j-1]+w[j].
* Consensus: heaviest-bundle scoring over in-edges in key order, backward
  walk to a source, forward walk to a sink (branch completion), column
  coverage per consensus node.
* Any limit hit (node slots, in-edge slots, traceback budget) raises the
  window's `failed` flag -> the driver re-runs it on the host POA engine,
  reproducing the reference's accelerator->CPU fallback lattice
  (/root/reference/src/cuda/cudapolisher.cpp:354-378).

Shapes are static per (batch, depth, max_nodes, max_len) bucket; the driver
buckets windows to bound padding waste.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..device import named
from .kernel_cache import Program, device_keyed_cache

NEG = jnp.int32(-(1 << 28))
KEY_INF = jnp.float32(jnp.inf)

#: Why a kernel gave a window up: the value of its `failed` output, 0 for
#: a window it served.  The first cause a window meets is the one it
#: keeps (nothing runs for it afterwards).  `nodes` and `edges` are the
#: two capacities of PoaConfig (a node that does not fit max_nodes, a
#: node's in-edge past max_edges); `distance` is the lockstep kernel's
#: own (an in-subgraph edge over more ranks than its H ring holds, which
#: the XLA twin has no limit on); `other` is a traceback that found no
#: way back.  Shared by the twin, poa_pallas_ls and the driver's
#: poa.windows.overflow.* counters.
FAIL_OTHER, FAIL_NODES, FAIL_EDGES, FAIL_DISTANCE = 1, 2, 3, 4
FAIL_CAUSES = {FAIL_OTHER: "other", FAIL_NODES: "nodes",
               FAIL_EDGES: "edges", FAIL_DISTANCE: "distance"}


def first_cause(failed, cond, cause: int):
    """`failed` with `cause` where `cond` holds and nothing failed yet."""
    return jnp.where((failed == 0) & cond, cause, failed)


class PoaConfig(NamedTuple):
    max_nodes: int = 1536     # node slots per window graph
    max_len: int = 768        # max layer sequence length
    max_backbone: int = 512   # max backbone (window) length
    max_edges: int = 12       # in-edge slots per node
    depth: int = 32           # layer slots (batch bucket)
    match: int = 5
    mismatch: int = -4
    gap: int = -8


class Graph(NamedTuple):
    base: jnp.ndarray    # i32 [N] code 0..4, -1 unused
    key: jnp.ndarray     # f32 [N] column key, +inf unused
    cov: jnp.ndarray     # i32 [N] paths through node
    in_src: jnp.ndarray  # i32 [N, E] source node id, -1 empty slot
    in_w: jnp.ndarray    # i32 [N, E] edge weight
    n: jnp.ndarray       # i32 [] node count
    failed: jnp.ndarray  # i32 [] 0 or the FAIL_* cause


def _init_graph(cfg: PoaConfig, bb_codes, bb_w, bb_len):
    """Backbone chain: node i = column key i, edge i-1 -> i with weight
    w[i-1]+w[i] (host analogue: rt_poa.cpp add_alignment, empty-alignment
    branch)."""
    N, E = cfg.max_nodes, cfg.max_edges
    idx = jnp.arange(N, dtype=jnp.int32)
    used = idx < bb_len
    base = jnp.where(used, jnp.pad(bb_codes.astype(jnp.int32),
                                   (0, N - cfg.max_backbone)), -1)
    key = jnp.where(used, idx.astype(jnp.float32), KEY_INF)
    cov = jnp.where(used, 1, 0).astype(jnp.int32)
    in_src = jnp.full((N, E), -1, dtype=jnp.int32)
    in_w = jnp.zeros((N, E), dtype=jnp.int32)
    bbw = jnp.pad(bb_w.astype(jnp.int32), (0, N - cfg.max_backbone))
    chain = (idx > 0) & used
    in_src = in_src.at[:, 0].set(jnp.where(chain, idx - 1, -1))
    prev_w = jnp.roll(bbw, 1)
    in_w = in_w.at[:, 0].set(jnp.where(chain, prev_w + bbw, 0))
    return Graph(base, key, cov, in_src, in_w,
                 bb_len.astype(jnp.int32), jnp.int32(0))


def _dp_matrix(cfg: PoaConfig, g: Graph, seq, sub_mask, order, n_sub):
    """H[node+1, j] for the subgraph; row 0 is the virtual start."""
    N, L = cfg.max_nodes, cfg.max_len
    gp = jnp.int32(cfg.gap)
    jj = jnp.arange(L + 1, dtype=jnp.int32)

    H0 = jnp.full((N + 1, L + 1), NEG, dtype=jnp.int32)
    H0 = H0.at[0].set(jj * gp)

    def cond(c):
        r, _ = c
        return r < n_sub

    def body(c):
        r, H = c
        u = order[r]
        ub = g.base[u]
        srcs = g.in_src[u]
        srcs_c = jnp.maximum(srcs, 0)
        valid = (srcs >= 0) & sub_mask[srcs_c]
        any_valid = valid.any()

        prows = jnp.where(valid[:, None], H[srcs_c + 1], NEG)   # [E, L+1]
        P = jnp.where(any_valid, prows.max(axis=0), H[0])       # [L+1]

        sc = jnp.where(seq == ub, jnp.int32(cfg.match),
                       jnp.int32(cfg.mismatch))                 # [L]
        diag = P[:-1] + sc
        up = P + gp
        V = up.at[1:].max(diag)

        # Linear-gap horizontal pass: H[j] = j*g + cummax(V[j] - j*g).
        tr = V - jj * gp
        row = jax.lax.cummax(tr) + jj * gp
        return (r + 1, H.at[u + 1].set(row))

    return jax.lax.while_loop(cond, body, (jnp.int32(0), H0))[1]


def _traceback(cfg: PoaConfig, g: Graph, H, seq, sub_mask, order, n_sub, L):
    """Walk optimal path from the best end node; returns pos_node[MAXL]
    (matched node per seq position, -1 = insertion) and an ok flag."""
    N, MAXL = cfg.max_nodes, cfg.max_len
    gp = jnp.int32(cfg.gap)

    # End nodes: subgraph nodes with no out-edge inside the subgraph.
    srcs_c = jnp.maximum(g.in_src, 0)
    edge_live = (g.in_src >= 0) & sub_mask[srcs_c] & sub_mask[:, None]
    has_out = jnp.zeros(N, dtype=jnp.bool_).at[srcs_c.reshape(-1)].max(
        edge_live.reshape(-1))
    end_mask = sub_mask & ~has_out

    colL = jnp.take(H, L, axis=1)                 # [N+1]
    end_score = colL[1:]                          # per node id
    # First best in key order (host picks first max in rank order).
    score_by_rank = jnp.where(end_mask[order], end_score[order], NEG)
    best_r = jnp.argmax(score_by_rank)
    start_u = order[best_r]

    def cond(c):
        u, j, _, steps, _ = c
        return ~((u == -1) & (j == 0)) & (steps < N + MAXL + 2)

    def body(c):
        u, j, pos_node, steps, ok = c
        at_virtual = u == -1
        u_c = jnp.maximum(u, 0)
        cur = H[u_c + 1, j]
        ub = g.base[u_c]
        srcs = g.in_src[u_c]
        srcs_c2 = jnp.maximum(srcs, 0)
        valid = (srcs >= 0) & sub_mask[srcs_c2]
        any_valid = valid.any()
        prow_jm1 = jnp.where(valid, H[srcs_c2 + 1, jnp.maximum(j - 1, 0)], NEG)
        prow_j = jnp.where(valid, H[srcs_c2 + 1, j], NEG)

        sc = jnp.where(seq[jnp.maximum(j - 1, 0)] == ub,
                       jnp.int32(cfg.match), jnp.int32(cfg.mismatch))

        diag_ok = valid & (j > 0) & (prow_jm1 + sc == cur)
        diag_virt = ~any_valid & (j > 0) & (
            H[0, jnp.maximum(j - 1, 0)] + sc == cur)
        any_diag = diag_ok.any() | diag_virt
        diag_slot = jnp.argmax(diag_ok)
        diag_pred = jnp.where(diag_ok.any(), srcs[diag_slot], -1)

        up_ok = valid & (prow_j + gp == cur)
        up_virt = ~any_valid & (H[0, j] + gp == cur)
        any_up = up_ok.any() | up_virt
        up_slot = jnp.argmax(up_ok)
        up_pred = jnp.where(up_ok.any(), srcs[up_slot], -1)

        # Priority: diag > up > left (host: rt_poa.cpp traceback order).
        take_diag = ~at_virtual & any_diag
        take_up = ~at_virtual & ~any_diag & any_up
        # left: insertion (also the only move from the virtual row)

        new_u = jnp.where(take_diag, diag_pred,
                          jnp.where(take_up, up_pred, u))
        new_j = jnp.where(take_diag | ~take_up, j - 1, j)
        new_j = jnp.where(take_up, j, new_j)
        wrote = take_diag
        pos_node = pos_node.at[jnp.maximum(j - 1, 0)].set(
            jnp.where(wrote, u, pos_node[jnp.maximum(j - 1, 0)]))
        return (new_u, new_j, pos_node, steps + 1, ok)

    pos_node0 = jnp.full(MAXL, -1, dtype=jnp.int32)
    u, j, pos_node, steps, _ = jax.lax.while_loop(
        cond, body, (start_u, L.astype(jnp.int32), pos_node0,
                     jnp.int32(0), jnp.bool_(True)))
    ok = (u == -1) & (j == 0)
    return pos_node, ok


def _update_graph(cfg: PoaConfig, g: Graph, pos_node, seq, w, L):
    """Thread the sequence through the graph along pos_node (host analogue:
    rt_poa.cpp add_alignment main loop)."""
    N, MAXL, E = cfg.max_nodes, cfg.max_len, cfg.max_edges
    jj = jnp.arange(MAXL, dtype=jnp.int32)
    active = jj < L
    matched = (pos_node >= 0) & active
    mkey = jnp.where(matched, g.key[jnp.maximum(pos_node, 0)], KEY_INF)

    # next matched column key at j' >= j, and remaining insertion-run length.
    def rev_scan(carry, x):
        nk, run = carry
        m, k = x
        nk = jnp.where(m, k, nk)
        run = jnp.where(m, 0, run + 1)
        return (nk, run), (nk, run)

    (_, _), (next_key, run_rem) = jax.lax.scan(
        rev_scan, (KEY_INF, jnp.int32(0)),
        (matched[::-1], mkey[::-1]))
    next_key = next_key[::-1]
    run_rem = run_rem[::-1]

    def body(carry):
        g, prev, prev_key, prev_w, j = carry
        act = active[j]
        b = seq[j].astype(jnp.int32)
        wj = w[j]

        k0 = mkey[j]
        is_match = matched[j]
        cand = (g.key == k0) & (g.base == b)
        has = cand.any() & is_match
        found = jnp.argmax(cand)

        hi = jnp.where(jnp.isfinite(next_key[j]), next_key[j], prev_key + 1.0)
        lo = jnp.where(prev >= 0, prev_key,
                       hi - run_rem[j].astype(jnp.float32) - 1.0)
        k_new = lo + (hi - lo) / (run_rem[j].astype(jnp.float32) + 1.0)
        key_val = jnp.where(is_match, k0, k_new)

        need_new = act & ~has
        overflow = need_new & (g.n >= N)
        do_new = need_new & ~overflow
        nid = jnp.where(has, found, jnp.minimum(g.n, N - 1))

        base = g.base.at[nid].set(jnp.where(do_new, b, g.base[nid]))
        key = g.key.at[nid].set(jnp.where(do_new, key_val, g.key[nid]))
        touch = act & ~overflow
        cov = g.cov.at[nid].add(jnp.where(touch, 1, 0))
        n = g.n + jnp.where(do_new, 1, 0)
        failed = first_cause(g.failed, overflow, FAIL_NODES)

        # Edge prev -> nid with weight w[j-1] + w[j].
        has_prev = touch & (prev >= 0)
        slots = g.in_src[nid]
        same = slots == prev
        empty = slots == -1
        ew = prev_w + wj
        use_same = has_prev & same.any()
        use_empty = has_prev & ~same.any() & empty.any()
        slot = jnp.where(same.any(), jnp.argmax(same), jnp.argmax(empty))
        in_w = g.in_w.at[nid, slot].add(
            jnp.where(use_same, ew, 0))
        in_w = in_w.at[nid, slot].set(
            jnp.where(use_empty, ew, in_w[nid, slot]))
        in_src = g.in_src.at[nid, slot].set(
            jnp.where(use_empty, prev, g.in_src[nid, slot]))
        failed = first_cause(
            failed, has_prev & ~same.any() & ~empty.any(), FAIL_EDGES)

        prev = jnp.where(act, nid, prev)
        prev_key = jnp.where(act, key[nid], prev_key)
        prev_w = jnp.where(act, wj, prev_w)
        g2 = Graph(base, key, cov, in_src, in_w, n, failed)
        return (g2, prev, prev_key, prev_w, j + 1)

    g = jax.lax.while_loop(
        lambda c: c[4] < L,
        body,
        (g, jnp.int32(-1), jnp.float32(-1.0), jnp.int32(0), jnp.int32(0)))[0]
    return g


def _add_layer(cfg: PoaConfig, g: Graph, seq, w, L, begin, end, bb_len):
    """Align one layer against the (sub)graph and merge it in
    (host analogue: rt_window.cpp generate_consensus loop body)."""
    offset = (0.01 * bb_len.astype(jnp.float32)).astype(jnp.int32)
    full = (begin < offset) & (end > bb_len - offset)
    lo = jnp.where(full, -jnp.inf, begin.astype(jnp.float32))
    hi = jnp.where(full, jnp.inf, end.astype(jnp.float32))

    sub_mask = (g.key >= lo) & (g.key <= hi)
    sort_keys = jnp.where(sub_mask, g.key, KEY_INF)
    order = jnp.argsort(sort_keys).astype(jnp.int32)
    n_sub = sub_mask.sum().astype(jnp.int32)

    H = _dp_matrix(cfg, g, seq, sub_mask, order, n_sub)
    pos_node, ok = _traceback(cfg, g, H, seq, sub_mask, order, n_sub, L)
    g = g._replace(failed=first_cause(g.failed, ~ok, FAIL_OTHER))
    return _update_graph(cfg, g, pos_node, seq, w, L)


def _consensus(cfg: PoaConfig, g: Graph):
    """Heaviest bundle + branch completion + column coverage
    (host analogue: rt_poa.cpp generate_consensus)."""
    N = cfg.max_nodes
    order = jnp.argsort(g.key).astype(jnp.int32)

    def score_body(c):
        r, score, pred = c
        u = order[r]
        srcs = g.in_src[u]
        srcs_c = jnp.maximum(srcs, 0)
        valid = srcs >= 0
        w = jnp.where(valid, g.in_w[u], NEG)
        ps = jnp.where(valid, score[srcs_c], NEG)
        wmax = w.max()
        any_valid = valid.any()
        cand = valid & (w == wmax)
        slot = jnp.argmax(jnp.where(cand, ps, NEG))
        s = jnp.where(any_valid, wmax + ps[slot], 0)
        p = jnp.where(any_valid, srcs[slot], -1)
        return (r + 1, score.at[u].set(s), pred.at[u].set(p))

    score0 = jnp.zeros(N, dtype=jnp.int32)
    pred0 = jnp.full(N, -1, dtype=jnp.int32)
    _, score, pred = jax.lax.while_loop(
        lambda c: c[0] < g.n, score_body, (jnp.int32(0), score0, pred0))

    rr = jnp.arange(N, dtype=jnp.int32)
    score_by_rank = jnp.where(rr < g.n, score[order], NEG)
    summit = order[jnp.argmax(score_by_rank)]

    # Backward to a source.
    def bcond(c):
        u, _, cnt = c
        return (u != -1) & (cnt < N)

    def bbody(c):
        u, buf, cnt = c
        buf = buf.at[cnt].set(u)
        return (pred[u], buf, cnt + 1)

    buf0 = jnp.full(N, -1, dtype=jnp.int32)
    _, rev_buf, cnt_b = jax.lax.while_loop(
        bcond, bbody, (summit, buf0, jnp.int32(0)))

    flip_idx = jnp.clip(cnt_b - 1 - rr, 0, N - 1)
    path = jnp.where(rr < cnt_b, rev_buf[flip_idx], -1)

    # Forward from the summit along heaviest out-edges to a sink.
    def fcond(c):
        u, _, cnt, more = c
        return more & (cnt < N)

    def fbody(c):
        u, path, cnt, _ = c
        ew = jnp.where(g.in_src == u, g.in_w, NEG)    # [N, E]
        wv = ew.max(axis=1)                           # best edge u->v per v
        any_out = (wv > NEG).any()
        wmax = wv.max()
        cand = wv == wmax
        v = jnp.argmax(jnp.where(cand, score, NEG))
        path = path.at[cnt].set(jnp.where(any_out, v, -1))
        return (jnp.where(any_out, v, u).astype(jnp.int32),
                path, cnt + jnp.where(any_out, 1, 0), any_out)

    path, cnt = jax.lax.while_loop(
        fcond, fbody, (summit, path, cnt_b, jnp.bool_(True)))[1:3]

    # Node coverage per path node (trim-rule input; matches the host
    # oracle's semantics).
    path_c = jnp.maximum(path, 0)
    cons_base = jnp.where(path >= 0, g.base[path_c], -1)
    cons_cov = jnp.where(path >= 0, g.cov[path_c], 0)
    return cons_base, cons_cov, cnt


def _polish_window(cfg: PoaConfig, bb_codes, bb_w, bb_len, n_layers,
                   seqs, ws, lens, begins, ends):
    """Full per-window program: init graph, fold in layers, consensus."""
    g = _init_graph(cfg, bb_codes, bb_w, bb_len)

    def layer_body(c):
        g, li = c
        seq = seqs[li]
        w = ws[li]
        L = lens[li]
        use = (L > 0) & (g.failed == 0)
        g = jax.lax.cond(
            use,
            lambda g: _add_layer(cfg, g, seq, w, L, begins[li], ends[li],
                                 bb_len),
            lambda g: g,
            g)
        return (g, li + 1)

    g = jax.lax.while_loop(
        lambda c: c[1] < n_layers, layer_body, (g, jnp.int32(0)))[0]

    cons_base, cons_cov, cons_len = _consensus(cfg, g)
    return cons_base, cons_cov, cons_len, g.failed, g.n


@device_keyed_cache(maxsize=32)
def build_poa_kernel(cfg: PoaConfig):
    """The batch kernel as a Program: all inputs have a leading batch
    dim."""

    @named("racon_poa_xla")
    def batch_fn(bb_codes, bb_w, bb_len, n_layers, seqs, ws, lens, begins,
                 ends):
        return jax.vmap(
            lambda a, b, c, d, e, f, gg, h, i:
            _polish_window(cfg, a, b, c, d, e, f, gg, h, i)
        )(bb_codes, bb_w, bb_len, n_layers, seqs, ws, lens, begins, ends)

    return Program(batch_fn, key=("racon_poa_xla", cfg))
