"""Stage 3 of the RGL pipeline: batched graph retrieval (paper §2.1.3).

TPU-native re-expression of RGL's C++ retrieval engine.  All three paper
strategies — RGL-BFS, RGL-Dense, RGL-Steiner — are implemented as
*fixed-shape frontier algebra* over the ELL adjacency:

* BFS          — pull-based frontier expansion: one (Q, N, K) gather per hop.
* Steiner      — Mehlhorn/KMB 2-approximation: one multi-source
                 label-propagating BFS builds Voronoi cells, bridge edges give
                 terminal-pair distances, a fixed-iteration Prim MST picks the
                 tree topology, and distance-descent backtracing marks path
                 nodes.  Unweighted graphs (all paper datasets) ⇒ BFS ≡ Dijkstra.
* Dense        — greedy peeling: the k-hop candidate ball is refined by
                 iterated internal-degree ranking (densest-subgraph heuristic).

Every strategy exists in two backends sharing one output contract:

* **dense**   — per-hop work is O(N): full-graph gathers, full-graph ranking.
                Exact, simple, and fine while N is small.
* **compact** — per-hop work is O(C): seeds are expanded into a fixed-capacity
                sorted *workset* of C candidate ids (:mod:`repro.core.workset`,
                with the ``kernels.frontier_expand`` hop), and the
                strategy runs over the workset-local induced adjacency.  When
                no query overflows the capacity, the output — nodes, mask,
                dist, including tie order — is bitwise identical to the dense
                backend; overflow is reported per query so callers can fall
                back (``mode="auto"`` does so automatically).

Everything is batched over queries (the paper's core speedup mechanism:
amortize per-query overhead) and jit-compiled; graphs must be symmetric
(generators symmetrize; pull-BFS reads in-neighbors).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.workset import (
    CSRGather, Workset, build_workset, csr_gather, localize, workset_adjacency,
)
from repro.graph.ell import ELLGraph
from repro.kernels.bfs_frontier import ops as bfs_frontier_ops

INF = jnp.int32(0x3FFFFFF)

# graphs at least this large route to the compact backend under mode="auto";
# set from the measured dense/compact crossover (BENCH_retrieval_scaling.json:
# compact loses below ~100k nodes on CPU, wins 3-15x above 200k)
AUTO_COMPACT_MIN_NODES = 100_000


@dataclasses.dataclass
class Subgraph:
    """Padded per-query subgraph: ``nodes`` ordered by retrieval priority.

    ``overflow`` is only populated by the compact backend: True for queries
    whose candidate ball exceeded the workset capacity (output truncated
    deterministically, no longer dense-parity).  ``None`` means the dense
    backend ran (never truncates).
    """

    nodes: jnp.ndarray  # (Q, M) int32, sentinel = num_nodes where ~mask
    mask: jnp.ndarray  # (Q, M) bool
    dist: jnp.ndarray  # (Q, M) int32 hop distance of each picked node
    num_nodes: int  # N of the parent graph
    overflow: Optional[jnp.ndarray] = None  # (Q,) bool, compact backend only


jax.tree_util.register_dataclass(
    Subgraph,
    data_fields=["nodes", "mask", "dist", "overflow"],
    meta_fields=["num_nodes"],
)


def seeds_to_mask(seeds: jnp.ndarray, n: int) -> jnp.ndarray:
    """(Q, S) seed indices (pad with -1 or >=n) -> (Q, N) bool mask."""
    q, s = seeds.shape
    valid = (seeds >= 0) & (seeds < n)
    safe = jnp.where(valid, seeds, 0)
    base = jnp.zeros((q, n), bool)
    return base.at[jnp.arange(q)[:, None], safe].max(valid)


@functools.partial(jax.jit, static_argnames=("max_hops",))
def bfs_distances(
    nbr: jnp.ndarray,
    nbr_mask: jnp.ndarray,
    seeds_mask: jnp.ndarray,
    max_hops: int,
) -> jnp.ndarray:
    """Batched BFS hop distances.  (Q, N) int32; INF where unreached."""
    dist0 = jnp.where(seeds_mask, 0, INF)

    def hop(carry, h):
        dist, frontier = carry
        # one pull hop through the kernels.bfs_frontier op (its jnp arm:
        # the Pallas kernel is opt-in, see that op's docstring)
        reach = bfs_frontier_ops.frontier_hop(frontier, nbr, nbr_mask)
        new = reach & (dist == INF)
        dist = jnp.where(new, h + 1, dist)
        return (dist, new), None

    (dist, _), _ = jax.lax.scan(
        hop, (dist0, seeds_mask), jnp.arange(max_hops, dtype=jnp.int32)
    )
    return dist


@functools.partial(jax.jit, static_argnames=("max_hops",))
def voronoi_bfs(
    nbr: jnp.ndarray,
    nbr_mask: jnp.ndarray,
    seeds: jnp.ndarray,  # (Q, T) terminal node ids (may contain -1 padding)
    max_hops: int,
):
    """Multi-source BFS with source labels.

    Returns (dist (Q,N) int32, label (Q,N) int32 in [0,T) or T for none).
    Ties: lowest terminal slot wins (deterministic).
    """
    q, t = seeds.shape
    n = nbr.shape[0]
    valid = (seeds >= 0) & (seeds < n)
    safe = jnp.where(valid, seeds, 0)
    label0 = jnp.full((q, n), t, jnp.int32)
    # lower slot wins ties at init: scatter in reverse slot order via min
    slot = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None], (q, t))
    slot = jnp.where(valid, slot, t)
    label0 = label0.at[jnp.arange(q)[:, None], safe].min(slot)
    dist0 = jnp.where(label0 < t, 0, INF)

    def hop(carry, h):
        dist, label, frontier = carry
        qn = frontier.shape[0]
        fp = jnp.concatenate([frontier, jnp.zeros((qn, 1), bool)], 1)
        lp = jnp.concatenate([label, jnp.full((qn, 1), t, jnp.int32)], 1)
        g_f = fp[:, nbr]  # (Q, N, K) neighbor-in-frontier
        g_l = lp[:, nbr]  # (Q, N, K) neighbor labels
        active = g_f & nbr_mask[None]
        cand = jnp.where(active, g_l, t)
        best = jnp.min(cand, axis=-1)  # (Q, N) best label among frontier nbrs
        reach = jnp.any(active, axis=-1)
        new = reach & (dist == INF)
        dist = jnp.where(new, h + 1, dist)
        label = jnp.where(new, best, label)
        return (dist, label, new), None

    (dist, label, _), _ = jax.lax.scan(
        hop, (dist0, label0, dist0 == 0), jnp.arange(max_hops, dtype=jnp.int32)
    )
    return dist, label


def _select_by_key(key: jnp.ndarray, keep: jnp.ndarray, m: int, n: int):
    """Pick m nodes with the smallest ``key`` among ``keep``; pad w/ sentinel n.

    Returns (nodes (Q,m) int32, mask (Q,m) bool, order-aligned gather of key).
    """
    big = jnp.int32(0x7FFFFFF0)
    k = jnp.where(keep, key, big)
    neg = -(k.astype(jnp.int32))
    topv, topi = jax.lax.top_k(neg, m)  # largest of -key == smallest key
    mask = topv > -big
    nodes = jnp.where(mask, topi, n).astype(jnp.int32)
    return nodes, mask, jnp.where(mask, -topv, INF)


def _select_ws(key: jnp.ndarray, keep: jnp.ndarray, ws: Workset, m: int):
    """Workset-local ``_select_by_key``: same keys, positions mapped back to
    global ids.  Keys embed the global node id, so with identical (key, keep)
    sets the selection — values, order, padding — matches the dense path.

    Returns (nodes (Q,m) int32 global, mask (Q,m) bool, topi (Q,m) positions).
    """
    n = ws.num_nodes
    big = jnp.int32(0x7FFFFFF0)
    k = jnp.where(keep & (ws.ids < n), key, big)
    topv, topi = jax.lax.top_k(-k, m)
    mask = topv > -big
    nodes = jnp.where(mask, jnp.take_along_axis(ws.ids, topi, 1), n)
    return nodes.astype(jnp.int32), mask, topi


def _gather_local(rowvals: jnp.ndarray, wnbr: jnp.ndarray, fill):
    """Gather per-slot values over the local adjacency with a slack column.

    rowvals (Q, C); wnbr (Q, C, K) positions with sentinel C; ``fill`` is the
    value served for sentinel slots.  Returns (Q, C, K).
    """
    q, c, k = wnbr.shape
    padded = jnp.concatenate(
        [rowvals, jnp.full((q, 1), fill, rowvals.dtype)], axis=1
    )
    return jnp.take_along_axis(padded, wnbr.reshape(q, c * k), 1).reshape(q, c, k)


# ---------------------------------------------------------------- BFS --------


@functools.partial(jax.jit, static_argnames=("max_hops", "max_nodes"))
def bfs_subgraph(
    nbr: jnp.ndarray,
    nbr_mask: jnp.ndarray,
    seeds: jnp.ndarray,  # (Q, S)
    *,
    max_hops: int = 3,
    max_nodes: int = 64,
) -> Subgraph:
    """RGL-BFS: closest-first ball around the retrieved seed nodes."""
    n = nbr.shape[0]
    sm = seeds_to_mask(seeds, n)
    dist = bfs_distances(nbr, nbr_mask, sm, max_hops)
    keep = dist < INF
    d = jnp.minimum(dist, max_hops + 1)
    key = d * jnp.int32(n) + jnp.arange(n, dtype=jnp.int32)[None, :]
    nodes, mask, _ = _select_by_key(key, keep, max_nodes, n)
    dsel = jnp.where(mask, jnp.take_along_axis(d, jnp.minimum(nodes, n - 1), 1), INF)
    return Subgraph(nodes=nodes, mask=mask, dist=dsel, num_nodes=n)


@functools.partial(
    jax.jit, static_argnames=("max_hops", "max_nodes", "workset_cap")
)
def bfs_subgraph_compact(
    nbr: jnp.ndarray,
    nbr_mask: jnp.ndarray,
    seeds: jnp.ndarray,  # (Q, S)
    *,
    max_hops: int = 3,
    max_nodes: int = 64,
    workset_cap: int = 2048,
    csr: Optional[CSRGather] = None,
) -> Subgraph:
    """RGL-BFS over the workset: O(C) per hop instead of O(N)."""
    n = nbr.shape[0]
    ws = build_workset(
        nbr, nbr_mask, seeds, max_hops=max_hops, cap=workset_cap, csr=csr
    )
    key = ws.dist * jnp.int32(n) + jnp.where(ws.ids < n, ws.ids, 0)
    nodes, mask, topi = _select_ws(key, ws.ids < n, ws, max_nodes)
    dsel = jnp.where(mask, jnp.take_along_axis(ws.dist, topi, 1), INF)
    return Subgraph(
        nodes=nodes, mask=mask, dist=dsel, num_nodes=n, overflow=ws.overflow
    )


# ---------------------------------------------------------------- Dense ------


@functools.partial(jax.jit, static_argnames=("max_hops", "max_nodes", "n_rounds"))
def dense_subgraph(
    nbr: jnp.ndarray,
    nbr_mask: jnp.ndarray,
    seeds: jnp.ndarray,
    *,
    max_hops: int = 2,
    max_nodes: int = 64,
    n_rounds: int = 3,
) -> Subgraph:
    """RGL-Dense: greedy internal-degree peeling of the k-hop candidate ball."""
    n, k = nbr.shape
    q = seeds.shape[0]
    sm = seeds_to_mask(seeds, n)
    dist = bfs_distances(nbr, nbr_mask, sm, max_hops)
    cand = dist < INF  # (Q, N) candidate ball

    def indeg(c):
        cp = jnp.concatenate([c, jnp.zeros((q, 1), bool)], 1)
        g = cp[:, nbr] & nbr_mask[None]  # (Q, N, K)
        return jnp.sum(g, axis=-1).astype(jnp.int32) * c

    def round_(c, _):
        deg = indeg(c)
        # threshold = max_nodes-th largest degree among candidates
        kth = jax.lax.top_k(jnp.where(c, deg, -1), min(max_nodes, n))[0][:, -1]
        keep = c & (deg >= kth[:, None])
        keep = keep | sm  # never peel seeds
        return keep, None

    cand, _ = jax.lax.scan(round_, cand, None, length=n_rounds)
    deg = indeg(cand)
    # final pick: highest internal degree first, then closer, then lower id;
    # seeds get the minimal key band (always < n) so they are never evicted
    d = jnp.minimum(dist, max_hops + 1)
    key = (jnp.int32(k + 1) - deg) * jnp.int32((max_hops + 2) * n) + d * jnp.int32(n) \
        + jnp.arange(n, dtype=jnp.int32)[None, :]
    key = jnp.where(sm, jnp.arange(n, dtype=jnp.int32)[None, :], key)
    nodes, mask, _ = _select_by_key(key, cand, max_nodes, n)
    dsel = jnp.where(mask, jnp.take_along_axis(d, jnp.minimum(nodes, n - 1), 1), INF)
    return Subgraph(nodes=nodes, mask=mask, dist=dsel, num_nodes=n)


@functools.partial(
    jax.jit,
    static_argnames=("max_hops", "max_nodes", "n_rounds", "workset_cap"),
)
def dense_subgraph_compact(
    nbr: jnp.ndarray,
    nbr_mask: jnp.ndarray,
    seeds: jnp.ndarray,
    *,
    max_hops: int = 2,
    max_nodes: int = 64,
    n_rounds: int = 3,
    workset_cap: int = 2048,
    csr: Optional[CSRGather] = None,
) -> Subgraph:
    """RGL-Dense over the workset: peeling scores C nodes per round, not N."""
    n, k = nbr.shape
    ws = build_workset(
        nbr, nbr_mask, seeds, max_hops=max_hops, cap=workset_cap, csr=csr
    )
    wnbr, wmask = workset_adjacency(nbr, nbr_mask, ws.ids)
    valid = ws.ids < n
    sm = valid & (ws.dist == 0)  # seed slots: the distinct valid seeds
    cand0 = valid  # every workset entry is inside the max_hops ball

    def indeg(c):
        g = _gather_local(c, wnbr, False) & wmask
        return jnp.sum(g, axis=-1).astype(jnp.int32) * c

    def round_(c, _):
        deg = indeg(c)
        kth = jax.lax.top_k(
            jnp.where(c, deg, -1), min(max_nodes, workset_cap)
        )[0][:, -1]
        keep = c & (deg >= kth[:, None])
        keep = keep | sm
        return keep, None

    cand, _ = jax.lax.scan(round_, cand0, None, length=n_rounds)
    deg = indeg(cand)
    d = jnp.minimum(ws.dist, max_hops + 1)
    gid = jnp.where(valid, ws.ids, 0)
    key = (jnp.int32(k + 1) - deg) * jnp.int32((max_hops + 2) * n) \
        + d * jnp.int32(n) + gid
    key = jnp.where(sm, gid, key)
    nodes, mask, topi = _select_ws(key, cand, ws, max_nodes)
    dsel = jnp.where(mask, jnp.take_along_axis(d, topi, 1), INF)
    return Subgraph(
        nodes=nodes, mask=mask, dist=dsel, num_nodes=n, overflow=ws.overflow
    )


# ---------------------------------------------------------------- Steiner ----


def _seg_min(vals, segs, t):
    return jax.vmap(
        lambda v, s: jax.ops.segment_min(v, s, num_segments=t * t)
    )(vals, segs)


def _terminal_metric(d_src, d_dst, l_src, l_dst, e_mask, eid, t, eid_sentinel):
    """Terminal-pair shortest-path metric from bridge edges.

    All inputs are flattened edge tables (Q, E) — the dense path passes the
    full N*K edge set, the compact path the C*K workset edge set; ``eid``
    carries *global* edge ids in both, so the per-pair argmin tie-break is
    backend independent.  Returns (w (Q,T,T) symmetric pair lengths with INF
    diagonal, best_eid (Q,T,T) global edge id realizing each pair).
    """
    q = d_src.shape[0]
    e_ok = (
        e_mask
        & (l_src < t) & (l_dst < t) & (l_src != l_dst)
        & (d_src < INF) & (d_dst < INF)
    )
    plen = jnp.where(e_ok, d_src + 1 + d_dst, INF)  # (Q, E)
    pair = jnp.where(e_ok, l_src * t + l_dst, 0)  # (Q, E) in [0, T*T)
    w = _seg_min(plen, pair, t)  # (Q, T*T) pairwise path lengths
    # best bridge edge per pair: two-pass argmin (value then edge id)
    at_min = e_ok & (plen == jnp.take_along_axis(w, pair, axis=1))
    best_eid = _seg_min(
        jnp.where(at_min, eid, jnp.int32(eid_sentinel)), pair, t
    )
    w = w.reshape(q, t, t)
    w = jnp.minimum(w, jnp.swapaxes(w, 1, 2))  # symmetrize
    w = jnp.where(jnp.eye(t, dtype=bool)[None], INF, w)
    best_eid = jnp.minimum(
        best_eid.reshape(q, t, t), jnp.swapaxes(best_eid.reshape(q, t, t), 1, 2)
    )
    return w, best_eid


def _prim_mst(w, t):
    """Fixed-iteration Prim MST over the (Q, T, T) terminal metric."""
    q = w.shape[0]
    in_tree0 = jnp.zeros((q, t), bool).at[:, 0].set(True)

    def prim(carry, _):
        in_tree, edges, step = carry
        m = jnp.where(in_tree[:, :, None] & ~in_tree[:, None, :], w, INF)
        flat = m.reshape(q, t * t)
        best = jnp.argmin(flat, axis=1)
        a, b = best // t, best % t
        ok = jnp.take_along_axis(flat, best[:, None], 1)[:, 0] < INF
        in_tree = in_tree.at[jnp.arange(q), jnp.where(ok, b, 0)].max(ok)
        edges = edges.at[:, step, 0].set(jnp.where(ok, a, -1))
        edges = edges.at[:, step, 1].set(jnp.where(ok, b, -1))
        return (in_tree, edges, step + 1), None

    edges0 = jnp.full((q, max(t - 1, 1), 2), -1, jnp.int32)
    (_, mst, _), _ = jax.lax.scan(
        prim, (in_tree0, edges0, 0), None, length=max(t - 1, 0)
    )
    return mst


def _descend_paths(marked, start, start_ok, dist, dp, row_fn, length):
    """Walk from ``start`` toward its terminal by strict dist descent,
    marking every visited position.  ``row_fn(cur)`` returns the (Q, K)
    neighbor positions + mask of each query's current node — global
    adjacency for the dense path, workset-local for the compact path."""
    q = start.shape[0]

    def body(carry, _):
        cur, ok, mk = carry
        mk = mk.at[jnp.arange(q), jnp.where(ok, cur, 0)].max(ok)
        dcur = jnp.take_along_axis(dist, cur[:, None], 1)[:, 0]
        nb, nbm = row_fn(cur)  # (Q, K) each
        dn = jnp.take_along_axis(dp, nb, 1)  # (Q, K)
        want = nbm & (dn == (dcur - 1)[:, None])
        pick = jnp.argmax(want, axis=1)
        nxt = jnp.take_along_axis(nb, pick[:, None], 1)[:, 0]
        ok = ok & jnp.any(want, axis=1) & (dcur > 0)
        cur = jnp.where(ok, nxt, cur)
        return (cur, ok, mk), None

    (_, _, marked), _ = jax.lax.scan(
        body, (start, start_ok, marked), None, length=length
    )
    return marked


@functools.partial(jax.jit, static_argnames=("max_hops", "max_nodes"))
def steiner_subgraph(
    nbr: jnp.ndarray,
    nbr_mask: jnp.ndarray,
    seeds: jnp.ndarray,  # (Q, T) terminals
    *,
    max_hops: int = 4,
    max_nodes: int = 64,
) -> Subgraph:
    """RGL-Steiner: KMB/Mehlhorn 2-approx Steiner tree over the terminals.

    1. Voronoi BFS: dist-to-nearest-terminal + owning terminal per node.
    2. Bridge edges (u,v), label(u) != label(v) give candidate terminal-pair
       path lengths dist(u)+1+dist(v); segment-min over label pairs.
    3. Prim MST over the (T, T) terminal metric (fixed T-1 iterations).
    4. Mark MST-edge bridge endpoints; distance-descent backtrace marks the
       connecting shortest paths.  Tree nodes ranked closest-first.
    """
    n, k = nbr.shape
    q, t = seeds.shape
    dist, label = voronoi_bfs(nbr, nbr_mask, seeds, max_hops)

    # ---- bridge edges between Voronoi cells -------------------------------
    src = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[:, None], (n, k))
    dst = nbr  # (N, K)
    dp = jnp.concatenate([dist, jnp.full((q, 1), INF, jnp.int32)], 1)
    lp = jnp.concatenate([label, jnp.full((q, 1), t, jnp.int32)], 1)
    d_src = dist[:, src.reshape(-1)].reshape(q, n * k)
    d_dst = dp[:, dst.reshape(-1)].reshape(q, n * k)
    l_src = label[:, src.reshape(-1)].reshape(q, n * k)
    l_dst = lp[:, dst.reshape(-1)].reshape(q, n * k)
    eid = jnp.broadcast_to(jnp.arange(n * k, dtype=jnp.int32)[None], (q, n * k))
    w, best_eid = _terminal_metric(
        d_src, d_dst, l_src, l_dst, nbr_mask.reshape(-1)[None, :],
        eid, t, n * k,
    )

    mst = _prim_mst(w, t)

    # ---- mark tree nodes: terminals + bridge endpoints + backtraces --------
    marked = seeds_to_mask(seeds, n)
    row_fn = lambda cur: (nbr[cur], nbr_mask[cur])  # noqa: E731

    n_mst = mst.shape[1]
    for e in range(n_mst):  # T is small (≤16); unrolled loop over MST edges
        a, b = mst[:, e, 0], mst[:, e, 1]
        ok = a >= 0
        be = best_eid[jnp.arange(q), jnp.maximum(a, 0), jnp.maximum(b, 0)]
        ok = ok & (be < n * k)
        be = jnp.where(ok, be, 0)
        u, slot = be // k, be % k
        v = nbr[u, slot]
        marked = _descend_paths(marked, u, ok, dist, dp, row_fn, max_hops + 1)
        marked = _descend_paths(
            marked, jnp.minimum(v, n - 1), ok & (v < n), dist, dp, row_fn,
            max_hops + 1,
        )

    d = jnp.minimum(dist, max_hops + 1)
    key = d * jnp.int32(n) + jnp.arange(n, dtype=jnp.int32)[None, :]
    nodes, mask, _ = _select_by_key(key, marked, max_nodes, n)
    dsel = jnp.where(mask, jnp.take_along_axis(d, jnp.minimum(nodes, n - 1), 1), INF)
    return Subgraph(nodes=nodes, mask=mask, dist=dsel, num_nodes=n)


def _workset_voronoi_labels(ws: Workset, wnbr, wmask, seeds, max_hops: int):
    """Voronoi owner labels over the workset.  ``ws.dist`` *is* the
    multi-source BFS distance from the terminal set, so only the label
    propagation re-runs: nodes at distance h inherit the minimum label among
    neighbors at distance h-1 — the dense path's tie-break exactly."""
    q, t = seeds.shape
    n = ws.num_nodes
    c = ws.ids.shape[1]
    valid_s = (seeds >= 0) & (seeds < n)
    pos, found = localize(ws.ids, jnp.where(valid_s, seeds, n))
    ok = valid_s & found
    slot = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None], (q, t))
    qi = jnp.arange(q)[:, None]
    tgt = jnp.where(ok, pos, c)  # slack column
    label0 = jnp.full((q, c + 1), t, jnp.int32).at[qi, tgt].min(
        jnp.where(ok, slot, t)
    )[:, :c]

    def prop(label, h):
        g_l = _gather_local(label, wnbr, t)
        g_d = _gather_local(ws.dist, wnbr, INF)
        active = wmask & (g_d == h - 1)
        best = jnp.min(jnp.where(active, g_l, t), axis=-1)
        label = jnp.where(ws.dist == h, best, label)
        return label, None

    label, _ = jax.lax.scan(
        prop, label0, jnp.arange(1, max_hops + 1, dtype=jnp.int32)
    )
    return label


@functools.partial(
    jax.jit, static_argnames=("max_hops", "max_nodes", "workset_cap")
)
def steiner_subgraph_compact(
    nbr: jnp.ndarray,
    nbr_mask: jnp.ndarray,
    seeds: jnp.ndarray,  # (Q, T) terminals
    *,
    max_hops: int = 4,
    max_nodes: int = 64,
    workset_cap: int = 2048,
    csr: Optional[CSRGather] = None,
) -> Subgraph:
    """RGL-Steiner over the workset: the bridge scan walks C*K workset edges
    instead of N*K, Voronoi labels propagate over the local adjacency, and
    backtracing descends in workset coordinates."""
    n, k = nbr.shape
    q, t = seeds.shape
    ws = build_workset(
        nbr, nbr_mask, seeds, max_hops=max_hops, cap=workset_cap, csr=csr
    )
    c = ws.ids.shape[1]
    wnbr, wmask = workset_adjacency(nbr, nbr_mask, ws.ids)
    label = _workset_voronoi_labels(ws, wnbr, wmask, seeds, max_hops)

    # ---- bridge edges over the C*K workset edge table ---------------------
    dp = jnp.concatenate([ws.dist, jnp.full((q, 1), INF, jnp.int32)], 1)
    lp = jnp.concatenate([label, jnp.full((q, 1), t, jnp.int32)], 1)
    d_src = jnp.broadcast_to(ws.dist[:, :, None], (q, c, k)).reshape(q, c * k)
    l_src = jnp.broadcast_to(label[:, :, None], (q, c, k)).reshape(q, c * k)
    flat_nbr = wnbr.reshape(q, c * k)
    d_dst = jnp.take_along_axis(dp, flat_nbr, 1)
    l_dst = jnp.take_along_axis(lp, flat_nbr, 1)
    gid = jnp.where(ws.ids < n, ws.ids, 0)
    eid = (
        gid[:, :, None] * jnp.int32(k)
        + jnp.arange(k, dtype=jnp.int32)[None, None, :]
    ).reshape(q, c * k)  # *global* edge ids: tie-break parity with dense
    w, best_eid = _terminal_metric(
        d_src, d_dst, l_src, l_dst, wmask.reshape(q, c * k), eid, t, n * k
    )

    mst = _prim_mst(w, t)

    marked = (ws.ids < n) & (ws.dist == 0)  # terminals

    def row_fn(cur):
        nb = jnp.take_along_axis(wnbr, cur[:, None, None], 1)[:, 0]  # (Q, K)
        nbm = jnp.take_along_axis(wmask, cur[:, None, None], 1)[:, 0]
        return nb, nbm

    n_mst = mst.shape[1]
    for e in range(n_mst):
        a, b = mst[:, e, 0], mst[:, e, 1]
        ok = a >= 0
        be = best_eid[jnp.arange(q), jnp.maximum(a, 0), jnp.maximum(b, 0)]
        ok = ok & (be < n * k)
        be = jnp.where(ok, be, 0)
        u_g, slot = be // k, be % k
        u_l, found_u = localize(ws.ids, u_g[:, None])
        u_l, found_u = u_l[:, 0], found_u[:, 0]
        ok = ok & found_u
        u_l = jnp.minimum(u_l, c - 1)
        # v is u's slot-th neighbor, already in workset coordinates
        v_l = wnbr[jnp.arange(q), u_l, slot]
        marked = _descend_paths(
            marked, u_l, ok, ws.dist, dp, row_fn, max_hops + 1
        )
        marked = _descend_paths(
            marked, jnp.minimum(v_l, c - 1), ok & (v_l < c), ws.dist, dp,
            row_fn, max_hops + 1,
        )

    d = jnp.minimum(ws.dist, max_hops + 1)
    key = d * jnp.int32(n) + gid
    nodes, mask, topi = _select_ws(key, marked, ws, max_nodes)
    dsel = jnp.where(mask, jnp.take_along_axis(d, topi, 1), INF)
    return Subgraph(
        nodes=nodes, mask=mask, dist=dsel, num_nodes=n, overflow=ws.overflow
    )


# ---------------------------------------------------------------- PPR --------


@functools.partial(jax.jit, static_argnames=("n_iter", "max_nodes", "max_hops"))
def ppr_subgraph(
    nbr: jnp.ndarray,
    nbr_mask: jnp.ndarray,
    seeds: jnp.ndarray,  # (Q, S)
    *,
    alpha: float = 0.85,
    n_iter: int = 10,
    max_nodes: int = 64,
    max_hops: int = None,  # accepted for strategy-API parity; PPR's reach is
    # governed by (alpha, n_iter), not a hop radius
) -> Subgraph:
    """Personalized-PageRank retrieval (paper's PPR baseline, batched).

    Fixed-iteration power method in pull form over the ELL adjacency:
      p <- (1-a)·s + a · sum_k p[nbr[v,k]] / deg[nbr[v,k]]
    Nodes ranked by PPR mass; `dist` carries the score rank (0 = seed-like).
    """
    n, k = nbr.shape
    q = seeds.shape[0]
    sm = seeds_to_mask(seeds, n)
    s = sm.astype(jnp.float32)
    s = s / jnp.maximum(s.sum(axis=1, keepdims=True), 1.0)
    deg = jnp.maximum(nbr_mask.sum(axis=1).astype(jnp.float32), 1.0)  # (N,)

    def step(p, _):
        contrib = p / deg[None, :]  # (Q, N) mass each node pushes per edge
        cp = jnp.concatenate([contrib, jnp.zeros((q, 1))], axis=1)
        gathered = cp[:, nbr]  # (Q, N, K)
        pulled = jnp.sum(jnp.where(nbr_mask[None], gathered, 0.0), axis=-1)
        return (1 - alpha) * s + alpha * pulled, None

    p, _ = jax.lax.scan(step, s, None, length=n_iter)
    keep = (p > 0) | sm
    # rank by score descending; quantize score into an integer key
    order = jnp.argsort(-p, axis=1)
    rank = jnp.zeros_like(order).at[
        jnp.arange(q)[:, None], order
    ].set(jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[None], (q, n)))
    nodes, mask, _ = _select_by_key(rank, keep, max_nodes, n)
    rsel = jnp.where(mask, jnp.take_along_axis(rank, jnp.minimum(nodes, n - 1), 1), INF)
    return Subgraph(nodes=nodes, mask=mask, dist=rsel, num_nodes=n)


@functools.partial(
    jax.jit, static_argnames=("n_iter", "max_nodes", "max_hops", "workset_cap")
)
def ppr_subgraph_compact(
    nbr: jnp.ndarray,
    nbr_mask: jnp.ndarray,
    seeds: jnp.ndarray,  # (Q, S)
    *,
    alpha: float = 0.85,
    n_iter: int = 10,
    max_nodes: int = 64,
    max_hops: int = None,  # API parity; expansion radius is n_iter
    workset_cap: int = 2048,
    csr: Optional[CSRGather] = None,
) -> Subgraph:
    """PPR over the workset.  After ``n_iter`` pull iterations mass reaches at
    most ``n_iter`` hops from the seeds, so the n_iter-hop workset carries the
    full support of p: the power method over the local adjacency is bitwise
    the dense computation (identical per-slot summation order), and ranks of
    all positive-mass nodes coincide."""
    n, k = nbr.shape
    q = seeds.shape[0]
    ws = build_workset(
        nbr, nbr_mask, seeds, max_hops=n_iter, cap=workset_cap, csr=csr
    )
    c = ws.ids.shape[1]
    wnbr, wmask = workset_adjacency(nbr, nbr_mask, ws.ids)
    valid = ws.ids < n
    sm = valid & (ws.dist == 0)
    s = sm.astype(jnp.float32)
    s = s / jnp.maximum(s.sum(axis=1, keepdims=True), 1.0)
    safe = jnp.minimum(ws.ids, n - 1)
    deg = jnp.maximum(nbr_mask[safe].sum(axis=-1).astype(jnp.float32), 1.0)

    def step(p, _):
        contrib = p / deg
        g = _gather_local(contrib, wnbr, jnp.float32(0.0))
        pulled = jnp.sum(jnp.where(wmask, g, 0.0), axis=-1)
        return (1 - alpha) * s + alpha * pulled, None

    p, _ = jax.lax.scan(step, s, None, length=n_iter)
    keep = ((p > 0) | sm) & valid
    order = jnp.argsort(-p, axis=1)  # stable: ties by position = global id
    rank = jnp.zeros_like(order).at[
        jnp.arange(q)[:, None], order
    ].set(jnp.broadcast_to(jnp.arange(c, dtype=jnp.int32)[None], (q, c)))
    nodes, mask, topi = _select_ws(rank, keep, ws, max_nodes)
    rsel = jnp.where(mask, jnp.take_along_axis(rank, topi, 1), INF)
    return Subgraph(
        nodes=nodes, mask=mask, dist=rsel, num_nodes=n, overflow=ws.overflow
    )


# ---------------------------------------------------------------- dispatch ---

STRATEGIES = {
    "bfs": bfs_subgraph,
    "dense": dense_subgraph,
    "steiner": steiner_subgraph,
    "ppr": ppr_subgraph,
}

COMPACT_STRATEGIES = {
    "bfs": bfs_subgraph_compact,
    "dense": dense_subgraph_compact,
    "steiner": steiner_subgraph_compact,
    "ppr": ppr_subgraph_compact,
}


def _compact_cap(g: ELLGraph, n_seeds: int, strategy: str, mode: str,
                 workset_cap: int, kw: dict) -> int:
    """The workset capacity where the compact backend runs, else 0."""
    if mode not in ("dense", "compact", "auto"):
        raise ValueError(f"unknown retrieval mode: {mode!r}")
    if mode == "compact" or (
        mode == "auto"
        and strategy != "ppr"
        and g.num_nodes >= AUTO_COMPACT_MIN_NODES
        and workset_cap < g.num_nodes
    ):
        return max(workset_cap, kw.get("max_nodes", 64), n_seeds)
    return 0


def hop_gather(
    g: ELLGraph,
    n_seeds: int,
    strategy: str = "bfs",
    *,
    mode: str = "auto",
    workset_cap: int = 2048,
    **kw,
) -> tuple:
    """The neighbor gather :func:`retrieve_subgraph` runs per hop, and its
    proposal slots per query: ``("csr", E)`` or ``("ell", C*K)`` on the
    compact backend, ``("dense", N*K)`` on the dense one."""
    cap = _compact_cap(g, n_seeds, strategy, mode, workset_cap, kw)
    if not cap:
        return "dense", g.num_nodes * g.max_deg
    csr = csr_gather(g, cap, n_seeds)
    return ("ell", cap * g.max_deg) if csr is None else ("csr", csr.width)


def retrieve_subgraph(
    g: ELLGraph,
    seeds: jnp.ndarray,
    strategy: str = "bfs",
    *,
    mode: str = "auto",
    workset_cap: int = 2048,
    **kw,
) -> Subgraph:
    """Strategy dispatch over an :class:`ELLGraph` (public entry point).

    ``mode`` selects the backend: ``"dense"`` (O(N) per hop, never
    truncates), ``"compact"`` (O(workset_cap) per hop, per-query
    ``overflow`` flags), or ``"auto"`` — compact for graphs with at least
    ``AUTO_COMPACT_MIN_NODES`` nodes (except ``ppr``, whose ``n_iter``-hop
    expansion radius overflows any practical cap on large connected graphs
    — it stays dense under auto), with a transparent dense re-run when any
    query overflows.  The overflow check is host-side (one device sync);
    inside an outer ``jax.jit`` trace the flags are tracers, so the check
    is skipped and the compact result is returned flags-and-all.  The
    compact backend's hops gather from the graph's CSR view where that is
    narrower than its ELL rows (:func:`repro.core.workset.csr_gather`).
    """
    seeds = jnp.asarray(seeds, jnp.int32)
    cap = _compact_cap(g, seeds.shape[1], strategy, mode, workset_cap, kw)
    if cap:
        sub = COMPACT_STRATEGIES[strategy](
            g.nbr, g.nbr_mask, seeds, workset_cap=cap,
            csr=csr_gather(g, cap, seeds.shape[1]), **kw
        )
        if (
            mode == "auto"
            and not isinstance(sub.overflow, jax.core.Tracer)
            and bool(np.asarray(sub.overflow).any())
        ):
            return STRATEGIES[strategy](g.nbr, g.nbr_mask, seeds, **kw)
        return sub
    return STRATEGIES[strategy](g.nbr, g.nbr_mask, seeds, **kw)


@functools.partial(jax.jit, static_argnames=())
def induced_adjacency(nbr: jnp.ndarray, nbr_mask: jnp.ndarray, sub: Subgraph):
    """Relabel the parent adjacency onto subgraph positions.

    Returns (sub_nbr (Q, M, K) positions into sub.nodes with sentinel M,
    sub_mask (Q, M, K)) — ready for downstream GNN encoding of the retrieved
    context, batched over queries.
    """
    q, m = sub.nodes.shape
    n, k = nbr.shape
    lut = jnp.full((q, n + 1), m, jnp.int32)
    safe = jnp.where(sub.mask, sub.nodes, n)
    lut = lut.at[jnp.arange(q)[:, None], safe].min(
        jnp.broadcast_to(jnp.arange(m, dtype=jnp.int32)[None], (q, m))
    )
    lut = lut.at[:, n].set(m)
    gn = nbr[jnp.minimum(safe, n - 1)]  # (Q, M, K) original neighbor ids
    gm = nbr_mask[jnp.minimum(safe, n - 1)] & sub.mask[:, :, None]
    pos = jnp.take_along_axis(lut, gn.reshape(q, -1), 1).reshape(q, m, k)
    ok = gm & (pos < m)
    return jnp.where(ok, pos, m).astype(jnp.int32), ok
