"""Workset-compacted candidate expansion for subgraph construction.

The dense stage-3 path does O(N) work per query — every BFS hop gathers
the full ``(Q, N, K)`` adjacency — for an O(max_nodes) result.  A
*workset* bounds that cost by the retrieved neighborhood instead: seeds
are expanded hop by hop into a fixed-capacity, per-query candidate set of
``C`` global node ids (C ≪ N), kept **sorted ascending** so that
membership tests (``kernels.frontier_expand``) and global→local id
translation are log-time searches over device arrays.

With no overflow the workset after ``max_hops`` hops is exactly the BFS
ball around the seeds, and ``dist`` holds exact hop distances (every
shortest path to a ball node stays inside the ball).  On overflow the
per-query flag is set and truncation is deterministic: entries are never
evicted, so complete hops survive whole and the overflowing hop keeps its
lowest fresh ids.

All retrieval strategies then run over the *workset-local induced
adjacency* (``workset_adjacency``): ``(Q, C, K)`` neighbor slots holding
positions into the workset, sentinel ``C`` where the neighbor is absent —
the same fixed-shape frontier algebra as the dense path, shrunk from N
rows to C.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.kernels.frontier_expand import ops as fe_ops

INF = jnp.int32(0x3FFFFFF)


@dataclasses.dataclass
class Workset:
    """Per-query candidate set: ``ids`` sorted ascending, sentinel = n."""

    ids: jnp.ndarray  # (Q, C) int32 global node ids, sentinel n where unused
    dist: jnp.ndarray  # (Q, C) int32 hop distance from the seed set, INF pad
    overflow: jnp.ndarray  # (Q,) bool — ball exceeded capacity, truncated
    num_nodes: int  # N of the parent graph

    @property
    def cap(self) -> int:
        return int(self.ids.shape[1])


jax.tree_util.register_dataclass(
    Workset, data_fields=["ids", "dist", "overflow"], meta_fields=["num_nodes"]
)


@functools.partial(jax.jit, static_argnames=("cap",))
def _seed_workset(seeds: jnp.ndarray, n: int, cap: int):
    """(Q, S) seed ids (pad with -1 or >= n) -> initial sorted workset."""
    q = seeds.shape[0]
    ids0 = jnp.where((seeds >= 0) & (seeds < n), seeds, n).astype(jnp.int32)
    ids0 = jnp.sort(ids0, axis=1)
    first = (ids0 < n) & jnp.concatenate(
        [jnp.ones((q, 1), bool), ids0[:, 1:] != ids0[:, :-1]], axis=1
    )
    rank = jnp.cumsum(first, axis=1, dtype=jnp.int32) - 1
    ok = first & (rank < cap)
    tgt = jnp.where(ok, rank, cap)
    qi = jnp.arange(q)[:, None]
    ws_ids = jnp.full((q, cap + 1), n, jnp.int32).at[qi, tgt].set(
        jnp.where(ok, ids0, n)
    )[:, :cap]
    ws_dist = jnp.full((q, cap + 1), INF, jnp.int32).at[qi, tgt].set(
        jnp.where(ok, 0, INF)
    )[:, :cap]
    overflow = jnp.any(first & (rank >= cap), axis=1)
    return ws_ids, ws_dist, overflow


@functools.partial(jax.jit, static_argnames=("max_hops", "cap", "use_kernel"))
def build_workset(
    nbr: jnp.ndarray,  # (N, K) int32 ELL adjacency, sentinel N
    nbr_mask: jnp.ndarray,  # (N, K) bool
    seeds: jnp.ndarray,  # (Q, S) int32 (pad with -1 or >= N)
    *,
    max_hops: int,
    cap: int,
    use_kernel: bool = False,
) -> Workset:
    """Expand seeds into the capacity-``cap`` workset of the max_hops ball."""
    n = nbr.shape[0]
    ws_ids, ws_dist, overflow = _seed_workset(seeds, n, cap)

    def hop(carry, h):
        wi, wd, ov = carry
        wi, wd, _, dropped = fe_ops.expand_hop(
            wi, wd, nbr, nbr_mask, h + 1, band=max_hops + 2,
            use_kernel=use_kernel,
        )
        return (wi, wd, ov | dropped), None

    (ws_ids, ws_dist, overflow), _ = jax.lax.scan(
        hop, (ws_ids, ws_dist, overflow),
        jnp.arange(max_hops, dtype=jnp.int32),
    )
    return Workset(ids=ws_ids, dist=ws_dist, overflow=overflow, num_nodes=n)


@jax.jit
def localize(ws_ids: jnp.ndarray, ids: jnp.ndarray):
    """Translate global node ids to workset positions.

    ws_ids (Q, C) sorted ascending; ids (Q, S) global.  Returns
    (pos (Q, S) int32 with sentinel C where absent, found (Q, S) bool).
    """
    c = ws_ids.shape[1]
    pos = jax.vmap(jnp.searchsorted)(ws_ids, ids).astype(jnp.int32)
    hit = jnp.take_along_axis(ws_ids, jnp.minimum(pos, c - 1), axis=1)
    found = (pos < c) & (hit == ids)
    return jnp.where(found, pos, c), found


@jax.jit
def workset_adjacency(
    nbr: jnp.ndarray, nbr_mask: jnp.ndarray, ws_ids: jnp.ndarray
):
    """Induce the parent adjacency onto workset positions.

    Returns (wnbr (Q, C, K) int32 positions into ws_ids with sentinel C,
    wmask (Q, C, K) bool — True iff the edge is real AND its endpoint is a
    workset member).  ELL row/slot order is preserved, so edge (c, k) here
    is edge (ws_ids[c], k) of the parent graph — tie-break parity with the
    dense path falls out of that.
    """
    q, c = ws_ids.shape
    n, k = nbr.shape
    valid = ws_ids < n
    safe = jnp.minimum(ws_ids, n - 1)
    gn = nbr[safe]  # (Q, C, K) global neighbor ids
    gm = valid[:, :, None] & nbr_mask[safe]
    pos, found = localize(ws_ids, gn.reshape(q, c * k))
    pos = pos.reshape(q, c, k)
    ok = gm & found.reshape(q, c, k)
    return jnp.where(ok, pos, c), ok
