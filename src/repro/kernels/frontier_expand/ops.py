"""Public workset hop-expansion ops: membership mark dispatch + one hop.

``ws_member`` runs the searchsorted ref by default on every backend.  The
Pallas mark kernel is opt-in (``use_kernel=True``, interpret mode off TPU,
for parity tests): the TPU compiler refuses it — its ``(1, C)`` blocks
break the (8, 128) block rule and its in-VMEM 1-D gathers are not
supported — so it cannot be the default until it is rewritten.

``expand_hop`` is the full fixed-shape hop: a ``(Q, C, K)`` neighbor
gather over the workset followed by a sort/unique dedup-merge.  All heavy
steps are *single-operand int32 sorts* over packed keys — XLA's variadic
(multi-key) sort and large scatters are several times slower on CPU — so
(id, dist) rides in one integer: ``id * band + dist`` for the id-major
dedup sort, ``dist * (n+1) + id`` for the distance-major truncation sort,
where ``band = max_hops + 2`` (every live distance is ≤ max_hops; slot
``band-1`` is the sentinel clamp).  This caps the compact path at
``(max_hops + 2) * (n + 1) < 2**31`` — ~200M nodes at the default radius.

Two arms produce bit-identical results:

* ref arm   — workset and candidates concat into one id-major sort; the
  first entry of each id group carries the minimum distance (existing
  entries always win: their distance is ≤ h < h+1).
* kernel arm — the Pallas ``ws_mark_kernel`` first marks candidates
  already in the workset (tiled binary search in VMEM), so only fresh ids
  enter the dedup sort.

Truncation under overflow is deterministic and identical in both arms:
surviving entries are the capacity-C smallest by (distance, id) — since
every existing entry's distance is < the hop's, complete hops are kept
whole and the overflowing hop keeps its lowest fresh ids.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.frontier_expand import ref
from repro.kernels.frontier_expand.kernel import ws_mark_kernel

INF = jnp.int32(0x3FFFFFF)
_MAX32 = jnp.int32(jnp.iinfo(jnp.int32).max)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


@functools.partial(jax.jit, static_argnames=("blk_w", "use_kernel"))
def ws_member(
    ws_ids: jnp.ndarray,  # (Q, C) int32 sorted ascending per row
    cand: jnp.ndarray,  # (Q, W) int32
    *,
    blk_w: int = 1024,
    use_kernel: bool = False,
) -> jnp.ndarray:
    """(Q, W) bool membership of each candidate in its row's sorted workset."""
    if not use_kernel:
        return ref.ws_member(ws_ids, cand)
    w = cand.shape[1]
    blk = min(blk_w, _ceil_to(w, 128))
    wp = _ceil_to(w, blk)
    if wp != w:  # pad with int32 max: never matches a real id
        cand = jnp.pad(cand, ((0, 0), (0, wp - w)),
                       constant_values=jnp.iinfo(jnp.int32).max)
    out = ws_mark_kernel(ws_ids, cand, blk_w=blk, interpret=not _on_tpu())
    return out[:, :w].astype(bool)


def _first_of_group(ids: jnp.ndarray, real: jnp.ndarray) -> jnp.ndarray:
    """First occurrence of each id along a sorted row."""
    q = ids.shape[0]
    prev = jnp.concatenate([jnp.full((q, 1), -1, ids.dtype), ids[:, :-1]], 1)
    return real & (ids != prev)


@functools.partial(jax.jit, static_argnames=("band", "use_kernel"))
def expand_hop(
    ws_ids: jnp.ndarray,  # (Q, C) int32 sorted ascending, sentinel n padded
    ws_dist: jnp.ndarray,  # (Q, C) int32 hop distance, INF at padding
    nbr: jnp.ndarray,  # (N, K) int32 ELL adjacency, sentinel n
    nbr_mask: jnp.ndarray,  # (N, K) bool
    hop_dist,  # scalar int32 in [1, band-2]: distance of nodes added now
    *,
    band: int,  # max_hops + 2: exclusive upper bound on packed distances
    use_kernel: bool = False,
):
    """One workset expansion hop (see module docstring for the algorithm).

    ``hop_dist`` must be strictly greater than every live distance in
    ``ws_dist`` (BFS expansion always satisfies this) — both the keep-min-
    distance dedup and the never-evict-existing truncation rely on it.

    Returns ``(ws_ids', ws_dist', fresh (Q,), dropped (Q,) bool)`` where
    ``fresh`` counts distinct new ids proposed (pre-truncation) and
    ``dropped`` flags rows whose merge exceeded capacity.
    """
    q, c = ws_ids.shape
    n, k = nbr.shape
    if band * (n + 1) >= 2 ** 31:
        raise ValueError(
            f"compact path needs (max_hops + 2) * (n + 1) < 2**31; got "
            f"band={band}, n={n}"
        )
    band_ = jnp.int32(band)
    n1 = jnp.int32(n + 1)
    thr = band_ * n1  # every real packed key (either packing) is < thr
    hd = jnp.asarray(hop_dist, jnp.int32)
    valid = ws_ids < n
    safe = jnp.minimum(ws_ids, n - 1)
    cand = jnp.where(valid[:, :, None] & nbr_mask[safe], nbr[safe], n)
    cand = cand.reshape(q, c * k)

    if use_kernel:
        # mark members with the Pallas kernel; only fresh ids enter the sort
        present = ws_member(ws_ids, cand, use_kernel=True)
        k1 = jnp.sort(
            jnp.where(present | (cand >= n), _MAX32, cand * band_ + hd), 1
        )  # (Q, C*K) id-major
        id1 = jnp.where(k1 < thr, k1 // band_, n)
        first = _first_of_group(id1, id1 < n)
        k2 = jnp.sort(jnp.where(first, hd * n1 + id1, _MAX32), 1)
        over_fresh = k2[:, c] < thr if c * k > c else jnp.zeros((q,), bool)
        old = jnp.where(valid, ws_dist * n1 + ws_ids, _MAX32)
        k3 = jnp.sort(jnp.concatenate([old, k2[:, :c]], 1), 1)  # (Q, 2C)
        fresh_n = jnp.sum(first, 1, dtype=jnp.int32)
        dropped = over_fresh | (k3[:, c] < thr)
        keep = k3[:, :c]
    else:
        # pure-sort arm: one id-major sort over workset + candidates; the
        # first entry of each id group is the keeper (min distance)
        old = jnp.where(valid, ws_ids * band_ + ws_dist, _MAX32)
        new = jnp.where(cand < n, cand * band_ + hd, _MAX32)
        k1 = jnp.sort(jnp.concatenate([old, new], 1), 1)  # (Q, C + C*K)
        id1 = jnp.where(k1 < thr, k1 // band_, n)
        d1 = k1 % band_
        first = _first_of_group(id1, id1 < n)
        k2 = jnp.sort(jnp.where(first, d1 * n1 + id1, _MAX32), 1)
        fresh_n = jnp.sum(first & (d1 == hd), 1, dtype=jnp.int32)
        dropped = k2[:, c] < thr
        keep = k2[:, :c]

    # repack (dist, id) -> id-major, restore sentinels, final small sort
    kid = keep % n1
    kd = keep // n1
    key3 = jnp.where(keep < thr, kid * band_ + kd, n * band_ + (band_ - 1))
    k4 = jnp.sort(key3, 1)  # (Q, C)
    out_ids = k4 // band_
    out_dist = jnp.where(out_ids < n, k4 % band_, INF)
    return out_ids.astype(jnp.int32), out_dist.astype(jnp.int32), fresh_n, dropped
