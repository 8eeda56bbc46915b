"""Sharded vector index: row-partitioned scan + hierarchical top-k merge.

``ShardedIndex`` splits the node-embedding matrix into ``n_shards`` row
blocks laid out across a 1-D ``"shards"`` device mesh via ``shard_map``.
Each device scans only its block(s) with the existing per-shard machinery
(the ``topk_sim`` Pallas kernel for brute scans, the tiled ``ivf_scan``
path for IVF), translates local row ids to global ids by shard offset, and
emits a per-shard ``(Q, kk)`` candidate list.  A jitted hierarchical
(binary-tree) top-k reduction then merges the ``(S, Q, kk)`` candidates
down to the exact ``(Q, k)`` contract of ``BruteIndex.search``.

Design notes:

* **Logical shards vs devices.**  ``n_shards`` is a layout property; the
  mesh uses the largest divisor of ``n_shards`` that fits the available
  devices, and each device sweeps its local shards with ``lax.map``.  The
  same index therefore runs unchanged on 1 host device (pure logical
  sharding) or on a real mesh.  The shard tables (and the per-shard IVF
  state) are placed with ``NamedSharding(mesh, P("shards"))`` at build, so
  each device holds only its own rows between searches.
* **Exactness under padding.**  N rarely divides ``n_shards``; the tail of
  the last shard is zero-padded (< n_shards rows).  Zero rows score 0.0 and
  could displace negative-scoring real rows from a shard's local top-k, so
  each shard returns ``kk = k + n_pad`` candidates — the k best *real* rows
  of a shard always survive — and padded ids are masked to (-inf, INT32_MAX)
  before the merge.
* **Tie-breaking.**  The pairwise merge sorts lexicographically by
  (score desc, global id asc) via a 2-key ``lax.sort``, the same total
  order ``jax.lax.top_k`` applies over the unsharded score matrix.
* **Contract with ``BruteIndex.search``: same ids, scores within a stated
  tolerance** (``SCORE_RTOL``/``SCORE_ATOL``), not identical bits.  Each
  shard's matmul covers fewer rows than the unsharded one, and XLA may
  accumulate a dot product in a different order for a different row count
  (a 1-ULP difference is seen on CPU); the chip's default matmul precision
  does not promise layout-independent bits either.  Ids therefore agree
  except where two candidates' scores lie within that tolerance of each
  other.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import indexing as _ix
from repro.kernels.topk_sim import ops as topk_ops

_I32_MAX = jnp.iinfo(jnp.int32).max

# how far a sharded score may sit from the unsharded one for the same row
# (float32 dot products of unit-norm rows; see the module docstring)
SCORE_RTOL = 1e-5
SCORE_ATOL = 1e-6


def _mesh_size(n_shards: int, n_devices: int) -> int:
    """Largest divisor of n_shards that is <= n_devices (each device must
    own a whole number of logical shards).  Warns when that collapses the
    mesh well below the available devices — e.g. 7 shards on 8 devices run
    on a single device; pick a shard count that shares a factor."""
    best = 1
    for m in range(min(n_shards, n_devices), 0, -1):
        if n_shards % m == 0:
            best = m
            break
    if best < min(n_shards, n_devices):
        import warnings

        warnings.warn(
            f"n_shards={n_shards} is coprime-ish to the {n_devices} available "
            f"devices; using a {best}-device mesh. Choose n_shards as a "
            f"multiple of the device count for full parallelism.",
            stacklevel=3,
        )
    return best


# --------------------------------------------------------------------------
# hierarchical top-k merge
# --------------------------------------------------------------------------
def _merge_pair(sa, ia, sb, ib, k: int):
    """Merge two sorted candidate lists along the last axis, keep top-k."""
    s = jnp.concatenate([sa, sb], axis=-1)
    i = jnp.concatenate([ia, ib], axis=-1)
    neg, ids = jax.lax.sort((-s, i), num_keys=2)
    return -neg[..., :k], ids[..., :k]


def hierarchical_topk_merge(scores: jnp.ndarray, ids: jnp.ndarray, k: int):
    """(S, Q, w) per-shard candidates -> exact (Q, k) via a binary tree.

    log2(S) rounds of pairwise merges; each round halves the shard axis.
    Selection of the k least elements under the total order
    (-score, id) is associative, so truncating to k at every node is exact.
    """
    if scores.shape[0] == 1:  # degenerate tree: sort + truncate directly
        kk = min(k, scores.shape[-1])
        neg, out_i = jax.lax.sort((-scores[0], ids[0]), num_keys=2)
        return -neg[..., :kk], out_i[..., :kk]
    while scores.shape[0] > 1:
        s = scores.shape[0]
        if s % 2:
            scores = jnp.concatenate(
                [scores, jnp.full_like(scores[:1], -jnp.inf)], axis=0
            )
            ids = jnp.concatenate(
                [ids, jnp.full_like(ids[:1], _I32_MAX)], axis=0
            )
        kk = min(k, 2 * scores.shape[-1])
        scores, ids = _merge_pair(
            scores[0::2], ids[0::2], scores[1::2], ids[1::2], kk
        )
    return scores[0], ids[0]


# --------------------------------------------------------------------------
# per-shard scan bodies (run inside shard_map; one device, s_local shards)
# --------------------------------------------------------------------------
def _brute_shard_fn(
    emb_block, q, *, kk: int, n_total: int, rows_per_shard: int,
    use_kernel: Optional[bool],
):
    p = jax.lax.axis_index("shards")
    s_local = emb_block.shape[0]

    def one(li):
        s, lid = topk_ops.topk_similarity(
            q, emb_block[li], kk, use_kernel=use_kernel
        )
        gid = lid + (p * s_local + li) * rows_per_shard
        ok = gid < n_total
        return (
            jnp.where(ok, s, -jnp.inf),
            jnp.where(ok, gid, _I32_MAX).astype(jnp.int32),
        )

    return jax.lax.map(one, jnp.arange(s_local))


def _ivf_shard_fn(
    emb_block, cent_block, lists_block, mask_block, q, *, k: int,
    n_total: int, rows_per_shard: int, nprobe: int,
):
    p = jax.lax.axis_index("shards")
    s_local = emb_block.shape[0]

    def one(li):
        s, lid = _ix.ivf_probe_scan(
            emb_block[li], cent_block[li], lists_block[li], mask_block[li],
            q, nprobe, k,
        )
        gid = lid + (p * s_local + li) * rows_per_shard
        # lid == rows_per_shard is the local sentinel (unfilled list slot)
        ok = (lid < rows_per_shard) & (gid < n_total)
        return (
            jnp.where(ok, s, -jnp.inf),
            jnp.where(ok, gid, _I32_MAX).astype(jnp.int32),
        )

    return jax.lax.map(one, jnp.arange(s_local))


# --------------------------------------------------------------------------
# jitted search entry points (module-level so index construction never
# recompiles; mesh is hashable and rides as a static arg)
# --------------------------------------------------------------------------
@partial(
    jax.jit,
    static_argnames=("mesh", "k", "n_total", "rows_per_shard", "use_kernel"),
)
def _sharded_brute_search(
    emb_shards, q, *, mesh: Mesh, k: int, n_total: int, rows_per_shard: int,
    use_kernel: Optional[bool],
):
    s, np_, _ = emb_shards.shape
    pad = s * np_ - n_total
    kk = min(k + pad, np_)
    fn = partial(
        _brute_shard_fn, kk=kk, n_total=n_total,
        rows_per_shard=rows_per_shard, use_kernel=use_kernel,
    )
    ss, ii = jax.shard_map(
        fn, mesh=mesh, in_specs=(P("shards"), P()),
        out_specs=(P("shards"), P("shards")), check_vma=False,
    )(emb_shards, q)
    return hierarchical_topk_merge(ss, ii, k)


@partial(
    jax.jit,
    static_argnames=("mesh", "k", "n_total", "rows_per_shard", "nprobe"),
)
def _sharded_ivf_search(
    emb_shards, centroids, lists, list_mask, q, *, mesh: Mesh, k: int,
    n_total: int, rows_per_shard: int, nprobe: int,
):
    fn = partial(
        _ivf_shard_fn, k=k, n_total=n_total,
        rows_per_shard=rows_per_shard, nprobe=nprobe,
    )
    ss, ii = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P("shards"), P("shards"), P("shards"), P("shards"), P()),
        out_specs=(P("shards"), P("shards")), check_vma=False,
    )(emb_shards, centroids, lists, list_mask, q)
    return hierarchical_topk_merge(ss, ii, k)


@dataclasses.dataclass
class ShardedIndex:
    """Row-partitioned vector index over a 1-D device mesh.

    ``inner="brute"`` is exact: the same ids as ``BruteIndex``, with scores
    within ``SCORE_RTOL``/``SCORE_ATOL`` of its scores (ids may swap only
    where two scores are that close; see the module docstring).
    ``inner="ivf"`` builds an independent IVF structure per shard and is
    approximate in the same way single-device IVF is.
    """

    emb_shards: jnp.ndarray  # (S, Np, D); last shard zero-padded at the tail
    n_total: int
    rows_per_shard: int
    mesh: Mesh
    normalized: bool = True
    inner: str = "brute"  # brute | ivf
    use_kernel: Optional[bool] = None  # passthrough to topk_sim ops
    # per-shard IVF state, stacked over shards (inner == "ivf" only)
    centroids: Optional[jnp.ndarray] = None  # (S, C, D)
    lists: Optional[jnp.ndarray] = None  # (S, C, L) local ids, sentinel = Np
    list_mask: Optional[jnp.ndarray] = None  # (S, C, L)
    nprobe: int = 4

    @property
    def n_shards(self) -> int:
        return self.emb_shards.shape[0]

    @staticmethod
    def build(
        emb,
        n_shards: Optional[int] = None,
        inner: str = "brute",
        normalize: bool = True,
        use_kernel: Optional[bool] = None,
        devices=None,
        n_clusters: int = 64,
        nprobe: int = 4,
        n_iter: int = 10,
        seed: int = 0,
    ) -> "ShardedIndex":
        emb = jnp.asarray(emb, dtype=jnp.float32)
        if normalize:
            emb = _ix.l2_normalize(emb)  # full-matrix, before partitioning
        n, d = emb.shape
        devices = list(devices) if devices is not None else jax.devices()
        if n_shards is None:
            n_shards = len(devices)
        n_shards = max(1, min(int(n_shards), n))
        rows = -(-n // n_shards)
        pad = n_shards * rows - n
        m = _mesh_size(n_shards, len(devices))
        mesh = Mesh(np.asarray(devices[:m]), ("shards",))
        shards = jax.device_put(
            jnp.pad(emb, ((0, pad), (0, 0))).reshape(n_shards, rows, d),
            NamedSharding(mesh, P("shards")),
        )
        idx = ShardedIndex(
            emb_shards=shards, n_total=n, rows_per_shard=rows, mesh=mesh,
            normalized=normalize, inner=inner, use_kernel=use_kernel,
        )
        if inner == "ivf":
            idx._build_shard_ivf(n_clusters, nprobe, n_iter, seed)
        elif inner != "brute":
            raise ValueError(f"unknown inner scan: {inner}")
        return idx

    def _build_shard_ivf(
        self, n_clusters: int, nprobe: int, n_iter: int, seed: int
    ) -> None:
        """Per-shard k-means + inverted lists over each shard's real rows,
        stacked over shards and placed like the shard tables."""
        s, rows, _ = self.emb_shards.shape
        per_cent, per_lists, per_mask = [], [], []
        c_eff = max(1, min(n_clusters, rows))
        for si in range(s):
            # ceil-partitioning can leave trailing shards with no real rows
            n_local = max(0, min(rows, self.n_total - si * rows))
            if n_local == 0:
                cent = np.zeros((c_eff, self.emb_shards.shape[2]), np.float32)
                lists = np.full((c_eff, 8), rows, np.int32)
                mask = np.zeros((c_eff, 8), bool)
                per_cent.append(cent)
                per_lists.append(lists)
                per_mask.append(mask)
                continue
            local = self.emb_shards[si, :n_local]
            c_s = max(1, min(c_eff, n_local))
            cent, assign = _ix.kmeans(local, c_s, n_iter=n_iter, seed=seed + si)
            lists, mask = _ix.build_inverted_lists(
                np.asarray(assign), n_local, c_s
            )
            # remap local sentinel n_local -> rows (uniform across shards)
            lists = np.where(mask, lists, rows)
            cent = np.asarray(cent)
            if c_s < c_eff:  # pad cluster axis; extra lists are all-sentinel
                cpad = c_eff - c_s
                cent = np.pad(cent, ((0, cpad), (0, 0)))
                lists = np.pad(lists, ((0, cpad), (0, 0)), constant_values=rows)
                mask = np.pad(mask, ((0, cpad), (0, 0)), constant_values=False)
            per_cent.append(cent)
            per_lists.append(lists)
            per_mask.append(mask)
        pad_l = max(a.shape[1] for a in per_lists)
        per_lists = [
            np.pad(a, ((0, 0), (0, pad_l - a.shape[1])), constant_values=rows)
            for a in per_lists
        ]
        per_mask = [
            np.pad(a, ((0, 0), (0, pad_l - a.shape[1])), constant_values=False)
            for a in per_mask
        ]
        place = NamedSharding(self.mesh, P("shards"))
        self.centroids = jax.device_put(np.stack(per_cent), place)
        self.lists = jax.device_put(
            np.stack(per_lists).astype(np.int32), place)
        self.list_mask = jax.device_put(np.stack(per_mask), place)
        self.nprobe = min(nprobe, c_eff)

    def search(self, queries: jnp.ndarray, k: int):
        """(Q, D) queries -> exact-contract (scores (Q, k), ids (Q, k))."""
        q = jnp.asarray(queries, dtype=jnp.float32)
        if q.ndim == 1:
            q = q[None]
        if self.normalized:
            q = _ix.l2_normalize(q)
        # replicate onto the mesh: queries committed to one device (e.g.
        # gathered from a one-chip table) cannot meet the sharded tables
        q = jax.device_put(q, NamedSharding(self.mesh, P()))
        k = min(k, self.n_total)
        if self.inner == "brute":
            return _sharded_brute_search(
                self.emb_shards, q, mesh=self.mesh, k=k,
                n_total=self.n_total, rows_per_shard=self.rows_per_shard,
                use_kernel=self.use_kernel,
            )
        return _sharded_ivf_search(
            self.emb_shards, self.centroids, self.lists, self.list_mask, q,
            mesh=self.mesh, k=k, n_total=self.n_total,
            rows_per_shard=self.rows_per_shard,
            nprobe=min(self.nprobe, self.centroids.shape[1]),
        )
