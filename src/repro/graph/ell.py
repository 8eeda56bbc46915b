"""ELL (padded neighbor list) graph format — the device-side layout.

TPU-native adaptation of RGL's C++ adjacency access: every node stores exactly
``max_deg`` neighbor slots; unused slots hold the sentinel ``num_nodes``.  All
gathers index arrays of length ``num_nodes + 1`` whose last row is a neutral
element, so frontier expansion / message passing are single fixed-shape
gathers with no bounds checks.  High-degree tails beyond ``max_deg`` are
truncated (documented; choose ``max_deg >= max degree`` for exactness).

:func:`csr_to_ell` also attaches a :class:`CSRView` of exactly the edges the
ELL keeps.  On a skewed graph ``K`` is set by a few hubs, so a workset hop
that gathers ``K`` slots per member is mostly padding; the compact backend
gathers real neighbours from the view instead (``repro.core.workset``).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp
import numpy as np

from repro.graph.csr import CSRGraph


@dataclasses.dataclass
class CSRView:
    """CSR of the edges an :class:`ELLGraph` keeps: row ``u`` holds
    ``indices[indptr[u]:indptr[u+1]]``, the real slots of ``nbr[u]`` in
    slot order."""

    indptr: jnp.ndarray  # (N+1,) int32
    indices: jnp.ndarray  # (nnz,) int32
    deg_top: np.ndarray  # (N+1,) int64 host: sum of the m largest degrees

    def top_degree_sum(self, m: int) -> int:
        """Most neighbour slots any ``m`` distinct rows can hold."""
        return int(self.deg_top[min(m, len(self.deg_top) - 1)])


@dataclasses.dataclass
class ELLGraph:
    """``nbr[i, k]`` = k-th neighbor of node i, or ``num_nodes`` (sentinel)."""

    nbr: jnp.ndarray  # (N, max_deg) int32
    nbr_mask: jnp.ndarray  # (N, max_deg) bool — True where a real edge exists
    num_nodes: int
    node_feat: Optional[jnp.ndarray] = None  # (N, F)
    csr: Optional[CSRView] = None  # set by csr_to_ell; None for other builds

    @property
    def max_deg(self) -> int:
        return int(self.nbr.shape[1])

    @property
    def sentinel(self) -> int:
        return self.num_nodes

    def degrees(self) -> jnp.ndarray:
        return jnp.sum(self.nbr_mask, axis=1).astype(jnp.int32)


def csr_to_ell(
    g: CSRGraph, max_deg: Optional[int] = None, *, pad_to_multiple: int = 8
) -> ELLGraph:
    """Convert CSR → ELL, truncating rows above ``max_deg`` (host-side).

    The result carries a :class:`CSRView` of the kept edges (rows truncated
    alike), so both hop gathers of the compact backend see one graph.
    """
    deg = g.degrees()
    if max_deg is None:
        max_deg = int(deg.max()) if g.num_nodes else 1
    max_deg = max(1, max_deg)
    if pad_to_multiple > 1:
        max_deg = -(-max_deg // pad_to_multiple) * pad_to_multiple
    n = g.num_nodes
    nbr = np.full((n, max_deg), n, dtype=np.int32)
    take = np.minimum(deg, max_deg)
    # Vectorized row fill: flat positions for each (node, slot) pair.
    rows = np.repeat(np.arange(n), take)
    slots = _ranges(take)
    src_pos = np.repeat(g.indptr[:-1], take) + slots
    kept = g.indices[src_pos]
    nbr[rows, slots] = kept
    mask = np.arange(max_deg)[None, :] < take[:, None]
    feat = jnp.asarray(g.node_feat) if g.node_feat is not None else None
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(take, out=indptr[1:])
    deg_top = np.zeros(n + 1, np.int64)
    np.cumsum(np.sort(take)[::-1], out=deg_top[1:])
    csr = CSRView(
        indptr=jnp.asarray(indptr, jnp.int32),
        indices=jnp.asarray(kept, jnp.int32),
        deg_top=deg_top,
    )
    return ELLGraph(
        nbr=jnp.asarray(nbr), nbr_mask=jnp.asarray(mask), num_nodes=n,
        node_feat=feat, csr=csr,
    )


def _ranges(counts: np.ndarray) -> np.ndarray:
    """[0..c0-1, 0..c1-1, ...] without a Python loop."""
    total = int(counts.sum())
    if total == 0:
        return np.zeros(0, dtype=np.int64)
    idx = np.arange(total, dtype=np.int64)
    starts = np.repeat(np.cumsum(counts) - counts, counts)
    return idx - starts
