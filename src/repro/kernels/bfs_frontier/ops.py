"""Public wrapper: bool<->int8 plumbing, padding, interpret fallback.

The jnp ref is the default on every backend.  The Pallas kernel is opt-in
(``use_kernel=True``, interpret mode off TPU, for parity tests): the TPU
compiler refuses it — its ``(1, N+1)`` frontier block breaks the (8, 128)
block rule and its in-VMEM 1-D gather is not supported.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.bfs_frontier import ref
from repro.kernels.bfs_frontier.kernel import frontier_hop_kernel


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=("blk_n", "use_kernel"))
def frontier_hop(
    frontier: jnp.ndarray,  # (Q, N) bool
    nbr: jnp.ndarray,  # (N, K) sentinel N
    nbr_mask: jnp.ndarray,
    *,
    blk_n: int = 512,
    use_kernel: bool = False,
):
    q, n = frontier.shape
    if not use_kernel:
        return ref.frontier_hop(frontier, nbr, nbr_mask)
    blk = min(blk_n, n)
    np_ = -(-n // blk) * blk
    f8 = jnp.zeros((q, np_ + 1), jnp.int8).at[:, :n].set(frontier.astype(jnp.int8))
    nb = jnp.full((np_, nbr.shape[1]), np_, jnp.int32)
    nb = nb.at[:n].set(jnp.where(nbr_mask, nbr, np_).astype(jnp.int32))
    nb = jnp.where(nb == n, np_, nb)
    mk = jnp.zeros((np_, nbr.shape[1]), bool).at[:n].set(nbr_mask)
    out = frontier_hop_kernel(f8, nb, mk, blk_n=blk, interpret=not _on_tpu())
    return out[:, :n].astype(bool)
