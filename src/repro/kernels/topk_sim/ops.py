"""jit'd public wrapper around the topk_sim Pallas kernel.

Handles padding to block multiples, CPU interpret fallback, the final
cross-block merge, and a size heuristic (tiny problems go straight to the
jnp oracle — kernel dispatch isn't worth it below one tile).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.topk_sim import ref
from repro.kernels.topk_sim.kernel import topk_sim_blocks


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


@functools.partial(jax.jit, static_argnames=("k", "q_blk", "c_blk", "use_kernel"))
def topk_similarity(
    q: jnp.ndarray,
    emb: jnp.ndarray,
    k: int,
    *,
    q_blk: int = 128,
    c_blk: int = 1024,
    use_kernel: bool | None = None,
):
    """Top-k similarity search: q (Q, D) x emb (N, D) -> ((Q,k) scores, (Q,k) idx)."""
    Q, D = q.shape
    N, De = emb.shape
    assert D == De, (D, De)
    k = min(k, N)
    if use_kernel is None:
        use_kernel = N >= 2 * c_blk  # heuristic: at least two candidate tiles
    if not use_kernel:
        return ref.topk_similarity(q, emb, k)

    Qp, Np, Dp = _ceil_to(Q, q_blk), _ceil_to(N, c_blk), _ceil_to(D, 128)
    qp = jnp.zeros((Qp, Dp), jnp.float32).at[:Q, :D].set(q.astype(jnp.float32))
    ep = jnp.zeros((Np, Dp), jnp.float32).at[:N, :D].set(emb.astype(jnp.float32))
    kk = min(k, c_blk)
    s_blk, i_blk = topk_sim_blocks(
        qp, ep, k=kk, q_blk=q_blk, c_blk=c_blk, n_valid=N,
        interpret=not _on_tpu(),
    )
    # (n_blocks, Qp, kk) -> (Qp, n_blocks * kk), tile-major per query
    s_flat = s_blk.transpose(1, 0, 2).reshape(Qp, -1)
    i_flat = i_blk.transpose(1, 0, 2).reshape(Qp, -1)
    top_s, pos = jax.lax.top_k(s_flat, k)
    top_i = jnp.take_along_axis(i_flat, pos, axis=1)
    return top_s[:Q], top_i[:Q]
