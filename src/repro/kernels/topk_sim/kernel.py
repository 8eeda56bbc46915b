"""Pallas TPU kernel: fused similarity scoring + per-block top-k.

The node-retrieval hot path of the RGL pipeline (paper §2.1.2) and the
recsys ``retrieval_cand`` shape.  Instead of materializing the full (Q, N)
score matrix in HBM (N can be 10^6), each grid cell

  * streams one (C_BLK, D) candidate tile from HBM into VMEM,
  * runs the (Q_BLK, D) x (D, C_BLK) product on the MXU,
  * reduces the tile to its local top-k on-chip,

so HBM writeback shrinks from N to k * n_blocks floats per query
(a ~C_BLK/k compression).  A cheap jnp merge in ops.py finishes the job.

Block sizes: Q_BLK x D and C_BLK x D tiles must fit VMEM (~16 MB on v5e);
defaults (128, 1024) with D <= 4096 use <= (128+1024) * 4096 * 4B = 18 MB
worst case, so ops.py clamps D-tiles by splitting D is unnecessary — D is an
embedding dim (<= 1024 in practice; asserted in ops.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _topk_sim_kernel(q_ref, e_ref, s_ref, i_ref, *, k: int, c_blk: int, n_valid: int):
    j = pl.program_id(1)
    q = q_ref[...]  # (Q_BLK, D)
    e = e_ref[...]  # (C_BLK, D)
    scores = jax.lax.dot_general(
        q, e, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (Q_BLK, C_BLK)
    col = j * c_blk + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    scores = jnp.where(col < n_valid, scores, -jnp.inf)
    # iterative top-k within the tile (k is small: <= 128); the k winners
    # collect in registers and leave in one (Q_BLK, k) store per output
    lane = jax.lax.broadcasted_iota(jnp.int32, (scores.shape[0], k), 1)
    s_acc = jnp.full((scores.shape[0], k), -jnp.inf, jnp.float32)
    i_acc = jnp.zeros((scores.shape[0], k), jnp.int32)
    for t in range(k):
        m = jnp.max(scores, axis=1)  # (Q_BLK,)
        a = jnp.argmax(scores, axis=1).astype(jnp.int32)  # (Q_BLK,)
        s_acc = jnp.where(lane == t, m[:, None], s_acc)
        i_acc = jnp.where(lane == t, (a + j * c_blk)[:, None], i_acc)
        # mask the winner out for the next round
        hit = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1) == a[:, None]
        scores = jnp.where(hit, -jnp.inf, scores)
    s_ref[0] = s_acc
    i_ref[0] = i_acc


@functools.partial(
    jax.jit, static_argnames=("k", "q_blk", "c_blk", "n_valid", "interpret")
)
def topk_sim_blocks(
    q: jnp.ndarray,
    emb: jnp.ndarray,
    *,
    k: int,
    q_blk: int = 128,
    c_blk: int = 1024,
    n_valid: int | None = None,
    interpret: bool = False,
):
    """q: (Q, D) fp32, emb: (N, D) fp32; Q % q_blk == 0, N % c_blk == 0.

    Returns (scores (n_c_blocks, Q, k), indices (n_c_blocks, Q, k)) of the
    per-tile top-k; caller merges.  The tile axis leads so that each output
    block ``(1, q_blk, k)`` ends in ``(q_blk, k)`` — a multiple of 8 rows
    and the array's full last dim, which the TPU lowering requires.
    """
    Q, D = q.shape
    N, _ = emb.shape
    assert Q % q_blk == 0 and N % c_blk == 0, (Q, q_blk, N, c_blk)
    assert k <= c_blk
    if n_valid is None:
        n_valid = N
    grid = (Q // q_blk, N // c_blk)
    kern = functools.partial(
        _topk_sim_kernel, k=k, c_blk=c_blk, n_valid=n_valid
    )
    out_shape = (
        jax.ShapeDtypeStruct((N // c_blk, Q, k), jnp.float32),
        jax.ShapeDtypeStruct((N // c_blk, Q, k), jnp.int32),
    )
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((q_blk, D), lambda i, j: (i, 0)),
            pl.BlockSpec((c_blk, D), lambda i, j: (j, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, q_blk, k), lambda i, j: (j, i, 0)),
            pl.BlockSpec((1, q_blk, k), lambda i, j: (j, i, 0)),
        ),
        out_shape=out_shape,
        interpret=interpret,
    )(q, emb)
