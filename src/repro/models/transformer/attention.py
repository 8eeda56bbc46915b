"""Attention: RoPE, chunked (flash-style) causal/sliding-window attention for
train/prefill, and KV-cache decode attention.

The chunked path is the memory-critical piece: a double `lax.scan` over
(q-chunk, kv-chunk) tiles with online-softmax accumulators keeps the largest
intermediate at (B, KV, rep, Cq, Ck) instead of (B, H, S, S) — the same
blocking the Pallas flash kernel (repro.kernels.flash_attn) uses on TPU.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

NEG = -1e30


def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (..., S, H, dh); positions: broadcastable to (..., S)."""
    dh = x.shape[-1]
    half = dh // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freq  # (..., S, half)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def yarn_inv_freq(dim: int, theta: float, factor: float, original_max: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """YaRN's rotary frequencies (float64, ``dim // 2`` of them): the
    original ``theta ** (-2i / dim)`` for fast-turning dims, the same divided
    by ``factor`` for slow ones, and a linear ramp between the dims that
    turn ``beta_fast`` and ``beta_slow`` times over ``original_max``."""
    extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if factor <= 1:
        return extra

    def corr(rotations):
        return (dim * math.log(original_max / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return extra / factor * ramp + extra * (1.0 - ramp)


def rope_freqs(x: jnp.ndarray, positions: jnp.ndarray, inv_freq: np.ndarray,
               mscale: float = 1.0) -> jnp.ndarray:
    """:func:`rope` with given frequencies (``dh // 2``) and a factor on the
    cos/sin tables.  x: (..., S, H, dh); positions: broadcastable to
    (..., S)."""
    half = x.shape[-1] // 2
    ang = positions[..., None].astype(jnp.float32) * jnp.asarray(
        inv_freq, jnp.float32)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    if mscale != 1.0:
        cos, sin = cos * mscale, sin * mscale
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def _tile_mask(qi, kj, cq, ck, window):
    """(Cq, Ck) causal/windowed mask for tile at q-offset qi, kv-offset kj."""
    iq = qi + jax.lax.broadcasted_iota(jnp.int32, (cq, ck), 0)
    jk = kj + jax.lax.broadcasted_iota(jnp.int32, (cq, ck), 1)
    m = jk <= iq
    if window is not None:
        m &= (iq - jk) < window
    return m


@functools.partial(
    jax.jit,
    static_argnames=("window", "q_chunk", "kv_chunk", "use_kernel", "scale"),
)
def chunked_attention(
    q: jnp.ndarray,  # (B, S, H, dh)
    k: jnp.ndarray,  # (B, S, KV, dh)
    v: jnp.ndarray,  # (B, S, KV, dv)
    *,
    window: Optional[int] = None,
    q_chunk: int = 512,
    kv_chunk: int = 512,
    use_kernel: bool = False,
    scale: Optional[float] = None,  # None = dh ** -0.5
) -> jnp.ndarray:
    """Differentiable flash attention (custom VJP).

    Naive autodiff through the (q-block, kv-block) double scan stashes every
    softmax tile — equivalent to materializing the full (B, H, S, S) score
    matrix (measured: 227 GiB/device for starcoder2 train_4k; EXPERIMENTS.md
    §Perf iteration 0).  The custom backward recomputes tiles from the saved
    (q, k, v, o, logsumexp) instead — the FlashAttention-2 bwd schedule."""
    if use_kernel:
        assert scale is None and v.shape[-1] == q.shape[-1]
        from repro.kernels.flash_attn import ops as fa_ops

        return fa_ops.flash_attention(
            q, k, v, window=window, q_blk=q_chunk, kv_blk=kv_chunk
        )
    s = q.shape[1]
    cq = min(q_chunk, s)
    ck = min(kv_chunk, s)
    assert s % cq == 0 and s % ck == 0, (s, cq, ck)
    return _flash(window, cq, ck, scale)(q, k, v)


@functools.lru_cache(maxsize=None)
def _flash(window, cq, ck, scale=None):
    """custom_vjp flash attention specialized to (window, q_chunk, kv_chunk,
    softmax scale)."""

    @jax.custom_vjp
    def fn(q, k, v):
        return _flash_fwd(q, k, v, window, cq, ck, scale)[0]

    def fwd(q, k, v):
        o, lse = _flash_fwd(q, k, v, window, cq, ck, scale)
        return o, (q, k, v, o, lse)

    def bwd(res, do):
        return _flash_bwd(res, do, window, cq, ck, scale)

    fn.defvjp(fwd, bwd)
    return fn


def _flash_fwd(q, k, v, window, cq, ck, scale=None):
    b, s, h, dh = q.shape
    kvh = k.shape[2]
    dv = v.shape[-1]
    rep = h // kvh
    nq, nk = s // cq, s // ck
    scale = dh**-0.5 if scale is None else scale
    qg = q.reshape(b, nq, cq, kvh, rep, dh)
    kg = k.reshape(b, nk, ck, kvh, dh)
    vg = v.reshape(b, nk, ck, kvh, dv)

    def q_block(carry, qi):
        qb = qg[:, qi]  # (B, Cq, KV, rep, dh)

        def kv_block(acc, kj):
            m, l, o = acc
            kb, vb = kg[:, kj], vg[:, kj]  # (B, Ck, KV, dh)
            s_ = jnp.einsum(
                "bqkrd,bckd->bkrqc", qb, kb, preferred_element_type=jnp.float32
            ) * scale  # (B, KV, rep, Cq, Ck)
            tm = _tile_mask(qi * cq, kj * ck, cq, ck, window)
            s_ = jnp.where(tm[None, None, None], s_, NEG)
            m_new = jnp.maximum(m, jnp.max(s_, axis=-1))
            p = jnp.exp(s_ - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + jnp.sum(p, axis=-1)
            o = o * corr[..., None] + jnp.einsum(
                "bkrqc,bckd->bkrqd", p.astype(vb.dtype), vb,
                preferred_element_type=jnp.float32,
            )
            return (m_new, l, o), None

        m0 = jnp.full((b, kvh, rep, cq), NEG, jnp.float32)
        l0 = jnp.zeros((b, kvh, rep, cq), jnp.float32)
        o0 = jnp.zeros((b, kvh, rep, cq, dv), jnp.float32)
        (m, l, o), _ = jax.lax.scan(
            kv_block, (m0, l0, o0), jnp.arange(nk, dtype=jnp.int32)
        )
        out = (o / jnp.maximum(l[..., None], 1e-20)).astype(q.dtype)
        lse = m + jnp.log(jnp.maximum(l, 1e-20))  # (B, KV, rep, Cq)
        return carry, (out, lse)

    _, (outs, lses) = jax.lax.scan(q_block, None, jnp.arange(nq, dtype=jnp.int32))
    # outs: (nq, B, KV, rep, Cq, dv) -> (B, S, H, dv)
    out = jnp.moveaxis(outs, 0, 1)  # (B, nq, KV, rep, Cq, dv)
    out = jnp.transpose(out, (0, 1, 4, 2, 3, 5)).reshape(b, s, h, dv)
    lse = jnp.moveaxis(lses, 0, 1)  # (B, nq, KV, rep, Cq)
    return out, lse


def _flash_bwd(res, do, window, cq, ck, scale=None):
    """FlashAttention-2 backward: recompute score tiles from (q,k,v,lse);
    pass 1 accumulates dq over kv blocks, pass 2 accumulates (dk, dv) over
    q blocks.  Live memory = one tile + the output grads."""
    q, k, v, o, lse = res
    b, s, h, dh = q.shape
    kvh = k.shape[2]
    d_v = v.shape[-1]
    rep = h // kvh
    nq, nk = s // cq, s // ck
    scale = dh**-0.5 if scale is None else scale
    qg = q.reshape(b, nq, cq, kvh, rep, dh)
    kg = k.reshape(b, nk, ck, kvh, dh)
    vg = v.reshape(b, nk, ck, kvh, d_v)
    og = o.reshape(b, nq, cq, kvh, rep, d_v)
    dog = do.reshape(b, nq, cq, kvh, rep, d_v)
    # delta[iq] = rowsum(do * o): (B, nq, KV, rep, Cq)
    delta = jnp.einsum("bnqkrd,bnqkrd->bnkrq", dog.astype(jnp.float32),
                       og.astype(jnp.float32))

    def tile_p(qb, kb, lse_q, qi, kj):
        s_ = jnp.einsum(
            "bqkrd,bckd->bkrqc", qb, kb, preferred_element_type=jnp.float32
        ) * scale
        tm = _tile_mask(qi * cq, kj * ck, cq, ck, window)
        s_ = jnp.where(tm[None, None, None], s_, NEG)
        return jnp.exp(s_ - lse_q[..., None])  # (B, KV, rep, Cq, Ck)

    # ---- pass 1: dq per q block (scan over kv blocks inside) ---------------
    def dq_block(_, qi):
        qb = qg[:, qi]
        lse_q, dob, dlt = lse[:, qi], dog[:, qi], delta[:, qi]

        def inner(acc, kj):
            p = tile_p(qb, kg[:, kj], lse_q, qi, kj)
            dp = jnp.einsum("bqkrd,bckd->bkrqc", dob.astype(jnp.float32),
                            vg[:, kj].astype(jnp.float32))
            ds = p * (dp - dlt[..., None]) * scale
            acc = acc + jnp.einsum("bkrqc,bckd->bqkrd", ds,
                                   kg[:, kj].astype(jnp.float32))
            return acc, None

        dq0 = jnp.zeros((b, cq, kvh, rep, dh), jnp.float32)
        dqb, _ = jax.lax.scan(inner, dq0, jnp.arange(nk, dtype=jnp.int32))
        return None, dqb

    _, dqs = jax.lax.scan(dq_block, None, jnp.arange(nq, dtype=jnp.int32))
    dq = jnp.moveaxis(dqs, 0, 1).reshape(b, s, h, dh).astype(q.dtype)

    # ---- pass 2: dk, dv per kv block (scan over q blocks inside) -----------
    def dkv_block(_, kj):
        kb, vb = kg[:, kj], vg[:, kj]

        def inner(acc, qi):
            dk_acc, dv_acc = acc
            qb = qg[:, qi]
            p = tile_p(qb, kb, lse[:, qi], qi, kj)
            dob = dog[:, qi].astype(jnp.float32)
            dv_acc = dv_acc + jnp.einsum("bkrqc,bqkrd->bckd", p, dob)
            dp = jnp.einsum("bqkrd,bckd->bkrqc", dob, vb.astype(jnp.float32))
            ds = p * (dp - delta[:, qi][..., None]) * scale
            dk_acc = dk_acc + jnp.einsum("bkrqc,bqkrd->bckd", ds,
                                         qb.astype(jnp.float32))
            return (dk_acc, dv_acc), None

        (dkb, dvb), _ = jax.lax.scan(
            inner, (jnp.zeros((b, ck, kvh, dh), jnp.float32),
                    jnp.zeros((b, ck, kvh, d_v), jnp.float32)),
            jnp.arange(nq, dtype=jnp.int32)
        )
        return None, (dkb, dvb)

    _, (dks, dvs) = jax.lax.scan(dkv_block, None, jnp.arange(nk, dtype=jnp.int32))
    dk = jnp.moveaxis(dks, 0, 1).reshape(b, s, kvh, dh).astype(k.dtype)
    dv = jnp.moveaxis(dvs, 0, 1).reshape(b, s, kvh, d_v).astype(v.dtype)
    return dq, dk, dv


def dense_attention(q, k, v, *, window=None, scale=None):
    """Reference O(S^2)-memory attention (tests / tiny shapes).  ``v`` may
    be narrower than ``q``/``k``; ``scale`` None = dh ** -0.5."""
    b, s, h, dh = q.shape
    kvh = k.shape[2]
    rep = h // kvh
    qg = q.reshape(b, s, kvh, rep, dh)
    s_ = jnp.einsum("bqkrd,bckd->bkrqc", qg, k, preferred_element_type=jnp.float32)
    s_ = s_ * (dh**-0.5 if scale is None else scale)
    i = jnp.arange(s)[:, None]
    j = jnp.arange(s)[None, :]
    m = j <= i
    if window is not None:
        m &= (i - j) < window
    s_ = jnp.where(m[None, None, None], s_, NEG)
    p = jax.nn.softmax(s_, axis=-1)
    o = jnp.einsum("bkrqc,bckd->bqkrd", p.astype(v.dtype), v)
    return o.reshape(b, s, h, v.shape[-1])


def verify_attention(
    q: jnp.ndarray,  # (B, W, H, dh) — RoPE'd queries for W fed tokens
    k_cache: jnp.ndarray,  # (B, Sc, KV, dh) — incl. the W freshly written rows
    v_cache: jnp.ndarray,  # (B, Sc, KV, dh)
    kv_pos: jnp.ndarray,  # (B, Sc) absolute positions, -1 = empty slot
    q_pos: jnp.ndarray,  # (B, W) absolute position of each fed token
    window: Optional[int] = None,
    k_scale: Optional[jnp.ndarray] = None,  # (B, Sc, KV) int8-mode absmax
    v_scale: Optional[jnp.ndarray] = None,  # scales (dequant fused into dots)
) -> jnp.ndarray:
    """Multi-query decode attention for speculative draft verification.

    Scores W query positions against the KV arena in one pass.  Query *i* is
    masked to ``kv_pos <= q_pos[:, i]`` — the exact visibility rule
    :func:`decode_attention` applies to its single query — so each verified
    position attends over precisely the cache a sequential decode step at
    that position would see (fed tokens at later positions are written into
    the arena but masked out; they only become visible once the query walks
    past them).
    """
    b, w, h, dh = q.shape
    kvh = k_cache.shape[2]
    rep = h // kvh
    qg = q.reshape(b, w, kvh, rep, dh)
    s_ = jnp.einsum(
        "bwkrd,bckd->bkrwc", qg, k_cache.astype(qg.dtype),
        preferred_element_type=jnp.float32,
    ) * (dh**-0.5)  # (B, KV, rep, W, Sc)
    if k_scale is not None:  # int8 cache: fold dequant scale into the scores
        s_ = s_ * jnp.transpose(k_scale, (0, 2, 1)).astype(
            jnp.float32)[:, :, None, None]
    ok = (kv_pos[:, None, :] >= 0) & (kv_pos[:, None, :] <= q_pos[..., None])
    if window is not None:
        ok &= (q_pos[..., None] - kv_pos[:, None, :]) < window
    s_ = jnp.where(ok[:, None, None], s_, NEG)  # (B, KV, rep, W, Sc)
    p = jax.nn.softmax(s_, axis=-1)
    if v_scale is not None:  # fold dequant into the probabilities
        p = p * jnp.transpose(v_scale, (0, 2, 1)).astype(
            p.dtype)[:, :, None, None]
        o = jnp.einsum(
            "bkrwc,bckd->bkrwd", p, v_cache.astype(p.dtype),
            preferred_element_type=jnp.float32,
        ).astype(qg.dtype)
    else:
        o = jnp.einsum("bkrwc,bckd->bkrwd", p.astype(v_cache.dtype), v_cache)
    return jnp.transpose(o, (0, 3, 1, 2, 4)).reshape(b, w, h, dh)


def decode_attention(
    q: jnp.ndarray,  # (B, 1, H, dh) — current-step query (already RoPE'd)
    k_cache: jnp.ndarray,  # (B, Sc, KV, dh) — rotated keys at absolute pos
    v_cache: jnp.ndarray,  # (B, Sc, KV, dh)
    kv_pos: jnp.ndarray,  # (B, Sc) absolute positions, -1 = empty slot
    cur_pos: jnp.ndarray,  # (B,) position of the current token
    window: Optional[int] = None,
    k_scale: Optional[jnp.ndarray] = None,  # (B, Sc, KV) int8-mode absmax
    v_scale: Optional[jnp.ndarray] = None,  # scales (dequant fused into dots)
) -> jnp.ndarray:
    b, _, h, dh = q.shape
    kvh = k_cache.shape[2]
    rep = h // kvh
    qg = q.reshape(b, kvh, rep, dh)
    s_ = jnp.einsum(
        "bkrd,bckd->bkrc", qg, k_cache.astype(qg.dtype),
        preferred_element_type=jnp.float32,
    ) * (dh**-0.5)  # (B, KV, rep, Sc)
    if k_scale is not None:  # int8 cache: fold dequant scale into the scores
        s_ = s_ * jnp.transpose(k_scale, (0, 2, 1)).astype(jnp.float32)[:, :, None]
    causal = kv_pos <= cur_pos[:, None]
    ok = (kv_pos >= 0) & causal
    if window is not None:
        ok &= (cur_pos[:, None] - kv_pos) < window
    s_ = jnp.where(ok[:, None, None], s_, NEG)
    p = jax.nn.softmax(s_, axis=-1)
    if v_scale is not None:  # fold dequant into the probabilities
        p = p * jnp.transpose(v_scale, (0, 2, 1)).astype(p.dtype)[:, :, None]
        o = jnp.einsum(
            "bkrc,bckd->bkrd", p, v_cache.astype(p.dtype),
            preferred_element_type=jnp.float32,
        )
        return o.astype(qg.dtype).reshape(b, 1, h, dh)
    o = jnp.einsum("bkrc,bckd->bkrd", p.astype(v_cache.dtype), v_cache)
    return o.reshape(b, 1, h, dh)


def latent_decode_attention(
    q_lat: jnp.ndarray,  # (B, H, R) — queries with kv_b's key part absorbed
    q_pe: jnp.ndarray,  # (B, H, P) — RoPE'd rope part of the queries
    c_cache: jnp.ndarray,  # (B, Sc, R) — normalised latent rows
    pe_cache: jnp.ndarray,  # (B, Sc, P) — RoPE'd rope key rows
    kv_pos: jnp.ndarray,  # (B, Sc) absolute positions, -1 = empty slot
    cur_pos: jnp.ndarray,  # (B,) position of the current token
    scale: float,
    window: Optional[int] = None,
) -> jnp.ndarray:
    """Latent-attention decode (MLA with absorbed weights): every query head
    scores its (R + P)-wide ``[q_lat | q_pe]`` against the cached
    ``[c | k_pe]`` rows, one key shared by all heads, and takes the
    probability-weighted latent rows.  Returns (B, H, R) float32, still to
    be multiplied by kv_b's value part."""
    s_ = (jnp.einsum("bhr,bsr->bhs", q_lat, c_cache,
                     preferred_element_type=jnp.float32)
          + jnp.einsum("bhp,bsp->bhs", q_pe, pe_cache,
                       preferred_element_type=jnp.float32)) * scale
    ok = (kv_pos >= 0) & (kv_pos <= cur_pos[:, None])
    if window is not None:
        ok &= (cur_pos[:, None] - kv_pos) < window
    s_ = jnp.where(ok[:, None], s_, NEG)
    p = jax.nn.softmax(s_, axis=-1)
    return jnp.einsum("bhs,bsr->bhr", p.astype(c_cache.dtype), c_cache,
                      preferred_element_type=jnp.float32)


# --------------------------------------------------------------------------
# paged KV: block-table indirection in front of the decode/verify kernels
# --------------------------------------------------------------------------
def paged_gather(pool: jnp.ndarray, rows: jnp.ndarray) -> jnp.ndarray:
    """Materialize a per-slot contiguous view out of a shared paged pool.

    ``pool`` (P, ...) holds the physical rows of every slot's KV blocks;
    ``rows`` (B, Sc) maps each slot's logical arena row to its pool row
    (pre-clamped to 0 under unallocated blocks — see
    ``repro.models.transformer.model.block_rows``).  One advanced-indexing
    gather -> (B, Sc, ...), the exact layout the contiguous kernels take.
    """
    return pool[rows]


def paged_decode_attention(
    q: jnp.ndarray,  # (B, 1, H, dh) — current-step query (already RoPE'd)
    k_pool: jnp.ndarray,  # (P, KV, dh) — this layer's shared block pool
    v_pool: jnp.ndarray,  # (P, KV, dh)
    rows: jnp.ndarray,  # (B, Sc) block-table row map (see paged_gather)
    kv_pos: jnp.ndarray,  # (B, Sc) absolute positions, -1 = empty/unallocated
    cur_pos: jnp.ndarray,  # (B,) position of the current token
    window: Optional[int] = None,
    k_scale: Optional[jnp.ndarray] = None,  # (P, KV) int8-mode absmax scales
    v_scale: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Block-table-indirected decode attention: gather the slot's logical
    view from the pool, then delegate to :func:`decode_attention` unchanged.

    Rows gathered from unallocated blocks (clamped to pool row 0) carry
    ``kv_pos == -1``; the NEG mask turns them into *exact* zeros after the
    softmax (exp underflows to 0.0, and 0.0 * finite == 0.0), so the output
    is bitwise identical to a contiguous arena holding the same live rows —
    the indirection cost is one gather per layer, not a different kernel.
    """
    kc = paged_gather(k_pool, rows)
    vc = paged_gather(v_pool, rows)
    ks = paged_gather(k_scale, rows) if k_scale is not None else None
    vs = paged_gather(v_scale, rows) if v_scale is not None else None
    return decode_attention(q, kc, vc, kv_pos, cur_pos, window,
                            k_scale=ks, v_scale=vs)


def paged_verify_attention(
    q: jnp.ndarray,  # (B, W, H, dh) — RoPE'd queries for W fed tokens
    k_pool: jnp.ndarray,  # (P, KV, dh) — incl. the W freshly written rows
    v_pool: jnp.ndarray,  # (P, KV, dh)
    rows: jnp.ndarray,  # (B, Sc) block-table row map
    kv_pos: jnp.ndarray,  # (B, Sc) absolute positions, -1 = empty
    q_pos: jnp.ndarray,  # (B, W) absolute position of each fed token
    window: Optional[int] = None,
    k_scale: Optional[jnp.ndarray] = None,  # (P, KV)
    v_scale: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Block-table-indirected :func:`verify_attention` — same gather-then-
    delegate construction (and the same bitwise-parity argument) as
    :func:`paged_decode_attention`, for the speculative verify pass."""
    kc = paged_gather(k_pool, rows)
    vc = paged_gather(v_pool, rows)
    ks = paged_gather(k_scale, rows) if k_scale is not None else None
    vs = paged_gather(v_scale, rows) if v_scale is not None else None
    return verify_attention(q, kc, vc, kv_pos, q_pos, window,
                            k_scale=ks, v_scale=vs)
