"""Decoder-only LM: init / train forward / prefill / decode, scan-over-layers.

Covers all 5 assigned LM architectures: GQA + RoPE, dense-SwiGLU or MoE FFN,
optional sliding-window attention (starcoder2), streamed cross-entropy (vocab
up to 131k), ring-buffer KV cache for long-context decode.

Layers are stacked on a leading L axis and driven by `lax.scan` (+ optional
`jax.checkpoint`), so HLO size and compile time are depth-independent — a
hard requirement for the 62-layer/33B dry-run on this container.

A config with ``mla`` set (DeepSeek-V2) takes the latent-attention path of
the section at the end: expanded attention for training and prefill,
absorbed attention over a latent cache for decode, leading dense layers in
a segment of their own, and dropless held-share MoE layers.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.distributed.constraints import shard_hint
from repro.models.transformer import attention as attn
from repro.models.transformer.config import TransformerConfig
from repro.models.transformer.moe import (
    init_moe_params, moe_ffn, moe_held, swiglu,
)


def rms_norm(x: jnp.ndarray, w: jnp.ndarray, eps: float) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    s = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * s * w.astype(jnp.float32)).astype(x.dtype)


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("cfg",))
def init_params(key, cfg: TransformerConfig) -> dict:
    """Random weights from ``key``.  Jitted so that each float32 draw fuses
    with its cast: at published widths the eager draw of ``w2`` alone would
    be a multi-GB float32 transient on top of the weights already built."""
    dtype = jnp.dtype(cfg.dtype)
    d, h, kv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    keys = jax.random.split(key, 8)
    s_d = d**-0.5
    L = cfg.n_layers

    def nrm(k, shape, scale):
        return (jax.random.normal(k, shape) * scale).astype(dtype)

    layers = {
        "ln1": jnp.ones((L, d), jnp.float32),
        "ln2": jnp.ones((L, d), jnp.float32),
        "wq": nrm(keys[0], (L, d, h * dh), s_d),
        "wk": nrm(keys[1], (L, d, kv * dh), s_d),
        "wv": nrm(keys[2], (L, d, kv * dh), s_d),
        "wo": nrm(keys[3], (L, h * dh, d), (h * dh) ** -0.5),
    }
    if cfg.moe is None:
        layers.update(
            w1=nrm(keys[4], (L, d, cfg.d_ff), s_d),
            w3=nrm(keys[5], (L, d, cfg.d_ff), s_d),
            w2=nrm(keys[6], (L, cfg.d_ff, d), cfg.d_ff**-0.5),
        )
    else:
        moe_keys = jax.random.split(keys[4], L)
        per_layer = [init_moe_params(k, d, cfg.moe, dtype) for k in moe_keys]
        layers["moe"] = jax.tree.map(lambda *xs: jnp.stack(xs), *per_layer)
    k_e, k_h = jax.random.split(keys[7])
    return {
        "embed": nrm(k_e, (cfg.vocab, d), 1.0),
        "layers": layers,
        "ln_f": jnp.ones((d,), jnp.float32),
        "head": nrm(k_h, (d, cfg.vocab), s_d),
    }


# --------------------------------------------------------------------------
# shared layer body
# --------------------------------------------------------------------------
def _attn_proj(p, xn, cfg: TransformerConfig):
    b, s, _ = xn.shape
    q = (xn @ p["wq"]).reshape(b, s, cfg.n_heads, cfg.d_head)
    k = (xn @ p["wk"]).reshape(b, s, cfg.n_kv_heads, cfg.d_head)
    v = (xn @ p["wv"]).reshape(b, s, cfg.n_kv_heads, cfg.d_head)
    return q, k, v


def _layer_train(x, p, cfg: TransformerConfig, positions):
    xn = rms_norm(x, p["ln1"], cfg.norm_eps)
    q, k, v = _attn_proj(p, xn, cfg)
    q = attn.rope(q, positions, cfg.rope_theta)
    k = attn.rope(k, positions, cfg.rope_theta)
    s = x.shape[1]
    if s <= max(cfg.q_chunk, 256):
        o = attn.dense_attention(q, k, v, window=cfg.sliding_window)
    else:
        o = attn.chunked_attention(
            q, k, v, window=cfg.sliding_window,
            q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk,
        )
    b, s_, h, dh = o.shape
    x = x + (o.reshape(b, s_, h * dh) @ p["wo"]).astype(x.dtype)

    xn = rms_norm(x, p["ln2"], cfg.norm_eps)
    if cfg.moe is None:
        hidden = jax.nn.silu(xn @ p["w1"]) * (xn @ p["w3"])
        y = (hidden @ p["w2"]).astype(x.dtype)
        aux = jnp.zeros((), jnp.float32)
    else:
        t = b * s_
        y, aux = moe_ffn(p["moe"], xn.reshape(t, -1), cfg.moe)
        y = y.reshape(b, s_, -1)
    return x + y, aux


# --------------------------------------------------------------------------
# train-time forward + streamed loss
# --------------------------------------------------------------------------
def backbone(params, tokens: jnp.ndarray, cfg: TransformerConfig) -> tuple:
    """tokens (B, S) -> (hidden (B, S, D), aux_loss)."""
    if cfg.mla is not None:
        return _mla_backbone(params, tokens, cfg)
    x = params["embed"][tokens]
    positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)[None, :]

    def body(carry, p):
        x, aux = carry
        x, a = _layer_train(x, p, cfg, positions)
        return (x, aux + a), None

    fn = jax.checkpoint(body) if cfg.remat else body
    if cfg.scan_layers:
        (x, aux), _ = jax.lax.scan(
            fn, (x, jnp.zeros((), jnp.float32)), params["layers"]
        )
    else:
        aux = jnp.zeros((), jnp.float32)
        for i in range(cfg.n_layers):
            p = jax.tree.map(lambda a: a[i], params["layers"])
            (x, aux), _ = fn((x, aux), p)
    return rms_norm(x, params["ln_f"], cfg.norm_eps), aux


def lm_logits(params, tokens, cfg: TransformerConfig):
    """Materialized logits — tests/small shapes only (V can be 131k)."""
    x, _ = backbone(params, tokens, cfg)
    return x.astype(jnp.float32) @ params["head"].astype(jnp.float32)


def lm_loss(params, tokens, loss_mask, cfg: TransformerConfig, aux_weight=0.01):
    """Next-token cross-entropy, streamed over sequence chunks.

    tokens (B, S) int32; loss_mask (B, S) — mask[t] gates prediction of
    token[t+1].  Returns (loss, metrics dict).
    """
    x, aux = backbone(params, tokens, cfg)
    b, s, d = x.shape
    c = min(cfg.loss_chunk, s - 1)
    n_pred = s - 1
    nc = n_pred // c
    rem = n_pred - nc * c
    head = params["head"]

    def chunk_nll(xc, yc, mc):
        lg = xc.astype(jnp.float32) @ head.astype(jnp.float32)  # (B, c, V)
        lse = jax.nn.logsumexp(lg, axis=-1)
        tgt = jnp.take_along_axis(lg, yc[..., None], axis=-1)[..., 0]
        return jnp.sum((lse - tgt) * mc), jnp.sum(mc)

    def body(acc, i):
        st = i * c
        xc = jax.lax.dynamic_slice_in_dim(x, st, c, axis=1)
        yc = jax.lax.dynamic_slice_in_dim(tokens, st + 1, c, axis=1)
        mc = jax.lax.dynamic_slice_in_dim(loss_mask, st, c, axis=1).astype(jnp.float32)
        nll, cnt = chunk_nll(xc, yc, mc)
        return (acc[0] + nll, acc[1] + cnt), None

    (nll, cnt), _ = jax.lax.scan(
        body, (jnp.zeros(()), jnp.zeros(())), jnp.arange(nc, dtype=jnp.int32)
    )
    if rem:
        nll_r, cnt_r = chunk_nll(
            x[:, nc * c : s - 1],
            tokens[:, nc * c + 1 :],
            loss_mask[:, nc * c : s - 1].astype(jnp.float32),
        )
        nll, cnt = nll + nll_r, cnt + cnt_r
    loss = nll / jnp.maximum(cnt, 1.0)
    total = loss + aux_weight * aux
    return total, {"nll": loss, "aux": aux, "tokens": cnt}


# --------------------------------------------------------------------------
# serving: KV cache, prefill, decode
# --------------------------------------------------------------------------
@dataclasses.dataclass
class KVCache:
    """The contiguous arena: per layer, slot and position one cached row.

    Under latent attention (``cfg.mla``) the same fields hold the latent
    cache, one row that every head shares and so no head axis: ``k``
    (L, B, Sc, kv_rank) is the normalised latent and ``v``
    (L, B, Sc, rope_dim) the rotated key, with Sc rounded up to a multiple
    of 8 (``_mla_rows``).  Everything that moves rows between slots
    (prefill, the engine's arena merge) is elementwise over (L, B, ...)
    leaves and so carries either layout unchanged.  ``routed`` and
    ``max_load`` are the held-share MoE's routing counters, accumulated on
    the device by every decode step (None without such layers).
    """

    k: jnp.ndarray  # (L, B, Sc, KV, dh) — int8 when quantized
    v: jnp.ndarray  # (L, B, Sc, KV, dh)
    pos: jnp.ndarray  # (B, Sc) absolute position per slot, -1 empty
    cursor: jnp.ndarray  # (B,) next absolute position to write
    k_scale: object = None  # (L, B, Sc, KV) bf16 absmax scales (int8 mode)
    v_scale: object = None
    # token-slots routed to each held expert, over every MoE layer and
    # decode row (live or not) since the arena was made: (n_held,) int32
    routed: object = None
    max_load: object = None  # () int32 largest one-layer load of a step


jax.tree_util.register_dataclass(
    KVCache, data_fields=["k", "v", "pos", "cursor", "k_scale", "v_scale",
                          "routed", "max_load"],
    meta_fields=[],
)


def _quant_rows(x: jnp.ndarray):
    """Per-(.., KV)-row absmax int8 quantization over d_head."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]), -127, 127)
    return q.astype(jnp.int8), scale.astype(jnp.bfloat16)


def init_cache(cfg: TransformerConfig, batch: int, cache_len: int) -> KVCache:
    dtype = jnp.dtype(cfg.dtype)
    if cfg.mla is not None:
        return _mla_init_cache(cfg, batch, cache_len)
    shape = (cfg.n_layers, batch, cache_len, cfg.n_kv_heads, cfg.d_head)
    if cfg.kv_quant:
        return KVCache(
            k=jnp.zeros(shape, jnp.int8),
            v=jnp.zeros(shape, jnp.int8),
            pos=jnp.full((batch, cache_len), -1, jnp.int32),
            cursor=jnp.zeros((batch,), jnp.int32),
            k_scale=jnp.zeros(shape[:-1], jnp.bfloat16),
            v_scale=jnp.zeros(shape[:-1], jnp.bfloat16),
        )
    return KVCache(
        k=jnp.zeros(shape, dtype),
        v=jnp.zeros(shape, dtype),
        pos=jnp.full((batch, cache_len), -1, jnp.int32),
        cursor=jnp.zeros((batch,), jnp.int32),
    )


def prefill(params, tokens, true_len, cfg: TransformerConfig, cache_len: int):
    """Run the prompt, fill the cache, return (next_token_logits, cache).

    tokens (B, S) left-aligned, padded; true_len (B,).  Requires S <= cache_len.
    """
    if cfg.mla is not None:
        return _mla_prefill(params, tokens, true_len, cfg, cache_len)
    b, s = tokens.shape
    assert s <= cache_len
    x = params["embed"][tokens]
    positions = jnp.arange(s, dtype=jnp.int32)[None, :]

    def body(x, p):
        xn = rms_norm(x, p["ln1"], cfg.norm_eps)
        q, k, v = _attn_proj(p, xn, cfg)
        q = attn.rope(q, positions, cfg.rope_theta)
        k = attn.rope(k, positions, cfg.rope_theta)
        if s <= max(cfg.q_chunk, 256):
            o = attn.dense_attention(q, k, v, window=cfg.sliding_window)
        else:
            o = attn.chunked_attention(
                q, k, v, window=cfg.sliding_window,
                q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk,
            )
        x = x + (o.reshape(b, s, -1) @ p["wo"]).astype(x.dtype)
        xn = rms_norm(x, p["ln2"], cfg.norm_eps)
        if cfg.moe is None:
            y = (jax.nn.silu(xn @ p["w1"]) * (xn @ p["w3"])) @ p["w2"]
        else:
            y, _ = moe_ffn(p["moe"], xn.reshape(b * s, -1), cfg.moe)
            y = y.reshape(b, s, -1)
        # cache rows: batch over dp, sequence over "model" (decode layout) —
        # unhinted, GSPMD replicated the 257 GB cache (§Perf).
        k = shard_hint(k, "dp", "model", None, None)
        v = shard_hint(v, "dp", "model", None, None)
        return x + y.astype(x.dtype), (k, v)

    fn = jax.checkpoint(body, static_argnums=()) if cfg.remat else body
    if cfg.scan_layers:
        x, (ks, vs) = jax.lax.scan(fn, x, params["layers"])
    else:  # unrolled (cost-analysis variants)
        ks_l, vs_l = [], []
        for i in range(cfg.n_layers):
            p = jax.tree.map(lambda a: a[i], params["layers"])
            x, (k_i, v_i) = fn(x, p)
            ks_l.append(k_i)
            vs_l.append(v_i)
        ks, vs = jnp.stack(ks_l), jnp.stack(vs_l)
    # cache layout
    pad = cache_len - s
    kc = jnp.pad(ks, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    vc = jnp.pad(vs, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    kc = shard_hint(kc, None, "dp", "model", None, None)
    vc = shard_hint(vc, None, "dp", "model", None, None)
    slot_pos = jnp.arange(cache_len, dtype=jnp.int32)[None, :]
    pos = jnp.where(slot_pos < true_len[:, None], slot_pos, -1)
    if cfg.kv_quant:
        kq, ksc = _quant_rows(kc)
        vq, vsc = _quant_rows(vc)
        cache = KVCache(k=kq, v=vq, pos=pos, cursor=true_len.astype(jnp.int32),
                        k_scale=ksc, v_scale=vsc)
    else:
        cache = KVCache(k=kc, v=vc, pos=pos, cursor=true_len.astype(jnp.int32))
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    last = jnp.take_along_axis(
        x, jnp.maximum(true_len - 1, 0)[:, None, None].astype(jnp.int32), axis=1
    )  # (B, 1, D)
    logits = last.astype(jnp.float32) @ params["head"].astype(jnp.float32)
    return logits[:, 0], cache


def decode_step(params, cache: KVCache, token, cfg: TransformerConfig):
    """One decode step.  token (B,) int32 -> (logits (B, V), new cache)."""
    if cfg.mla is not None:
        return _mla_decode_step(params, cache, token, cfg)
    b = token.shape[0]
    sc = cache.k.shape[2]
    cur = cache.cursor  # (B,) position of the token being processed
    slot = cur % sc
    bidx = jnp.arange(b)
    pos = jnp.where(jnp.arange(sc, dtype=jnp.int32)[None, :] == slot[:, None],
                    cur[:, None], cache.pos)
    x = params["embed"][token][:, None]  # (B, 1, D)
    quant = cfg.kv_quant

    # The arena rides in the layer loop's carry: each layer writes its B new
    # rows (and their scales) in place at (layer, b, cur % Sc), then attends.
    def body(carry, xs):
        x, kc, vc, ks, vs = carry
        p, i = xs
        xn = rms_norm(x, p["ln1"], cfg.norm_eps)
        q, k, v = _attn_proj(p, xn, cfg)
        q = attn.rope(q, cur[:, None], cfg.rope_theta)
        k = attn.rope(k, cur[:, None], cfg.rope_theta)
        if quant:
            k, ksc = _quant_rows(k)
            v, vsc = _quant_rows(v)
            ks = ks.at[i, bidx, slot].set(ksc[:, 0])
            vs = vs.at[i, bidx, slot].set(vsc[:, 0])
        kc = kc.at[i, bidx, slot].set(k[:, 0])
        vc = vc.at[i, bidx, slot].set(v[:, 0])
        o = attn.decode_attention(
            q, kc[i], vc[i], pos, cur, cfg.sliding_window,
            k_scale=ks[i] if quant else None, v_scale=vs[i] if quant else None,
        )
        x = x + (o.reshape(b, 1, -1) @ p["wo"]).astype(x.dtype)
        xn = rms_norm(x, p["ln2"], cfg.norm_eps)
        if cfg.moe is None:
            y = (jax.nn.silu(xn @ p["w1"]) * (xn @ p["w3"])) @ p["w2"]
        else:
            y, _ = moe_ffn(p["moe"], xn.reshape(b, -1), cfg.moe)
            y = y[:, None]
        return (x + y.astype(x.dtype), kc, vc, ks, vs), None

    carry = (x, cache.k, cache.v, cache.k_scale, cache.v_scale)
    if cfg.scan_layers:
        idx = jnp.arange(cfg.n_layers, dtype=jnp.int32)
        carry, _ = jax.lax.scan(body, carry, (params["layers"], idx))
    else:  # unrolled (cost-analysis variants)
        for i in range(cfg.n_layers):
            p = jax.tree.map(lambda a: a[i], params["layers"])
            carry, _ = body(carry, (p, i))
    x, kc, vc, ks, vs = carry
    new_cache = KVCache(k=kc, v=vc, pos=pos, cursor=cur + 1,
                        k_scale=ks, v_scale=vs)
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    logits = x[:, 0].astype(jnp.float32) @ params["head"].astype(jnp.float32)
    return logits, new_cache


@functools.partial(jax.jit, static_argnames=("cfg",),
                   donate_argnames=("cache",))
def serve_step(params, cache: KVCache, token, cfg: TransformerConfig):
    """Greedy decode step.  The cache is donated: the step writes its new
    rows into the caller's arena, which the caller must not read again."""
    logits, cache = decode_step(params, cache, token, cfg)
    return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache


# --------------------------------------------------------------------------
# self-speculative verification: score W fed tokens in one dispatch
# --------------------------------------------------------------------------
def verify_window(params, cache: KVCache, tokens, cfg: TransformerConfig):
    """Score a window of fed tokens against the KV arena in ONE dispatch.

    tokens (B, W): column 0 is the last committed token, columns 1..W-1 are
    draft continuations.  Token *i* is processed at absolute position
    ``cursor + i``: all W tokens' K/V rows are written first (masked to
    positions < cache_len so a window near the arena end never wraps onto a
    live row), then every query attends under the per-position visibility
    mask of :func:`repro.models.transformer.attention.verify_attention` —
    each position sees exactly the cache a sequential :func:`decode_step`
    at that position would see, which is what makes greedy acceptance
    token-exact against one-token decode.

    Returns (greedy (B, W), cache).  The cache holds all W written rows and
    an UNCHANGED cursor; :func:`verify_step` rewinds to the first rejection
    by advancing the cursor only past the accepted prefix.  Rows written for
    rejected positions are left in place: their ``pos`` values exceed every
    later query position until the cursor catches up, so the `<=` mask hides
    them, and the next window overwrites them before any attention runs.
    """
    _contiguous_only(cfg, "speculative verification")
    b, w = tokens.shape
    sc = cache.k.shape[2]
    cur = cache.cursor  # (B,)
    positions = cur[:, None] + jnp.arange(w, dtype=jnp.int32)[None, :]
    writable = positions < sc  # never ring-wrap onto live rows
    slot = positions % sc
    slot_mask = (jnp.arange(sc, dtype=jnp.int32)[None, None, :]
                 == slot[..., None]) & writable[..., None]  # (B, W, Sc)
    x = params["embed"][tokens]  # (B, W, D)
    new_pos = cache.pos
    for i in range(w):
        new_pos = jnp.where(slot_mask[:, i], positions[:, i:i + 1], new_pos)
    quant = cfg.kv_quant

    def body(x, inputs):
        p, kc, vc, ks, vs = inputs
        xn = rms_norm(x, p["ln1"], cfg.norm_eps)
        q, k, v = _attn_proj(p, xn, cfg)
        q = attn.rope(q, positions, cfg.rope_theta)
        k = attn.rope(k, positions, cfg.rope_theta)
        if quant:
            kq, ksc = _quant_rows(k)
            vq, vsc = _quant_rows(v)
            for i in range(w):
                m = slot_mask[:, i][:, :, None, None]
                kc = jnp.where(m, kq[:, i][:, None], kc)
                vc = jnp.where(m, vq[:, i][:, None], vc)
                ks = jnp.where(slot_mask[:, i][:, :, None],
                               ksc[:, i][:, None], ks)
                vs = jnp.where(slot_mask[:, i][:, :, None],
                               vsc[:, i][:, None], vs)
        else:
            # one fused masked merge instead of W sequential full-array
            # passes: ring slots within a window are distinct, so the
            # one-hot contraction selects exactly one (w) row per written
            # slot — multiply-by-one/add-zero keeps the merge bitwise
            # identical to the sequential wheres
            onehot = slot_mask.astype(k.dtype)  # (B, W, Sc)
            wrote = slot_mask.any(axis=1)[:, :, None, None]  # (B, Sc, 1, 1)
            kc = jnp.where(wrote, jnp.einsum("bws,bwkd->bskd", onehot, k), kc)
            vc = jnp.where(wrote, jnp.einsum("bws,bwkd->bskd", onehot, v), vc)
        o = attn.verify_attention(
            q, kc, vc, new_pos, positions, cfg.sliding_window,
            k_scale=ks, v_scale=vs,
        )
        x = x + (o.reshape(b, w, -1) @ p["wo"]).astype(x.dtype)
        xn = rms_norm(x, p["ln2"], cfg.norm_eps)
        if cfg.moe is None:
            y = (jax.nn.silu(xn @ p["w1"]) * (xn @ p["w3"])) @ p["w2"]
        else:
            y, _ = moe_ffn(p["moe"], xn.reshape(b * w, -1), cfg.moe)
            y = y.reshape(b, w, -1)
        return x + y.astype(x.dtype), (kc, vc, ks, vs)

    xs = (params["layers"], cache.k, cache.v, cache.k_scale, cache.v_scale)
    if cfg.scan_layers:
        x, (kc, vc, ks, vs) = jax.lax.scan(body, x, xs)
    else:  # unrolled (cost-analysis variants)
        outs = []
        for i in range(cfg.n_layers):
            sl = jax.tree.map(lambda a: a[i], xs)
            x, o_i = body(x, sl)
            outs.append(o_i)
        cols = list(zip(*outs))
        kc, vc = jnp.stack(cols[0]), jnp.stack(cols[1])
        ks = jnp.stack(cols[2]) if quant else None
        vs = jnp.stack(cols[3]) if quant else None
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    logits = x.astype(jnp.float32) @ params["head"].astype(jnp.float32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # (B, W)
    new_cache = KVCache(k=kc, v=vc, pos=new_pos, cursor=cache.cursor,
                        k_scale=ks, v_scale=vs)
    return greedy, new_cache


def _accept_prefix(greedy, tokens, room, w: int, eos_id):
    """Greedy-exact acceptance shared by the contiguous and paged verify
    steps: position 0 always accepts; draft *i* accepts iff it equals the
    accepted output at *i-1*; ``room`` caps the prefix and ``eos_id``
    truncates it just past the first EOS.  Returns (accepted, cur_tok)."""
    match = (tokens[:, 1:] == greedy[:, :-1]).astype(jnp.int32)  # (B, W-1)
    raw = 1 + jnp.cumprod(match, axis=1).sum(axis=1)  # (B,) in [1, W]
    accepted = jnp.minimum(raw, jnp.maximum(room, 1))
    if eos_id is not None:
        idx = jnp.arange(w, dtype=jnp.int32)[None, :]
        is_eos = (greedy == eos_id) & (idx < accepted[:, None])
        first_eos = jnp.min(jnp.where(is_eos, idx, w), axis=1)
        accepted = jnp.minimum(accepted, first_eos + 1)
    cur_tok = jnp.take_along_axis(greedy, (accepted - 1)[:, None], axis=1)[:, 0]
    return accepted, cur_tok


@functools.partial(jax.jit, static_argnames=("cfg", "eos_id"))
def verify_step(params, cache: KVCache, tokens, room,
                cfg: TransformerConfig, eos_id=None):
    """One speculative engine step: verify W fed tokens, accept the greedy-
    matching prefix, rewind the cache cursor to the first rejection.

    tokens (B, W): [committed last token, draft_1 .. draft_{W-1}].
    room (B,): per-slot cap on accepted tokens this step
    (``min(max_new_tokens remaining, cache_len - cursor)``; clamped to
    >= 1 here, so a dead slot's cursor still drifts — by 1 to W per step
    depending on its stale room — until admission re-pins it).

    Acceptance is greedy-exact: position 0's output is always accepted (it
    is what one-token decode would emit); draft *i* is accepted iff it equals
    the accepted output at position *i-1*, so the accepted prefix is bitwise
    identical to step-by-step decode.  ``eos_id`` truncates the accepted
    prefix just past the first EOS, mirroring the sequential stop check.

    Returns (greedy (B, W), accepted (B,) in [1, W], next committed token
    (B,), cache with ``cursor += accepted``).
    """
    b, w = tokens.shape
    greedy, cache = verify_window(params, cache, tokens, cfg)
    accepted, cur_tok = _accept_prefix(greedy, tokens, room, w, eos_id)
    cache = KVCache(k=cache.k, v=cache.v, pos=cache.pos,
                    cursor=cache.cursor + accepted,
                    k_scale=cache.k_scale, v_scale=cache.v_scale)
    return greedy, accepted, cur_tok, cache


# --------------------------------------------------------------------------
# paged KV pool: block-table indirection over a shared block arena
# --------------------------------------------------------------------------
@dataclasses.dataclass
class PagedKVCache:
    """KV arena as a pool of fixed-size blocks shared by every decode slot.

    Same logical semantics as :class:`KVCache` — ``pos`` / ``cursor`` keep
    the per-slot absolute-position view over a virtual (B, Sc) arena — but
    the physical rows live in a (L, P, KV, dh) pool of ``pool_blocks``
    blocks of ``block_size`` tokens each (P = pool_blocks * block_size).
    ``table[b, j]`` names the pool block backing logical positions
    [j*bs, (j+1)*bs) of slot b; -1 = unallocated.  Allocated entries always
    form a prefix of the row because positions only grow until the slot
    retires and frees everything at once.

    ``free`` is a device free-list stack whose valid entries are
    ``free[:n_free]``: the jitted step pops blocks from the top as cursors
    cross block boundaries, :func:`free_slot_blocks` pushes a retired
    slot's blocks back in one small dispatch.  Neither direction syncs the
    host; the serving engine replays the same arithmetic on host mirrors
    (cursor → blocks needed → stack depth), so pool-exhaustion checks are
    host-only and deterministic.

    ``ref`` is the per-pool-block refcount that makes prefix sharing safe:
    a block's count is the number of holders — table entries across slots
    plus retrieval-cache pins (:func:`acquire_blocks`).  Allocation pops a
    block at count 0 and sets it to 1; :func:`free_slot_blocks` /
    :func:`release_blocks` decrement and push a block back onto the stack
    only when its count hits zero, so an aliased prompt prefix outlives
    any single holder.
    """

    k: jnp.ndarray  # (L, P, KV, dh) — int8 when quantized
    v: jnp.ndarray  # (L, P, KV, dh)
    pos: jnp.ndarray  # (B, Sc) absolute position per logical row, -1 empty
    cursor: jnp.ndarray  # (B,) next absolute position to write
    table: jnp.ndarray  # (B, max_blocks) pool block per logical block, -1 none
    free: jnp.ndarray  # (pool_blocks,) free-list stack storage
    n_free: jnp.ndarray  # () int32 valid stack depth
    ref: jnp.ndarray  # (pool_blocks,) int32 holders per block (0 = free)
    k_scale: object = None  # (L, P, KV) bf16 absmax scales (int8 mode)
    v_scale: object = None


jax.tree_util.register_dataclass(
    PagedKVCache,
    data_fields=["k", "v", "pos", "cursor", "table", "free", "n_free",
                 "ref", "k_scale", "v_scale"],
    meta_fields=[],
)


def init_paged_cache(cfg: TransformerConfig, batch: int, cache_len: int,
                     block_size: int, pool_blocks: int) -> PagedKVCache:
    _contiguous_only(cfg, "the paged KV arena")
    if cache_len % block_size != 0:
        raise ValueError(
            f"block_size={block_size} must divide cache_len={cache_len}"
        )
    dtype = jnp.dtype(cfg.dtype)
    p = pool_blocks * block_size
    m = cache_len // block_size
    shape = (cfg.n_layers, p, cfg.n_kv_heads, cfg.d_head)
    kv_dtype = jnp.int8 if cfg.kv_quant else dtype
    scales = (jnp.zeros(shape[:-1], jnp.bfloat16) if cfg.kv_quant else None)
    return PagedKVCache(
        k=jnp.zeros(shape, kv_dtype),
        v=jnp.zeros(shape, kv_dtype),
        pos=jnp.full((batch, cache_len), -1, jnp.int32),
        cursor=jnp.zeros((batch,), jnp.int32),
        table=jnp.full((batch, m), -1, jnp.int32),
        free=jnp.arange(pool_blocks, dtype=jnp.int32),
        n_free=jnp.asarray(pool_blocks, jnp.int32),
        ref=jnp.zeros((pool_blocks,), jnp.int32),
        k_scale=scales,
        v_scale=(None if scales is None else scales),
    )


def block_rows(table: jnp.ndarray, block_size: int) -> jnp.ndarray:
    """(B, M) block table -> (B, M*bs) pool-row gather map.  Rows under
    unallocated blocks map to pool row 0 — callers mask those logical rows
    via ``pos == -1``, so the gathered garbage is exact zero-weight."""
    b, m = table.shape
    off = jnp.arange(block_size, dtype=jnp.int32)
    rows = table[:, :, None] * block_size + off[None, None, :]
    return jnp.where(rows >= 0, rows, 0).reshape(b, m * block_size)


def alloc_blocks(table, free, n_free, ref, target, live, max_new: int):
    """Grow each live slot's allocated-block prefix to ``target[b]`` blocks
    by popping from the free stack — at most ``max_new`` new blocks per slot
    (a static bound, so the pop unrolls to ``max_new`` masked writes).
    Every popped block's refcount is set to 1 (its sole holder is the slot
    whose table entry now names it).

    The caller guarantees ``sum(need) <= n_free``: the serving engine
    retires slots host-side (``truncated=True``) before dispatch whenever
    the pool cannot cover the step, so no in-jit exhaustion handling — and
    no host sync — is ever needed (the engine's ``RGL_KV_DEBUG`` guard
    raises host-side if the invariant is ever violated; in-jit the
    violation would silently alias stale stack entries).  Dead slots
    (``~live``) never allocate, even though their cursors drift between
    admissions.
    """
    b, m = table.shape
    p = free.shape[0]
    n_tab = jnp.sum(table >= 0, axis=1).astype(jnp.int32)
    need = jnp.where(live, jnp.clip(target - n_tab, 0, max_new), 0)
    offs = (jnp.cumsum(need) - need).astype(jnp.int32)  # exclusive prefix sum
    cols = jnp.arange(m, dtype=jnp.int32)[None, :]
    for j in range(max_new):
        take = j < need  # (B,)
        src = jnp.clip(n_free - 1 - offs - j, 0, p - 1)
        blk = free[src]  # (B,) popped block ids (garbage where ~take)
        write = take[:, None] & (cols == (n_tab + j)[:, None])
        table = jnp.where(write, blk[:, None], table)
        ref = ref.at[jnp.where(take, blk, p)].set(1, mode="drop")
    return table, (n_free - jnp.sum(need)).astype(jnp.int32), ref


def _release_refs(free, n_free, ref, drops):
    """Decrement per-block refcounts by ``drops`` (a (P,) count of holds
    being dropped per pool block) and push every block whose count hits
    zero back onto the free stack, in ascending block-id order.  The
    per-POOL-BLOCK accounting (rather than per-table-entry) makes the push
    set duplicate-free by construction even when several retiring holders
    reference the same shared block."""
    p = free.shape[0]
    ref = ref - drops
    push = (drops > 0) & (ref <= 0)
    npush = jnp.cumsum(push.astype(jnp.int32))
    dst = jnp.where(push, n_free + npush - 1, p)
    free = free.at[dst].set(jnp.arange(p, dtype=jnp.int32), mode="drop")
    return free, (n_free + npush[-1]).astype(jnp.int32), jnp.maximum(ref, 0)


@jax.jit
def free_slot_blocks(cache: PagedKVCache, mask) -> PagedKVCache:
    """Drop every masked slot's hold on its blocks and clear its
    table/pos/cursor — ONE small dispatch per retirement step, batched over
    however many slots finished together.  A block returns to the free
    stack only when its refcount hits zero, so prompt-prefix blocks shared
    with other slots (or pinned by the retrieval cache) survive the
    retirement."""
    table = cache.table
    p = cache.free.shape[0]
    valid = (mask[:, None] & (table >= 0)).reshape(-1)
    ids = jnp.where(valid, table.reshape(-1), p)
    drops = jnp.zeros((p,), jnp.int32).at[ids].add(1, mode="drop")
    free, n_free, ref = _release_refs(
        cache.free, cache.n_free, cache.ref, drops
    )
    return dataclasses.replace(
        cache,
        free=free,
        n_free=n_free,
        ref=ref,
        table=jnp.where(mask[:, None], -1, table),
        pos=jnp.where(mask[:, None], -1, cache.pos),
        cursor=jnp.where(mask, 0, cache.cursor),
    )


@jax.jit
def acquire_blocks(cache: PagedKVCache, ids) -> PagedKVCache:
    """Add one hold per listed pool block (``ids`` int32, -1 entries
    ignored) — the retrieval-cache pin / pending-share side of the
    refcount protocol."""
    p = cache.free.shape[0]
    ref = cache.ref.at[jnp.where(ids >= 0, ids, p)].add(1, mode="drop")
    return dataclasses.replace(cache, ref=ref)


@jax.jit
def release_blocks(cache: PagedKVCache, ids) -> PagedKVCache:
    """Drop one hold per listed pool block (``ids`` int32, -1 entries
    ignored), pushing blocks that hit refcount zero back onto the free
    stack — the eviction side of :func:`acquire_blocks`."""
    p = cache.free.shape[0]
    drops = jnp.zeros((p,), jnp.int32).at[
        jnp.where(ids >= 0, ids, p)
    ].add(1, mode="drop")
    free, n_free, ref = _release_refs(
        cache.free, cache.n_free, cache.ref, drops
    )
    return dataclasses.replace(cache, free=free, n_free=n_free, ref=ref)


@functools.partial(jax.jit, static_argnames=("block_size",))
def adopt_prefix_blocks(cache: PagedKVCache, cur_tok, mask, src_table,
                        length, tail_src, first, block_size: int):
    """Map an already-prefilled prompt's pool blocks into each masked
    slot's table instead of re-running prefill.

    For slot b with ``mask[b]``: alias the ``length[b] // bs`` full leading
    blocks from ``src_table[b]`` (the holders' refcounts were bumped by the
    engine before this dispatch — the slot takes those holds over), and
    when the prompt ends mid-block (``tail_src[b] >= 0`` names the donor's
    partial tail block) pop a fresh block, copy the tail block's K/V rows
    into it, and point the table at the copy — copy-on-write at the first
    divergent write position, done eagerly because the very next decode
    write for this slot lands inside that block.  Rows past the prompt ride
    along in the copy but carry ``pos == -1`` until overwritten, so the
    masked attention never sees them.  The one-dispatch hold the engine
    took on each copied source block is dropped here (pushing it back if
    the donor entry was released mid-flight).

    ``pos``/``cursor`` pin to the prompt length and ``cur_tok`` takes
    ``first`` (the donor prefill's recorded argmax), so decode proceeds
    exactly as if this slot had been admitted through the prefill path —
    greedy decode only reads KV, and the aliased rows are bitwise the
    donor's, so outputs are bitwise identical to unshared admission.
    """
    bs = block_size
    b, sc = cache.pos.shape
    p_rows = cache.k.shape[1]
    p = cache.free.shape[0]
    m = cache.table.shape[1]
    nfull = jnp.where(mask, length // bs, 0)
    has_tail = mask & (tail_src >= 0)
    need = has_tail.astype(jnp.int32)
    offs = (jnp.cumsum(need) - need).astype(jnp.int32)
    src_i = jnp.clip(cache.n_free - 1 - offs, 0, p - 1)
    fresh = cache.free[src_i]  # (B,) popped tail copies (garbage where ~take)
    n_free = (cache.n_free - jnp.sum(need)).astype(jnp.int32)
    ref = cache.ref.at[jnp.where(has_tail, fresh, p)].set(1, mode="drop")
    # drop the engine's one-dispatch hold on each copied source block
    drops = jnp.zeros((p,), jnp.int32).at[
        jnp.where(has_tail, tail_src, p)
    ].add(1, mode="drop")
    free, n_free, ref = _release_refs(cache.free, n_free, ref, drops)
    cols = jnp.arange(m, dtype=jnp.int32)[None, :]
    t = jnp.where(cols < nfull[:, None], src_table, -1)
    t = jnp.where((cols == nfull[:, None]) & has_tail[:, None],
                  fresh[:, None], t)
    table = jnp.where(mask[:, None], t, cache.table)
    # COW row copy: all bs rows of each tail block, batched over slots
    off = jnp.arange(bs, dtype=jnp.int32)[None, :]
    srows = (jnp.clip(tail_src, 0, p - 1) * bs)[:, None] + off  # (B, bs)
    drows = jnp.where(has_tail[:, None], fresh[:, None] * bs + off,
                      p_rows).reshape(-1)

    def cpy(pool):
        if pool is None:
            return None
        return pool.at[:, drows].set(pool[:, srows.reshape(-1)], mode="drop")

    spos = jnp.arange(sc, dtype=jnp.int32)[None, :]
    pos_new = jnp.where(spos < length[:, None], spos, -1)
    new_cache = PagedKVCache(
        k=cpy(cache.k),
        v=cpy(cache.v),
        pos=jnp.where(mask[:, None], pos_new, cache.pos),
        cursor=jnp.where(mask, length.astype(jnp.int32), cache.cursor),
        table=table,
        free=free,
        n_free=n_free,
        ref=ref,
        k_scale=cpy(cache.k_scale),
        v_scale=cpy(cache.v_scale),
    )
    return new_cache, jnp.where(mask, first, cur_tok)


def paged_decode_step(params, cache: PagedKVCache, token, live,
                      cfg: TransformerConfig, block_size: int):
    """One decode step over the paged pool — same logical semantics (and
    bitwise-identical outputs for live slots) as :func:`decode_step` on a
    contiguous arena.

    The (B, Sc) per-slot view that the attention consumes is gathered from
    the pool through the block table
    (:func:`repro.models.transformer.attention.paged_decode_attention`);
    rows under unallocated blocks carry ``pos == -1`` and the masked
    softmax zeroes them exactly, so the attention math cannot tell the two
    layouts apart.  ``live`` (B,) gates allocation and writes: a dead
    slot's cursor drifts between admissions exactly as it does on the
    contiguous arena, but it never pops a free block or scatters a row.
    """
    b = token.shape[0]
    sc = cache.pos.shape[1]
    p_rows = cache.k.shape[1]
    bs = block_size
    m = cache.table.shape[1]
    cur = cache.cursor  # (B,) position of the token being processed
    # allocate the block holding position `cur` (at most 1 new per step)
    target = jnp.where(live, cur // bs + 1, 0)
    table, n_free, ref = alloc_blocks(
        cache.table, cache.free, cache.n_free, cache.ref, target, live, 1
    )
    rows = block_rows(table, bs)  # (B, Sc)
    ent = jnp.take_along_axis(
        table, jnp.clip(cur // bs, 0, m - 1)[:, None], axis=1
    )[:, 0]
    ok_w = live & (ent >= 0) & (cur < sc)
    # out-of-range destination == dropped write: dead/over-arena slots
    # scatter nowhere, deterministically
    wrow = jnp.where(ok_w, ent * bs + cur % bs, p_rows)
    slot_mask = (jnp.arange(sc, dtype=jnp.int32)[None, :] == cur[:, None]) \
        & live[:, None]  # live slots never wrap: cur < sc by retirement
    x = params["embed"][token][:, None]  # (B, 1, D)
    quant = cfg.kv_quant

    def body(x, inputs):
        p, kc, vc, ks, vs = inputs  # kc/vc (P, KV, dh) — this layer's pool
        xn = rms_norm(x, p["ln1"], cfg.norm_eps)
        q, k, v = _attn_proj(p, xn, cfg)
        q = attn.rope(q, cur[:, None], cfg.rope_theta)
        k = attn.rope(k, cur[:, None], cfg.rope_theta)
        if quant:
            kq, ksc = _quant_rows(k)
            vq, vsc = _quant_rows(v)
            kc = kc.at[wrow].set(kq[:, 0], mode="drop")
            vc = vc.at[wrow].set(vq[:, 0], mode="drop")
            ks = ks.at[wrow].set(ksc[:, 0], mode="drop")
            vs = vs.at[wrow].set(vsc[:, 0], mode="drop")
        else:
            kc = kc.at[wrow].set(k[:, 0], mode="drop")
            vc = vc.at[wrow].set(v[:, 0], mode="drop")
        pos = jnp.where(slot_mask, cur[:, None], cache.pos)
        o = attn.paged_decode_attention(
            q, kc, vc, rows, pos, cur, cfg.sliding_window,
            k_scale=ks, v_scale=vs,
        )
        x = x + (o.reshape(b, 1, -1) @ p["wo"]).astype(x.dtype)
        xn = rms_norm(x, p["ln2"], cfg.norm_eps)
        if cfg.moe is None:
            y = (jax.nn.silu(xn @ p["w1"]) * (xn @ p["w3"])) @ p["w2"]
        else:
            y, _ = moe_ffn(p["moe"], xn.reshape(b, -1), cfg.moe)
            y = y[:, None]
        return x + y.astype(x.dtype), (kc, vc, ks, vs)

    xs = (params["layers"], cache.k, cache.v, cache.k_scale, cache.v_scale)
    if cfg.scan_layers:
        x, (kc, vc, ks, vs) = jax.lax.scan(body, x, xs)
    else:  # unrolled (cost-analysis variants)
        outs = []
        for i in range(cfg.n_layers):
            sl = jax.tree.map(lambda a: a[i], xs)
            x, o_i = body(x, sl)
            outs.append(o_i)
        cols = list(zip(*outs))
        kc, vc = jnp.stack(cols[0]), jnp.stack(cols[1])
        ks = jnp.stack(cols[2]) if quant else None
        vs = jnp.stack(cols[3]) if quant else None
    new_pos = jnp.where(slot_mask, cur[:, None], cache.pos)
    new_cache = PagedKVCache(k=kc, v=vc, pos=new_pos, cursor=cur + 1,
                             table=table, free=cache.free, n_free=n_free,
                             ref=ref, k_scale=ks, v_scale=vs)
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    logits = x[:, 0].astype(jnp.float32) @ params["head"].astype(jnp.float32)
    return logits, new_cache


@functools.partial(jax.jit, static_argnames=("cfg", "block_size"))
def paged_serve_step(params, cache: PagedKVCache, token, live,
                     cfg: TransformerConfig, block_size: int):
    """Greedy paged decode step — :func:`serve_step` over the block pool."""
    logits, cache = paged_decode_step(params, cache, token, live, cfg,
                                      block_size)
    return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache


def paged_verify_window(params, cache: PagedKVCache, tokens, live,
                        cfg: TransformerConfig, block_size: int):
    """:func:`verify_window` over the paged pool: allocate the blocks the
    W-token window crosses, scatter all W rows, score every position under
    the same per-position visibility mask.  Values written and the gathered
    per-slot view are identical to the contiguous merge, so greedy outputs
    are bitwise identical.  Returns (greedy (B, W), cache) with an
    UNCHANGED cursor — :func:`paged_verify_step` advances it by the
    accepted count, leaving rejected rows in place exactly like the
    contiguous arena (their ``pos`` exceeds later query positions until
    overwritten)."""
    b, w = tokens.shape
    sc = cache.pos.shape[1]
    p_rows = cache.k.shape[1]
    bs = block_size
    m = cache.table.shape[1]
    cur = cache.cursor  # (B,)
    positions = cur[:, None] + jnp.arange(w, dtype=jnp.int32)[None, :]
    writable = (positions < sc) & live[:, None]
    # a W-window starting anywhere inside a block spans at most
    # ceil(W/bs) + 1 blocks, so the allocator's static bound stays tiny
    hi = jnp.minimum(cur + w, sc)
    target = jnp.where(live, (hi + bs - 1) // bs, 0)
    max_new = min(m, (w + bs - 1) // bs + 1)
    table, n_free, ref = alloc_blocks(
        cache.table, cache.free, cache.n_free, cache.ref, target, live,
        max_new
    )
    rows = block_rows(table, bs)  # (B, Sc)
    ent = jnp.take_along_axis(
        table, jnp.clip(positions // bs, 0, m - 1), axis=1
    )  # (B, W)
    wrows = jnp.where(writable & (ent >= 0),
                      ent * bs + positions % bs, p_rows).reshape(-1)  # (B*W,)
    slot_mask = (jnp.arange(sc, dtype=jnp.int32)[None, None, :]
                 == jnp.clip(positions, 0, sc - 1)[..., None]) \
        & writable[..., None]  # (B, W, Sc)
    x = params["embed"][tokens]  # (B, W, D)
    new_pos = cache.pos
    for i in range(w):
        new_pos = jnp.where(slot_mask[:, i], positions[:, i:i + 1], new_pos)
    quant = cfg.kv_quant

    def body(x, inputs):
        p, kc, vc, ks, vs = inputs  # kc/vc (P, KV, dh)
        xn = rms_norm(x, p["ln1"], cfg.norm_eps)
        q, k, v = _attn_proj(p, xn, cfg)
        q = attn.rope(q, positions, cfg.rope_theta)
        k = attn.rope(k, positions, cfg.rope_theta)
        if quant:
            kq, ksc = _quant_rows(k)
            vq, vsc = _quant_rows(v)
            kc = kc.at[wrows].set(kq.reshape(b * w, -1, kq.shape[-1]),
                                  mode="drop")
            vc = vc.at[wrows].set(vq.reshape(b * w, -1, vq.shape[-1]),
                                  mode="drop")
            ks = ks.at[wrows].set(ksc.reshape(b * w, -1), mode="drop")
            vs = vs.at[wrows].set(vsc.reshape(b * w, -1), mode="drop")
        else:
            kc = kc.at[wrows].set(k.reshape(b * w, -1, k.shape[-1]),
                                  mode="drop")
            vc = vc.at[wrows].set(v.reshape(b * w, -1, v.shape[-1]),
                                  mode="drop")
        o = attn.paged_verify_attention(
            q, kc, vc, rows, new_pos, positions, cfg.sliding_window,
            k_scale=ks, v_scale=vs,
        )
        x = x + (o.reshape(b, w, -1) @ p["wo"]).astype(x.dtype)
        xn = rms_norm(x, p["ln2"], cfg.norm_eps)
        if cfg.moe is None:
            y = (jax.nn.silu(xn @ p["w1"]) * (xn @ p["w3"])) @ p["w2"]
        else:
            y, _ = moe_ffn(p["moe"], xn.reshape(b * w, -1), cfg.moe)
            y = y.reshape(b, w, -1)
        return x + y.astype(x.dtype), (kc, vc, ks, vs)

    xs = (params["layers"], cache.k, cache.v, cache.k_scale, cache.v_scale)
    if cfg.scan_layers:
        x, (kc, vc, ks, vs) = jax.lax.scan(body, x, xs)
    else:  # unrolled (cost-analysis variants)
        outs = []
        for i in range(cfg.n_layers):
            sl = jax.tree.map(lambda a: a[i], xs)
            x, o_i = body(x, sl)
            outs.append(o_i)
        cols = list(zip(*outs))
        kc, vc = jnp.stack(cols[0]), jnp.stack(cols[1])
        ks = jnp.stack(cols[2]) if quant else None
        vs = jnp.stack(cols[3]) if quant else None
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    logits = x.astype(jnp.float32) @ params["head"].astype(jnp.float32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # (B, W)
    new_cache = PagedKVCache(k=kc, v=vc, pos=new_pos, cursor=cache.cursor,
                             table=table, free=cache.free, n_free=n_free,
                             ref=ref, k_scale=ks, v_scale=vs)
    return greedy, new_cache


@functools.partial(jax.jit, static_argnames=("cfg", "eos_id", "block_size"))
def paged_verify_step(params, cache: PagedKVCache, tokens, room, live,
                      cfg: TransformerConfig, eos_id=None, *,
                      block_size: int):
    """:func:`verify_step` over the paged pool: verify W fed tokens, accept
    the greedy-matching prefix (same :func:`_accept_prefix` arithmetic, so
    acceptance is bitwise identical), advance the cursor past it."""
    b, w = tokens.shape
    greedy, cache = paged_verify_window(params, cache, tokens, live, cfg,
                                        block_size)
    accepted, cur_tok = _accept_prefix(greedy, tokens, room, w, eos_id)
    cache = dataclasses.replace(cache, cursor=cache.cursor + accepted)
    return greedy, accepted, cur_tok, cache


# --------------------------------------------------------------------------
# latent attention (DeepSeek-V2): MLA, leading dense layers, held-share MoE
# --------------------------------------------------------------------------
# Parameter layout: ``params["layers"]`` stacks the layers after the leading
# dense ones (MoE when ``cfg.moe`` is set), ``params["dense"]`` the
# ``cfg.moe.dense_layers`` leading ones; each layer has ``ln1``, ``ln2``,
# ``wq`` (D, H * (nope + rope)), ``wkv_a`` (D, rank + rope), ``kv_norm``
# (rank,), ``wkv_b`` (rank, H * (nope + v)), ``wo`` (H * v, D), and either
# ``w1``/``w3``/``w2`` (the dense FFN) or ``moe`` (see moe.moe_held).
def _contiguous_only(cfg: TransformerConfig, what: str) -> None:
    if cfg.mla is not None:
        raise ValueError(
            f"{what} does not carry a latent KV cache; config {cfg.name!r} "
            f"uses latent attention (MLA) and is served on the contiguous "
            f"arena with one-token decode")


def _mla_segments(params, cfg: TransformerConfig) -> list:
    """(stacked layers, dense FFN?) in depth order."""
    n_dense = cfg.moe.dense_layers if cfg.moe is not None else 0
    segs = [(params["dense"], True)] if n_dense else []
    return segs + [(params["layers"], cfg.moe is None)]


def _mla_rope(x, positions, cfg: TransformerConfig):
    m = cfg.mla
    inv = attn.yarn_inv_freq(m.rope_dim, cfg.rope_theta, m.yarn_factor,
                             m.yarn_original_max, m.yarn_beta_fast,
                             m.yarn_beta_slow)
    return attn.rope_freqs(x, positions, inv, m.rope_mscale)


def _mla_proj(p, xn, positions, cfg: TransformerConfig):
    """(q_nope (B, S, H, nope), RoPE'd q_pe (B, S, H, rope), normalised
    latent c (B, S, rank), RoPE'd shared key k_pe (B, S, rope))."""
    m = cfg.mla
    b, s, _ = xn.shape
    q = (xn @ p["wq"]).reshape(b, s, cfg.n_heads, m.qk_dim)
    kv = xn @ p["wkv_a"]
    c = rms_norm(kv[..., :m.kv_rank], p["kv_norm"], cfg.norm_eps)
    k_pe = _mla_rope(kv[..., None, m.kv_rank:], positions, cfg)[:, :, 0]
    q_pe = _mla_rope(q[..., m.nope_dim:], positions, cfg)
    return q[..., :m.nope_dim], q_pe, c, k_pe


def _mla_ffn(p, xn, cfg: TransformerConfig, dense: bool):
    """xn (T, D) -> (y (T, D), aux, held-expert load or None)."""
    if dense:
        return swiglu(xn, p["w1"], p["w3"], p["w2"]), jnp.zeros(
            (), jnp.float32), None
    return moe_held(p["moe"], xn, cfg.moe)


def _mla_layer(x, p, cfg: TransformerConfig, positions, dense: bool):
    """A whole-sequence layer with expanded attention: kv_b projects every
    position's latent to per-head keys and values.  Returns (x, aux,
    (c, k_pe)), the latter the rows the latent cache keeps."""
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.n_heads
    xn = rms_norm(x, p["ln1"], cfg.norm_eps)
    with jax.named_scope("mla_attention"):
        q_nope, q_pe, c, k_pe = _mla_proj(p, xn, positions, cfg)
        kv = (c @ p["wkv_b"]).reshape(b, s, h, m.nope_dim + m.v_dim)
        q = jnp.concatenate([q_nope, q_pe], axis=-1)
        k = jnp.concatenate(
            [kv[..., :m.nope_dim],
             jnp.broadcast_to(k_pe[:, :, None], (b, s, h, m.rope_dim))],
            axis=-1)
        v = kv[..., m.nope_dim:]
        if s <= max(cfg.q_chunk, 256):
            o = attn.dense_attention(q, k, v, window=cfg.sliding_window,
                                     scale=m.softmax_scale)
        else:
            o = attn.chunked_attention(
                q, k, v, window=cfg.sliding_window, q_chunk=cfg.q_chunk,
                kv_chunk=cfg.kv_chunk, scale=m.softmax_scale)
        x = x + (o.reshape(b, s, h * m.v_dim) @ p["wo"]).astype(x.dtype)
    xn = rms_norm(x, p["ln2"], cfg.norm_eps)
    y, aux, _ = _mla_ffn(p, xn.reshape(b * s, -1), cfg, dense)
    return x + y.reshape(b, s, -1).astype(x.dtype), aux, (c, k_pe)


def _mla_backbone(params, tokens, cfg: TransformerConfig):
    x = params["embed"][tokens]
    positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)[None, :]
    aux = jnp.zeros((), jnp.float32)
    for stack, dense in _mla_segments(params, cfg):
        def body(carry, p, dense=dense):
            x, aux = carry
            x, a, _ = _mla_layer(x, p, cfg, positions, dense)
            return (x, aux + a), None

        fn = jax.checkpoint(body) if cfg.remat else body
        (x, aux), _ = jax.lax.scan(fn, (x, aux), stack)
    return rms_norm(x, params["ln_f"], cfg.norm_eps), aux


def _mla_rows(cache_len: int) -> int:
    """Rows of a latent arena of ``cache_len`` positions: rounded up to the
    TPU's sublane tile of 8.  Otherwise the device tiles the arena over
    (slot, rank) to avoid padding, and every layer's rows are copied to
    another layout before they are attended.  The rows past ``cache_len``
    stay empty (``pos == -1``): the engine retires a slot at
    ``cache_len``."""
    return -(-cache_len // 8) * 8


def _mla_init_cache(cfg: TransformerConfig, batch: int, cache_len: int):
    if cfg.kv_quant:
        raise ValueError("the latent cache has no int8 mode")
    m = cfg.mla
    dtype = jnp.dtype(cfg.dtype)
    rows = _mla_rows(cache_len)
    lead = (cfg.n_layers, batch, rows)
    return KVCache(
        k=jnp.zeros(lead + (m.kv_rank,), dtype),
        v=jnp.zeros(lead + (m.rope_dim,), dtype),
        pos=jnp.full((batch, rows), -1, jnp.int32),
        cursor=jnp.zeros((batch,), jnp.int32),
        **_route_counters(cfg),
    )


def _route_counters(cfg: TransformerConfig) -> dict:
    if cfg.moe is None:
        return {}
    return {"routed": jnp.zeros((cfg.moe.held,), jnp.int32),
            "max_load": jnp.zeros((), jnp.int32)}


def _mla_prefill(params, tokens, true_len, cfg: TransformerConfig,
                 cache_len: int):
    b, s = tokens.shape
    assert s <= cache_len
    x = params["embed"][tokens]
    positions = jnp.arange(s, dtype=jnp.int32)[None, :]
    cs, pes = [], []
    for stack, dense in _mla_segments(params, cfg):
        def body(x, p, dense=dense):
            x, _, rows = _mla_layer(x, p, cfg, positions, dense)
            return x, rows

        x, (c, pe) = jax.lax.scan(body, x, stack)
        cs.append(c)
        pes.append(pe)
    rows = _mla_rows(cache_len)
    pad = ((0, 0), (0, 0), (0, rows - s), (0, 0))
    kc = jnp.pad(jnp.concatenate(cs), pad)
    pc = jnp.pad(jnp.concatenate(pes), pad)
    slot_pos = jnp.arange(rows, dtype=jnp.int32)[None, :]
    pos = jnp.where(slot_pos < true_len[:, None], slot_pos, -1)
    cache = KVCache(k=kc, v=pc, pos=pos, cursor=true_len.astype(jnp.int32))
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    last = jnp.take_along_axis(
        x, jnp.maximum(true_len - 1, 0)[:, None, None].astype(jnp.int32), axis=1
    )
    logits = last.astype(jnp.float32) @ params["head"].astype(jnp.float32)
    return logits[:, 0], cache


def _mla_absorbed(p, q_nope, q_pe, c_rows, pe_rows, pos, cur,
                  cfg: TransformerConfig):
    """Decode attention with kv_b absorbed: its key part folds into the
    query, its value part into the output, so the step attends over the
    cached latent rows themselves.  q_nope (B, H, nope), q_pe (B, H, rope),
    c_rows (B, Sc, rank), pe_rows (B, Sc, rope) -> (B, H * v)."""
    m = cfg.mla
    b, h = q_nope.shape[:2]
    wkv_b = p["wkv_b"].reshape(m.kv_rank, h, m.nope_dim + m.v_dim)
    q_lat = jnp.einsum("bhn,rhn->bhr", q_nope, wkv_b[..., :m.nope_dim],
                       preferred_element_type=jnp.float32)
    o_lat = attn.latent_decode_attention(
        q_lat.astype(c_rows.dtype), q_pe, c_rows, pe_rows, pos, cur,
        m.softmax_scale, cfg.sliding_window)
    o = jnp.einsum("bhr,rhv->bhv", o_lat.astype(c_rows.dtype),
                   wkv_b[..., m.nope_dim:], preferred_element_type=jnp.float32)
    return o.astype(c_rows.dtype).reshape(b, h * m.v_dim)


def _mla_decode_step(params, cache: KVCache, token, cfg: TransformerConfig):
    """One decode step over the latent cache.  The arena rides in the layer
    loop's carry, so each layer writes only its B new rows in place before
    it attends, and the leading dense layers need no second arena to be
    joined to."""
    b = token.shape[0]
    sc = cache.k.shape[2]
    cur = cache.cursor
    slot = cur % sc
    bidx = jnp.arange(b)
    pos = jnp.where(jnp.arange(sc, dtype=jnp.int32)[None, :] == slot[:, None],
                    cur[:, None], cache.pos)
    x = params["embed"][token][:, None]  # (B, 1, D)

    def layer(carry, p, i, dense):
        x, kc, pc, routed, top = carry
        xn = rms_norm(x, p["ln1"], cfg.norm_eps)
        with jax.named_scope("mla_attention"):
            q_nope, q_pe, c, k_pe = _mla_proj(p, xn, cur[:, None], cfg)
            kc = kc.at[i, bidx, slot].set(c[:, 0])
            pc = pc.at[i, bidx, slot].set(k_pe[:, 0])
            o = _mla_absorbed(p, q_nope[:, 0], q_pe[:, 0], kc[i], pc[i], pos,
                              cur, cfg)
            x = x + (o[:, None] @ p["wo"]).astype(x.dtype)
        xn = rms_norm(x, p["ln2"], cfg.norm_eps)
        y, _, load = _mla_ffn(p, xn.reshape(b, -1), cfg, dense)
        if load is not None:
            routed = routed + load
            top = jnp.maximum(top, jnp.max(load))
        return (x + y[:, None].astype(x.dtype), kc, pc, routed, top)

    held = cfg.moe.held if cfg.moe is not None else 0
    routed = cache.routed if cache.routed is not None else jnp.zeros(
        (held,), jnp.int32)
    carry = (x, cache.k, cache.v, routed, jnp.zeros((), jnp.int32))
    first = 0
    for stack, dense in _mla_segments(params, cfg):
        n = jax.tree.leaves(stack)[0].shape[0]
        idx = jnp.arange(first, first + n, dtype=jnp.int32)

        def body(carry, xs, dense=dense):
            return layer(carry, xs[0], xs[1], dense), None

        carry, _ = jax.lax.scan(body, carry, (stack, idx))
        first += n
    x, kc, pc, routed, top = carry
    counters = {}
    if cfg.moe is not None:
        prev = cache.max_load if cache.max_load is not None else top
        counters = {"routed": routed, "max_load": jnp.maximum(prev, top)}
    new_cache = KVCache(k=kc, v=pc, pos=pos, cursor=cur + 1, **counters)
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    logits = x[:, 0].astype(jnp.float32) @ params["head"].astype(jnp.float32)
    return logits, new_cache
