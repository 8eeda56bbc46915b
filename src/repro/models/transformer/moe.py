"""Mixture-of-Experts FFN (GShard/Mixtral-style) with fixed shapes.

TPU-idiomatic dispatch: tokens are sorted by assigned expert (stable argsort),
truncated at per-expert capacity C = cf * T * k / E, batched through an
(E, C, D) x (E, D, F) grouped GEMM, and combined back with gate weights via
segment-sum.  All shapes static; overflow tokens are dropped (standard
capacity-factor semantics) and the auxiliary load-balance loss (Switch) keeps
the router near-uniform.

Sharding: "expert" mode shards the E axis (EP — dispatch becomes all-to-all
under GSPMD); "tp" mode shards the F axis (TP within expert, for E < mesh).

:func:`moe_held` is the dropless layer (``capacity_factor=None``): it
routes over all ``n_experts``, computes only the part of the result that
the experts this chip holds give (one chip's share of an expert-parallel
deployment; the rest would come from the other chips, whose exchange is not
here), and adds the shared experts that every token runs.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.distributed.constraints import shard_hint
from repro.models.transformer.config import MoEConfig


def init_moe_params(key, d_model: int, cfg: MoEConfig, dtype):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    e, f = cfg.n_experts, cfg.d_ff
    s_in = d_model**-0.5
    s_ff = f**-0.5
    return {
        "router": (jax.random.normal(k1, (d_model, e)) * s_in).astype(jnp.float32),
        "w1": (jax.random.normal(k2, (e, d_model, f)) * s_in).astype(dtype),
        "w3": (jax.random.normal(k3, (e, d_model, f)) * s_in).astype(dtype),
        "w2": (jax.random.normal(k4, (e, f, d_model)) * s_ff).astype(dtype),
    }


def moe_ffn(params, x: jnp.ndarray, cfg: MoEConfig):
    """x: (T, D) token-major. Returns (y (T, D), aux_loss scalar).  A
    config without a capacity factor takes the dropless :func:`moe_held`."""
    if cfg.capacity_factor is None:
        y, aux, _ = moe_held(params, x, cfg)
        return y, aux
    t, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    cap = int(cfg.capacity_factor * t * k / e)
    cap = max(8, -(-cap // 8) * 8)  # pad capacity to a multiple of 8

    logits = x.astype(jnp.float32) @ params["router"]  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, top_e = jax.lax.top_k(probs, k)  # (T, k)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    # Switch aux loss: E * sum_e f_e * p_e
    me = jnp.mean(probs, axis=0)
    ce = jnp.zeros((e,), jnp.float32).at[top_e.reshape(-1)].add(1.0) / (t * k)
    aux = e * jnp.sum(me * ce)

    # ---- sort-based dispatch ------------------------------------------------
    flat_e = top_e.reshape(-1)  # (T*k,) expert of each (token, slot)
    flat_tok = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    flat_gate = gate.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)  # (T*k,)
    se = flat_e[order]
    st = flat_tok[order]
    sg = flat_gate[order]
    # rank within expert group = idx - start_of_group
    counts = jnp.zeros((e,), jnp.int32).at[se].add(1)
    starts = jnp.cumsum(counts) - counts  # (E,)
    rank = jnp.arange(t * k, dtype=jnp.int32) - starts[se]
    keep = rank < cap
    slot = jnp.where(keep, se * cap + rank, e * cap)  # sentinel = E*C

    xg = shard_hint(x[st], "dp", None)  # (T*k, D) tokens in sorted order
    xpad = jnp.zeros((e * cap + 1, d), x.dtype).at[slot].add(
        jnp.where(keep[:, None], xg, 0)
    )[: e * cap]
    xe = xpad.reshape(e, cap, d)
    # EP: experts over "model" (dispatch = all-to-all); TP: capacity over dp,
    # d_ff over "model" inside each expert.
    if cfg.shard_mode == "expert":
        xe = shard_hint(xe, "model", None, None)
    else:
        xe = shard_hint(xe, None, "dp", None)

    # ---- grouped GEMM (SwiGLU experts) -------------------------------------
    h = jnp.einsum("ecd,edf->ecf", xe, params["w1"], preferred_element_type=jnp.float32)
    g = jnp.einsum("ecd,edf->ecf", xe, params["w3"], preferred_element_type=jnp.float32)
    if cfg.shard_mode == "expert":
        h = shard_hint(h, "model", None, None)
    else:
        h = shard_hint(h, None, "dp", "model")
    h = (jax.nn.silu(h) * g).astype(x.dtype)
    ye = jnp.einsum("ecf,efd->ecd", h, params["w2"], preferred_element_type=jnp.float32)
    ye = shard_hint(
        ye, *(("model", None, None) if cfg.shard_mode == "expert"
              else (None, "dp", None))
    )

    # ---- combine -------------------------------------------------------------
    yflat = ye.reshape(e * cap, d)
    yg = shard_hint(
        jnp.where(keep[:, None], yflat[jnp.minimum(slot, e * cap - 1)], 0.0),
        "dp", None,
    )
    y = jax.ops.segment_sum(yg * sg[:, None], st, num_segments=t)
    return y.astype(x.dtype), aux


# --------------------------------------------------------------------------
# dropless routing over a held share, with shared experts
# --------------------------------------------------------------------------
# Up to this many tokens the held experts run as one dense pass over every
# token: the pass reads each held expert's weights once, as a sorted grouped
# product would, and its extra multiply-adds (every token through every held
# expert) cost less than that read while T <= peak FLOP/s over HBM bytes/s
# (197e12 / 819e9 = 240 on a TPU v5e).  Decode batches sit below it.
DENSE_MAX_TOKENS = 256
# Above it tokens are sorted by expert and run through a grouped product
# (``lax.ragged_dot``), in chunks of at most this many tokens: the gather of
# a chunk's T * top_k rows and their float32 products are the largest
# intermediates of a prefill wave.
TOKEN_CHUNK = 8192


def token_chunks(t: int) -> int:
    """How many token chunks :func:`moe_held` splits ``t`` tokens into
    (0 = the dense pass)."""
    return 0 if t <= DENSE_MAX_TOKENS else -(-t // TOKEN_CHUNK)


def swiglu(x, w1, w3, w2):
    """Gated SiLU MLP (the dense FFN and the shared experts)."""
    return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2


def _held_dense(params, x, w):
    """Every token through every held expert; ``w`` (T, n_held) float32 is
    each token's gate on each held expert (0 where not routed there)."""
    h1 = jnp.einsum("td,edf->tef", x, params["w1"],
                    preferred_element_type=jnp.float32)
    h3 = jnp.einsum("td,edf->tef", x, params["w3"],
                    preferred_element_type=jnp.float32)
    h = (jax.nn.silu(h1) * h3 * w[..., None]).astype(x.dtype)
    return jnp.einsum("tef,efd->td", h, params["w2"],
                      preferred_element_type=jnp.float32)


def _held_sorted(params, x, local, gate, n_held: int):
    """Token-expert pairs sorted by held expert through a grouped product.
    ``local`` (T, k) is each pair's held-expert index (``n_held`` = not
    held here); pairs not held sort last and are left out."""
    t, k = local.shape
    key = local.reshape(-1)
    order = jnp.argsort(key, stable=True)
    tok = (jnp.arange(t * k, dtype=jnp.int32) // k)[order]
    g = gate.reshape(-1)[order]
    sizes = jnp.zeros((n_held + 1,), jnp.int32).at[key].add(1)[:n_held]
    valid = (jnp.arange(t * k, dtype=jnp.int32) < jnp.sum(sizes))[:, None]
    xs = x[tok]
    h1 = jax.lax.ragged_dot(xs, params["w1"], sizes,
                            preferred_element_type=jnp.float32)
    h3 = jax.lax.ragged_dot(xs, params["w3"], sizes,
                            preferred_element_type=jnp.float32)
    h = jnp.where(valid, jax.nn.silu(h1) * h3 * g[:, None], 0.0)
    yp = jax.lax.ragged_dot(h.astype(x.dtype), params["w2"], sizes,
                            preferred_element_type=jnp.float32)
    return jax.ops.segment_sum(jnp.where(valid, yp, 0.0), tok,
                               num_segments=t)


def moe_held(params, x: jnp.ndarray, cfg: MoEConfig):
    """Dropless top-k routing over all ``cfg.n_experts``; computes the held
    experts' part plus the shared experts.  x: (T, D) token-major.

    Returns (y (T, D), Switch aux loss, load (n_held,) int32: the token-
    slots routed to each held expert)."""
    t, d = x.shape
    e, k, n_held = cfg.n_experts, cfg.top_k, cfg.held
    logits = x.astype(jnp.float32) @ params["router"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, top_e = jax.lax.top_k(probs, k)  # (T, k)
    if cfg.norm_topk:
        gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    me = jnp.mean(probs, axis=0)
    ce = jnp.zeros((e,), jnp.float32).at[top_e.reshape(-1)].add(1.0) / (t * k)
    aux = e * jnp.sum(me * ce)

    local = top_e - cfg.first_held
    local = jnp.where((local >= 0) & (local < n_held), local, n_held)
    onehot = local[..., None] == jnp.arange(n_held, dtype=local.dtype)
    load = jnp.sum(onehot, axis=(0, 1), dtype=jnp.int32)
    with jax.named_scope("moe_experts"):
        n = token_chunks(t)
        if n == 0:
            w = jnp.sum(jnp.where(onehot, gate[..., None], 0.0), axis=1)
            y = _held_dense(params, x, w)
        else:
            c = -(-t // n)
            c = -(-c // 8) * 8  # tokens per chunk, 8-aligned
            pad = n * c - t
            xc = jnp.pad(x, ((0, pad), (0, 0))).reshape(n, c, d)
            lc = jnp.pad(local, ((0, pad), (0, 0)),
                         constant_values=n_held).reshape(n, c, k)
            gc = jnp.pad(gate, ((0, pad), (0, 0))).reshape(n, c, k)
            y = jax.lax.map(
                lambda a: _held_sorted(params, a[0], a[1], a[2], n_held),
                (xc, lc, gc)).reshape(n * c, d)[:t]
        if cfg.d_shared:
            y = y + swiglu(x, params["s1"], params["s3"], params["s2"])
    return y.astype(x.dtype), aux, load
