"""The DeepSeek-V2 block: multi-head latent attention (MLA) without a query
low-rank projection, YaRN-scaled rotary embeddings, leading dense SwiGLU
layers, then MoE layers with softmax top-k routing over the routed experts
and shared experts that every token runs.  Hugging Face names it
``DeepseekV2ForCausalLM``; DeepSeek-V2-Lite uses it.

One chip holds a share of an expert-parallel deployment: the model block's
``n_routed_experts`` experts of every MoE layer, numbered from
``first_held`` among the ``n_routed_experts_published`` the router scores.
The router keeps its published width and experts per token; what the absent
experts would add is left out, in the program and in the reference alike.

The six names the harness finds by ``model["architectures"][0]`` (see
``LlamaForCausalLM.py``), and :func:`decode_bytes`, the least bytes one
decode step moves.

Departures of the reference from Hugging Face's code, neither of which
changes the model's equations: the rope dims of the query and the shared
key are rotated as two halves (the program's layout), where Hugging Face
first de-interleaves them (``view(d/2, 2).transpose``), a fixed
permutation of the rope columns of ``q_proj`` and ``kv_a_proj_with_mqa``
that random weights do not see; and each matrix is stored input-major
(``x @ w``).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import HI, linear
from bench.weights import seed_key

# settings the program implements; any other value is refused
SUPPORTED = {"scoring_func": "softmax", "topk_method": "greedy",
             "n_group": 1, "topk_group": 1, "routed_scaling_factor": 1,
             "moe_layer_freq": 1, "q_lora_rank": None, "attention_bias": False,
             "hidden_act": "silu", "tie_word_embeddings": False}


def _check(model: dict) -> None:
    for k, want in SUPPORTED.items():
        if k in model and model[k] != want:
            raise ValueError(f"{k}={model[k]!r} is not implemented "
                             f"(only {want!r})")
    rs = model.get("rope_scaling")
    if rs is not None and rs.get("type") != "yarn":
        raise ValueError(f"rope_scaling type {rs.get('type')!r} is not "
                         f"implemented (only 'yarn')")


def _yarn(model: dict) -> tuple:
    """(factor, original_max, beta_fast, beta_slow, mscale, mscale_all_dim);
    factor 1 without rope scaling."""
    rs = model.get("rope_scaling") or {}
    if not rs:
        return (1.0, 4096, 32.0, 1.0, 1.0, 0.0)
    return (float(rs["factor"]), int(rs["original_max_position_embeddings"]),
            float(rs["beta_fast"]), float(rs["beta_slow"]),
            float(rs["mscale"]), float(rs["mscale_all_dim"]))


def _experts(model: dict) -> tuple:
    """(router width, held, first held)."""
    held = int(model["n_routed_experts"])
    return (int(model.get("n_routed_experts_published", held)), held,
            int(model.get("first_held", 0)))


# --------------------------------------------------------------------------
# the system under test
# --------------------------------------------------------------------------
def program_config(model: dict, name: str):
    """The program's config type, filled from the configuration file."""
    from repro.models.transformer.config import (
        MLAConfig, MoEConfig, TransformerConfig,
    )

    _check(model)
    factor, orig, b_fast, b_slow, ms, ms_all = _yarn(model)
    n_exp, held, first = _experts(model)
    moe_f = int(model["moe_intermediate_size"])
    h = int(model["num_attention_heads"])
    return TransformerConfig(
        name=name, n_layers=int(model["num_hidden_layers"]),
        d_model=int(model["hidden_size"]), n_heads=h, n_kv_heads=h,
        d_head=int(model["v_head_dim"]),
        d_ff=int(model["intermediate_size"]),
        vocab=int(model["vocab_size"]), rope_theta=float(model["rope_theta"]),
        sliding_window=model.get("sliding_window"),
        norm_eps=float(model["rms_norm_eps"]),
        dtype=str(model["torch_dtype"]),
        mla=MLAConfig(
            kv_rank=int(model["kv_lora_rank"]),
            rope_dim=int(model["qk_rope_head_dim"]),
            nope_dim=int(model["qk_nope_head_dim"]),
            v_dim=int(model["v_head_dim"]), yarn_factor=factor,
            yarn_original_max=orig, yarn_beta_fast=b_fast,
            yarn_beta_slow=b_slow, yarn_mscale=ms, yarn_mscale_all_dim=ms_all),
        moe=MoEConfig(
            n_experts=n_exp, top_k=int(model["num_experts_per_tok"]),
            d_ff=moe_f, capacity_factor=None, n_held=held, first_held=first,
            d_shared=int(model["n_shared_experts"]) * moe_f,
            norm_topk=bool(model["norm_topk_prob"]),
            dense_layers=int(model["first_k_dense_replace"])),
    )


# --------------------------------------------------------------------------
# weights: one jitted call, layer by layer inside ``lax.map``, in the
# program's layout (``repro.models.transformer.model``, latent-attention
# section); only the held experts are made, each from its own expert id
# --------------------------------------------------------------------------
def model_dims(model: dict) -> tuple:
    n_exp, held, first = _experts(model)
    return (int(model["num_hidden_layers"]),
            int(model["first_k_dense_replace"]), int(model["hidden_size"]),
            int(model["num_attention_heads"]), int(model["qk_nope_head_dim"]),
            int(model["qk_rope_head_dim"]), int(model["v_head_dim"]),
            int(model["kv_lora_rank"]), int(model["intermediate_size"]),
            int(model["moe_intermediate_size"]),
            int(model["n_shared_experts"]), n_exp, held, first,
            int(model["vocab_size"]), str(model["torch_dtype"]))


@functools.partial(jax.jit, static_argnames=("dims",))
def _make(key, dims: tuple):
    (n_l, n_dense, d, h, nope, rope, vd, rank, dff, f, n_shared, n_exp,
     held, first, vocab, dtype) = dims
    dtype = jnp.dtype(dtype)

    def nrm(k, shape, scale):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)

    def attention(k):
        ks = jax.random.split(k, 4)
        return {
            "ln1": jnp.ones((d,), jnp.float32),
            "ln2": jnp.ones((d,), jnp.float32),
            "wq": nrm(ks[0], (d, h * (nope + rope)), d ** -0.5),
            "wkv_a": nrm(ks[1], (d, rank + rope), d ** -0.5),
            "kv_norm": jnp.ones((rank,), jnp.float32),
            "wkv_b": nrm(ks[2], (rank, h * (nope + vd)), rank ** -0.5),
            "wo": nrm(ks[3], (h * vd, d), (h * vd) ** -0.5),
        }

    def dense_layer(k):
        ka, k1, k3, k2 = jax.random.split(k, 4)
        return dict(attention(ka), w1=nrm(k1, (d, dff), d ** -0.5),
                    w3=nrm(k3, (d, dff), d ** -0.5),
                    w2=nrm(k2, (dff, d), dff ** -0.5))

    def expert(k):
        k1, k3, k2 = jax.random.split(k, 3)
        return (nrm(k1, (d, f), d ** -0.5), nrm(k3, (d, f), d ** -0.5),
                nrm(k2, (f, d), f ** -0.5))

    def moe_layer(k):
        ka, kr, ke, ks = jax.random.split(k, 4)
        ids = jnp.arange(first, first + held)
        w1, w3, w2 = jax.vmap(
            lambda i: expert(jax.random.fold_in(ke, i)))(ids)
        fs = n_shared * f
        s1, s3, s2 = jax.random.split(ks, 3)
        moe = {"router": nrm(kr, (d, n_exp), d ** -0.5),
               "w1": w1, "w3": w3, "w2": w2}
        if fs:
            moe.update(s1=nrm(s1, (d, fs), d ** -0.5),
                       s3=nrm(s3, (d, fs), d ** -0.5),
                       s2=nrm(s2, (fs, d), fs ** -0.5))
        return dict(attention(ka), moe=moe)

    k_dense, k_moe, k_embed, k_head = jax.random.split(key, 4)
    out = {
        "embed": nrm(k_embed, (vocab, d), 1.0),
        "layers": jax.lax.map(moe_layer,
                              jax.random.split(k_moe, n_l - n_dense)),
        "ln_f": jnp.ones((d,), jnp.float32),
        "head": nrm(k_head, (d, vocab), d ** -0.5),
    }
    if n_dense:
        out["dense"] = jax.lax.map(dense_layer,
                                   jax.random.split(k_dense, n_dense))
    return out


def make_params(model: dict, seed: int) -> dict:
    return _make(seed_key(seed), model_dims(model))


# --------------------------------------------------------------------------
# the plain reference: float32 at ``highest`` matmul precision, written from
# the published equations, with the expanded (not absorbed) attention
# --------------------------------------------------------------------------
def hparams(model: dict) -> tuple:
    """Static hyper-parameters of :func:`lm_logits`."""
    n_exp, held, first = _experts(model)
    return (int(model["num_attention_heads"]), int(model["qk_nope_head_dim"]),
            int(model["qk_rope_head_dim"]), int(model["v_head_dim"]),
            int(model["kv_lora_rank"]), float(model["rope_theta"]),
            model.get("sliding_window"), float(model["rms_norm_eps"]),
            _yarn(model),
            (n_exp, int(model["num_experts_per_tok"]), first, held,
             bool(model["norm_topk_prob"])))


def yarn_mscale(scale: float, mscale: float) -> float:
    """YaRN: 0.1 * mscale * ln(scale) + 1 (1 where nothing is scaled)."""
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(dim: int, theta: float, yarn: tuple) -> np.ndarray:
    """YaRN's rotary frequencies: the original ones (extrapolation) on
    dims that turn more than ``beta_fast`` times over the original context,
    the interpolated ones (divided by ``factor``) on dims that turn fewer
    than ``beta_slow`` times, and a linear ramp between."""
    factor, orig, b_fast, b_slow = yarn[:4]
    pos = np.arange(0, dim, 2, dtype=np.float64) / dim
    extrapolated = 1.0 / theta ** pos
    if factor <= 1:
        return extrapolated
    interpolated = 1.0 / (factor * theta ** pos)

    def dim_of(rotations):  # the dim that turns ``rotations`` times
        return (dim * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(dim_of(b_fast)), 0)
    high = min(math.ceil(dim_of(b_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp  # 1 = extrapolate
    return interpolated * (1.0 - keep) + extrapolated * keep


def softmax_scale(qk_dim: int, yarn: tuple) -> float:
    factor, _, _, _, _, ms_all = yarn
    m = yarn_mscale(factor, ms_all) if ms_all else 1.0
    return qk_dim ** -0.5 * m * m


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, inv_freq, factor):
    """x (S, H, d): rotate the two halves of each head by position; the
    cos and sin tables carry YaRN's factor."""
    s, _, d = x.shape
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * jnp.asarray(
        inv_freq, jnp.float32)[None, :]
    cos = (jnp.cos(ang) * factor)[:, None, :]
    sin = (jnp.sin(ang) * factor)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(h, w1, w3, w2, quant):
    g = jax.nn.silu(linear(h, w1, quant)) * linear(h, w3, quant)
    return linear(g, w2, quant)


def moe_layer(m: dict, h, moe_hp: tuple, quant: bool = False):
    """The MoE FFN of one layer in float32: softmax over every routed
    expert, greedy top-k, the held experts' gated outputs summed, the
    shared experts added once.  m holds the held experts' weights."""
    n_exp, k, first, held, norm = moe_hp
    probs = jax.nn.softmax(linear(h, m["router"], quant), axis=-1)
    top_p, top_i = jax.lax.top_k(probs, k)
    if norm:
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    y = jnp.zeros_like(h)
    for j in range(held):
        g = jnp.sum(jnp.where(top_i == first + j, top_p, 0.0), -1)
        y = y + g[:, None] * _swiglu(h, m["w1"][j], m["w3"][j], m["w2"][j],
                                     quant)
    if "s1" in m:
        y = y + _swiglu(h, m["s1"], m["s3"], m["s2"], quant)
    return y


@functools.partial(jax.jit, static_argnames=("hp", "quant"))
def lm_logits(params, tokens, hp: tuple, quant: bool = False):
    """tokens (S,) int32 -> (S, V) float32 logits of the next token."""
    n_heads, nope, rope, vd, rank, theta, window, eps, yarn, moe_hp = hp
    s = tokens.shape[0]
    inv = yarn_inv_freq(rope, theta, yarn)
    rope_factor = (yarn_mscale(yarn[0], yarn[4])
                   / yarn_mscale(yarn[0], yarn[5]))
    scale = softmax_scale(nope + rope, yarn)
    i = jnp.arange(s)[:, None]
    j = jnp.arange(s)[None, :]
    allowed = j <= i
    if window is not None:
        allowed &= (i - j) < window
    x = params["embed"].astype(jnp.float32)[tokens]

    def attention(x, p):
        h = _rms(x, p["ln1"], eps)
        q = linear(h, p["wq"], quant).reshape(s, n_heads, nope + rope)
        kv_a = linear(h, p["wkv_a"], quant)
        c = _rms(kv_a[:, :rank], p["kv_norm"], eps)
        k_pe = _rope(kv_a[:, None, rank:], inv, rope_factor)
        kv = linear(c, p["wkv_b"], quant).reshape(s, n_heads, nope + vd)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_pe, (s, n_heads, rope))], -1)
        q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], inv,
                                                  rope_factor)], -1)
        sc = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) * scale
        sc = jnp.where(allowed[None], sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        o = jnp.einsum("hqk,khd->qhd", pr, kv[..., nope:],
                       precision=HI).reshape(s, -1)
        return x + linear(o, p["wo"], quant)

    def dense_layer(x, p):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
        x = attention(x, p)
        h = _rms(x, p["ln2"], eps)
        return x + _swiglu(h, p["w1"], p["w3"], p["w2"], quant), None

    def moe_block(x, p):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
        x = attention(x, p)
        return x + moe_layer(p["moe"], _rms(x, p["ln2"], eps), moe_hp,
                             quant), None

    if "dense" in params:
        x, _ = jax.lax.scan(dense_layer, x, params["dense"])
    x, _ = jax.lax.scan(moe_block, x, params["layers"])
    x = _rms(x, params["ln_f"].astype(jnp.float32), eps)
    return linear(x, params["head"].astype(jnp.float32), quant)


# --------------------------------------------------------------------------
# operations and bytes of the served work, from shapes alone.  Counts are
# of the published (expanded) equations, each token's keys and values
# projected once; the held experts are counted at their expectation per
# token, top_k * held / routed (0.75 for 6 of 64 with 8 held), as if the
# router spread tokens evenly.
# --------------------------------------------------------------------------
def _widths(model: dict) -> dict:
    (n_l, n_dense, d, h, nope, rope, vd, rank, dff, f, n_shared, n_exp,
     held, _, vocab, dtype) = model_dims(model)
    return dict(n_l=n_l, n_dense=n_dense, d=d, h=h, nope=nope, rope=rope,
                vd=vd, rank=rank, dff=dff, f=f, fs=n_shared * f, n_exp=n_exp,
                held=held, k=int(model["num_experts_per_tok"]), vocab=vocab,
                itemsize=jnp.dtype(dtype).itemsize)


def _attn_params(w: dict) -> int:
    return (w["d"] * w["h"] * (w["nope"] + w["rope"])
            + w["d"] * (w["rank"] + w["rope"])
            + w["rank"] * w["h"] * (w["nope"] + w["vd"])
            + w["h"] * w["vd"] * w["d"])


def token_flops(model: dict) -> int:
    """2 x the multiply-adds of one token's projections, FFNs and head."""
    w = _widths(model)
    dense = _attn_params(w) + 3 * w["d"] * w["dff"]
    # held experts at their expectation: k * held / routed of them a token
    held_mads = 3 * w["d"] * w["f"] * w["k"] * w["held"] // w["n_exp"]
    moe = (_attn_params(w) + w["d"] * w["n_exp"] + 3 * w["d"] * w["fs"]
           + held_mads)
    n_moe = w["n_l"] - w["n_dense"]
    return 2 * (w["n_dense"] * dense + n_moe * moe + w["d"] * w["vocab"])


_COSTS: dict = {}


def _costs(model: dict) -> tuple:
    """(token flops, attention flops per key, window) of a model block,
    kept per block object: the harness counts every served token."""
    hit = _COSTS.get(id(model))
    if hit is None or hit[0] is not model:
        w = _widths(model)
        per_key = 2 * w["n_l"] * w["h"] * (w["nope"] + w["rope"] + w["vd"])
        window = model.get("sliding_window")
        hit = (model, token_flops(model), per_key,
               int(window) if window else None)
        _COSTS[id(model)] = hit
    return hit[1:]


def attn_flops(model: dict, keys: int) -> int:
    """One query over ``keys`` positions, all layers: q.k over nope + rope
    dims and p.v over v dims, per head."""
    _, per_key, window = _costs(model)
    return per_key * (min(keys, window) if window else keys)


def prefill_flops(model: dict, length: int) -> int:
    """A causal prefill of ``length`` tokens (token i sees i + 1 keys)."""
    tok, per_key, w = _costs(model)
    if w and length > w:
        keys = w * (w + 1) // 2 + (length - w) * w
    else:
        keys = length * (length + 1) // 2
    return length * tok + per_key * keys


def decode_flops(model: dict, position: int) -> int:
    """One decoded token whose input sits at ``position``."""
    return _costs(model)[0] + attn_flops(model, position + 1)


def matrix_params(model: dict) -> int:
    """Weights of every matrix a decode step multiplies: each layer's MLA
    projections, the dense FFNs, each MoE layer's router, shared experts
    and held experts, and the output head."""
    w = _widths(model)
    per_moe = (w["d"] * w["n_exp"] + 3 * w["d"] * w["fs"]
               + w["held"] * 3 * w["d"] * w["f"])
    return (w["n_l"] * _attn_params(w) + w["n_dense"] * 3 * w["d"] * w["dff"]
            + (w["n_l"] - w["n_dense"]) * per_moe + w["d"] * w["vocab"])


def cache_row_bytes(model: dict) -> int:
    """Latent cache bytes of one position: every layer's normalised latent
    and rotated shared key."""
    w = _widths(model)
    return w["n_l"] * (w["rank"] + w["rope"]) * w["itemsize"]


def decode_bytes(model: dict, live: float, keys: float) -> float:
    """Least HBM bytes of one decode step: one read of every matrix it
    multiplies, and of the latent rows of ``live`` sequences attending to
    ``keys`` positions each."""
    return (matrix_params(model) * _widths(model)["itemsize"]
            + live * keys * cache_row_bytes(model))
