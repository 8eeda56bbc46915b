"""The Llama block: RMSNorm, rotary embeddings, grouped-query causal
attention with an optional sliding window, gated SiLU MLP, untied output
head.  Hugging Face names it ``LlamaForCausalLM``; DeepSeek-LLM uses it.

A model family file exports six names, which the harness finds by the
configuration's ``model["architectures"][0]``:

* :func:`program_config` -- the program's config type for the block;
* :func:`make_params` -- random weights from ``--seed``, on the device;
* :func:`hparams`, :func:`lm_logits` -- the plain float32 reference and, with
  ``quant=True``, its float8 control;
* :func:`prefill_flops`, :func:`decode_flops` -- the served work's
  operations, from shapes alone.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import HI, linear
from bench.weights import seed_key


# --------------------------------------------------------------------------
# the system under test
# --------------------------------------------------------------------------
def program_config(model: dict, name: str):
    """The program's config type, filled from the configuration file."""
    from repro.models.transformer.config import TransformerConfig

    eps = model.get("rms_norm_eps", model.get("norm_epsilon"))
    return TransformerConfig(
        name=name, n_layers=int(model["num_hidden_layers"]),
        d_model=int(model["hidden_size"]),
        n_heads=int(model["num_attention_heads"]),
        n_kv_heads=int(model["num_key_value_heads"]),
        d_head=int(model["head_dim"]), d_ff=int(model["intermediate_size"]),
        vocab=int(model["vocab_size"]), rope_theta=float(model["rope_theta"]),
        sliding_window=model.get("sliding_window"), norm_eps=float(eps),
        dtype=str(model["torch_dtype"]),
    )


# --------------------------------------------------------------------------
# weights: one jitted call builds every leaf in the dtype the model is
# served in, layer by layer inside ``lax.map`` so that no full-depth float32
# transient exists.  The layout is the one the program's decoder takes
# (leaves stacked on a leading layer axis); the reference reads the same
# arrays.
# --------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("dims",))
def _make(key, dims: tuple):
    n_layers, d, h, kv, dh, dff, vocab, dtype = dims
    dtype = jnp.dtype(dtype)

    def nrm(k, shape, scale):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)

    def layer(k):
        ks = jax.random.split(k, 7)
        return {
            "ln1": jnp.ones((d,), jnp.float32),
            "ln2": jnp.ones((d,), jnp.float32),
            "wq": nrm(ks[0], (d, h * dh), d ** -0.5),
            "wk": nrm(ks[1], (d, kv * dh), d ** -0.5),
            "wv": nrm(ks[2], (d, kv * dh), d ** -0.5),
            "wo": nrm(ks[3], (h * dh, d), (h * dh) ** -0.5),
            "w1": nrm(ks[4], (d, dff), d ** -0.5),
            "w3": nrm(ks[5], (d, dff), d ** -0.5),
            "w2": nrm(ks[6], (dff, d), dff ** -0.5),
        }

    k_layers, k_embed, k_head = jax.random.split(key, 3)
    layers = jax.lax.map(layer, jax.random.split(k_layers, n_layers))
    return {
        "embed": nrm(k_embed, (vocab, d), 1.0),
        "layers": layers,
        "ln_f": jnp.ones((d,), jnp.float32),
        "head": nrm(k_head, (d, vocab), d ** -0.5),
    }


def model_dims(model: dict) -> tuple:
    """The static sizes of :func:`make_params` from a configuration's
    ``model`` block (Hugging Face key names)."""
    return (int(model["num_hidden_layers"]), int(model["hidden_size"]),
            int(model["num_attention_heads"]),
            int(model["num_key_value_heads"]), int(model["head_dim"]),
            int(model["intermediate_size"]), int(model["vocab_size"]),
            str(model["torch_dtype"]))


def make_params(model: dict, seed: int) -> dict:
    return _make(seed_key(seed), model_dims(model))


# --------------------------------------------------------------------------
# the plain reference: float32 at ``highest`` matmul precision, written from
# the block's equations
# --------------------------------------------------------------------------
def hparams(model: dict) -> tuple:
    """Static hyper-parameters of :func:`lm_logits` from a configuration's
    ``model`` block."""
    eps = model.get("rms_norm_eps", model.get("norm_epsilon"))
    return (int(model["num_attention_heads"]),
            int(model["num_key_value_heads"]), int(model["head_dim"]),
            float(model["rope_theta"]), model.get("sliding_window"),
            float(eps))


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x (S, H, dh): rotate the two halves of each head by position."""
    s, _, dh = x.shape
    half = dh // 2
    inv = theta ** (-np.arange(half, dtype=np.float64) * 2.0 / dh)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * jnp.asarray(
        inv, jnp.float32)[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("hp", "quant"))
def lm_logits(params, tokens, hp: tuple, quant: bool = False):
    """tokens (S,) int32 -> (S, V) float32 logits of the next token."""
    n_heads, n_kv, dh, theta, window, eps = hp
    s = tokens.shape[0]
    rep = n_heads // n_kv
    i = jnp.arange(s)[:, None]
    j = jnp.arange(s)[None, :]
    allowed = j <= i
    if window is not None:
        allowed &= (i - j) < window
    emb = params["embed"].astype(jnp.float32)
    x = emb[tokens]

    def layer(x, p):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
        h = _rms(x, p["ln1"], eps)
        q = _rope(linear(h, p["wq"], quant).reshape(s, n_heads, dh), theta)
        k = _rope(linear(h, p["wk"], quant).reshape(s, n_kv, dh), theta)
        v = linear(h, p["wv"], quant).reshape(s, n_kv, dh)
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
        sc = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / np.sqrt(dh)
        sc = jnp.where(allowed[None], sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        o = jnp.einsum("hqk,khd->qhd", pr, v, precision=HI).reshape(s, -1)
        x = x + linear(o, p["wo"], quant)
        h = _rms(x, p["ln2"], eps)
        g = jax.nn.silu(linear(h, p["w1"], quant)) * linear(h, p["w3"], quant)
        return x + linear(g, p["w2"], quant), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = _rms(x, params["ln_f"].astype(jnp.float32), eps)
    return linear(x, params["head"].astype(jnp.float32), quant)


# --------------------------------------------------------------------------
# operations of the served work.  Counts are of the algorithm, not of the
# program's padding.
# --------------------------------------------------------------------------
def matmul_params(model: dict) -> int:
    """Weights that every token multiplies (all layers' projections and MLP,
    and the output head; the embedding is a lookup)."""
    n_l, d, h, kv, dh, dff, vocab, _ = model_dims(model)
    per_layer = d * h * dh + 2 * d * kv * dh + h * dh * d + 3 * d * dff
    return n_l * per_layer + d * vocab


def token_flops(model: dict) -> int:
    """2 x N_active: one multiply-add per weight per token."""
    return 2 * matmul_params(model)


def attn_flops(model: dict, keys: int) -> int:
    """Attention of one query token over ``keys`` positions, all layers:
    q.k and p.v are 2 * keys * head_dim flops per head each."""
    n_l, _, h, _, dh, _, _, _ = model_dims(model)
    window = model.get("sliding_window")
    if window:
        keys = min(keys, int(window))
    return 4 * n_l * h * dh * keys


def prefill_flops(model: dict, length: int) -> int:
    """A causal prefill of ``length`` tokens (token i sees i + 1 keys)."""
    n_l, _, h, _, dh, _, _, _ = model_dims(model)
    window = model.get("sliding_window")
    if window and length > int(window):
        w = int(window)
        keys = w * (w + 1) // 2 + (length - w) * w
    else:
        keys = length * (length + 1) // 2
    return length * token_flops(model) + 4 * n_l * h * dh * keys


def decode_flops(model: dict, position: int) -> int:
    """One decoded token whose input sits at ``position`` (it attends to
    ``position + 1`` keys, itself included)."""
    return token_flops(model) + attn_flops(model, position + 1)
