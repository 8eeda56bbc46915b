"""Random model weights, made by the benchmark on the device from ``--seed``.

One jitted call builds every leaf in the dtype the model is served in,
layer by layer inside ``lax.map`` so that no full-depth float32 transient
exists.  The layout is the one the program's decoder takes (leaves stacked
on a leading layer axis); the plain reference reads the same arrays.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key from any non-negative seed, including ones past 32 bits."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


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
