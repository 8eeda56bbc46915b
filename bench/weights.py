"""Random model weights are made by the benchmark on the device from
``--seed``, by each model family's ``make_params``
(``bench/models/<architecture>.py``); this is the key they start from.
"""
from __future__ import annotations

import jax


def seed_key(seed: int):
    """A PRNG key from any non-negative seed, including ones past 32 bits."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
