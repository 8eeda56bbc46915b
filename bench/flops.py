"""Operations and bytes the served work needs, from shapes alone, and the
table of peaks they are held against.

Counts are of the algorithm, not of the program's padding: a kernel or a
step that pads its inputs does more work than counted here, which shows as
a lower share of the roofline or of the peak.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}; known: {sorted(table)}")
    return table[device_kind]


def roofline_s(flops: float, nbytes: float, pk: dict):
    """(least seconds, bound) at the chip's bf16 peak and HBM bandwidth."""
    t_c = flops / pk["bf16_flops_per_s"]
    t_m = nbytes / pk["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


# -- kernels -----------------------------------------------------------------
def topk_sim(q: int, n: int, d: int, k: int):
    """Fused similarity + top-k over a float32 table: (flops, bytes).

    Scores are a (q, d) x (d, n) product; the table and the queries are read
    once, and the kernel writes k float32 scores and k int32 ids per query.
    """
    flops = 2 * q * n * d
    nbytes = 4 * n * d + 4 * q * d + 8 * q * k
    return flops, nbytes


# -- the decoder -----------------------------------------------------------
def _dims(model: dict):
    return (int(model["num_hidden_layers"]), int(model["hidden_size"]),
            int(model["num_attention_heads"]),
            int(model["num_key_value_heads"]), int(model["head_dim"]),
            int(model["intermediate_size"]), int(model["vocab_size"]))


def matmul_params(model: dict) -> int:
    """Weights that every token multiplies (all layers' projections and MLP,
    and the output head; the embedding is a lookup)."""
    n_l, d, h, kv, dh, dff, vocab = _dims(model)
    per_layer = d * h * dh + 2 * d * kv * dh + h * dh * d + 3 * d * dff
    return n_l * per_layer + d * vocab


def token_flops(model: dict) -> int:
    """2 x N_active: one multiply-add per weight per token."""
    return 2 * matmul_params(model)


def attn_flops(model: dict, keys: int) -> int:
    """Attention of one query token over ``keys`` positions, all layers:
    q.k and p.v are 2 * keys * head_dim flops per head each."""
    n_l, _, h, _, dh, _, _ = _dims(model)
    window = model.get("sliding_window")
    if window:
        keys = min(keys, int(window))
    return 4 * n_l * h * dh * keys


def prefill_flops(model: dict, length: int) -> int:
    """A causal prefill of ``length`` tokens (token i sees i + 1 keys)."""
    n_l, _, h, _, dh, _, _ = _dims(model)
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
