"""Operations and bytes the served work needs, from shapes alone, and the
table of peaks they are held against.  A decoder's counts belong to its
model family (``bench/models/<architecture>.py``: ``prefill_flops``,
``decode_flops``).

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
