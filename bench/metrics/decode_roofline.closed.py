"""Share of its roofline the decode step reaches (layer: model step): the
least time one step could take, the larger of the bytes it must move over
HBM bandwidth (``family.decode_bytes``: every matrix it multiplies once,
and the cached rows of its live sequences) and its operations over the
bf16 peak, over the device time per decode-step call.

``live`` is the decode rows that emitted a token, per step, in the traced
slice: (emitted tokens - prefills) / decode steps; ``keys`` the mean
attended length of the tokens the window's requests decoded.  A family
without ``decode_bytes`` reads nothing."""
from bench import flops
from bench import trace as tr
from bench.metrics._device import DECODE


def mean_keys(recs) -> float:
    """Mean keys attended by the decoded tokens of ``recs``: token t >= 1
    of a request with a P-token prompt sits at position P + t - 1 and
    attends P + t keys (its first token came from prefill)."""
    total = n = 0
    for r in recs:
        p, k = len(r.req.prompt_ids), r.n_tokens - 1
        if k > 0:
            total += k * p + k * (k + 1) // 2
            n += k
    return total / n if n else 0.0


def read(run):
    fam = run.cell.family
    if run.trace is None or not hasattr(fam, "decode_bytes"):
        return None
    c = run.counters
    steps = c.get("decode_steps", 0)
    lo, hi = run.trace_window
    t, calls = tr.module_time(run.trace, DECODE, lo, hi)
    keys = mean_keys(run.recs)
    if not (steps and calls and keys):
        return None
    live = (c.get("emitted_tokens", 0) - c.get("prefills", 0)) / steps
    model = run.cell.config["model"]
    least, _ = flops.roofline_s(
        live * fam.decode_flops(model, round(keys) - 1),
        fam.decode_bytes(model, live, keys), run.peaks)
    return 100.0 * least / (t / calls)
