"""Whole-step model FLOP/s utilization (layer: whole step): 2 x N_active
flops per prompt and output token served in the traced slice, plus their
attention at its lengths, over the bf16 peak times the slice."""


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace_window
    f = run.counters.get("useful_flops", 0.0)
    if not f:
        return None
    return 100.0 * f / (run.peaks["bf16_flops_per_s"] * (hi - lo) / 1e9)
