"""Device idle share (layer: device): 1 - (union of device-busy intervals)
over the traced slice."""
from bench import trace as tr


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace_window
    return 100.0 * (1.0 - tr.busy_s(run.trace, lo, hi) / ((hi - lo) / 1e9))
