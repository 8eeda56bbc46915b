"""Queue wait (layer: admission): median over requests due in the traced
slice of the program's own stamps, retrieval dispatched (or answered from
the cache) minus entered the pending queue, on the engine's clock (an
unserved request counts as infinite).  A program without the stamps reads
nothing."""
from bench.harness import quantile


def read(run):
    recs = run.traced_recs()
    if run.loop != "open" or not recs:
        return None
    waits = []
    for r in recs:
        sub = getattr(r.req, "submitted_at", None)
        launched = getattr(r.req, "launched_at", None)
        if not r.ok:
            waits.append(float("inf"))
        elif sub is None or launched is None:
            return None
        else:
            waits.append((launched - sub) * 1e3)
    return quantile(waits, 0.50)
