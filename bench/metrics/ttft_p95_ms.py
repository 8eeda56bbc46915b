"""95th percentile time to first token over every request due in the window
(an unserved request counts as infinite)."""
from bench.harness import quantile


def read(run):
    if run.loop != "open" or not run.recs:
        return None
    return quantile([(r.first_at - r.due) * 1e3 if r.ok else float("inf")
                     for r in run.recs], 0.95)
