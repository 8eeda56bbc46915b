"""Median time to first token: from each request's scheduled arrival to the
end of the engine step that produced its first token, over every request
due in the window (an unserved request counts as infinite)."""
from bench.harness import quantile


def ttft_ms(run):
    return [(r.first_at - r.due) * 1e3 if r.ok else float("inf")
            for r in run.recs]


def read(run):
    if run.loop != "open" or not run.recs:
        return None
    return quantile(ttft_ms(run), 0.50)
