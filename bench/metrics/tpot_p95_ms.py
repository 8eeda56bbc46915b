"""95th percentile over served requests of the mean gap between output
tokens: (last token - first token) / (tokens - 1)."""
from bench.harness import quantile


def read(run):
    gaps = [(r.done_at - r.first_at) * 1e3 / (r.n_tokens - 1)
            for r in run.recs if r.ok and r.n_tokens > 1]
    if run.loop != "open" or not gaps:
        return None
    return quantile(gaps, 0.95)
