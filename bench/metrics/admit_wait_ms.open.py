"""Admission wait (layer: admission): median time from a request's due time
to the end of the engine step that set its prompt (retrieval, filter and
linearization done), over requests due in the traced slice."""
from bench.harness import quantile


def read(run):
    recs = run.traced_recs()
    if run.loop != "open" or not recs:
        return None
    return quantile([(r.prompt_at - r.due) * 1e3 if r.ok else float("inf")
                     for r in recs], 0.50)
