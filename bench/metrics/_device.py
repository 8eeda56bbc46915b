"""Shared device-time readers for the per-layer metric files."""
from bench import trace as tr

# device programs of the retrieval layer (core/pipeline.py retrieve_many:
# index, compact BFS, filter)
RETRIEVAL = ("jit_topk_similarity", "jit_bfs_subgraph_compact",
             "jit_similarity_scores", "jit_dynamic_filter")
# the decode-step program of each arena and decode mode (serving/engine.py)
DECODE = ("jit_serve_step", "jit_paged_serve_step", "jit__spec_step",
          "jit__paged_spec_step")


def retrieval_ms_per_q(run):
    if run.trace is None:
        return None
    q = run.counters.get("retrieved_queries", 0)
    lo, hi = run.trace_window
    s, n = tr.module_time(run.trace, RETRIEVAL, lo, hi)
    return s * 1e3 / q if q and n else None


def decode_step_ms(run):
    if run.trace is None:
        return None
    lo, hi = run.trace_window
    s, n = tr.module_time(run.trace, DECODE, lo, hi)
    return s * 1e3 / n if n else None
