"""Share of its roofline the topk_sim kernel reaches (layer: kernels): the
least time the chip could take for the similarity scans in the traced slice
(bytes over HBM bandwidth or flops over the bf16 peak, whichever is larger;
the table read bounds it) over the kernel's device time."""
from bench import flops
from bench import trace as tr

# the Pallas kernel's op in the device trace
KERNEL = ("topk_sim",)


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace_window
    t, n = tr.op_time(run.trace, KERNEL, lo, hi)
    q = run.counters.get("retrieved_queries", 0)
    calls = run.counters.get("retrieval_batches", 0)
    if not (t and n and q and calls):
        return None
    c = run.cell.config
    n_nodes, d = c["corpus"]["nodes"], c["corpus"]["feat_dim"]
    k = c["retrieval"]["k_seeds"]
    f, b = flops.topk_sim(q, n_nodes, d, k)
    # the table is read once per call, not once per query
    b += (calls - 1) * 4 * n_nodes * d
    least, _ = flops.roofline_s(f, b, run.peaks)
    return 100.0 * least / t
