"""Fused RAG serving benchmark: batched retrieval+decode vs per-query serial.

Three measured passes over the same synthetic workload (citation graph +
tiny LM), all jit-warm (a warmup wave runs every trace first):

* sequential — one request at a time through a 1-slot fused engine with the
  cache disabled: per-query retrieval dispatch + per-query decode.  This is
  the no-batching deployment the paper argues against.
* fused      — all requests stream through an N-slot ``RAGServeEngine``:
  ONE jitted retrieval per admission wave, one decode step for all slots.
* replay     — the fused workload resubmitted against a warm retrieval
  cache (100% hit rate): index + BFS + filter skipped entirely.

Reports tokens/s per pass, the fused/sequential throughput ratio (target:
>= 2x), and the cold vs cached retrieval-stage time.  CPU container: ratios
are the reproduction target, not absolute times.

    PYTHONPATH=src python -m benchmarks.rag_serving
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    GraphTokenizer, PipelineConfig, RGLPipeline, Vocab, index_from_config,
)
from repro.graph import csr_to_ell, generators
from repro.models.transformer import TransformerConfig, model as tm
from repro.serving import (
    RAGRequest, RAGServeEngine, RetrievalCache, ServingConfig,
)


def _build(n_nodes: int, seed: int = 0, index_kind: str = "brute",
           index_shards: int | None = None):
    g = generators.citation_graph(n_nodes, avg_deg=8, seed=seed)
    ell = csr_to_ell(g)
    emb = jnp.asarray(g.node_feat)
    vocab = Vocab.build(g.node_text)
    tok = GraphTokenizer(vocab, max_len=128, node_budget=8)
    pcfg = PipelineConfig(strategy="bfs", k_seeds=3, max_nodes=16,
                          filter_budget=6, index_kind=index_kind,
                          index_shards=index_shards)
    pipe = RGLPipeline(
        graph=ell, index=index_from_config(emb, pcfg), node_emb=emb,
        tokenizer=tok, node_text=g.node_text, config=pcfg,
    )
    cfg = TransformerConfig(
        name="bench-lm", n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
        d_head=16, d_ff=256, vocab=vocab.size, dtype="float32",
    )
    params = tm.init_params(jax.random.PRNGKey(0), cfg)
    return g, pipe, cfg, params


def _requests(g, emb_np, q_ids, max_new):
    return [
        RAGRequest(
            uid=u, query_emb=emb_np[qi],
            query_text=" ".join(g.node_text[qi].split()[:4]),
            max_new_tokens=max_new,
        )
        for u, qi in enumerate(q_ids)
    ]


def run(n_nodes: int = 2000, n_requests: int = 32, slots: int = 8,
        max_new: int = 24, seed: int = 0, index_kind: str = "brute",
        index_shards: int | None = None) -> dict:
    g, pipe, cfg, params = _build(n_nodes, seed, index_kind, index_shards)
    emb_np = np.asarray(pipe.node_emb)
    rng = np.random.default_rng(seed)
    q_ids = rng.choice(n_nodes, size=n_requests, replace=False)

    def make_engine(n_slots, capacity):
        return RAGServeEngine(
            pipe, params, cfg,
            config=ServingConfig(slots=n_slots, cache_len=192),
            retrieval_cache=RetrievalCache(capacity=capacity),
        )

    # -- warmup: run the full workload once per engine shape so every trace
    # (retrieval batch, each prefill bucket, decode, merge) is compiled before
    # any timed pass
    for n_slots in (1, slots):
        warm = make_engine(n_slots, capacity=0)
        for r in _requests(g, emb_np, q_ids, max_new):
            warm.submit(r)
        warm.run_to_completion()

    # -- sequential per-query baseline (1 slot, no cache) --------------------
    seq = make_engine(1, capacity=0)
    t0 = time.perf_counter()
    seq_toks = 0
    for r in _requests(g, emb_np, q_ids, max_new):
        seq.submit(r)
        done = seq.run_to_completion()
        seq_toks += sum(len(d.out_tokens) for d in done)
    seq_s = time.perf_counter() - t0

    # -- fused batched engine, cold cache ------------------------------------
    fused = make_engine(slots, capacity=n_requests)
    t0 = time.perf_counter()
    for r in _requests(g, emb_np, q_ids, max_new):
        fused.submit(r)
    done = fused.run_to_completion()
    fused_s = time.perf_counter() - t0
    fused_toks = sum(len(d.out_tokens) for d in done)
    cold_retrieval_s = fused.retrieval_seconds
    assert fused.cache_misses == n_requests and fused.cache_hits == 0

    # -- replay: identical queries against the warm cache --------------------
    t0 = time.perf_counter()
    for r in _requests(g, emb_np, q_ids, max_new):
        fused.submit(r)
    done2 = fused.run_to_completion()
    replay_s = time.perf_counter() - t0
    replay_toks = sum(len(d.out_tokens) for d in done2)
    warm_retrieval_s = fused.retrieval_seconds - cold_retrieval_s
    assert fused.cache_hits == n_requests  # 100% hit replay

    return {
        "n_nodes": n_nodes, "index_kind": index_kind,
        "n_requests": n_requests, "slots": slots, "max_new": max_new,
        "seq_s": seq_s, "seq_tok_s": seq_toks / seq_s,
        "fused_s": fused_s, "fused_tok_s": fused_toks / fused_s,
        "throughput_ratio": (fused_toks / fused_s) / (seq_toks / seq_s),
        "replay_s": replay_s, "replay_tok_s": replay_toks / replay_s,
        "cold_retrieval_s": cold_retrieval_s,
        "warm_retrieval_s": warm_retrieval_s,
        "retrieval_speedup": cold_retrieval_s / max(warm_retrieval_s, 1e-9),
        "replay_speedup": fused_s / replay_s,
    }


def write_json(result: dict, path: str = "BENCH_rag_serving.json") -> None:
    with open(path, "w") as f:
        json.dump(result, f, indent=2)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=2000)
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--max_new", type=int, default=24)
    ap.add_argument("--index", default="brute",
                    choices=["brute", "ivf", "sharded", "sharded_ivf"])
    ap.add_argument("--shards", type=int, default=None)
    ap.add_argument("--out", default="BENCH_rag_serving.json")
    args = ap.parse_args()
    r = run(n_nodes=args.nodes, n_requests=args.requests, slots=args.slots,
            max_new=args.max_new, index_kind=args.index,
            index_shards=args.shards)
    print(f"workload: {r['n_requests']} requests x {r['max_new']} new tokens, "
          f"{args.nodes}-node graph, index={r['index_kind']}")
    print(f"sequential (1 slot, no cache): {r['seq_s']:.2f}s "
          f"({r['seq_tok_s']:.1f} tok/s)")
    print(f"fused ({r['slots']} slots, cold cache): {r['fused_s']:.2f}s "
          f"({r['fused_tok_s']:.1f} tok/s)")
    print(f"fused/sequential throughput: {r['throughput_ratio']:.1f}x "
          f"(target >= 2x)")
    print(f"replay (100% cache hits): {r['replay_s']:.2f}s "
          f"({r['replay_tok_s']:.1f} tok/s, {r['replay_speedup']:.2f}x cold)")
    print(f"retrieval stage: cold {r['cold_retrieval_s'] * 1e3:.1f}ms -> "
          f"cached {r['warm_retrieval_s'] * 1e3:.1f}ms "
          f"({r['retrieval_speedup']:.0f}x)")
    write_json(r, args.out)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
