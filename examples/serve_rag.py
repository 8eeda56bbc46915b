"""Serve a small RAG-LM end to end with the fused engine.

Raw (query embedding, query text) requests go through the whole RGL stack —
index -> seed retrieval -> subgraph -> dynamic filter -> tokenization ->
batched prefill -> continuous-batching decode — inside one RAGServeEngine.
Retrieval is batched across each admission wave and cached (LRU on quantized
query embeddings), so repeated queries skip index + BFS entirely.

    PYTHONPATH=src python examples/serve_rag.py --requests 12
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    BruteIndex, GraphTokenizer, PipelineConfig, RGLPipeline, Vocab,
)
from repro.models.transformer import TransformerConfig, model as tm
from repro.graph import csr_to_ell, generators
from repro.serving import RAGRequest, RAGServeEngine, ServingConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max_new", type=int, default=16)
    ap.add_argument("--repeat", type=int, default=0,
                    help="extra duplicate requests (exercise the cache)")
    args = ap.parse_args()

    g = generators.citation_graph(1000, avg_deg=8, seed=0)
    ell = csr_to_ell(g)
    emb = jnp.asarray(g.node_feat)
    vocab = Vocab.build(g.node_text)
    tok = GraphTokenizer(vocab, max_len=160, node_budget=10)
    pipe = RGLPipeline(
        graph=ell, index=BruteIndex.build(emb), node_emb=emb, tokenizer=tok,
        node_text=g.node_text,
        config=PipelineConfig(strategy="bfs", k_seeds=3, max_nodes=16,
                              filter_budget=6),
    )

    cfg = TransformerConfig(
        name="serve-lm", n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
        d_head=16, d_ff=256, vocab=vocab.size, dtype="float32",
    )
    params = tm.init_params(jax.random.PRNGKey(0), cfg)
    eng = RAGServeEngine(pipe, params, cfg,
                         config=ServingConfig(slots=args.slots, cache_len=224))

    rng = np.random.default_rng(0)
    q_ids = rng.choice(1000, size=args.requests, replace=False)
    emb_np = np.asarray(emb)
    t0 = time.time()
    for u, qi in enumerate(q_ids):
        eng.submit(RAGRequest(
            uid=int(qi), query_emb=emb_np[qi],
            query_text=" ".join(g.node_text[qi].split()[:4]),
            max_new_tokens=args.max_new,
        ))
    for _ in range(args.repeat):  # duplicates — served from the cache
        qi = q_ids[int(rng.integers(len(q_ids)))]
        eng.submit(RAGRequest(
            uid=10_000 + int(qi), query_emb=emb_np[qi],
            query_text=" ".join(g.node_text[qi].split()[:4]),
            max_new_tokens=args.max_new,
        ))
    done = eng.run_to_completion()
    dt = time.time() - t0
    toks = sum(len(r.out_tokens) for r in done)
    s = eng.stats()
    print(f"served {len(done)} requests / {toks} tokens in {dt:.1f}s "
          f"({toks / dt:.1f} tok/s, {args.slots} slots)")
    print(f"retrieval: {s['retrieval_batches']} batched calls for "
          f"{s['retrieved_queries']} queries in {s['retrieval_seconds']:.2f}s; "
          f"cache {s['hits']} hits / {s['misses']} misses")
    id2w = {v + 6: k for k, v in vocab.word_to_id.items()}
    sample = done[0]
    words = " ".join(id2w.get(t, "?") for t in sample.out_tokens[:10])
    print(f"request {sample.uid} -> {words} ...")


if __name__ == "__main__":
    main()
