"""Async admission prefetch benchmark: sync vs double-buffered serving.

The sync schedule retrieves at every wave boundary and blocks the decode
arena for the full retrieval latency; the prefetch schedule launches wave
*i+1*'s retrieval while wave *i* decodes and only blocks on whatever decode
didn't hide.  Retrieval cost on the tiny CPU benchmark graph is
microseconds, so the sweep injects controlled per-wave retrieval costs via
:class:`repro.serving.simulate.DelayedRetrieval` — the same force-blocks-
until-ready semantics as JAX async dispatch — at several multiples of the
measured decode-wave time (the regime knob: overlap helps most when
retrieval cost is comparable to a decode wave).  A zero-injection "real"
leg is measured too.

Reports per cost ratio: wall time and tok/s for both schedules, the
end-to-end speedup (target >= 1.3x at ratio 1.0), and the overlap telemetry
(overlap_seconds, hidden_frac) from the prefetch run.

    PYTHONPATH=src python -m benchmarks.async_serving
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
    DelayedRetrieval, RAGRequest, RAGServeEngine, ServingConfig,
)


def _build(n_nodes: int, seed: int = 0):
    g = generators.citation_graph(n_nodes, avg_deg=8, seed=seed)
    ell = csr_to_ell(g)
    emb = jnp.asarray(g.node_feat)
    vocab = Vocab.build(g.node_text)
    tok = GraphTokenizer(vocab, max_len=128, node_budget=8)
    pcfg = PipelineConfig(strategy="bfs", k_seeds=3, max_nodes=16,
                          filter_budget=6)
    pipe = RGLPipeline(
        graph=ell, index=index_from_config(emb, pcfg), node_emb=emb,
        tokenizer=tok, node_text=g.node_text, config=pcfg,
    )
    cfg = TransformerConfig(
        name="async-bench-lm", n_layers=2, d_model=64, n_heads=4,
        n_kv_heads=2, d_head=16, d_ff=256, vocab=vocab.size, dtype="float32",
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


def _measure(pipe_like, g, emb_np, q_ids, params, cfg, *, slots, max_new,
             prefetch):
    eng = RAGServeEngine(pipe_like, params, cfg, config=ServingConfig(
        slots=slots, cache_len=192, prefetch=prefetch))
    t0 = time.perf_counter()
    for r in _requests(g, emb_np, q_ids, max_new):
        eng.submit(r)
    done = eng.run_to_completion()
    wall = time.perf_counter() - t0
    toks = sum(len(d.out_tokens) for d in done)
    return wall, toks, eng.stats()


def run(n_nodes: int = 2000, n_requests: int = 24, slots: int = 4,
        max_new: int = 16, seed: int = 0, repeats: int = 3,
        cost_ratios: tuple = (0.5, 1.0, 2.0)) -> dict:
    g, pipe, cfg, params = _build(n_nodes, seed)
    emb_np = np.asarray(pipe.node_emb)
    rng = np.random.default_rng(seed)
    q_ids = rng.choice(n_nodes, size=n_requests, replace=False)
    n_waves = -(-n_requests // slots)

    # warm every trace (retrieval batch, prefill buckets, decode, merge)
    for pf in (False, True):
        _measure(pipe, g, emb_np, q_ids, params, cfg, slots=slots,
                 max_new=max_new, prefetch=pf)

    # calibrate: decode-wave seconds = median uninjected sync pass
    walls = []
    for _ in range(max(repeats, 2)):
        sync_wall, _, sync_stats = _measure(
            pipe, g, emb_np, q_ids, params, cfg, slots=slots,
            max_new=max_new, prefetch=False,
        )
        walls.append(sync_wall - sync_stats["retrieval_seconds"])
    decode_wave_s = max(float(np.median(walls)), 1e-6) / n_waves

    # each leg is measured `repeats` times with sync/prefetch interleaved so
    # host-load drift hits both schedules equally; medians are reported
    results = []
    for ratio in (0.0,) + tuple(cost_ratios):
        cost = ratio * decode_wave_s
        src = pipe if ratio == 0.0 else DelayedRetrieval(pipe, cost_s=cost)
        s_runs, p_runs = [], []
        for _ in range(repeats):
            s_runs.append(_measure(
                src, g, emb_np, q_ids, params, cfg, slots=slots,
                max_new=max_new, prefetch=False,
            ))
            p_runs.append(_measure(
                src, g, emb_np, q_ids, params, cfg, slots=slots,
                max_new=max_new, prefetch=True,
            ))
        s_wall = float(np.median([r[0] for r in s_runs]))
        p_wall = float(np.median([r[0] for r in p_runs]))
        s_toks, p_toks = s_runs[0][1], p_runs[0][1]
        p_stats = p_runs[int(np.argsort([r[0] for r in p_runs])[len(p_runs)
                                                                // 2])][2]
        results.append({
            "cost_ratio": ratio,
            "retrieval_cost_s": cost,
            "sync_s": s_wall, "sync_tok_s": s_toks / s_wall,
            "prefetch_s": p_wall, "prefetch_tok_s": p_toks / p_wall,
            "speedup": s_wall / p_wall,
            "prefetch_waves": p_stats["prefetch_waves"],
            "overlap_seconds": p_stats["overlap_seconds"],
            "hidden_frac": p_stats["hidden_frac"],
        })

    return {
        "n_nodes": n_nodes, "n_requests": n_requests, "slots": slots,
        "max_new": max_new, "n_waves": n_waves,
        "decode_wave_s": decode_wave_s,
        "results": results,
    }


def write_json(report: dict, path: str = "BENCH_async_serving.json") -> None:
    with open(path, "w") as f:
        json.dump(report, f, indent=2)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=2000)
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max_new", type=int, default=16)
    ap.add_argument("--out", default="BENCH_async_serving.json")
    args = ap.parse_args()
    rep = run(n_nodes=args.nodes, n_requests=args.requests, slots=args.slots,
              max_new=args.max_new)
    print(f"workload: {rep['n_requests']} requests x {rep['max_new']} new "
          f"tokens, {rep['slots']} slots, {rep['n_waves']} waves, "
          f"decode wave ~{rep['decode_wave_s'] * 1e3:.1f}ms")
    for r in rep["results"]:
        label = "real" if r["cost_ratio"] == 0.0 else f"{r['cost_ratio']:.1f}x"
        print(f"retrieval cost {label:>5}: sync {r['sync_tok_s']:.1f} tok/s "
              f"-> prefetch {r['prefetch_tok_s']:.1f} tok/s "
              f"({r['speedup']:.2f}x, hidden_frac={r['hidden_frac']:.2f})")
    write_json(rep, args.out)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
