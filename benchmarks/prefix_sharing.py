"""Prefix-shared paged KV benchmark: repeated-query serving.

The regime RGL's retrieval cache already wins on — few unique queries
repeated across many requests (hot entities, repeated questions) — still
pays full prefill compute and private KV pool blocks per request for a
prompt head that is byte-identical across the repeats.  This benchmark
prices what block-level prefix sharing recovers, on the same repeated-query
workload shape as ``BENCH_rag_serving.json``:

* **admission latency** — wall time inside the engine's admission path
  (``admit_seconds``) and prefilled prompt rows; a shared admission aliases
  the donor's blocks and copies at most one tail block instead of running
  the full prefill dispatch.
* **peak pool residency** — ``pool_high_water_blocks``; concurrent repeats
  of one prompt alias a single pinned block set instead of each holding a
  private copy.

Outputs are bitwise identical with sharing on and off (enforced here and
by the parity tests); only the cost changes.

    PYTHONPATH=src python -m benchmarks.prefix_sharing
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
from repro.serving import RAGRequest, RAGServeEngine, ServingConfig

CACHE_LEN = 192
BLOCK = 16


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
        name="share-bench-lm", n_layers=2, d_model=64, n_heads=4,
        n_kv_heads=2, d_head=16, d_ff=256, vocab=vocab.size, dtype="float32",
    )
    params = tm.init_params(jax.random.PRNGKey(0), cfg)
    return g, pipe, cfg, params


def _measure(pipe, params, cfg, g, seed_ids, q_ids, *, slots, share,
             max_new):
    """Two-phase workload: a seeding pass admits each unique query once
    (sharing pins its prefilled blocks), then the repeated storm — where
    share-on admissions alias the pinned blocks and allocate only a tail."""
    eng = RAGServeEngine(pipe, params, cfg, config=ServingConfig(
        slots=slots, cache_len=CACHE_LEN, paged_kv=True, kv_block_size=BLOCK,
        prefix_share=share))
    emb_np = np.asarray(pipe.node_emb).astype(np.float32)

    def req(u, qi):
        return RAGRequest(
            uid=u, query_emb=emb_np[qi],
            query_text=" ".join(g.node_text[qi].split()[:4]),
            max_new_tokens=max_new,
        )

    t0 = time.perf_counter()
    done = []
    for u, qi in enumerate(seed_ids):
        eng.submit(req(u, qi))
    done.extend(eng.drain())
    for u, qi in enumerate(q_ids):
        eng.submit(req(len(seed_ids) + u, qi))
    done.extend(eng.drain())
    wall = time.perf_counter() - t0
    outs = {r.uid: list(r.out_tokens) for r in done if r.done}
    return wall, outs, eng.engine.decode_stats()


def run(n_nodes: int = 2000, n_requests: int = 32, n_unique: int = 2,
        slots: int = 4, max_new: int = 8, seed: int = 0,
        repeats: int = 3) -> dict:
    """Repeated-query workload: ``n_unique`` distinct queries round-robined
    over ``n_requests`` requests — after the first wave, every admission's
    prompt is a byte-identical repeat whose prefilled blocks are pinned."""
    g, pipe, cfg, params = _build(n_nodes, seed)
    rng = np.random.default_rng(seed)
    uniq = rng.choice(n_nodes, size=n_unique, replace=False)
    seed_ids = [int(q) for q in uniq]
    q_ids = [int(uniq[u % n_unique]) for u in range(n_requests)]

    # warm both traces
    for share in (False, True):
        _measure(pipe, params, cfg, g, seed_ids, q_ids[:slots], slots=slots,
                 share=share, max_new=max_new)

    runs = {False: [], True: []}
    stats = {}
    ref_outs = None
    for _ in range(max(repeats, 2)):
        for share in (False, True):
            wall, outs, ds = _measure(pipe, params, cfg, g, seed_ids, q_ids,
                                      slots=slots, share=share,
                                      max_new=max_new)
            if ref_outs is None:
                ref_outs = outs
            assert outs == ref_outs, "sharing changed outputs"
            runs[share].append((wall, ds))
            stats[share] = ds

    def med(share, key):
        return float(np.median([ds[key] for _, ds in runs[share]]))

    off, on = stats[False], stats[True]
    admit_off = med(False, "admit_seconds")
    admit_on = med(True, "admit_seconds")
    hw_off = med(False, "pool_high_water_blocks")
    hw_on = med(True, "pool_high_water_blocks")
    return {
        "n_nodes": n_nodes, "n_requests": n_requests, "n_unique": n_unique,
        "slots": slots, "max_new": max_new, "cache_len": CACHE_LEN,
        "block_size": BLOCK,
        "wall_off_s": float(np.median([w for w, _ in runs[False]])),
        "wall_on_s": float(np.median([w for w, _ in runs[True]])),
        "admission": {
            "admit_off_s": admit_off,
            "admit_on_s": admit_on,
            "admit_speedup": admit_off / max(admit_on, 1e-9),
            "prefill_rows_off": int(off["prefill_rows"]),
            "prefill_rows_on": int(on["prefill_rows"]),
            "shared_admits": int(on["kv_shared_admits"]),
            "shared_admit_frac": on["kv_shared_admits"] / n_requests,
            "reused_tokens": int(on["kv_reused_tokens"]),
            "cow_copies": int(on["kv_cow_copies"]),
        },
        "residency": {
            "pool_blocks": int(on["pool_blocks"]),
            "high_water_off_blocks": int(hw_off),
            "high_water_on_blocks": int(hw_on),
            "residency_frac_vs_unshared": hw_on / max(hw_off, 1.0),
            "pins": int(on["kv_pins"]),
            "pinned_blocks_final": int(on["kv_pinned_blocks"]),
        },
    }


def write_json(report: dict, path: str = "BENCH_prefix_sharing.json") -> None:
    with open(path, "w") as f:
        json.dump(report, f, indent=2)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=2000)
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--unique", type=int, default=2)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--out", default="BENCH_prefix_sharing.json")
    args = ap.parse_args()
    rep = run(n_nodes=args.nodes, n_requests=args.requests,
              n_unique=args.unique, slots=args.slots)
    adm, res = rep["admission"], rep["residency"]
    print(f"workload: {rep['n_requests']} requests over {rep['n_unique']} "
          f"unique queries, {rep['slots']} slots")
    print(f"admission: {adm['admit_off_s']:.3f}s -> {adm['admit_on_s']:.3f}s "
          f"({adm['admit_speedup']:.2f}x), prefill rows "
          f"{adm['prefill_rows_off']} -> {adm['prefill_rows_on']}, "
          f"{adm['shared_admits']} shared admits "
          f"({adm['shared_admit_frac'] * 100:.0f}%), "
          f"{adm['reused_tokens']} prompt tokens reused")
    print(f"residency: high water {res['high_water_off_blocks']} -> "
          f"{res['high_water_on_blocks']} blocks "
          f"({res['residency_frac_vs_unshared'] * 100:.0f}% of unshared), "
          f"{res['pins']} pins / {res['pinned_blocks_final']} blocks held")
    write_json(rep, args.out)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
