"""Paged KV + continuous admission benchmark: skewed request mixes.

Two claims are measured over a skewed workload (mostly short chat-style
requests plus a minority of long generations with expensive retrieval
rows — the mixed-traffic regime the contiguous wave scheduler handles
worst):

* **wave vs continuous admission** (both paged, prefetch on) — wave
  admission collects a whole wave's retrieval before admitting any of it
  and holds freed slots until the next wave boundary, so one slow
  retrieval row gates every wave-mate; continuous admission launches one
  retrieval per request and admits whichever is ready the moment a slot
  frees.  Per-row retrieval costs are injected with
  :class:`repro.serving.simulate.DelayedRetrieval`'s ``cost_fn`` (the
  long-generation requests carry the expensive rows), calibrated against
  the measured decode-wave time exactly like ``benchmarks/async_serving``.
* **paged vs contiguous arena** (no injected cost, wave admission) — the
  block-table indirection adds one gather per attention call; this leg
  prices it end-to-end.  The paged run also reports its pool high-water
  mark: with per-request retirement, peak KV block residency tracks live
  tokens, not ``slots * cache_len``.

    PYTHONPATH=src python -m benchmarks.paged_kv
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
        name="paged-bench-lm", n_layers=2, d_model=64, n_heads=4,
        n_kv_heads=2, d_head=16, d_ff=256, vocab=vocab.size, dtype="float32",
    )
    params = tm.init_params(jax.random.PRNGKey(0), cfg)
    return g, pipe, cfg, params


def _skewed_requests(g, emb_np, q_ids, *, short_new, long_new, long_every):
    """Mostly short requests; every ``long_every``-th is a long generation.
    Returns (requests, slow_row_keys) — the long requests' embedding rows
    are the designated expensive retrievals."""
    reqs, slow_keys = [], set()
    for u, qi in enumerate(q_ids):
        is_long = (u % long_every) == long_every - 1
        if is_long:
            slow_keys.add(emb_np[qi].tobytes())
        reqs.append(RAGRequest(
            uid=u, query_emb=emb_np[qi],
            query_text=" ".join(g.node_text[qi].split()[:4]),
            max_new_tokens=long_new if is_long else short_new,
        ))
    return reqs, slow_keys


def _measure(pipe_like, reqs_factory, params, cfg, *, slots, paged,
             admission, prefetch=True):
    eng = RAGServeEngine(pipe_like, params, cfg, config=ServingConfig(
        slots=slots, cache_len=CACHE_LEN, prefetch=prefetch,
        admission=admission, paged_kv=paged,
        kv_block_size=BLOCK if paged else None))
    t0 = time.perf_counter()
    for r in reqs_factory():
        eng.submit(r)
    done = eng.run_to_completion()
    wall = time.perf_counter() - t0
    toks = sum(len(d.out_tokens) for d in done)
    return wall, toks, eng.stats()


def run(n_nodes: int = 2000, n_requests: int = 24, slots: int = 4,
        short_new: int = 6, long_new: int = 48, long_every: int = 4,
        seed: int = 0, repeats: int = 3, slow_cost_ratio: float = 2.0) -> dict:
    g, pipe, cfg, params = _build(n_nodes, seed)
    emb_np = np.asarray(pipe.node_emb).astype(np.float32)
    rng = np.random.default_rng(seed)
    q_ids = rng.choice(n_nodes, size=n_requests, replace=False)
    reqs, slow_keys = _skewed_requests(
        g, emb_np, q_ids, short_new=short_new, long_new=long_new,
        long_every=long_every,
    )

    def factory():
        return [RAGRequest(uid=r.uid, query_emb=r.query_emb,
                           query_text=r.query_text,
                           max_new_tokens=r.max_new_tokens) for r in reqs]

    # warm every trace on both arenas and both admission granularities
    for paged in (False, True):
        for adm in ("wave", "continuous"):
            _measure(pipe, factory, params, cfg, slots=slots, paged=paged,
                     admission=adm)

    # -- leg 1: indirection overhead (no injected cost, wave admission) -------
    cont_walls, paged_walls, paged_stats = [], [], None
    for _ in range(max(repeats, 2)):
        w, toks, _ = _measure(pipe, factory, params, cfg, slots=slots,
                              paged=False, admission="wave")
        cont_walls.append(w)
        w, _, paged_stats = _measure(pipe, factory, params, cfg, slots=slots,
                                     paged=True, admission="wave")
        paged_walls.append(w)
    contiguous_s = float(np.median(cont_walls))
    paged_s = float(np.median(paged_walls))
    n_waves = -(-n_requests // slots)
    decode_wave_s = max(contiguous_s / n_waves, 1e-6)

    # -- leg 2: wave vs continuous under per-row retrieval cost skew ----------
    slow_cost = slow_cost_ratio * decode_wave_s

    def cost_fn(row):
        return slow_cost if row.tobytes() in slow_keys else 0.0

    wave_runs, cont_runs = [], []
    wave_stats = cont_stats = None
    for _ in range(repeats):
        src = DelayedRetrieval(pipe, cost_s=0.0, cost_fn=cost_fn)
        w, toks, wave_stats = _measure(src, factory, params, cfg, slots=slots,
                                       paged=True, admission="wave")
        wave_runs.append((w, toks))
        src = DelayedRetrieval(pipe, cost_s=0.0, cost_fn=cost_fn)
        w, toks, cont_stats = _measure(src, factory, params, cfg, slots=slots,
                                       paged=True, admission="continuous")
        cont_runs.append((w, toks))
    wave_s = float(np.median([r[0] for r in wave_runs]))
    continuous_s = float(np.median([r[0] for r in cont_runs]))
    toks = wave_runs[0][1]

    # KV-memory accounting: peak blocks actually resident vs the contiguous
    # arena's static full allocation
    hw_blocks = int(paged_stats["pool_high_water_blocks"])
    full_blocks = slots * (CACHE_LEN // BLOCK)

    return {
        "n_nodes": n_nodes, "n_requests": n_requests, "slots": slots,
        "short_new": short_new, "long_new": long_new,
        "long_every": long_every, "cache_len": CACHE_LEN,
        "block_size": BLOCK, "slow_cost_ratio": slow_cost_ratio,
        "slow_cost_s": slow_cost, "decode_wave_s": decode_wave_s,
        "indirection": {
            "contiguous_s": contiguous_s, "paged_s": paged_s,
            "paged_overhead": paged_s / contiguous_s - 1.0,
            "pool_high_water_blocks": hw_blocks,
            "full_arena_blocks": full_blocks,
            "kv_residency_frac": hw_blocks / full_blocks,
        },
        "skewed_admission": {
            "tokens": toks,
            "wave_s": wave_s, "wave_tok_s": toks / wave_s,
            "continuous_s": continuous_s,
            "continuous_tok_s": toks / continuous_s,
            "speedup": wave_s / continuous_s,
            "wave_truncations": wave_stats["truncations"],
            "continuous_truncations": cont_stats["truncations"],
        },
    }


def write_json(report: dict, path: str = "BENCH_paged_kv.json") -> None:
    with open(path, "w") as f:
        json.dump(report, f, indent=2)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=2000)
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--out", default="BENCH_paged_kv.json")
    args = ap.parse_args()
    rep = run(n_nodes=args.nodes, n_requests=args.requests, slots=args.slots)
    ind, skew = rep["indirection"], rep["skewed_admission"]
    print(f"workload: {rep['n_requests']} requests "
          f"({rep['long_every'] - 1}:1 short {rep['short_new']} / long "
          f"{rep['long_new']} new tokens), {rep['slots']} slots")
    print(f"indirection: contiguous {ind['contiguous_s']:.3f}s vs paged "
          f"{ind['paged_s']:.3f}s ({ind['paged_overhead'] * 100:+.1f}%), "
          f"KV residency {ind['pool_high_water_blocks']}/"
          f"{ind['full_arena_blocks']} blocks "
          f"({ind['kv_residency_frac'] * 100:.0f}% of contiguous)")
    print(f"skewed admission: wave {skew['wave_tok_s']:.1f} tok/s -> "
          f"continuous {skew['continuous_tok_s']:.1f} tok/s "
          f"({skew['speedup']:.2f}x)")
    write_json(rep, args.out)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
