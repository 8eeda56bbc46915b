"""Online-mutation benchmark: serving goodput under a live write mix.

A graph-RAG corpus is not frozen in production: nodes and edges arrive and
die while requests decode.  This benchmark measures what the streaming
mutation tier (:mod:`repro.core.mutation`) costs and what it buys:

* ``frozen``   — the request stream served over a pristine store (zero
  mutations: retrieval runs against the exact frozen graph/index objects).
* ``mutating`` — the same stream with a seeded mutation batch applied
  between engine steps at ``write_mix`` probability (edge inserts / edge
  deletes / node adds), flowing through ``RAGServeEngine.apply_mutations``:
  delta-tier read-through, incremental IVF/brute index maintenance, and
  versioned cache invalidation — no rebuilds, no engine restarts.

Reported: **goodput ratio** (mutating / frozen tokens-per-second — the
price of freshness; the acceptance bar is > 0.7x at a 10% write mix), a
**staleness probe** (after a node-add lands next to an already-cached
query, the very next lookup must reflect it — the versioned cache may
never serve across a touched region's epoch), and a **parity check**
(post-run ``compact()`` must be bitwise identical to a from-scratch
rebuild of the merged corpus — recorded as ``parity.ok``).

Every leg asserts terminal accounting: completed + failed + shed ==
submitted.

    PYTHONPATH=src python -m benchmarks.online_mutation
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from repro.core import (
    GraphTokenizer, MutableGraphStore, MutationBatch, PipelineConfig, Vocab,
)
from repro.graph import CSRGraph, generators
from repro.models.transformer import TransformerConfig, model as tm
from repro.serving import RAGRequest, RAGServeEngine, ServingConfig


def _build(n_nodes: int, seed: int = 0, index_kind: str = "brute"):
    g = generators.citation_graph(n_nodes, avg_deg=8, seed=seed)
    vocab = Vocab.build(g.node_text)
    tok = GraphTokenizer(vocab, max_len=128, node_budget=8)
    pcfg = PipelineConfig(strategy="bfs", k_seeds=3, max_nodes=16,
                          filter_budget=6)
    cfg = TransformerConfig(
        name="mut-bench-lm", n_layers=2, d_model=64, n_heads=4,
        n_kv_heads=2, d_head=16, d_ff=256, vocab=vocab.size, dtype="float32",
    )
    params = tm.init_params(jax.random.PRNGKey(0), cfg)
    return g, tok, pcfg, cfg, params


def _requests(g, q_ids, max_new):
    return [
        RAGRequest(
            uid=u, query_emb=np.asarray(g.node_feat[qi]),
            query_text=" ".join(g.node_text[qi].split()[:4]),
            max_new_tokens=max_new,
        )
        for u, qi in enumerate(q_ids)
    ]


def _seeded_batch(store, rng, d_feat):
    """One mutation drawn from the 45/45/10 insert/delete/node-add mix."""
    n = store.n_nodes
    alive = np.flatnonzero(np.asarray(store.alive)[:n])
    u, v = int(rng.choice(alive)), int(rng.choice(alive))
    roll = rng.random()
    if roll < 0.45:
        return MutationBatch(add_edges=np.array([[u, v]]))
    if roll < 0.9:
        return MutationBatch(del_edges=np.array([[u, v]]))
    return MutationBatch(
        add_node_feat=rng.normal(size=(1, d_feat)).astype(np.float32),
        add_node_text=[f"streamed node {n}"],
        add_edges=np.array([[n, u], [n, v]]),
    )


def _measure(store, pipe, g, q_ids, params, cfg, *, slots, max_new,
             write_mix, seed, compact_every):
    eng = RAGServeEngine(pipe, params, cfg, config=ServingConfig(
        slots=slots, cache_len=192, prefetch=True,
        compact_every=compact_every))
    rng = np.random.default_rng(seed)
    for r in _requests(g, q_ids, max_new):
        eng.submit(r)
    done, steps = [], 0
    t0 = time.perf_counter()
    while not eng._drained() and steps < 10_000:
        done.extend(eng.step())
        steps += 1
        if write_mix > 0 and rng.random() < write_mix:
            eng.apply_mutations(
                _seeded_batch(store, rng, g.node_feat.shape[1]))
    wall = time.perf_counter() - t0
    n = len(q_ids)
    completed = [r for r in done if r.done and not r.failed]
    failed = [r for r in done if r.failed]
    shed = [r for r in done if r.shed]
    if len(completed) + len(failed) + len(shed) != n or len(done) != n:
        raise AssertionError(
            f"terminal accounting broken: {len(completed)} completed + "
            f"{len(failed)} failed + {len(shed)} shed != {n} submitted"
        )
    s = eng.stats()
    return eng, {
        "wall_s": wall,
        "goodput_tok_s": sum(len(r.out_tokens) for r in completed) / wall,
        "completed": len(completed),
        "failed": len(failed),
        "shed": len(shed),
        "steps": steps,
        "mutation_batches": s["mutation_batches"],
        "mutation_epoch": s["mutation_epoch"],
        "mutation_compactions": s["mutation_compactions"],
        "mutation_invalidated": s["mutation_invalidated"],
        "stale_rejects": s["stale_rejects"],
        "cache_hits": s["hits"],
        "cache_misses": s["misses"],
    }


def _staleness_probe(store, pipe, g, params, cfg, *, slots, max_new,
                     n_probes, seed):
    """Freshness after a write: cache a query, land a node-add whose new
    node is a near-duplicate of that query (wired into its neighborhood),
    and re-ask.  The region invalidation must force a re-retrieval that
    surfaces the new node — ``fresh_frac`` counts probes where it did."""
    eng = RAGServeEngine(pipe, params, cfg, config=ServingConfig(
        slots=slots, cache_len=192, prefetch=True))
    rng = np.random.default_rng(seed + 1)
    fresh = 0
    for p in range(n_probes):
        qi = int(rng.integers(0, g.num_nodes))
        q = np.asarray(g.node_feat[qi])
        eng.submit(RAGRequest(uid=2 * p, query_emb=q, query_text="probe",
                              max_new_tokens=max_new))
        eng.drain()
        feat = (g.node_feat[qi]
                + rng.normal(size=q.shape).astype(np.float32) * 1e-3)
        rep = eng.apply_mutations(MutationBatch(
            add_node_feat=feat[None].astype(np.float32),
            add_node_text=[f"probe twin {p}"],
            add_edges=np.array([[store.n_nodes, qi]]),
        ))
        new_id = rep.added_nodes[0]
        eng.submit(RAGRequest(uid=2 * p + 1, query_emb=q, query_text="probe",
                              max_new_tokens=max_new))
        r = eng.drain()[0]
        if new_id in np.asarray(r.retrieved_nodes).tolist():
            fresh += 1
    return {"probes": n_probes, "fresh": fresh,
            "fresh_frac": fresh / n_probes}


def _parity_check(store) -> dict:
    """Post-run ``compact()`` vs a from-scratch rebuild of the merged
    corpus: bitwise identical graph layout and embeddings, or the report
    carries ``ok = 0`` (and the envelope gate fails the job)."""
    store.compact()
    src, dst = store.delta.live_edge_list()
    n = store.n_nodes
    g2 = CSRGraph.from_edges(src, dst, n,
                             node_feat=store.h_feat[:n].copy(),
                             node_text=list(store.node_text[:n]))
    ikw = {}
    if hasattr(store.index, "centroids"):
        ikw = {"index_kw": {"centroids": np.asarray(store.index.centroids),
                            "nprobe": store.index.nprobe}}
    ref = MutableGraphStore.build(g2, index_kind=store.index_kind,
                                  alive=store.alive, active=True, **ikw)
    ok = (
        np.array_equal(np.asarray(store.graph.nbr), np.asarray(ref.graph.nbr))
        and np.array_equal(np.asarray(store.graph.nbr_mask),
                           np.asarray(ref.graph.nbr_mask))
        and np.array_equal(np.asarray(store.node_emb),
                           np.asarray(ref.node_emb))
    )
    return {"ok": int(ok), "epoch": store.epoch,
            "compactions": store.compactions, "n_nodes": n}


def run(n_nodes: int = 2000, n_requests: int = 24, slots: int = 4,
        max_new: int = 12, seed: int = 0, write_mix: float = 0.1,
        n_probes: int = 4, index_kind: str = "brute",
        compact_every: int | None = 64) -> dict:
    g, tok, pcfg, cfg, params = _build(n_nodes, seed, index_kind)
    rng = np.random.default_rng(seed)
    q_ids = rng.choice(n_nodes, size=n_requests, replace=False)

    def fresh_store():
        store = MutableGraphStore.build(g, index_kind=index_kind)
        return store, store.make_pipeline(tokenizer=tok, config=pcfg)

    # warm every trace: a frozen pass, then a mutating pass so the
    # post-activation retrieval shapes and compaction path compile too
    ws, wp = fresh_store()
    _measure(ws, wp, g, q_ids, params, cfg, slots=slots, max_new=max_new,
             write_mix=0.0, seed=seed, compact_every=compact_every)
    _measure(ws, wp, g, q_ids, params, cfg, slots=slots, max_new=max_new,
             write_mix=write_mix, seed=seed, compact_every=compact_every)

    store_f, pipe_f = fresh_store()
    _, frozen = _measure(store_f, pipe_f, g, q_ids, params, cfg, slots=slots,
                         max_new=max_new, write_mix=0.0, seed=seed,
                         compact_every=compact_every)
    assert store_f.epoch == 0  # the frozen leg really was frozen

    store_m, pipe_m = fresh_store()
    _, mutating = _measure(store_m, pipe_m, g, q_ids, params, cfg,
                           slots=slots, max_new=max_new,
                           write_mix=write_mix, seed=seed,
                           compact_every=compact_every)

    store_p, pipe_p = fresh_store()
    probe = _staleness_probe(store_p, pipe_p, g, params, cfg, slots=slots,
                             max_new=max_new, n_probes=n_probes, seed=seed)

    return {
        "n_nodes": n_nodes, "n_requests": n_requests, "slots": slots,
        "max_new": max_new, "write_mix": write_mix,
        "index_kind": index_kind,
        "frozen": frozen,
        "mutating": mutating,
        "goodput_ratio": (mutating["goodput_tok_s"]
                          / frozen["goodput_tok_s"]),
        "staleness": probe,
        "parity": _parity_check(store_m),
    }


def write_json(report: dict, path: str = "BENCH_online_mutation.json") -> None:
    with open(path, "w") as f:
        json.dump(report, f, indent=2)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=2000)
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max_new", type=int, default=12)
    ap.add_argument("--write_mix", type=float, default=0.1)
    ap.add_argument("--index", default="brute", choices=("brute", "ivf"))
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes for CI: checks the section still runs")
    ap.add_argument("--out", default="BENCH_online_mutation.json")
    args = ap.parse_args()
    if args.smoke:
        rep = run(n_nodes=500, n_requests=8, slots=3, max_new=6, n_probes=2,
                  write_mix=args.write_mix, index_kind=args.index)
        out = args.out.replace(".json", ".smoke.json")
    else:
        rep = run(n_nodes=args.nodes, n_requests=args.requests,
                  slots=args.slots, max_new=args.max_new,
                  write_mix=args.write_mix, index_kind=args.index)
        out = args.out
    m, f = rep["mutating"], rep["frozen"]
    print(f"workload: {rep['n_requests']} requests x {rep['max_new']} new "
          f"tokens, {rep['slots']} slots, write mix "
          f"{rep['write_mix']:.0%}, index {rep['index_kind']}")
    print(f"frozen   {f['goodput_tok_s']:.1f} tok/s "
          f"({f['completed']} ok / {f['failed']} failed)")
    print(f"mutating {m['goodput_tok_s']:.1f} tok/s "
          f"({m['completed']} ok, {m['mutation_batches']} batches -> "
          f"epoch {m['mutation_epoch']}, "
          f"{m['mutation_invalidated']} invalidated, "
          f"{m['mutation_compactions']} compactions)")
    print(f"goodput ratio {rep['goodput_ratio']:.2f}x | staleness probe "
          f"{rep['staleness']['fresh_frac']:.2f} fresh | parity "
          f"{'OK' if rep['parity']['ok'] else 'BROKEN'}")
    write_json(rep, out)
    print(f"wrote {out}")


if __name__ == "__main__":
    main()
