"""Multi-replica serving benchmark: goodput + latency vs replica count, and
failover containment when a replica crashes mid-run.

One engine is one fault domain; the router
(:class:`repro.serving.router.ReplicaRouter`) is what turns N engines into a
fleet that *survives* losing one.  This benchmark measures both halves of
that claim on a single host (replicas step round-robin — the router's
overhead and containment behavior, not hardware parallelism):

* **scaling** — a healthy fleet at 1 / 2 / 3 replicas over the same request
  stream: aggregate goodput (tokens from completed requests per second of
  wall time) and submit-to-terminal latency p50/p99.  All replicas share one
  :class:`~repro.serving.cache.RetrievalCache`, so the retrieval tier's
  single-flight dedup works fleet-wide.
* **crash** — a 3-replica fleet where one replica crashes a few steps in,
  measured three ways: the **failover** router (crashed replica's in-flight
  requests re-dispatched onto survivors), the **naive** router (failover
  off: those requests are delivered failed — stranded), and the **2-healthy**
  baseline (the fleet that never had the third replica).  The headline
  number is ``goodput_ratio_vs_2healthy``: a failover fleet that loses a
  replica mid-run should still deliver at least ~0.8x the goodput of the
  fleet that never had it (it did extra, wasted work before the crash),
  while the naive fleet additionally strands completed-able requests.

Every leg asserts the fleet-wide terminal accounting invariant (completed +
failed + shed == submitted, exactly one terminal per request) and zero
leaked in-flight cache keys.

    PYTHONPATH=src python -m benchmarks.multi_replica
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
    FaultyReplica, RAGRequest, RAGServeEngine, ReplicaRouter, RetrievalCache,
    ServingConfig,
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
        name="replica-bench-lm", n_layers=2, d_model=64, n_heads=4,
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


def _measure(pipe, g, emb_np, q_ids, params, cfg, *, n_replicas, slots,
             max_new, crash_step=None, failover=True,
             max_steps=20_000) -> dict:
    cache = RetrievalCache(capacity=512)
    engines = [
        RAGServeEngine(pipe, params, cfg, config=ServingConfig(
            slots=slots, cache_len=192, prefetch=True), retrieval_cache=cache)
        for _ in range(n_replicas)
    ]
    if crash_step is not None:
        # the LAST replica crashes: the router must contain it
        engines[-1] = FaultyReplica(engines[-1], mode="crash",
                                    crash_step=crash_step)
    router = ReplicaRouter(engines, failover=failover,
                           cooldown_steps=10**6)  # dead stays dead here
    reqs = _requests(g, emb_np, q_ids, max_new)
    lat: dict = {}
    t0 = time.perf_counter()
    for r in reqs:
        router.submit(r)
    steps = 0
    while router.outstanding and steps < max_steps:
        for r in router.step():
            lat[r.uid] = time.perf_counter() - t0
        steps += 1
    wall = time.perf_counter() - t0

    n = len(reqs)
    completed = [r for r in reqs if r.done and not r.failed]
    failed = [r for r in reqs if r.failed]
    shed = [r for r in reqs if r.shed]
    if len(completed) + len(failed) + len(shed) != n or len(lat) != n:
        raise AssertionError(
            f"terminal accounting broken: {len(completed)} completed + "
            f"{len(failed)} failed + {len(shed)} shed != {n} submitted "
            f"({len(lat)} delivered)"
        )
    s = router.stats()
    if s["duplicate_deliveries"]:
        raise AssertionError(
            f"{s['duplicate_deliveries']} duplicate deliveries"
        )
    assert cache.inflight_count == 0, "leaked in-flight cache keys"
    good_toks = sum(len(r.out_tokens) for r in completed)
    done_lat = sorted(lat[r.uid] for r in completed) or [0.0]
    return {
        "replicas": n_replicas,
        "wall_s": wall,
        "router_steps": steps,
        "goodput_tok_s": good_toks / wall,
        "completed": len(completed),
        "failed": len(failed),
        "shed": len(shed),
        "p50_s": float(np.percentile(done_lat, 50)),
        "p99_s": float(np.percentile(done_lat, 99)),
        "failovers": s["failovers"],
        "redispatched": s["redispatched"],
        "stranded": s["stranded"],
        "cache": {k: cache.stats()[k] for k in ("hits", "misses", "size")},
    }


def run(n_nodes: int = 2000, n_requests: int = 24, slots: int = 4,
        max_new: int = 12, seed: int = 0,
        replica_counts: tuple = (1, 2, 3), crash_step: int = 3) -> dict:
    g, pipe, cfg, params = _build(n_nodes, seed)
    emb_np = np.asarray(pipe.node_emb)
    rng = np.random.default_rng(seed)
    q_ids = rng.choice(n_nodes, size=n_requests, replace=False)

    # warm the compile traces (prefill/decode buckets, retrieval batch)
    _measure(pipe, g, emb_np, q_ids, params, cfg, n_replicas=1, slots=slots,
             max_new=max_new)

    scaling = [
        _measure(pipe, g, emb_np, q_ids, params, cfg, n_replicas=k,
                 slots=slots, max_new=max_new)
        for k in replica_counts
    ]

    baseline_2 = _measure(pipe, g, emb_np, q_ids, params, cfg, n_replicas=2,
                          slots=slots, max_new=max_new)
    failover_3 = _measure(pipe, g, emb_np, q_ids, params, cfg, n_replicas=3,
                          slots=slots, max_new=max_new,
                          crash_step=crash_step, failover=True)
    naive_3 = _measure(pipe, g, emb_np, q_ids, params, cfg, n_replicas=3,
                       slots=slots, max_new=max_new,
                       crash_step=crash_step, failover=False)
    crash = {
        "crash_step": crash_step,
        "baseline_2healthy": baseline_2,
        "failover_3_with_crash": failover_3,
        "naive_3_with_crash": naive_3,
        # headline: losing 1-of-3 mid-run still delivers ~the goodput of the
        # fleet that never had the third replica
        "goodput_ratio_vs_2healthy":
            failover_3["goodput_tok_s"] / baseline_2["goodput_tok_s"],
    }
    return {
        "n_nodes": n_nodes, "n_requests": n_requests, "slots": slots,
        "max_new": max_new, "replica_counts": list(replica_counts),
        "scaling": scaling,
        "crash": crash,
    }


def write_json(report: dict, path: str = "BENCH_multi_replica.json") -> None:
    with open(path, "w") as f:
        json.dump(report, f, indent=2)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=2000)
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max_new", type=int, default=12)
    ap.add_argument("--out", default="BENCH_multi_replica.json")
    args = ap.parse_args()
    rep = run(n_nodes=args.nodes, n_requests=args.requests, slots=args.slots,
              max_new=args.max_new)
    print(f"workload: {rep['n_requests']} requests x {rep['max_new']} new "
          f"tokens, {rep['slots']} slots per replica")
    for row in rep["scaling"]:
        print(f"replicas={row['replicas']}: "
              f"{row['goodput_tok_s']:.1f} tok/s, "
              f"p50 {row['p50_s'] * 1e3:.0f} ms, "
              f"p99 {row['p99_s'] * 1e3:.0f} ms")
    c = rep["crash"]
    fo, na, b2 = (c["failover_3_with_crash"], c["naive_3_with_crash"],
                  c["baseline_2healthy"])
    print(f"crash @step {c['crash_step']}: failover "
          f"{fo['goodput_tok_s']:.1f} tok/s "
          f"({fo['completed']} ok, {fo['redispatched']} re-dispatched) = "
          f"{c['goodput_ratio_vs_2healthy']:.2f}x of 2-healthy "
          f"({b2['goodput_tok_s']:.1f} tok/s) | naive "
          f"{na['goodput_tok_s']:.1f} tok/s "
          f"({na['completed']} ok, {na['stranded']} stranded)")
    write_json(rep, args.out)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
