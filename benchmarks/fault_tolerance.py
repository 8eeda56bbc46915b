"""Fault-tolerance benchmark: goodput vs injected retrieval-fault rate.

Production graph-RAG serving lives or dies on behavior under partial
failure: a single poisoned retrieval row must cost one request's latency,
not the engine.  This benchmark injects a seeded per-row fault schedule
(:class:`repro.serving.simulate.FaultyRetrieval` — dispatch raises, force
raises, stuck rows, corrupt results) at several fault rates and measures
**goodput** (tokens from requests that completed, per second of wall time)
for two configurations:

* ``resilient`` — retrieval timeout + bounded per-group retries + the
  graceful-degradation ladder (stale cache -> retrieval-free decode ->
  per-request failure).  Transient faults (``fails_per_row`` healing
  budget) recover via retry; permanent ones degrade just their request.
* ``naive``     — no retries, degraded mode off: every faulted row fails
  its request outright (the timeout still bounds stuck waits, since an
  un-timed stuck row would otherwise fail loudly at force).

Every leg asserts the terminal-state accounting invariant: completed +
failed + shed == submitted — no request is ever lost or double-counted.

    PYTHONPATH=src python -m benchmarks.fault_tolerance
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
    FaultyRetrieval, RAGRequest, RAGServeEngine, ServingConfig,
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
        name="fault-bench-lm", n_layers=2, d_model=64, n_heads=4,
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
             timeout_s, retries, degraded):
    eng = RAGServeEngine(pipe_like, params, cfg, config=ServingConfig(
        slots=slots, cache_len=192, prefetch=True,
        retrieval_timeout_s=timeout_s, max_retries=retries,
        retry_backoff_s=0.0, degraded_mode=degraded))
    t0 = time.perf_counter()
    for r in _requests(g, emb_np, q_ids, max_new):
        eng.submit(r)
    done = eng.drain()
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
    good_toks = sum(len(r.out_tokens) for r in completed)
    s = eng.stats()
    assert eng.cache.inflight_count == 0, "leaked in-flight cache keys"
    return {
        "wall_s": wall,
        "goodput_tok_s": good_toks / wall,
        "completed": len(completed),
        "failed": len(failed),
        "shed": len(shed),
        "degraded_served": s["degraded"],
        "stale_served": s["stale_served"],
        "retries": s["retries"],
        "timeouts": s["timeouts"],
        "retrieval_failures": s["retrieval_failures"],
    }


def run(n_nodes: int = 2000, n_requests: int = 24, slots: int = 4,
        max_new: int = 12, seed: int = 0,
        fault_rates: tuple = (0.0, 0.1, 0.2, 0.4),
        timeout_s: float = 0.25, retries: int = 2,
        fails_per_row: int = 2) -> dict:
    g, pipe, cfg, params = _build(n_nodes, seed)
    emb_np = np.asarray(pipe.node_emb)
    rng = np.random.default_rng(seed)
    q_ids = rng.choice(n_nodes, size=n_requests, replace=False)

    # warm every trace: clean path, then a faulted pass so the degraded
    # (query-only) prompt bucket and retry dispatches are compiled too
    _measure(pipe, g, emb_np, q_ids, params, cfg, slots=slots,
             max_new=max_new, timeout_s=timeout_s, retries=retries,
             degraded=True)
    _measure(FaultyRetrieval(pipe, seed=seed, fault_rate=0.3),
             g, emb_np, q_ids, params, cfg, slots=slots, max_new=max_new,
             timeout_s=timeout_s, retries=retries, degraded=True)

    results = []
    for rate in fault_rates:
        row = {"fault_rate": rate}
        for label, (n_retries, degraded) in (
            ("resilient", (retries, True)),
            ("naive", (0, False)),
        ):
            # fresh wrapper per leg: the fails_per_row healing budget and
            # injection counters must not carry across configurations
            src = pipe if rate == 0.0 else FaultyRetrieval(
                pipe, seed=seed, fault_rate=rate,
                fails_per_row=fails_per_row,
            )
            row[label] = _measure(
                src, g, emb_np, q_ids, params, cfg, slots=slots,
                max_new=max_new, timeout_s=timeout_s, retries=n_retries,
                degraded=degraded,
            )
            if rate > 0:
                row[label]["injected"] = dict(src.injected)
        results.append(row)

    return {
        "n_nodes": n_nodes, "n_requests": n_requests, "slots": slots,
        "max_new": max_new, "timeout_s": timeout_s, "retries": retries,
        "fails_per_row": fails_per_row,
        "results": results,
    }


def write_json(report: dict, path: str = "BENCH_fault_tolerance.json") -> None:
    with open(path, "w") as f:
        json.dump(report, f, indent=2)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=2000)
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max_new", type=int, default=12)
    ap.add_argument("--out", default="BENCH_fault_tolerance.json")
    args = ap.parse_args()
    rep = run(n_nodes=args.nodes, n_requests=args.requests, slots=args.slots,
              max_new=args.max_new)
    print(f"workload: {rep['n_requests']} requests x {rep['max_new']} new "
          f"tokens, {rep['slots']} slots, timeout {rep['timeout_s']}s, "
          f"{rep['retries']} retries, faults heal after "
          f"{rep['fails_per_row']} dispatches")
    for row in rep["results"]:
        res, nai = row["resilient"], row["naive"]
        print(f"fault rate {row['fault_rate']:.0%}: resilient "
              f"{res['goodput_tok_s']:.1f} tok/s "
              f"({res['completed']} ok / {res['failed']} failed, "
              f"{res['degraded_served']} degraded, {res['retries']} retries)"
              f" | naive {nai['goodput_tok_s']:.1f} tok/s "
              f"({nai['completed']} ok / {nai['failed']} failed)")
    write_json(rep, args.out)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
