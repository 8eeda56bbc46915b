"""Serving launcher: --arch <id> spins up the serving engine on the arch's
reduced smoke config, or with ``--published`` on its published config
(full widths, published vocabulary, random weights from a seed).

Two modes:

* token mode (default) — random already-tokenized prompts through the
  slot-based ``ServeEngine`` (generation stage only).
* ``--rag`` — the fused end-to-end path: a synthetic citation graph + vector
  index feed raw (query embedding, query text) requests through
  ``RAGServeEngine`` (batched retrieval admission + retrieval cache + decode).

``--rag --replicas N`` (N > 1) serves the same stream through an N-replica
fleet behind ``ReplicaRouter``: a shared retrieval cache (fleet-wide
single-flight), health-scored circuit breakers per replica, and —
with ``--crash-replica STEP`` — a live failover demo where one replica
crashes mid-run and its in-flight requests are re-dispatched onto the
survivors.

    PYTHONPATH=src python -m repro.launch.serve --arch starcoder2-3b --requests 8
    PYTHONPATH=src python -m repro.launch.serve --arch starcoder2-3b --rag
    PYTHONPATH=src python -m repro.launch.serve --arch starcoder2-3b --rag \
        --index sharded --shards 4
    PYTHONPATH=src python -m repro.launch.serve --arch starcoder2-3b --rag \
        --replicas 3 --crash-replica 3
    PYTHONPATH=src python -m repro.launch.serve --arch starcoder2-3b --rag \
        --published --nodes 169343 --retrieval compact   # one TPU v5e chip

The process exits non-zero when any request failed, was served degraded
(retrieval-free) or stale, unless ``--fault-rate`` asked for injected faults.
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs as C
from repro.launch.compile_cache import setup_compile_cache
from repro.models.transformer import model as tm
from repro.serving import (
    FaultyReplica, FaultyRetrieval, RAGRequest, RAGServeEngine, ReplicaRouter,
    Request, RetrievalCache, ServeEngine, ServingConfig,
)


def _print_decode_stats(ds: dict) -> None:
    if ds["spec_decode"]:
        print(f"  spec decode: window={ds['draft_window']}, "
              f"{ds['tokens_per_step']:.2f} accepted tokens/step, "
              f"accept rate {ds['draft_accept_rate']:.2f} "
              f"({ds['decode_steps']} verify dispatches)")
    if ds.get("paged_kv"):
        print(f"  paged KV: block={ds['block_size']} tokens, "
              f"pool={ds['pool_blocks']} blocks, "
              f"high water {ds['pool_high_water_blocks']} blocks")
    if ds.get("prefix_share"):
        print(f"  prefix share: {ds['kv_shared_admits']} shared admits / "
              f"{ds['kv_reused_tokens']} prompt tokens reused, "
              f"{ds['kv_cow_copies']} COW tail copies, "
              f"{ds['kv_pins']} pins ({ds['kv_pinned_blocks']} blocks held, "
              f"{ds['kv_releases']} released)")
    if ds.get("truncations"):
        print(f"  truncations: {ds['truncations']} request(s) retired by KV "
              f"exhaustion before reaching max_new_tokens")


def lm_config(arch: str, *, published: bool, vocab_size=None):
    """The arch's published config (``published=True``) or its reduced
    smoke config.  ``vocab_size`` is the tokenizer's id range: the published
    vocabulary is kept and must cover it, the smoke config takes it as its
    vocabulary (its own 128 ids cover no graph tokenizer)."""
    spec = C.get_config(arch)
    if not published:
        cfg = spec.reduced_cfg
        return cfg if vocab_size is None else \
            dataclasses.replace(cfg, vocab=vocab_size)
    cfg = spec.model_cfg
    if vocab_size is not None and vocab_size > cfg.vocab:
        raise ValueError(
            f"tokenizer ids reach {vocab_size - 1}, past {arch}'s published "
            f"vocabulary of {cfg.vocab}"
        )
    return cfg


def build_corpus(nodes: int, *, avg_deg: int = 8, seed: int = 0):
    """Seeded citation graph (OGBN-Arxiv stand-in, 128-d features):
    returns (CSR graph, ELL adjacency, node embeddings on the device)."""
    from repro.graph import csr_to_ell, generators

    g = generators.citation_graph(nodes, avg_deg=avg_deg, seed=seed)
    return g, csr_to_ell(g), jnp.asarray(g.node_feat)


def graph_tokenizer(g, *, max_len: int = 96, node_budget: int = 8):
    """Word tokenizer over the corpus vocabulary + graph linearizer."""
    from repro.core import GraphTokenizer, Vocab

    return GraphTokenizer(Vocab.build(g.node_text), max_len=max_len,
                          node_budget=node_budget)


def build_rag_pipeline(g, ell, emb, pcfg, tok):
    """The ``RGLPipeline`` that ``RAGServeEngine`` serves, with the stage-1
    index named by ``pcfg``."""
    from repro.core import RGLPipeline, index_from_config

    return RGLPipeline(
        graph=ell, index=index_from_config(emb, pcfg), node_emb=emb,
        tokenizer=tok, node_text=g.node_text, config=pcfg,
    )


def build_lm(arch: str, *, published: bool, vocab_size=None, seed: int = 0):
    """(config, random weights from ``seed``) for the serving LM."""
    cfg = lm_config(arch, published=published, vocab_size=vocab_size)
    return cfg, tm.init_params(jax.random.PRNGKey(seed), cfg)


def arena_len(cfg, tok, max_new: int) -> int:
    """KV arena length: the linearized graph prompt (<= tokenizer max_len)
    plus generated tokens must fit; sliding_window only bounds attention
    reach, and a windowed arch keeps its full window."""
    return max(cfg.sliding_window or 0, tok.max_len + max_new + 1)


def unserved(done: list) -> list:
    """Requests that did not get a retrieval-backed answer."""
    return [r for r in done if r.failed or r.degraded or r.stale]


def serving_config(args, cache_len: int) -> ServingConfig:
    """Every serving flag in one resolved ``ServingConfig``: an unset flag
    (None) falls through to its ``RGL_*`` variable, then the default."""
    return ServingConfig.resolve(
        None,
        slots=args.slots, cache_len=cache_len,
        cache_policy=args.cache_policy,
        cache_ttl=args.cache_ttl,
        prefetch=args.prefetch,
        prefetch_depth=args.prefetch_depth,
        admission=args.admission,
        spec_decode=args.spec_decode,
        draft_window=args.draft_window,
        paged_kv=args.paged_kv,
        kv_block_size=args.kv_block,
        kv_pool_blocks=args.pool_blocks,
        prefix_share=args.prefix_share,
        retrieval_timeout_s=args.retrieval_timeout,
        max_retries=args.retries,
        retry_backoff_s=args.retry_backoff,
        degraded_mode=args.degraded,
        max_pending=args.max_pending,
        shed_policy=args.shed_policy,
        default_deadline_s=args.deadline,
        compact_every=args.compact_every,
    )


def _serve_tokens(args) -> None:
    cfg, params = build_lm(args.arch, published=args.published)
    sc = serving_config(args, cfg.sliding_window or 128)
    eng = ServeEngine(params, cfg, slots=sc.slots, cache_len=sc.cache_len,
                      spec_decode=sc.spec_decode,
                      draft_window=sc.draft_window,
                      paged_kv=sc.paged_kv, kv_block_size=sc.kv_block_size,
                      kv_pool_blocks=sc.kv_pool_blocks)
    rng = np.random.default_rng(0)
    t0 = time.time()
    for u in range(args.requests):
        eng.submit(Request(
            uid=u,
            prompt_ids=rng.integers(1, cfg.vocab, size=int(rng.integers(4, 16))
                                    ).astype(np.int32),
            max_new_tokens=args.max_new,
        ))
    done = eng.run_to_completion()
    dt = time.time() - t0
    toks = sum(len(r.out_tokens) for r in done)
    print(f"[{args.arch}] served {len(done)} requests / {toks} tokens "
          f"in {dt:.1f}s ({toks / dt:.1f} tok/s)")
    _print_decode_stats(eng.decode_stats())


def _serve_rag(args) -> list:
    from repro.core import PipelineConfig

    g, ell, emb = build_corpus(args.nodes)
    pcfg = PipelineConfig(strategy="bfs", k_seeds=3, max_nodes=16,
                          filter_budget=6, index_kind=args.index,
                          index_shards=args.shards,
                          retrieval_mode=args.retrieval,
                          workset_cap=args.workset_cap)
    tok = graph_tokenizer(g)
    store = None
    if args.mutate_rate > 0:
        from repro.core import MutableGraphStore
        if args.index not in MutableGraphStore.MUTABLE_INDEX_KINDS:
            raise SystemExit(
                f"--mutate-rate needs --index in "
                f"{MutableGraphStore.MUTABLE_INDEX_KINDS}, got {args.index!r}"
            )
        store = MutableGraphStore.build(g, index_kind=args.index)
        pipe = store.make_pipeline(tokenizer=tok, config=pcfg)
    else:
        pipe = build_rag_pipeline(g, ell, emb, pcfg, tok)
    cfg, params = build_lm(args.arch, published=args.published,
                           vocab_size=tok.vocab.size)
    if args.fault_rate > 0:
        # fault-injection demo mode: a seeded fraction of retrieval rows
        # raise / stall / corrupt, exercising the retry + degradation path
        pipe = FaultyRetrieval(pipe, seed=args.fault_seed,
                               fault_rate=args.fault_rate)
    serve_cfg = serving_config(args, arena_len(cfg, tok, args.max_new))
    if args.replicas > 1:
        return _serve_rag_fleet(pipe, g, emb, params, cfg, serve_cfg, args)
    eng = RAGServeEngine(pipe, params, cfg, config=serve_cfg)
    rng = np.random.default_rng(0)
    q_ids = rng.choice(args.nodes, size=args.requests, replace=True)
    emb_np = np.asarray(emb)
    t0 = time.time()
    for u, qi in enumerate(q_ids):
        eng.submit(RAGRequest(
            uid=u, query_emb=emb_np[qi],
            query_text=" ".join(g.node_text[qi].split()[:4]),
            max_new_tokens=args.max_new,
        ))
    if store is not None:
        done = _drain_with_mutations(eng, store, args)
    else:
        # drain() never raises: under fault injection (or tight deadlines)
        # the stragglers are aborted and reported instead of crashing the
        # launcher
        done = eng.drain()
    dt = time.time() - t0
    ok = [r for r in done if r.done and not r.failed]
    toks = sum(len(r.out_tokens) for r in ok)
    s = eng.stats()
    print(f"[{args.arch}] RAG-served {len(ok)}/{len(done)} requests / "
          f"{toks} tokens in {dt:.1f}s ({toks / dt:.1f} tok/s); "
          f"{s['retrieval_batches']} retrieval batches, "
          f"cache {s['hits']}/{s['hits'] + s['misses']} hits")
    ft = (s["retries"], s["timeouts"], s["failed"], s["shed"],
          s["degraded"], s["stale_served"])
    if any(ft) or args.fault_rate > 0:
        print(f"  fault tolerance: {s['retries']} retries, "
              f"{s['timeouts']} timeouts, {s['failed']} failed, "
              f"{s['shed']} shed, {s['degraded']} degraded-served, "
              f"{s['stale_served']} stale-served")
    if s["prefetch"]:
        print(f"  prefetch: {s['prefetch_waves']} waves, "
              f"{s['overlap_seconds'] * 1e3:.1f}ms overlapped "
              f"({s['overlap_steps']} decode steps / "
              f"{s['overlap_tokens']} accepted tokens), "
              f"hidden_frac={s['hidden_frac']:.2f}")
    if s.get("mutation_batches"):
        print(f"  mutation: {s['mutation_batches']} batches "
              f"(epoch {s['mutation_epoch']}, "
              f"{s['mutation_compactions']} compactions, "
              f"{s['mutation_invalidated']} cache entries invalidated, "
              f"{s['stale_rejects']} stale puts rejected)")
    _print_decode_stats(s)
    return done


def _drain_with_mutations(eng, store, args, max_steps: int = 10_000) -> list:
    """Serve to completion while a seeded writer mutates the live corpus:
    each engine step, with probability ``--mutate-rate``, one mutation batch
    (an edge insert, an edge delete, or a node add) lands between steps via
    ``apply_mutations`` — the read/write-mix the online-mutation tier
    exists for."""
    from repro.core import MutationBatch

    rng = np.random.default_rng(args.fault_seed + 1)
    done = []
    for _ in range(max_steps):
        done.extend(eng.step())
        if eng._drained():
            return done
        if rng.random() >= args.mutate_rate:
            continue
        kind = rng.random()
        n = store.n_nodes
        if kind < 0.45:  # insert an edge
            batch = MutationBatch(add_edges=np.array(
                [[rng.integers(0, n), rng.integers(0, n)]]))
        elif kind < 0.9:  # delete an edge (no-op if it does not exist)
            batch = MutationBatch(del_edges=np.array(
                [[rng.integers(0, n), rng.integers(0, n)]]))
        else:  # add a node wired to two random anchors
            feat = rng.normal(size=(1, store.h_feat.shape[1] if store.active
                                    else store.node_emb.shape[1]))
            batch = MutationBatch(
                add_node_feat=feat.astype(np.float32),
                add_node_text=[f"live node {n}"],
                add_edges=np.array([[n, rng.integers(0, n)],
                                    [n, rng.integers(0, n)]]),
            )
        eng.apply_mutations(batch)
    done.extend(eng.abort(reason=f"drain gave up after {max_steps} steps"))
    return done


def _serve_rag_fleet(pipe, g, emb, params, cfg, serve_cfg, args) -> list:
    # shed/deadline options move to the router's front door: the router
    # pins the absolute deadline at submit and sheds on queue overflow, so
    # the per-replica engines run unbounded underneath it
    cache = RetrievalCache(capacity=256 * args.replicas,
                           policy=serve_cfg.cache_policy,
                           ttl=serve_cfg.cache_ttl)
    replica_cfg = dataclasses.replace(serve_cfg, max_pending=0,
                                      shed_policy="reject",
                                      default_deadline_s=None)
    engines = [
        RAGServeEngine(pipe, params, cfg, config=replica_cfg,
                       retrieval_cache=cache)
        for _ in range(args.replicas)
    ]
    if args.crash_replica is not None:
        engines[-1] = FaultyReplica(engines[-1], mode="crash",
                                    crash_step=args.crash_replica)
    router = ReplicaRouter(engines,
                           failover=args.failover,
                           max_pending=serve_cfg.max_pending,
                           shed_policy=serve_cfg.shed_policy,
                           replica_depth=args.router_depth,
                           health_window=args.router_window,
                           trip_threshold=args.router_trip,
                           cooldown_steps=args.router_cooldown,
                           default_deadline_s=serve_cfg.default_deadline_s)
    rng = np.random.default_rng(0)
    q_ids = rng.choice(args.nodes, size=args.requests, replace=True)
    emb_np = np.asarray(emb)
    t0 = time.time()
    for u, qi in enumerate(q_ids):
        router.submit(RAGRequest(
            uid=u, query_emb=emb_np[qi],
            query_text=" ".join(g.node_text[qi].split()[:4]),
            max_new_tokens=args.max_new,
        ))
    done = router.drain()
    dt = time.time() - t0
    ok = [r for r in done if r.done and not r.failed]
    toks = sum(len(r.out_tokens) for r in ok)
    s = router.stats()
    cs = cache.stats()
    print(f"[{args.arch}] fleet of {args.replicas} replicas RAG-served "
          f"{len(ok)}/{len(done)} requests / {toks} tokens in {dt:.1f}s "
          f"({toks / dt:.1f} tok/s)")
    print(f"  router: {s['submitted']} submitted, "
          f"{s['front_door_shed']} shed, "
          f"{s['failovers']} failover(s), {s['redispatched']} re-dispatched, "
          f"{s['stranded']} stranded")
    print(f"  shared cache: {cs['hits']}/{cs['hits'] + cs['misses']} hits, "
          f"{cs['stale_hits']} stale hits, {cs['size']} entries")
    for pr in s["per_replica"]:
        line = (f"  {pr['name']}: circuit={pr['circuit']}, "
                f"dispatched={pr['dispatched']}, "
                f"delivered={pr['delivered']}, "
                f"crashes={pr['crashes']}, trips={pr['trips']}")
        h = pr["health"]  # None for a crashed replica (health unreadable)
        if h is not None:
            line += (f"; retries={h['retries']}, timeouts={h['timeouts']}, "
                     f"failed={h['failed']}, degraded={h['degraded']}")
        print(line)
    return done


def main():
    setup_compile_cache()
    ap = argparse.ArgumentParser()
    lm_archs = [a for a in C.ARCH_IDS if C.get_config(a).family == "lm"]
    ap.add_argument("--arch", required=True, choices=lm_archs)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max_new", type=int, default=12)
    ap.add_argument("--rag", action="store_true",
                    help="serve end-to-end through the fused RAG engine")
    ap.add_argument("--published", action="store_true",
                    help="serve the arch's published config (full widths "
                         "and vocabulary) instead of its reduced smoke "
                         "config")
    ap.add_argument("--nodes", type=int, default=1000,
                    help="synthetic graph size for --rag")
    ap.add_argument("--index", default="brute",
                    choices=["brute", "ivf", "sharded", "sharded_ivf"],
                    help="stage-1 vector index backend for --rag")
    ap.add_argument("--shards", type=int, default=None,
                    help="shard count for sharded index kinds "
                         "(default: one per device)")
    ap.add_argument("--retrieval", default="auto",
                    choices=["dense", "compact", "auto"],
                    help="stage-3 subgraph construction backend for --rag")
    ap.add_argument("--workset-cap", type=int, default=2048,
                    help="compact backend candidate capacity per query")
    ap.add_argument("--cache-policy", default="lru",
                    choices=["lru", "lfu", "ttl"],
                    help="retrieval-cache eviction policy for --rag")
    ap.add_argument("--cache-ttl", type=float, default=None,
                    help="retrieval-cache entry expiry in seconds")
    ap.add_argument("--prefetch", action=argparse.BooleanOptionalAction,
                    default=None,
                    help="double-buffered async admission: overlap the next "
                         "wave's retrieval with the current decode steps "
                         "(--no-prefetch forces sync; default honors "
                         "RGL_PREFETCH)")
    ap.add_argument("--prefetch-depth", type=int, default=None,
                    help="max launched-but-uncollected admission waves "
                         "(default: slots when --admission continuous, "
                         "else 1)")
    ap.add_argument("--admission", default=None,
                    choices=["wave", "continuous"],
                    help="admission granularity for --rag: whole waves, or "
                         "per-request launch/collect so a slow retrieval "
                         "row only delays its own request (default honors "
                         "RGL_ADMISSION, 'wave')")
    ap.add_argument("--paged-kv", action=argparse.BooleanOptionalAction,
                    default=None,
                    help="paged KV pool: block-table indirection over "
                         "fixed-size blocks; slots return blocks the step "
                         "they retire (--no-paged-kv forces the contiguous "
                         "arena; default honors RGL_PAGED_KV)")
    ap.add_argument("--prefix-share", action=argparse.BooleanOptionalAction,
                    default=None,
                    help="prefix-shared paged KV: pin hot retrieval-cache "
                         "entries' prefilled prompt blocks and alias them "
                         "into later identical prompts (refcounted "
                         "copy-on-write; needs --paged-kv; default honors "
                         "RGL_PREFIX_SHARE)")
    ap.add_argument("--kv-block", type=int, default=None,
                    help="tokens per KV block (must divide cache_len; "
                         "default: largest divisor <= 16, or RGL_KV_BLOCK)")
    ap.add_argument("--pool-blocks", type=int, default=None,
                    help="total blocks in the shared KV pool (default "
                         "slots*cache_len/block — full capacity; smaller "
                         "values save memory and may truncate long "
                         "generations under pressure)")
    ap.add_argument("--spec-decode", action=argparse.BooleanOptionalAction,
                    default=None,
                    help="self-speculative multi-token decode: verify a "
                         "window of prompt-lookup drafts per jitted step "
                         "(--no-spec-decode forces one-token decode; "
                         "default honors RGL_SPEC_DECODE)")
    ap.add_argument("--draft-window", type=int, default=None,
                    help="fed tokens per speculative step (1 committed + "
                         "W-1 drafts; default honors RGL_DRAFT_WINDOW, 4)")
    ap.add_argument("--retrieval-timeout", type=float, default=None,
                    help="seconds before an unready retrieval wave is "
                         "declared timed out (default honors "
                         "RGL_RETRIEVAL_TIMEOUT; unset = wait forever)")
    ap.add_argument("--retries", type=int, default=None,
                    help="retry budget for a failed retrieval miss-group "
                         "(size-1 isolated relaunches; default honors "
                         "RGL_RETRIES, 0)")
    ap.add_argument("--retry-backoff", type=float, default=None,
                    help="base seconds for exponential retry backoff "
                         "(default honors RGL_RETRY_BACKOFF, 0)")
    ap.add_argument("--deadline", type=float, default=None,
                    help="per-request deadline in seconds from submit; "
                         "expired requests are shed, never dispatched "
                         "(default honors RGL_DEADLINE; unset = none)")
    ap.add_argument("--max-pending", type=int, default=None,
                    help="pending-queue bound; overflow triggers "
                         "--shed-policy (default honors RGL_MAX_PENDING, "
                         "0 = unbounded)")
    ap.add_argument("--shed-policy", default=None,
                    choices=["reject", "evict-oldest"],
                    help="overflow victim: reject the new request or evict "
                         "the oldest pending one (default honors "
                         "RGL_SHED_POLICY, 'reject')")
    ap.add_argument("--degraded", action=argparse.BooleanOptionalAction,
                    default=None,
                    help="retrieval-free (query-only) decode when retries "
                         "and the stale cache are exhausted (--no-degraded "
                         "fails such requests; default honors RGL_DEGRADED, "
                         "on)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="serve --rag through N engine replicas behind the "
                         "health-aware ReplicaRouter with a shared "
                         "retrieval cache (1 = single engine, no router)")
    ap.add_argument("--failover", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="re-dispatch a crashed replica's in-flight "
                         "requests onto survivors (--no-failover strands "
                         "them failed — the naive baseline)")
    ap.add_argument("--crash-replica", type=int, default=None,
                    metavar="STEP",
                    help="failover demo: the last replica crashes after "
                         "STEP engine steps")
    ap.add_argument("--router-depth", type=int, default=None,
                    help="max assigned requests per replica before the "
                         "router stops routing to it (default 2x slots)")
    ap.add_argument("--router-window", type=int, default=8,
                    help="router health window: fault-counter deltas from "
                         "the last N delivery rounds feed the circuit "
                         "breaker")
    ap.add_argument("--router-trip", type=int, default=3,
                    help="fault-delta sum over the window that trips a "
                         "replica's circuit open")
    ap.add_argument("--router-cooldown", type=int, default=8,
                    help="router steps an open circuit waits before "
                         "half-open probing (also the crashed-replica "
                         "revival probe interval)")
    ap.add_argument("--mutate-rate", type=float, default=0.0,
                    help="online mutation demo: per-step probability that "
                         "one mutation batch (edge insert/delete or node "
                         "add) lands between decode steps while serving "
                         "(needs --rag and a mutable --index; 0 = frozen "
                         "corpus)")
    ap.add_argument("--compact-every", type=int, default=None,
                    help="fold the mutation delta into a fresh base every "
                         "N applied batches (default honors "
                         "RGL_COMPACT_EVERY, 0 = manual)")
    ap.add_argument("--fault-rate", type=float, default=0.0,
                    help="inject seeded retrieval faults on this fraction "
                         "of query rows (demo/bench mode; 0 = off)")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed for the per-row fault schedule")
    args = ap.parse_args()

    if not args.rag:
        _serve_tokens(args)
        return
    bad = unserved(_serve_rag(args))
    if bad and args.fault_rate == 0:
        for r in bad:
            kind = "failed" if r.failed else \
                "degraded" if r.degraded else "stale"
            print(f"  request {r.uid}: {kind}"
                  + (f" ({r.error})" if r.error else ""), file=sys.stderr)
        sys.exit(f"{len(bad)} request(s) served without fresh retrieval "
                 f"and no --fault-rate was asked for")


if __name__ == "__main__":
    main()
