"""Fused RAG serving: golden pipeline behaviour + engine/reference parity +
retrieval-cache semantics."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    BruteIndex, GraphTokenizer, PipelineConfig, RGLPipeline, Vocab,
    index_from_config,
)
from repro.core import naive
from repro.core.tokenization import subgraph_texts
from repro.graph import csr_to_ell, generators
from repro.models.transformer import TransformerConfig, model as tm
from repro.models.transformer.generate import generate_tokens
from repro.serving import (
    RAGRequest, RAGServeEngine, RetrievalCache, ServingConfig,
)

N_NODES = 200
MAX_LEN = 96
MAX_NEW = 6
CACHE_LEN = 128


@pytest.fixture(scope="module")
def stack():
    g = generators.citation_graph(N_NODES, avg_deg=6, seed=11)
    ell = csr_to_ell(g)
    emb = jnp.asarray(g.node_feat)
    vocab = Vocab.build(g.node_text)
    tok = GraphTokenizer(vocab, max_len=MAX_LEN, node_budget=8)
    pcfg = PipelineConfig(strategy="bfs", k_seeds=3, max_hops=2,
                          max_nodes=16, filter_budget=8)
    from repro.serving.config import env_flag
    if env_flag("RGL_MUTATION"):
        # RGL_MUTATION CI cell: the whole serving matrix runs on a pipeline
        # built through a pristine MutableGraphStore — zero-mutation serving
        # must be bitwise identical to the frozen setup below
        from repro.core import MutableGraphStore
        store = MutableGraphStore.build(g, index_kind="brute")
        pipe = store.make_pipeline(tokenizer=tok, config=pcfg)
    else:
        pipe = RGLPipeline(
            graph=ell, index=BruteIndex.build(emb), node_emb=emb,
            tokenizer=tok, node_text=g.node_text, config=pcfg,
        )
    cfg = TransformerConfig(
        name="rag-t", n_layers=2, d_model=32, n_heads=2, n_kv_heads=2,
        d_head=16, d_ff=64, vocab=vocab.size, dtype="float32",
    )
    params = tm.init_params(jax.random.PRNGKey(0), cfg)
    return g, pipe, cfg, params


# ---------------------------------------------------------------- golden ----
def test_pipeline_run_golden(stack):
    """RGLPipeline.run on a small deterministic graph: seed ids, filtered
    subgraph membership, and prompt shapes all match the reference path."""
    g, pipe, _, _ = stack
    qe = jnp.asarray(g.node_feat[:3])
    texts = [g.node_text[i] for i in range(3)]
    out = pipe.run(qe, texts)

    # seeds == the exact top-k of the brute index (ref oracle)
    from repro.kernels.topk_sim import ref as tref
    from repro.core.indexing import l2_normalize

    emb_n = l2_normalize(jnp.asarray(g.node_feat))
    _, exp_seeds = tref.topk_similarity(l2_normalize(qe), emb_n, 3)
    np.testing.assert_array_equal(out["seeds"], np.asarray(exp_seeds))
    for qi in range(3):  # a node's own embedding retrieves itself
        assert qi in out["seeds"][qi]

    # filtered membership: subset of the naive BFS ball, seeds preserved,
    # budget respected
    adj = g.to_adj_dict()
    sub = out["subgraph"]
    nodes = np.asarray(sub.nodes)
    mask = np.asarray(sub.mask)
    for qi in range(3):
        got = {int(v) for v, m in zip(nodes[qi], mask[qi]) if m}
        ball = set(naive.bfs_subgraph(adj, sorted(set(out["seeds"][qi].tolist())),
                                      2, N_NODES))
        assert got <= ball
        assert set(out["seeds"][qi].tolist()) <= got
        assert len(got) <= pipe.config.filter_budget + pipe.config.k_seeds

    # fixed prompt shapes + determinism
    assert out["prompt_ids"].shape == (3, MAX_LEN)
    assert out["prompt_mask"].shape == (3, MAX_LEN)
    out2 = pipe.run(qe, texts)
    np.testing.assert_array_equal(out["prompt_ids"], out2["prompt_ids"])


def test_retrieve_many_padding_is_inert(stack):
    """Padded rows in the fixed-shape serving batch never perturb real rows."""
    g, pipe, _, _ = stack
    qe = np.asarray(g.node_feat[:2], np.float32)
    res1 = pipe.retrieve(jnp.asarray(qe))
    sub1, seeds1 = res1.sub, res1.seeds
    res8 = pipe.retrieve_many(qe, batch_size=8)
    sub8, seeds8, n_valid = res8.sub, res8.seeds, res8.n_valid
    assert n_valid == 2 and seeds8.shape[0] == 8
    assert res8.epoch == pipe.epoch
    np.testing.assert_array_equal(np.asarray(seeds8)[:2], np.asarray(seeds1))
    np.testing.assert_array_equal(np.asarray(sub8.nodes)[:2],
                                  np.asarray(sub1.nodes))
    np.testing.assert_array_equal(np.asarray(sub8.mask)[:2],
                                  np.asarray(sub1.mask))


# ---------------------------------------------------- engine vs reference ----
def _reference_tokens(g, pipe, cfg, params, qi):
    """Unbatched pipeline + offline greedy decode — the fused engine oracle."""
    sub = pipe.retrieve(jnp.asarray(g.node_feat[qi])[None]).sub
    texts = subgraph_texts(sub, g.node_text)[0]
    ids, mask = pipe.tokenizer.linearize(g.node_text[qi], texts)
    prompt = ids[mask]
    out = generate_tokens(
        params, jnp.asarray(prompt)[None], jnp.asarray([len(prompt)]),
        jax.random.PRNGKey(0), cfg, max_new=MAX_NEW, cache_len=CACHE_LEN,
        temperature=0.0,
    )
    return np.asarray(out[0]).tolist()


def test_fused_engine_matches_unbatched_pipeline(stack):
    """A single fused-engine request is token-identical to the unbatched
    RGLPipeline + greedy-decode reference path."""
    g, pipe, cfg, params = stack
    eng = RAGServeEngine(pipe, params, cfg,
                         config=ServingConfig(slots=2, cache_len=CACHE_LEN))
    eng.submit(RAGRequest(uid=0, query_emb=np.asarray(g.node_feat[0]),
                          query_text=g.node_text[0], max_new_tokens=MAX_NEW))
    done = eng.run_to_completion()
    assert len(done) == 1 and done[0].done
    assert done[0].out_tokens[:MAX_NEW] == _reference_tokens(
        g, pipe, cfg, params, 0
    )


def test_fused_engine_batch_matches_reference(stack):
    """Batched admission (shared prefill + shared retrieval batch) stays
    token-identical per request."""
    g, pipe, cfg, params = stack
    eng = RAGServeEngine(pipe, params, cfg,
                         config=ServingConfig(slots=4, cache_len=CACHE_LEN))
    for qi in range(4):
        eng.submit(RAGRequest(uid=qi, query_emb=np.asarray(g.node_feat[qi]),
                              query_text=g.node_text[qi],
                              max_new_tokens=MAX_NEW))
    done = {r.uid: r for r in eng.run_to_completion()}
    assert set(done) == {0, 1, 2, 3}
    assert eng.retrieval_batches == 1  # one jitted call for the whole wave
    for qi in range(4):
        assert done[qi].out_tokens[:MAX_NEW] == _reference_tokens(
            g, pipe, cfg, params, qi
        )


def test_fused_engine_on_sharded_index_matches_brute_reference(stack):
    """RAGServeEngine admission works unchanged on a sharded index: with
    ``index_kind="sharded"`` the fused engine emits tokens identical to the
    brute-index reference path (sharded brute search is bit-identical)."""
    g, pipe, cfg, params = stack
    pcfg = PipelineConfig(strategy="bfs", k_seeds=3, max_hops=2,
                          max_nodes=16, filter_budget=8,
                          index_kind="sharded", index_shards=3)
    sharded_pipe = RGLPipeline(
        graph=pipe.graph,
        index=index_from_config(jnp.asarray(g.node_feat), pcfg),
        node_emb=pipe.node_emb, tokenizer=pipe.tokenizer,
        node_text=g.node_text, config=pcfg,
    )
    eng = RAGServeEngine(sharded_pipe, params, cfg,
                         config=ServingConfig(slots=4, cache_len=CACHE_LEN))
    for qi in range(4):
        eng.submit(RAGRequest(uid=qi, query_emb=np.asarray(g.node_feat[qi]),
                              query_text=g.node_text[qi],
                              max_new_tokens=MAX_NEW))
    done = {r.uid: r for r in eng.run_to_completion()}
    assert set(done) == {0, 1, 2, 3}
    for qi in range(4):  # reference runs on the brute-index pipeline
        assert done[qi].out_tokens[:MAX_NEW] == _reference_tokens(
            g, pipe, cfg, params, qi
        )


# ------------------------------------------------------------------ cache ----
def test_retrieval_cache_hit_and_counters(stack):
    g, pipe, cfg, params = stack
    eng = RAGServeEngine(pipe, params, cfg,
                         config=ServingConfig(slots=2, cache_len=CACHE_LEN))

    def ask(uid):
        eng.submit(RAGRequest(uid=uid, query_emb=np.asarray(g.node_feat[5]),
                              query_text=g.node_text[5],
                              max_new_tokens=MAX_NEW))
        return eng.run_to_completion()[0]

    first = ask(0)
    assert (eng.cache_hits, eng.cache_misses) == (0, 1)
    assert not first.cache_hit

    second = ask(1)  # identical query -> served from the retrieval cache
    assert (eng.cache_hits, eng.cache_misses) == (1, 1)
    assert second.cache_hit
    assert eng.retrieved_queries == 1  # no second retrieval ran
    assert second.out_tokens == first.out_tokens
    np.testing.assert_array_equal(second.prompt_ids, first.prompt_ids)

    # near-duplicate within quantization eps also hits
    jitter = np.asarray(g.node_feat[5]) + 1e-5
    eng.submit(RAGRequest(uid=2, query_emb=jitter, query_text=g.node_text[5],
                          max_new_tokens=MAX_NEW))
    third = eng.run_to_completion()[0]
    assert third.cache_hit and eng.cache_hits == 2


def test_retrieval_cache_lru_eviction():
    cache = RetrievalCache(capacity=2)
    from repro.serving import CachedRetrieval

    def entry(i):
        return CachedRetrieval(
            nodes=np.asarray([i], np.int32), mask=np.asarray([True]),
            dist=np.asarray([0], np.int32), seeds=np.asarray([i], np.int32),
        )

    e0, e1, e2 = (np.full(4, i, np.float32) for i in range(3))
    cache.put(e0, entry(0))
    cache.put(e1, entry(1))
    assert cache.get(e0) is not None  # refresh e0 -> e1 becomes LRU
    cache.put(e2, entry(2))  # evicts e1
    assert cache.get(e1) is None
    assert cache.get(e0) is not None and cache.get(e2) is not None
    assert cache.evictions == 1
    assert cache.stats()["size"] == 2


def test_oversized_prompt_rejected_loudly(stack):
    """Prompts that cannot fit the KV arena fail at submit, not silently."""
    from repro.serving import Request, ServeEngine

    g, pipe, cfg, params = stack
    eng = ServeEngine(params, cfg, slots=2, cache_len=16)
    with pytest.raises(ValueError, match="cannot fit"):
        eng.submit(Request(uid=0, prompt_ids=np.arange(1, 40, dtype=np.int32)))
    # and the fused engine refuses a tokenizer/arena mismatch at construction
    with pytest.raises(ValueError, match="max_len"):
        RAGServeEngine(pipe, params, cfg,
                       config=ServingConfig(slots=2, cache_len=MAX_LEN))


def test_cache_disabled():
    cache = RetrievalCache(capacity=0)
    emb = np.ones(4, np.float32)
    assert cache.get(emb) is None
    cache.put(emb, None)  # no-op
    assert len(cache) == 0 and cache.misses == 1


# ----------------------------------------------------- eviction policies ----
def _entry(i):
    from repro.serving import CachedRetrieval

    return CachedRetrieval(
        nodes=np.asarray([i], np.int32), mask=np.asarray([True]),
        dist=np.asarray([0], np.int32), seeds=np.asarray([i], np.int32),
    )


def _emb(i):
    return np.full(4, i, np.float32)


def test_cache_lfu_eviction():
    """lfu keeps warm regulars; the coldest (fewest hits) entry goes."""
    cache = RetrievalCache(capacity=2, policy="lfu")
    cache.put(_emb(0), _entry(0))
    cache.put(_emb(1), _entry(1))
    for _ in range(3):
        assert cache.get(_emb(0)) is not None  # e0: 3 hits
    assert cache.get(_emb(1)) is not None  # e1: 1 hit, more recent
    cache.put(_emb(2), _entry(2))  # evicts e1 (fewest hits), not e0
    assert cache.get(_emb(1)) is None
    assert cache.get(_emb(0)) is not None
    assert cache.evictions == 1
    assert cache.hit_count(_emb(0)) == 4
    # a 0-hit newcomer is protected at insertion: e2 (1 hit) goes, not e3
    assert cache.get(_emb(2)) is not None
    cache.put(_emb(3), _entry(3))
    assert cache.get(_emb(2)) is None
    assert cache.get(_emb(0)) is not None and cache.get(_emb(3)) is not None
    assert cache.evictions == 2


def test_cache_reinsert_preserves_hits_under_lfu():
    """Re-putting a live key must keep its accumulated hit count: a warm
    lfu entry re-inserted (e.g. prefetch.py re-publishing an owner wave's
    result after the owner's copy was evicted) used to restart at 0 hits
    and become the next eviction victim."""
    cache = RetrievalCache(capacity=2, policy="lfu")
    cache.put(_emb(0), _entry(0))
    cache.put(_emb(1), _entry(1))
    for _ in range(3):
        assert cache.get(_emb(0)) is not None  # e0: warm, 3 hits
    assert cache.get(_emb(1)) is not None  # e1: 1 hit
    cache.put(_emb(0), _entry(0))  # re-insert the warm key
    assert cache.hit_count(_emb(0)) == 3  # hits survive the re-insert
    cache.put(_emb(2), _entry(2))  # must evict e1 (1 hit), NOT warm e0
    assert cache.get(_emb(0)) is not None
    assert cache.get(_emb(1)) is None
    assert cache.evictions == 1


def test_cache_reinsert_refreshes_ttl_window():
    """inserted_at DOES refresh on re-insert (documented): a re-put carries
    fresh data, so its TTL expiry window restarts."""
    clock = {"t": 0.0}
    cache = RetrievalCache(capacity=4, policy="ttl", ttl=10.0,
                           now_fn=lambda: clock["t"])
    cache.put(_emb(0), _entry(0))
    clock["t"] = 8.0
    cache.put(_emb(0), _entry(0))  # re-insert at t=8 restarts the window
    clock["t"] = 15.0  # 15 > 0+10 but < 8+10: alive only if refreshed
    assert cache.get(_emb(0)) is not None
    clock["t"] = 18.1
    assert cache.get(_emb(0)) is None  # 18.1 > 8+10: expires on schedule


def test_cache_ttl_expiry_and_fifo_eviction():
    clock = {"t": 0.0}
    cache = RetrievalCache(capacity=2, policy="ttl", ttl=10.0,
                           now_fn=lambda: clock["t"])
    cache.put(_emb(0), _entry(0))
    clock["t"] = 5.0
    cache.put(_emb(1), _entry(1))
    assert cache.get(_emb(0)) is not None  # 5s old, alive
    clock["t"] = 11.0  # e0 expired (11 > 10), e1 alive (6s old)
    assert cache.get(_emb(0)) is None
    assert cache.expired == 1 and cache.misses == 1
    # repeated lookups of the same expired resident entry count misses,
    # but the EXPIRY is counted once per entry, not once per lookup
    assert cache.get(_emb(0)) is None
    assert cache.get(_emb(0)) is None
    assert cache.expired == 1 and cache.misses == 3
    # resident/live split: the expired entry still occupies capacity
    # (peek_stale can serve it) but is not live for get()
    s = cache.stats()
    assert s["resident"] == 2 and s["live"] == 1
    assert s["size"] == s["resident"]  # historical meaning preserved
    assert cache.get(_emb(1)) is not None
    # capacity pressure evicts oldest-inserted, not least-recent
    cache.put(_emb(2), _entry(2))
    cache.put(_emb(3), _entry(3))  # purge finds nothing fresh-expired -> FIFO
    assert cache.get(_emb(1)) is None  # oldest inserted went first
    assert cache.stats()["expired"] >= 1
    assert cache.stats()["policy"] == "ttl"


def test_cache_ttl_purge_before_policy_eviction():
    clock = {"t": 0.0}
    cache = RetrievalCache(capacity=2, policy="lru", ttl=1.0,
                           now_fn=lambda: clock["t"])
    cache.put(_emb(0), _entry(0))
    cache.put(_emb(1), _entry(1))
    clock["t"] = 2.0  # both expired
    cache.put(_emb(2), _entry(2))  # expiry purge, no policy eviction needed
    assert cache.expired == 2 and cache.evictions == 0
    assert cache.stats()["size"] == 1


def test_cache_policy_validation_and_engine_kwargs(stack):
    with pytest.raises(ValueError, match="policy"):
        RetrievalCache(policy="mru")
    g, pipe, cfg, params = stack
    eng = RAGServeEngine(pipe, params, cfg, config=ServingConfig(
        slots=2, cache_len=CACHE_LEN, cache_policy="lfu", cache_ttl=60.0))
    assert eng.cache.policy == "lfu" and eng.cache.ttl == 60.0
