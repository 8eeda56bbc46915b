"""Online mutation tier: delta graph, incremental index, rebuild parity,
versioned cache invalidation, ServingConfig precedence, unified stats."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    GraphTokenizer, MutableGraphStore, MutationBatch, PipelineConfig,
    RetrievalResult, Vocab,
)
from repro.graph import CSRGraph, DeltaGraph, SlackOverflow, generators
from repro.models.transformer import TransformerConfig, model as tm
from repro.serving import (
    CachedRetrieval, RAGRequest, RAGServeEngine, RetrievalCache,
    ServingConfig, flatten_stats,
)

N = 80
D = 16


def _graph(seed=0, n=N):
    return generators.citation_graph(n, avg_deg=5, d_feat=D, seed=seed)


def _store(g=None, **kw):
    return MutableGraphStore.build(g if g is not None else _graph(), **kw)


def _rand_batches(store, rng, rounds):
    """A deterministic mixed mutation workload over live endpoints."""
    reports = []
    for _ in range(rounds):
        n = store.n_nodes
        alive = np.flatnonzero(np.asarray(store.alive)[:n])
        u, v = int(rng.choice(alive)), int(rng.choice(alive))
        kind = rng.random()
        if kind < 0.35:
            b = MutationBatch(add_edges=np.array([[u, v]]))
        elif kind < 0.6:
            b = MutationBatch(del_edges=np.array([[u, v]]))
        elif kind < 0.85:
            b = MutationBatch(
                add_node_feat=rng.normal(size=(1, D)).astype(np.float32),
                add_node_text=[f"added {n}"],
                add_edges=np.array([[n, u]]),
            )
        else:
            b = MutationBatch(del_nodes=np.array([u]))
        reports.append(store.apply(b))
    return reports


# ------------------------------------------------------- delta vs oracle ----
def test_delta_merged_view_matches_host_oracle(rng):
    g = _graph(seed=3)
    from repro.graph import csr_to_ell
    ell = csr_to_ell(g)
    cap = g.num_nodes + 10
    d = DeltaGraph(np.asarray(ell.nbr), np.asarray(ell.nbr_mask),
                   g.num_nodes, cap, extra_deg=4)
    r = np.random.default_rng(7)
    for _ in range(60):
        op = r.random()
        n = d.n_nodes
        live = np.flatnonzero(~d.tomb[:n])
        u, v = int(r.choice(live)), int(r.choice(live))
        if op < 0.4:
            try:
                d.add_edge(u, v)
            except SlackOverflow:
                pass
        elif op < 0.7:
            d.del_edge(u, v)
        elif op < 0.9 and n < cap:
            d.add_node()
        elif live.size > 2:
            d.del_node(u)
        nbr_h, mask_h = d.merged_host()
        m = d.merged()
        np.testing.assert_array_equal(np.asarray(m.nbr), nbr_h)
        np.testing.assert_array_equal(np.asarray(m.nbr_mask), mask_h)
        assert m.num_nodes == cap


def test_delta_edge_semantics():
    base_nbr = np.zeros((2, 1), np.int32)
    base_mask = np.zeros((2, 1), bool)
    d = DeltaGraph(base_nbr, base_mask, 2, 4, extra_deg=2)
    assert d.add_edge(0, 1) and not d.add_edge(0, 1)  # dedup
    assert d.del_edge(0, 1) and not d.del_edge(0, 1)  # idempotent delete
    assert d.add_edge(0, 1)  # re-add after delete
    u = d.add_node()
    assert u == 2
    assert d.add_edge(0, u)
    with pytest.raises(SlackOverflow):
        d.add_edge(0, 3 if d.add_node() == 3 else 0)  # third slack slot
    d.del_node(1)
    assert 1 not in d.neighbors_live(0)
    with pytest.raises(ValueError):
        d.add_edge(0, 1)  # tombstoned endpoint


# ------------------------------------------------ rebuild/bitwise parity ----
@pytest.mark.parametrize("kind", ["brute", "ivf"])
def test_compaction_bitwise_equals_from_scratch_rebuild(kind):
    g = _graph(seed=5)
    kw = {"index_kw": {"n_clusters": 8}} if kind == "ivf" else {}
    store = _store(g, index_kind=kind, **kw)
    rng = np.random.default_rng(42)
    _rand_batches(store, rng, 25)
    store.compact()

    # from-scratch comparator: same merged corpus, same frozen quantizer
    src, dst = store.delta.live_edge_list()
    g2 = CSRGraph.from_edges(src, dst, store.n_nodes,
                             node_feat=store.h_feat[:store.n_nodes].copy(),
                             node_text=list(store.node_text[:store.n_nodes]))
    ikw = {}
    if kind == "ivf":
        ikw = {"index_kw": {"centroids": np.asarray(store.index.centroids),
                            "nprobe": store.index.nprobe}}
    ref = MutableGraphStore.build(g2, index_kind=kind, alive=store.alive,
                                  active=True, **ikw)

    np.testing.assert_array_equal(np.asarray(store.graph.nbr),
                                  np.asarray(ref.graph.nbr))
    np.testing.assert_array_equal(np.asarray(store.graph.nbr_mask),
                                  np.asarray(ref.graph.nbr_mask))
    np.testing.assert_array_equal(np.asarray(store.node_emb),
                                  np.asarray(ref.node_emb))
    if kind == "brute":
        np.testing.assert_array_equal(np.asarray(store.index.emb),
                                      np.asarray(ref.index.emb))
    else:
        np.testing.assert_array_equal(store.index.h_lists, ref.index.h_lists)
        np.testing.assert_array_equal(store.index.h_counts, ref.index.h_counts)
    # and search parity on live queries
    q = np.asarray(g.node_feat[:5], np.float32)
    s1, i1 = store.index.search(q, 5)
    s2, i2 = ref.index.search(q, 5)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))


def test_incremental_ivf_add_and_delete_visibility():
    g = _graph(seed=9)
    store = _store(g, index_kind="ivf", index_kw={"n_clusters": 6})
    rng = np.random.default_rng(1)
    feat = rng.normal(size=(1, D)).astype(np.float32)
    rep = store.apply(MutationBatch(add_node_feat=feat,
                                    add_node_text=["fresh"],
                                    add_edges=np.array([[N, 0]])))
    new_id = rep.added_nodes[0]
    # the new embedding is findable immediately (no compaction needed)
    _, idx = store.index.search(feat, 1)
    assert int(np.asarray(idx)[0, 0]) == new_id
    # a deleted node disappears from results at scan time
    store.apply(MutationBatch(del_nodes=np.array([new_id])))
    _, idx = store.index.search(feat, 5)
    assert new_id not in np.asarray(idx)[0].tolist()


def test_mid_apply_compaction_no_duplicate_ivf_entries():
    # A batch that adds a node and then overflows edge slack forces an
    # inline compaction *after* the node add; the rebuilt index already
    # holds the new id, so the post-apply incremental add must not insert
    # it a second time (it used to, yielding topk like [32, 32, ...]).
    g = _graph(seed=6)
    store = _store(g, index_kind="ivf", index_kw={"n_clusters": 6},
                   extra_deg=1)
    rng = np.random.default_rng(3)
    feat = rng.normal(size=(1, D)).astype(np.float32)
    edges = np.array([[N, v] for v in range(10)])
    rep = store.apply(MutationBatch(add_node_feat=feat,
                                    add_node_text=["fresh"],
                                    add_edges=edges))
    assert rep.compactions > 0  # the mid-apply scenario actually fired
    new_id = rep.added_nodes[0]
    idx = store.index
    flat = np.concatenate([idx.h_lists[c, : idx.h_counts[c]]
                           for c in range(idx.n_clusters)])
    _, dup = np.unique(flat, return_counts=True)
    assert dup.max() == 1  # every alive id indexed exactly once
    _, top = idx.search(feat, 5)
    top = np.asarray(top)[0].tolist()
    assert top[0] == new_id
    assert len(set(top)) == len(top)  # no duplicate results


def test_incremental_ivf_add_is_idempotent():
    g = _graph(seed=8)
    store = _store(g, index_kind="ivf", index_kw={"n_clusters": 6})
    rep = store.apply(MutationBatch(
        add_node_feat=np.ones((1, D), np.float32), add_node_text=["x"]))
    new_id = rep.added_nodes[0]
    idx = store.index
    before = (idx.h_lists.copy(), idx.h_counts.copy())
    idx.add(np.array([new_id], np.int32))  # re-add of an indexed id
    np.testing.assert_array_equal(idx.h_lists, before[0])
    np.testing.assert_array_equal(idx.h_counts, before[1])


def test_is_empty_handles_numpy_edge_arrays():
    assert MutationBatch().is_empty
    assert not MutationBatch(add_edges=np.array([[0, 1]])).is_empty
    assert not MutationBatch(del_edges=np.array([[0, 1]])).is_empty
    assert not MutationBatch(del_nodes=np.array([3])).is_empty
    assert not MutationBatch(
        add_node_feat=np.zeros((1, D), np.float32)).is_empty


def test_slack_overflow_triggers_inline_compaction():
    g = _graph(seed=2)
    store = _store(g, extra_deg=2)
    targets = np.arange(1, 40)
    for v in targets:  # way past 2 slack slots on node 0
        store.apply(MutationBatch(add_edges=np.array([[0, int(v)]])))
    assert store.compactions > 0  # overflow handled inline, no raise
    nbrs = set(store.delta.neighbors_live(0).tolist())
    assert set(targets.tolist()) <= nbrs


# -------------------------------------------------- zero-mutation parity ----
def test_pristine_store_serves_frozen_objects():
    g = _graph(seed=4)
    from repro.graph import csr_to_ell
    from repro.core.indexing import BruteIndex
    store = _store(g)
    ell = csr_to_ell(g)
    # pristine passthrough: identical arrays, not just equal ones
    np.testing.assert_array_equal(np.asarray(store.graph.nbr),
                                  np.asarray(ell.nbr))
    np.testing.assert_array_equal(np.asarray(store.node_emb), g.node_feat)
    frozen = BruteIndex.build(jnp.asarray(g.node_feat))
    q = np.asarray(g.node_feat[:4], np.float32)
    s1, i1 = store.index.search(q, 4)
    s2, i2 = frozen.search(q, 4)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    assert not store.active and store.epoch == 0


def test_retrieval_result_surface():
    store = _store()
    pipe = store.make_pipeline(config=PipelineConfig(
        strategy="bfs", k_seeds=2, max_hops=2, max_nodes=12, filter_budget=6))
    q = np.asarray(store.node_emb)[:3]
    res = pipe.retrieve_many(q, batch_size=4)
    assert isinstance(res, RetrievalResult)
    assert res.n_valid == 3 and res.epoch == 0
    assert res.nodes is res.sub.nodes and res.mask is res.sub.mask
    store.apply(MutationBatch(add_edges=np.array([[0, 1]])))
    assert pipe.retrieve_many(q, batch_size=4).epoch == 1
    assert pipe.n_valid_nodes == store.n_nodes


# ------------------------------------------- versioned cache invalidation ----
def _entry(nodes, seeds=None, epoch=0):
    nodes = np.asarray(nodes, np.int32)
    if seeds is None:
        seeds = nodes[:1]  # seed inside the entry's own region
    return CachedRetrieval(
        nodes=nodes, mask=np.ones_like(nodes, bool),
        dist=np.zeros(nodes.shape, np.int32),
        seeds=np.asarray(seeds, np.int32), epoch=epoch,
    )


def test_cache_region_invalidation_is_selective():
    c = RetrievalCache(capacity=8, region_bucket=4)
    c.put(np.ones(D) * 1, _entry([0, 1, 2]))       # buckets {0}
    c.put(np.ones(D) * 2, _entry([16, 17]))        # buckets {4}
    assert c.invalidate_regions(np.array([1]), epoch=1) == 1
    assert c.get(np.ones(D) * 1) is None           # touched region dropped
    assert c.get(np.ones(D) * 2) is not None       # untouched survives
    assert c.graph_epoch == 1
    s = c.stats()
    assert s["invalidated"] == 1 and s["graph_epoch"] == 1


def test_cache_put_gate_rejects_superseded_inflight_results():
    c = RetrievalCache(capacity=8, region_bucket=4)
    # a mutation lands (epoch 1, touching node 2) while a wave launched at
    # epoch 0 is still in flight; its late put must be refused
    c.invalidate_regions(np.array([2]), epoch=1)
    c.put(np.ones(D), _entry([0, 1, 2], epoch=0))
    assert c.get(np.ones(D)) is None and c.stats()["stale_rejects"] == 1
    # a late put whose region the mutation did NOT touch is still accepted
    c.put(np.ones(D) * 3, _entry([32, 33], epoch=0))
    assert c.get(np.ones(D) * 3) is not None


def test_cache_mutation_flush_all_mode():
    c = RetrievalCache(capacity=8, mutation_flush="all")
    c.put(np.ones(D), _entry([0]))
    c.put(np.ones(D) * 2, _entry([64]))
    assert c.invalidate_regions(np.array([0]), epoch=1) == 2
    assert c.stats()["resident"] == 0


def test_invalidation_releases_kv_pins():
    c = RetrievalCache(capacity=8, region_bucket=4)
    released = []

    def release(entry):
        released.append(entry)
        entry.kv_blocks = None
        return 2  # blocks returned to the pool

    e = _entry([0, 1])
    c.put(np.ones(D), e)
    e.kv_blocks = np.array([3, 4], np.int32)
    e.kv_release = release
    assert c.invalidate_regions(np.array([0]), epoch=1) == 1
    assert len(released) == 1 and released[0] is e
    assert e.kv_blocks is None


# --------------------------------------------- ServingConfig precedence ----
def test_serving_config_precedence_kwarg_env_default(monkeypatch):
    # pin a clean environment even when a CI cell arms these engine-wide
    monkeypatch.delenv("RGL_RETRIES", raising=False)
    monkeypatch.delenv("RGL_MUTATION", raising=False)
    # default
    assert ServingConfig.from_env().max_retries == 0
    # env beats default
    monkeypatch.setenv("RGL_RETRIES", "3")
    assert ServingConfig.from_env().max_retries == 3
    # kwarg beats env
    assert ServingConfig.resolve(None, max_retries=5).max_retries == 5
    # config object beats env too (it IS the kwarg layer once constructed)
    cfg = ServingConfig(max_retries=7).finalize()
    assert cfg.max_retries == 7
    # bools: env only consulted when field unset
    monkeypatch.setenv("RGL_MUTATION", "1")
    assert ServingConfig.from_env().mutation is True
    assert ServingConfig.resolve(None, mutation=False).mutation is False


def test_serving_config_validation():
    with pytest.raises(ValueError, match="admission"):
        ServingConfig(admission="bogus").finalize()
    with pytest.raises(ValueError, match="shed_policy"):
        ServingConfig(shed_policy="drop-all").finalize()
    with pytest.raises(ValueError, match="max_pending"):
        ServingConfig(max_pending=-1).finalize()
    with pytest.raises(ValueError, match="mutation_flush"):
        RetrievalCache(mutation_flush="sometimes")
    with pytest.raises(TypeError, match="unknown"):
        ServingConfig.resolve(None, not_a_field=1)


def test_flatten_stats_namespaces():
    ns = {"cache": {"hits": 1}, "engine": {"shed": 2},
          "prefetch": {"retries": 0}, "decode": {"decode_steps": 9},
          "mutation": {"epoch": 3}, "router": {"failovers": 1}}
    flat = flatten_stats(ns)
    assert flat["hits"] == 1 and flat["decode_steps"] == 9  # legacy unprefixed
    assert flat["mutation_epoch"] == 3 and flat["router_failovers"] == 1


# ---------------------------------------------- serving-level integration ----
@pytest.fixture(scope="module")
def serving_stack():
    g = _graph(seed=11, n=120)
    vocab = Vocab.build(g.node_text)
    tok = GraphTokenizer(vocab, max_len=48, node_budget=6)
    cfg = TransformerConfig(
        name="mut-t", n_layers=1, d_model=32, n_heads=2, n_kv_heads=2,
        d_head=16, d_ff=64, vocab=vocab.size, dtype="float32",
    )
    params = tm.init_params(jax.random.PRNGKey(0), cfg)
    pcfg = PipelineConfig(strategy="bfs", k_seeds=2, max_hops=2,
                          max_nodes=12, filter_budget=6)
    return g, tok, cfg, params, pcfg


def _engine(serving_stack, **kw):
    g, tok, cfg, params, pcfg = serving_stack
    store = MutableGraphStore.build(g, index_kind="brute")
    pipe = store.make_pipeline(tokenizer=tok, config=pcfg)
    eng = RAGServeEngine(pipe, params, cfg,
                         config=ServingConfig(slots=2, cache_len=96, **kw))
    return store, pipe, eng


def _req(g, qi, uid=0, tokens=4):
    return RAGRequest(uid=uid, query_emb=np.asarray(g.node_feat[qi]),
                      query_text=g.node_text[qi], max_new_tokens=tokens)


def test_zero_mutation_serving_bitwise_identical(serving_stack):
    g, tok, cfg, params, pcfg = serving_stack
    from repro.graph import csr_to_ell
    from repro.core import RGLPipeline
    from repro.core.indexing import BruteIndex
    frozen_pipe = RGLPipeline(
        graph=csr_to_ell(g), index=BruteIndex.build(jnp.asarray(g.node_feat)),
        node_emb=jnp.asarray(g.node_feat), tokenizer=tok,
        node_text=g.node_text, config=pcfg,
    )
    ref_eng = RAGServeEngine(frozen_pipe, params, cfg,
                             config=ServingConfig(slots=2, cache_len=96))
    store, _, eng = _engine(serving_stack)
    for u, qi in enumerate([3, 14, 15, 9, 2, 6]):
        ref_eng.submit(_req(g, qi, uid=u))
        eng.submit(_req(g, qi, uid=u))
    ref_done = {r.uid: r for r in ref_eng.run_to_completion()}
    mut_done = {r.uid: r for r in eng.run_to_completion()}
    assert store.epoch == 0  # never activated
    for uid, r in ref_done.items():
        assert mut_done[uid].out_tokens == r.out_tokens
        np.testing.assert_array_equal(mut_done[uid].retrieved_nodes,
                                      r.retrieved_nodes)


def test_apply_mutations_interleaves_with_serving(serving_stack):
    g, *_ = serving_stack
    store, pipe, eng = _engine(serving_stack)
    for u, qi in enumerate([1, 5, 8, 12]):
        eng.submit(_req(g, qi, uid=u))
    done = []
    rng = np.random.default_rng(0)
    steps = 0
    while not eng._drained() and steps < 200:
        done.extend(eng.step())
        steps += 1
        n = store.n_nodes
        eng.apply_mutations(MutationBatch(add_edges=np.array(
            [[rng.integers(0, n), rng.integers(0, n)]])))
    assert len(done) == 4 and all(r.done for r in done)
    assert store.epoch >= 1
    s = eng.stats()
    assert s["mutation_batches"] == store.batches_applied
    ns = eng.stats_ns()
    assert ns["mutation"]["epoch"] == store.epoch
    assert set(ns) >= {"cache", "engine", "prefetch", "decode", "mutation"}
    # flat view keeps every legacy key
    for k in ("hits", "decode_steps", "prefetch_waves", "shed"):
        assert k in s


def test_mutation_invalidates_cached_retrieval_and_serves_fresh(serving_stack):
    g, *_ = serving_stack
    store, pipe, eng = _engine(serving_stack)
    eng.submit(_req(g, 7, uid=0))
    first = eng.run_to_completion()[0]
    assert eng.cache_misses == 1
    # sever node 7's whole neighborhood: region-touching mutation
    victim = int(first.retrieved_nodes[-1])
    rep = eng.apply_mutations(MutationBatch(del_nodes=np.array([victim])))
    assert eng.mutation_invalidated >= 1  # the cached entry was dropped
    eng.submit(_req(g, 7, uid=1))
    second = eng.run_to_completion()[0]
    assert eng.cache_misses == 2  # re-retrieved, not served from cache
    assert victim not in second.retrieved_nodes.tolist()


def test_mutation_releases_prefix_share_kv_pin(serving_stack):
    g, *_ = serving_stack
    store, pipe, eng = _engine(serving_stack, paged_kv=True, prefix_share=True)
    eng.submit(_req(g, 4, uid=0))
    r0 = eng.run_to_completion()[0]
    assert eng.engine.kv_pins >= 1  # entry pinned its prompt blocks
    pinned_before = eng.engine.kv_pinned_blocks
    assert pinned_before > 0
    victim = int(r0.retrieved_nodes[-1])
    eng.apply_mutations(MutationBatch(del_nodes=np.array([victim])))
    # invalidation released the pin: no stale prefill can ever be aliased
    assert eng.engine.kv_pinned_blocks == 0
    assert eng.engine.kv_releases >= 1
    eng.submit(_req(g, 4, uid=1))
    r1 = eng.run_to_completion()[0]
    assert victim not in r1.retrieved_nodes.tolist()
    assert eng.engine.kv_shared_admits == 0  # nothing stale was reused


def test_mid_flight_epoch_bump_does_not_corrupt_wave(serving_stack):
    """A mutation landing between launch and collect: the in-flight wave
    completes against its launch-time snapshot, and its (superseded) result
    is refused by the cache's epoch put-gate."""
    g, *_ = serving_stack
    store, pipe, eng = _engine(serving_stack, prefetch=True)
    eng.submit(_req(g, 9, uid=0))
    # launch the admission wave but do not collect yet
    eng._launch_pending()
    assert eng.prefetcher.in_flight == 1
    # mutation lands mid-flight: delete the queried node itself, so the
    # in-flight wave's region is guaranteed superseded
    rep = eng.apply_mutations(MutationBatch(del_nodes=np.array([9])))
    assert eng.cache.graph_epoch == rep.epoch
    done = eng.run_to_completion()
    assert len(done) == 1 and done[0].done and not done[0].failed
    # the wave's entry was epoch-0 and touched node 9's region -> rejected
    assert eng.cache.stats()["stale_rejects"] >= 1


def test_rgl_mutation_env_cell_smoke(serving_stack, monkeypatch):
    """RGL_MUTATION=1 routes engine construction through the store-backed
    pipeline (see tests/test_rag_serving.py stack fixture); here we assert
    the env knob resolves into ServingConfig."""
    monkeypatch.setenv("RGL_MUTATION", "1")
    assert ServingConfig.from_env().mutation is True
    monkeypatch.setenv("RGL_COMPACT_EVERY", "7")
    assert ServingConfig.from_env().compact_every == 7


def test_compact_every_auto_compaction(serving_stack):
    g, *_ = serving_stack
    store, pipe, eng = _engine(serving_stack, compact_every=2)
    rng = np.random.default_rng(3)
    for _ in range(4):
        n = store.n_nodes
        eng.apply_mutations(MutationBatch(add_edges=np.array(
            [[rng.integers(0, n), rng.integers(0, n)]])))
    assert store.compactions >= 2
    assert store.mutations_since_compact == 0
