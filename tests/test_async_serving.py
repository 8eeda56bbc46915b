"""Async admission prefetch: sync/prefetch parity, overlap oracle, in-flight
dedup, admission tickets, and run_to_completion exhaustion semantics."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import BruteIndex, GraphTokenizer, PipelineConfig, \
    RGLPipeline, Vocab
from repro.graph import csr_to_ell, generators
from repro.models.transformer import TransformerConfig, model as tm
from repro.serving import (
    DelayedRetrieval, RAGRequest, RAGServeEngine, Request, ServeEngine,
    ServingConfig,
)

N_NODES = 120
MAX_LEN = 64
CACHE_LEN = 96
SLOTS = 3


@pytest.fixture(scope="module")
def stack():
    g = generators.citation_graph(N_NODES, avg_deg=6, seed=7)
    ell = csr_to_ell(g)
    emb = jnp.asarray(g.node_feat)
    vocab = Vocab.build(g.node_text)
    tok = GraphTokenizer(vocab, max_len=MAX_LEN, node_budget=6)
    pipe = RGLPipeline(
        graph=ell, index=BruteIndex.build(emb), node_emb=emb, tokenizer=tok,
        node_text=g.node_text,
        config=PipelineConfig(strategy="bfs", k_seeds=3, max_hops=2,
                              max_nodes=16, filter_budget=8),
    )
    cfg = TransformerConfig(
        name="async-t", n_layers=2, d_model=32, n_heads=2, n_kv_heads=2,
        d_head=16, d_ff=64, vocab=vocab.size, dtype="float32",
    )
    params = tm.init_params(jax.random.PRNGKey(0), cfg)
    return g, pipe, cfg, params


def _stream(g):
    """Deterministic request stream: more requests than slots, exact repeats
    across waves (cache hits), duplicates inside one wave (dedup collisions),
    and mixed generation lengths (staggered slot turnover)."""
    q_ids = [0, 1, 2, 0, 3, 3, 4, 1, 5, 0]
    max_new = [4, 6, 4, 5, 4, 4, 6, 4, 4, 5]
    return [
        RAGRequest(uid=u, query_emb=np.asarray(g.node_feat[qi]),
                   query_text=g.node_text[qi], max_new_tokens=mn)
        for u, (qi, mn) in enumerate(zip(q_ids, max_new))
    ]


def _run(g, pipe, cfg, params, **kw):
    eng = RAGServeEngine(pipe, params, cfg, config=ServingConfig(
        slots=SLOTS, cache_len=CACHE_LEN, **kw))
    for r in _stream(g):
        eng.submit(r)
    done = {r.uid: r for r in eng.run_to_completion()}
    assert len(done) == 10 and all(r.done for r in done.values())
    return eng, done


# ------------------------------------------------------------------ parity ----
@pytest.mark.parametrize("depth", [1, 2])
def test_sync_prefetch_parity(stack, depth):
    """The same request stream through sync and prefetched admission yields
    bitwise-identical per-request outputs and identical cache accounting.

    Output parity is unconditional.  Accounting parity is unconditional at
    depth=1; at depth>=2 it additionally requires no capacity pressure
    (ample cache here) — pipelined lookups reorder recency updates, so
    eviction victims may differ under pressure (see prefetch.py docstring).
    """
    g, pipe, cfg, params = stack
    sync_eng, sync_done = _run(g, pipe, cfg, params, prefetch=False)
    pf_eng, pf_done = _run(g, pipe, cfg, params, prefetch=True,
                           prefetch_depth=depth)

    for uid in sync_done:
        assert pf_done[uid].out_tokens == sync_done[uid].out_tokens
        np.testing.assert_array_equal(
            pf_done[uid].retrieved_nodes, sync_done[uid].retrieved_nodes
        )
        np.testing.assert_array_equal(
            pf_done[uid].prompt_ids, sync_done[uid].prompt_ids
        )
        assert pf_done[uid].cache_hit == sync_done[uid].cache_hit

    assert pf_eng.cache_hits == sync_eng.cache_hits
    assert pf_eng.cache_misses == sync_eng.cache_misses
    assert pf_eng.retrieval_batches == sync_eng.retrieval_batches
    assert pf_eng.retrieved_queries == sync_eng.retrieved_queries
    # the stream has 3 cross-wave repeats and 1 intra-wave duplicate
    assert (sync_eng.cache_hits, sync_eng.cache_misses) == (3, 7)
    assert sync_eng.retrieved_queries == 6  # dedup collapsed the dup pair

    s_sync, s_pf = sync_eng.stats(), pf_eng.stats()
    assert s_sync["prefetch_waves"] == 0 and s_sync["overlap_seconds"] == 0.0
    assert s_pf["prefetch_waves"] > 0
    assert s_pf["prefetch"] and not s_sync["prefetch"]


# ----------------------------------------------------------- overlap oracle ----
def test_overlap_oracle_decode_between_launch_and_collect(stack):
    """With an injected retrieval latency, decode steps demonstrably execute
    between a wave's launch and its collect, and the overlap telemetry sees
    the hidden window; the sync schedule reports exactly zero overlap."""
    g, pipe, cfg, params = stack
    cost = 0.05
    events = []
    delayed = DelayedRetrieval(pipe, cost_s=cost, events=events)
    eng = RAGServeEngine(delayed, params, cfg, config=ServingConfig(
        slots=2, cache_len=CACHE_LEN, prefetch=True))
    inner_step = eng.engine.step

    def step_logged():
        was_live = eng.engine.live.any()
        out = inner_step()
        if was_live:
            events.append(("decode", time.perf_counter()))
        return out

    eng.engine.step = step_logged
    for u in range(4):  # 2 waves of 2 distinct queries each
        eng.submit(RAGRequest(uid=u, query_emb=np.asarray(g.node_feat[u]),
                              query_text=g.node_text[u], max_new_tokens=8))
    done = eng.run_to_completion()
    assert len(done) == 4

    s = eng.stats()
    assert s["prefetch_waves"] >= 1
    assert s["overlap_seconds"] > 0.0
    assert s["overlap_steps"] >= 1
    assert 0.0 < s["hidden_frac"] <= 1.0

    # event-order oracle: some decode step lies strictly between a wave's
    # launch (dispatch return) and its collect (first force)
    launches = [t for tag, t in events if tag == "launch"]
    forces = [t for tag, t in events if tag == "force"]
    decodes = [t for tag, t in events if tag == "decode"]
    assert len(launches) == len(forces) == 2
    assert any(
        any(lt < dt < ft for dt in decodes)
        for lt, ft in zip(launches, forces)
    )

    # sync schedule on the same delayed pipeline: zero overlap, and the full
    # injected latency shows up as blocking retrieval time
    sync = RAGServeEngine(delayed, params, cfg, config=ServingConfig(
        slots=2, cache_len=CACHE_LEN, prefetch=False))
    for u in range(4):
        sync.submit(RAGRequest(uid=u, query_emb=np.asarray(g.node_feat[u]),
                               query_text=g.node_text[u], max_new_tokens=8))
    sync.run_to_completion()
    ss = sync.stats()
    assert ss["overlap_seconds"] == 0.0 and ss["prefetch_waves"] == 0
    assert ss["retrieval_seconds"] >= 2 * cost * 0.9


def test_inflight_key_not_redispatched(stack):
    """A query whose key is retrieved-but-not-yet-collected defers to the
    in-flight wave instead of dispatching a second retrieval (depth=2 keeps
    two waves in flight, so the launch of wave 1 sees wave 0's keys)."""
    g, pipe, cfg, params = stack
    delayed = DelayedRetrieval(pipe, cost_s=0.02)
    eng = RAGServeEngine(delayed, params, cfg, config=ServingConfig(
        slots=2, cache_len=CACHE_LEN, prefetch=True, prefetch_depth=2))
    qis = [0, 1, 0, 2]  # wave0 = {0, 1}; wave1 = {0 (in flight), 2}
    for u, qi in enumerate(qis):
        eng.submit(RAGRequest(uid=u, query_emb=np.asarray(g.node_feat[qi]),
                              query_text=g.node_text[qi], max_new_tokens=4))
    done = {r.uid: r for r in eng.run_to_completion()}
    assert len(done) == 4
    assert delayed.dispatches == 2  # wave1 dispatched only query 2
    assert eng.retrieved_queries == 3
    assert (eng.cache_hits, eng.cache_misses) == (1, 3)
    assert done[2].cache_hit and not done[0].cache_hit
    assert done[2].out_tokens == done[0].out_tokens
    np.testing.assert_array_equal(done[2].retrieved_nodes,
                                  done[0].retrieved_nodes)
    assert eng.cache.inflight_count == 0  # all keys released at collect


def test_deferred_fallback_when_owner_entry_evicted(stack):
    """If the owner wave's cache entry is evicted between its collect and
    the deferring wave's collect (tiny capacity), the deferred request is
    still served the owner's result — counted as a miss, exactly as the
    sync schedule would count it — and the entry is re-inserted as sync's
    re-retrieval would have done.  Only the dispatch count differs (one
    fewer: retrieval is deterministic so re-dispatching is pure waste)."""
    g, pipe, cfg, params = stack
    # capacity=1: wave0 retrieves {A=0, B=1}; put(B) evicts A before the
    # deferring wave collects
    qis = [0, 1, 0, 2]  # wave0 = {A, B}; wave1 = {A (in flight), C}

    def run(prefetch):
        eng = RAGServeEngine(pipe, params, cfg, config=ServingConfig(
            slots=2, cache_len=CACHE_LEN, cache_capacity=1, prefetch=prefetch,
            prefetch_depth=2))
        for u, qi in enumerate(qis):
            eng.submit(RAGRequest(uid=u,
                                  query_emb=np.asarray(g.node_feat[qi]),
                                  query_text=g.node_text[qi],
                                  max_new_tokens=4))
        return eng, {r.uid: r for r in eng.run_to_completion()}

    sync_eng, sync_done = run(False)
    pf_eng, pf_done = run(True)
    for uid in sync_done:
        assert pf_done[uid].out_tokens == sync_done[uid].out_tokens
        np.testing.assert_array_equal(pf_done[uid].retrieved_nodes,
                                      sync_done[uid].retrieved_nodes)
        assert pf_done[uid].cache_hit == sync_done[uid].cache_hit
    assert pf_eng.cache_hits == sync_eng.cache_hits == 0
    assert pf_eng.cache_misses == sync_eng.cache_misses == 4
    assert not pf_done[2].cache_hit  # served, but honestly not a cache hit
    # sync re-dispatched the evicted key; prefetch served the in-flight copy
    assert sync_eng.retrieved_queries == 4
    assert pf_eng.retrieved_queries == 3
    assert pf_eng.cache.inflight_count == 0


def test_stale_inflight_marker_from_shared_cache_redispatches(stack):
    """An in-flight marker with no owning wave in this engine (a shared
    cache carrying a dead engine's leftover, or another engine's wave) must
    fall through to a normal re-dispatch, not defer to a result that will
    never arrive."""
    from repro.serving import RetrievalCache

    g, pipe, cfg, params = stack
    cache = RetrievalCache(capacity=8)
    cache.mark_inflight(cache.key(np.asarray(g.node_feat[0])))
    eng = RAGServeEngine(pipe, params, cfg, config=ServingConfig(
        slots=2, cache_len=CACHE_LEN, prefetch=True), retrieval_cache=cache)
    eng.submit(RAGRequest(uid=0, query_emb=np.asarray(g.node_feat[0]),
                          query_text=g.node_text[0], max_new_tokens=4))
    done = eng.run_to_completion()
    assert len(done) == 1 and done[0].retrieved_nodes is not None
    assert eng.retrieved_queries == 1  # re-dispatched despite the marker


def test_inflight_keys_released_on_retrieval_failure(stack):
    """A retrieval that fails at force time is *contained*: the engine
    completes (degraded-mode admission decodes a query-only prompt instead
    of raising out of step()), and the failed wave's keys leave the cache's
    in-flight set so later launches re-dispatch, not defer to a dead wave."""
    g, pipe, cfg, params = stack

    class BoomArray:
        def __array__(self, dtype=None, copy=None):
            raise RuntimeError("device boom")

    class BoomSub:
        nodes, mask, dist = BoomArray(), BoomArray(), BoomArray()

    class BoomPipe:
        def __init__(self, inner):
            self.inner = inner

        def __getattr__(self, name):
            return getattr(self.inner, name)

        def retrieve_many(self, q, *, batch_size=None, encoder=None):
            from repro.core.pipeline import RetrievalResult
            return RetrievalResult(sub=BoomSub(), seeds=BoomArray(),
                                   n_valid=int(q.shape[0]))

    eng = RAGServeEngine(BoomPipe(pipe), params, cfg, config=ServingConfig(
        slots=2, cache_len=CACHE_LEN, prefetch=True))
    eng.submit(RAGRequest(uid=0, query_emb=np.asarray(g.node_feat[0]),
                          query_text=g.node_text[0], max_new_tokens=2))
    done = eng.run_to_completion()
    assert len(done) == 1 and done[0].done and done[0].degraded
    assert not done[0].failed and len(done[0].out_tokens) == 2
    assert eng.cache.inflight_count == 0  # released despite the failure
    assert eng.stats()["retrieval_failures"] == 1

    # a dispatch-time failure marks nothing in the first place; with the
    # degraded rung disabled the request fails closed — alone, not the engine
    class BoomDispatch(BoomPipe):
        def retrieve_many(self, q, **kw):
            raise RuntimeError("dispatch boom")

    eng2 = RAGServeEngine(
        BoomDispatch(pipe), params, cfg,
        config=ServingConfig(slots=2, cache_len=CACHE_LEN, prefetch=True,
                             degraded_mode=False))
    eng2.submit(RAGRequest(uid=1, query_emb=np.asarray(g.node_feat[1]),
                           query_text=g.node_text[1], max_new_tokens=2))
    done2 = eng2.run_to_completion()
    assert len(done2) == 1 and done2[0].failed and not done2[0].done
    assert "dispatch boom" in done2[0].error
    assert eng2.cache.inflight_count == 0


# -------------------------------------------------------- admission tickets ----
def test_admission_tickets_survive_request_churn(stack):
    """Many short-lived requests through few slots: every completion maps
    back to the right RAGRequest via its monotonic ticket (id()-keyed
    mapping could silently cross-wire recycled objects)."""
    g, pipe, cfg, params = stack
    eng = RAGServeEngine(pipe, params, cfg,
                         config=ServingConfig(slots=2, cache_len=CACHE_LEN))
    n, seen = 30, {}
    for u in range(n):
        qi = u % 5
        eng.submit(RAGRequest(uid=u, query_emb=np.asarray(g.node_feat[qi]),
                              query_text=g.node_text[qi], max_new_tokens=2))
        if u % 3 == 2:  # churn: drain between small submission bursts
            for r in eng.run_to_completion():
                seen[r.uid] = r
    for r in eng.run_to_completion():
        seen[r.uid] = r
    assert set(seen) == set(range(n))
    assert eng._next_ticket == n  # one fresh ticket per admission, no reuse
    assert not eng._inflight
    # identical queries must have produced identical outputs, churn or not
    for u in range(5, n):
        assert seen[u].out_tokens == seen[u % 5].out_tokens


def test_inner_requests_carry_distinct_tickets(stack):
    g, pipe, cfg, params = stack
    eng = RAGServeEngine(pipe, params, cfg,
                         config=ServingConfig(slots=2, cache_len=CACHE_LEN))
    for u in range(4):
        eng.submit(RAGRequest(uid=u, query_emb=np.asarray(g.node_feat[u]),
                              query_text=g.node_text[u], max_new_tokens=6))
    eng.step()  # admits the first wave; nothing finishes yet
    tickets = [q.ticket for q in list(eng.engine.queue)] + \
        [q.ticket for q in eng.engine.active if q is not None]
    assert len(tickets) == len(set(tickets)) == 2
    assert all(t >= 0 for t in tickets)
    eng.run_to_completion()


# ------------------------------------------------- run_to_completion limits ----
def test_serve_engine_run_to_completion_raises_on_exhaustion():
    cfg = TransformerConfig(
        name="tiny", n_layers=1, d_model=32, n_heads=2, n_kv_heads=2,
        d_head=16, d_ff=64, vocab=64, dtype="float32",
    )
    params = tm.init_params(jax.random.PRNGKey(0), cfg)
    eng = ServeEngine(params, cfg, slots=1, cache_len=48)
    eng.submit(Request(uid=0, prompt_ids=np.asarray([3, 5], np.int32),
                       max_new_tokens=30))
    with pytest.raises(RuntimeError, match="still pending"):
        eng.run_to_completion(max_steps=3)
    done = eng.run_to_completion()  # clean drain picks up where it stopped
    assert [r.uid for r in done] == [0]
    assert not eng.queue and not eng.live.any()
    assert eng.run_to_completion() == []  # empty engine drains immediately


def test_rag_engine_run_to_completion_raises_on_exhaustion(stack):
    g, pipe, cfg, params = stack
    for prefetch in (False, True):
        eng = RAGServeEngine(pipe, params, cfg, config=ServingConfig(
            slots=2, cache_len=CACHE_LEN, prefetch=prefetch))
        for u in range(3):
            eng.submit(RAGRequest(uid=u, query_emb=np.asarray(g.node_feat[u]),
                                  query_text=g.node_text[u],
                                  max_new_tokens=20))
        with pytest.raises(RuntimeError, match="still pending"):
            eng.run_to_completion(max_steps=2)
        done = eng.run_to_completion()
        assert {r.uid for r in done} == {0, 1, 2}
        assert eng._drained()


# ----------------------------------------------------------- configuration ----
def test_prefetch_env_default_and_override(stack, monkeypatch):
    g, pipe, cfg, params = stack

    def make(**kw):
        return RAGServeEngine(pipe, params, cfg, config=ServingConfig(
            slots=2, cache_len=CACHE_LEN, **kw))

    monkeypatch.delenv("RGL_PREFETCH", raising=False)
    assert not ServingConfig.from_env().prefetch
    assert not make().prefetch
    monkeypatch.setenv("RGL_PREFETCH", "1")
    assert ServingConfig.from_env().prefetch
    assert make().prefetch
    assert not ServingConfig.resolve(None, prefetch=False).prefetch
    assert not make(prefetch=False).prefetch  # explicit beats env
    monkeypatch.setenv("RGL_PREFETCH", "0")
    assert not make().prefetch
    assert make(prefetch=True).prefetch
    with pytest.raises(ValueError, match="depth"):
        make(prefetch=True, prefetch_depth=0)


def test_free_slots_backpressure_signal():
    cfg = TransformerConfig(
        name="tiny", n_layers=1, d_model=32, n_heads=2, n_kv_heads=2,
        d_head=16, d_ff=64, vocab=64, dtype="float32",
    )
    params = tm.init_params(jax.random.PRNGKey(0), cfg)
    eng = ServeEngine(params, cfg, slots=2, cache_len=48)
    assert eng.free_slots == 2
    eng.submit(Request(uid=0, prompt_ids=np.asarray([3], np.int32),
                       max_new_tokens=4))
    assert eng.free_slots == 1  # queued work claims a future slot
    eng.step()
    assert eng.free_slots == 1  # admitted: one live slot, empty queue
    eng.run_to_completion()
    assert eng.free_slots == 2
