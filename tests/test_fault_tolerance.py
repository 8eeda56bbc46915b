"""Fault-tolerant serving: seeded fault injection, retry isolation, the
graceful-degradation ladder, deadlines + load shedding, abort/drain
reconciliation, and the chaos soak across the admission/decode matrix."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import BruteIndex, GraphTokenizer, PipelineConfig, \
    RGLPipeline, Vocab
from repro.graph import csr_to_ell, generators
from repro.models.transformer import TransformerConfig, model as tm
from repro.serving import (
    CachedRetrieval, DelayedRetrieval, FaultyRetrieval, RAGRequest,
    RAGServeEngine, RetrievalCache, RetrievalFault, ServingConfig,
)

N_NODES = 120
CACHE_LEN = 96
SLOTS = 3


@pytest.fixture(scope="module")
def stack():
    g = generators.citation_graph(N_NODES, avg_deg=6, seed=7)
    ell = csr_to_ell(g)
    emb = jnp.asarray(g.node_feat)
    vocab = Vocab.build(g.node_text)
    tok = GraphTokenizer(vocab, max_len=64, node_budget=6)
    pipe = RGLPipeline(
        graph=ell, index=BruteIndex.build(emb), node_emb=emb, tokenizer=tok,
        node_text=g.node_text,
        config=PipelineConfig(strategy="bfs", k_seeds=3, max_hops=2,
                              max_nodes=16, filter_budget=8),
    )
    cfg = TransformerConfig(
        name="fault-t", n_layers=2, d_model=32, n_heads=2, n_kv_heads=2,
        d_head=16, d_ff=64, vocab=vocab.size, dtype="float32",
    )
    params = tm.init_params(jax.random.PRNGKey(0), cfg)
    return g, pipe, cfg, params


def _req(g, qi, uid=0, max_new=4, **kw):
    return RAGRequest(uid=uid, query_emb=np.asarray(g.node_feat[qi]),
                      query_text=g.node_text[qi], max_new_tokens=max_new,
                      **kw)


def _assert_clean(eng):
    """No leaked state in any layer once the engine settles."""
    assert eng.cache.inflight_count == 0
    assert eng.prefetcher.in_flight == 0
    assert not eng._inflight and not eng._terminal
    assert not eng.engine.queue and not eng.engine.live.any()
    inner = eng.engine
    if inner.paged_kv:
        # all blocks returned except those deliberately held by retrieval-
        # cache prefill pins (prefix sharing keeps hot prompts resident)
        assert inner._free_host == inner.pool_blocks - inner.kv_pinned_blocks
        assert int(inner._ntab.sum()) == 0


# -------------------------------------------------------- fault scheduling ----
def test_fault_schedule_is_seeded_and_deterministic(stack):
    g, pipe, *_ = stack
    a = FaultyRetrieval(pipe, seed=3, fault_rate=0.5)
    b = FaultyRetrieval(pipe, seed=3, fault_rate=0.5)
    c = FaultyRetrieval(pipe, seed=4, fault_rate=0.5)
    rows = [np.asarray(g.node_feat[i]) for i in range(40)]
    sched_a = [a.fault_of(r) for r in rows]
    assert sched_a == [b.fault_of(r) for r in rows]  # same seed, same fate
    assert sched_a != [c.fault_of(r) for r in rows]  # seed changes the draw
    hit = [s for s in sched_a if s is not None]
    assert hit and len(hit) < len(rows)  # some faulty, some clean
    assert set(hit) <= set(FaultyRetrieval.FAULT_TYPES)
    none = FaultyRetrieval(pipe, seed=3, fault_rate=0.0)
    assert all(none.fault_of(r) is None for r in rows)
    with pytest.raises(ValueError, match="fault_rate"):
        FaultyRetrieval(pipe, fault_rate=1.5)
    with pytest.raises(ValueError, match="unknown fault types"):
        FaultyRetrieval(pipe, fault_types=("gremlin",))


# ------------------------------------------------------------ retry layer ----
def test_transient_fault_recovers_via_retry_bitwise(stack):
    """A row that faults once and then heals (fails_per_row=1) recovers
    through the retry path: every request completes un-degraded with
    outputs bitwise identical to a no-fault run."""
    g, pipe, cfg, params = stack

    def run(src, retries):
        eng = RAGServeEngine(src, params, cfg, config=ServingConfig(
            slots=SLOTS, cache_len=CACHE_LEN, prefetch=True,
            max_retries=retries, retrieval_timeout_s=0.05))
        for u in range(6):
            eng.submit(_req(g, u, uid=u))
        done = {r.uid: r for r in eng.run_to_completion()}
        _assert_clean(eng)
        return eng, done

    _, clean = run(pipe, 0)
    faulty = FaultyRetrieval(pipe, seed=11, fault_rate=0.5, fails_per_row=1)
    assert any(faulty.fault_of(np.asarray(g.node_feat[u])) for u in range(6))
    eng, done = run(faulty, 2)
    assert len(done) == 6
    for u in range(6):
        assert done[u].done and not done[u].failed and not done[u].degraded
        assert done[u].out_tokens == clean[u].out_tokens
        np.testing.assert_array_equal(done[u].retrieved_nodes,
                                      clean[u].retrieved_nodes)
    s = eng.stats()
    assert s["retries"] > 0 and s["retrieval_failures"] == 0
    assert s["failed"] == s["degraded"] == 0


def test_permanent_fault_isolated_to_its_own_request(stack):
    """One permanently-poisoned row degrades only its own request; its
    wave-mates complete with outputs bitwise identical to a no-fault run
    (the retry layer re-dispatches failed miss-groups one by one)."""
    g, pipe, cfg, params = stack
    faulty = FaultyRetrieval(pipe, seed=11, fault_rate=0.5,
                             fault_types=("corrupt",))
    sched = {u: faulty.fault_of(np.asarray(g.node_feat[u])) for u in range(6)}
    bad = {u for u, s in sched.items() if s is not None}
    assert bad and len(bad) < 6  # mixed wave compositions

    def run(src):
        eng = RAGServeEngine(src, params, cfg, config=ServingConfig(
            slots=SLOTS, cache_len=CACHE_LEN, prefetch=True, max_retries=1,
            retrieval_timeout_s=0.05))
        for u in range(6):
            eng.submit(_req(g, u, uid=u))
        done = {r.uid: r for r in eng.run_to_completion()}
        _assert_clean(eng)
        return eng, done

    _, clean = run(pipe)
    eng, done = run(faulty)
    for u in range(6):
        if u in bad:
            assert done[u].degraded and done[u].done
            assert done[u].retrieved_nodes.size == 0
        else:
            assert not done[u].degraded
            assert done[u].out_tokens == clean[u].out_tokens
            np.testing.assert_array_equal(done[u].retrieved_nodes,
                                          clean[u].retrieved_nodes)
    assert eng.stats()["degraded"] == len(bad)


# ----------------------------------------------------- degradation ladder ----
def test_ladder_rung_stale_cache_entry(stack):
    """Retry exhaustion falls back to a TTL-expired but still-resident cache
    entry before considering degraded mode."""
    g, pipe, cfg, params = stack
    now = [0.0]
    cache = RetrievalCache(capacity=8, ttl=10.0, now_fn=lambda: now[0])
    ok = RAGServeEngine(pipe, params, cfg,
                        config=ServingConfig(slots=2, cache_len=CACHE_LEN),
                        retrieval_cache=cache)
    ok.submit(_req(g, 0, uid=0))
    clean = ok.run_to_completion()[0]
    assert cache.peek_stale(np.asarray(g.node_feat[0])) is not None

    now[0] = 100.0  # entry is now TTL-expired (but resident)
    boom = FaultyRetrieval(pipe, seed=0, fault_rate=1.0,
                           fault_types=("dispatch",))
    eng = RAGServeEngine(boom, params, cfg,
                         config=ServingConfig(slots=2, cache_len=CACHE_LEN),
                         retrieval_cache=cache)
    eng.submit(_req(g, 0, uid=1))
    r = eng.run_to_completion()[0]
    assert r.done and r.stale and not r.degraded and not r.failed
    assert r.out_tokens == clean.out_tokens  # same entry -> same decode
    np.testing.assert_array_equal(r.retrieved_nodes, clean.retrieved_nodes)
    assert eng.stats()["stale_served"] == 1 and eng.stats()["degraded"] == 0
    _assert_clean(eng)


@pytest.mark.parametrize("ftype", ["dispatch", "force", "stuck", "corrupt"])
def test_ladder_rung_degraded_per_fault_type(stack, ftype):
    """With no cache fallback, every fault type exhausts into retrieval-free
    decode: the request completes on a query-only prompt."""
    g, pipe, cfg, params = stack
    boom = FaultyRetrieval(pipe, seed=0, fault_rate=1.0, fault_types=(ftype,))
    eng = RAGServeEngine(boom, params, cfg, config=ServingConfig(
        slots=2, cache_len=CACHE_LEN, prefetch=True, max_retries=1,
        retrieval_timeout_s=0.05))
    eng.submit(_req(g, 0, uid=0, max_new=3))
    r = eng.run_to_completion()[0]
    assert r.done and r.degraded and not r.failed
    assert len(r.out_tokens) == 3 and r.retrieved_nodes.size == 0
    s = eng.stats()
    assert s["degraded"] == 1 and s["retrieval_failures"] >= 1
    assert boom.injected[ftype] > 0
    _assert_clean(eng)


def test_ladder_rung_failed_when_degraded_disabled(stack):
    g, pipe, cfg, params = stack
    boom = FaultyRetrieval(pipe, seed=0, fault_rate=1.0,
                           fault_types=("corrupt",))
    eng = RAGServeEngine(boom, params, cfg, config=ServingConfig(
        slots=2, cache_len=CACHE_LEN, degraded_mode=False))
    eng.submit(_req(g, 0, uid=7))
    r = eng.run_to_completion()[0]
    assert r.failed and not r.done and not r.degraded
    assert "corrupt" in r.error and "node id out of range" in r.error
    assert eng.stats()["failed"] == 1
    _assert_clean(eng)


def test_stuck_row_without_timeout_fails_loud_not_hung(stack, monkeypatch):
    """An unconfigured timeout over a never-ready row must not deadlock the
    engine: forcing the stuck array raises (contained by the ladder)."""
    g, pipe, cfg, params = stack
    # pin the no-timeout configuration even when the CI fault-injection
    # cell arms RGL_RETRIEVAL_TIMEOUT engine-wide
    monkeypatch.delenv("RGL_RETRIEVAL_TIMEOUT", raising=False)
    monkeypatch.delenv("RGL_RETRIES", raising=False)
    boom = FaultyRetrieval(pipe, seed=0, fault_rate=1.0,
                           fault_types=("stuck",))
    eng = RAGServeEngine(boom, params, cfg,
                         config=ServingConfig(slots=2, cache_len=CACHE_LEN))
    eng.submit(_req(g, 0, uid=0))
    r = eng.run_to_completion()[0]
    assert r.done and r.degraded
    with pytest.raises(RetrievalFault, match="stuck"):
        np.asarray(boom.retrieve_many(np.asarray(g.node_feat[0])).seeds)


# ------------------------------------------------- deadlines & overload ----
def test_deadline_expired_requests_shed_never_dispatched(stack):
    g, pipe, cfg, params = stack
    now = [0.0]
    eng = RAGServeEngine(pipe, params, cfg,
                         config=ServingConfig(slots=2, cache_len=CACHE_LEN),
                         now_fn=lambda: now[0])
    eng.submit(_req(g, 0, uid=0, deadline_s=5.0))
    eng.submit(_req(g, 1, uid=1))  # no deadline: must still complete
    now[0] = 6.0  # past uid=0's deadline before any step ran
    done = {r.uid: r for r in eng.run_to_completion()}
    assert done[0].shed and not done[0].done and "deadline" in done[0].error
    assert done[1].done and not done[1].shed
    assert eng.prefetcher.queries == 1  # the shed request never dispatched
    assert eng.stats()["shed"] == 1
    _assert_clean(eng)


def test_default_deadline_env_and_kwarg(stack, monkeypatch):
    g, pipe, cfg, params = stack
    now = [0.0]
    eng = RAGServeEngine(pipe, params, cfg, config=ServingConfig(
        slots=2, cache_len=CACHE_LEN, default_deadline_s=2.0),
        now_fn=lambda: now[0])
    eng.submit(_req(g, 0, uid=0))
    assert eng.pending[0].deadline_at == 2.0
    monkeypatch.setenv("RGL_DEADLINE", "7.5")
    assert ServingConfig.from_env().default_deadline_s == 7.5
    assert ServingConfig.resolve(
        None, default_deadline_s=2.0).default_deadline_s == 2.0
    eng2 = RAGServeEngine(pipe, params, cfg,
                          config=ServingConfig(slots=2, cache_len=CACHE_LEN),
                          now_fn=lambda: now[0])
    assert eng2.default_deadline_s == 7.5
    monkeypatch.setenv("RGL_DEADLINE", "junk")
    with pytest.raises(ValueError, match="RGL_DEADLINE"):
        ServingConfig.from_env()
    with pytest.raises(ValueError, match="RGL_DEADLINE"):
        RAGServeEngine(pipe, params, cfg,
                       config=ServingConfig(slots=2, cache_len=CACHE_LEN))


def test_bounded_pending_queue_shed_policies(stack):
    g, pipe, cfg, params = stack
    # reject: the newcomer is refused
    eng = RAGServeEngine(pipe, params, cfg, config=ServingConfig(
        slots=2, cache_len=CACHE_LEN, max_pending=2, shed_policy="reject"))
    assert eng.submit(_req(g, 0, uid=0))
    assert eng.submit(_req(g, 1, uid=1))
    assert not eng.submit(_req(g, 2, uid=2))  # queue full -> shed on arrival
    done = {r.uid: r for r in eng.run_to_completion()}
    assert done[0].done and done[1].done
    assert done[2].shed and "reject" in done[2].error
    assert eng.stats()["shed"] == 1

    # evict-oldest: the oldest pending request makes room
    eng2 = RAGServeEngine(pipe, params, cfg, config=ServingConfig(
        slots=2, cache_len=CACHE_LEN, max_pending=2,
        shed_policy="evict-oldest"))
    for u in range(3):
        eng2.submit(_req(g, u, uid=u))
    done2 = {r.uid: r for r in eng2.run_to_completion()}
    assert done2[0].shed and "evict-oldest" in done2[0].error
    assert done2[1].done and done2[2].done
    with pytest.raises(ValueError, match="shed_policy"):
        RAGServeEngine(pipe, params, cfg, config=ServingConfig(
            slots=2, cache_len=CACHE_LEN, shed_policy="drop-newest"))


def test_submit_validation_rejects_poison_requests(stack):
    g, pipe, cfg, params = stack
    eng = RAGServeEngine(pipe, params, cfg,
                         config=ServingConfig(slots=2, cache_len=CACHE_LEN))
    good = np.asarray(g.node_feat[0])
    nan = good.copy()
    nan[0] = np.nan
    with pytest.raises(ValueError, match="request 3.*NaN"):
        eng.submit(RAGRequest(uid=3, query_emb=nan, query_text="q"))
    with pytest.raises(ValueError, match="request 4.*1-D"):
        eng.submit(RAGRequest(uid=4, query_emb=np.stack([good, good]),
                              query_text="q"))
    with pytest.raises(ValueError, match="request 5.*dim"):
        eng.submit(RAGRequest(uid=5, query_emb=good[:3], query_text="q"))
    with pytest.raises(ValueError, match="request 6.*query_text"):
        eng.submit(RAGRequest(uid=6, query_emb=good, query_text="   "))
    with pytest.raises(ValueError, match="request 7.*max_new_tokens"):
        eng.submit(RAGRequest(uid=7, query_emb=good, query_text="q",
                              max_new_tokens=0))
    with pytest.raises(ValueError, match="request 8.*deadline_s"):
        eng.submit(RAGRequest(uid=8, query_emb=good, query_text="q",
                              deadline_s=-1.0))
    assert not eng.pending and eng.run_to_completion() == []


# ------------------------------------------------------- abort & recovery ----
@pytest.mark.parametrize("paged", [False, True])
def test_abort_reconciles_all_layers_and_engine_reusable(stack, paged):
    """abort() mid-flight retires live slots (returning paged KV blocks),
    drops in-flight waves (releasing their cache keys), sheds the queue, and
    leaves the engine able to serve a fresh workload correctly."""
    g, pipe, cfg, params = stack
    eng = RAGServeEngine(pipe, params, cfg, config=ServingConfig(
        slots=2, cache_len=CACHE_LEN, prefetch=True, prefetch_depth=2,
        paged_kv=paged))
    for u in range(6):
        eng.submit(_req(g, u, uid=u, max_new=8))
    eng.step()  # some admitted + decoding, some in flight, some pending
    out = eng.abort(reason="test teardown")
    done = {r.uid: r for r in out}
    assert set(done) == set(range(6))
    for r in done.values():
        assert (r.failed or r.shed) and r.error is not None
    _assert_clean(eng)

    # fresh workload on the same engine matches a clean engine's outputs
    for u in range(3):
        eng.submit(_req(g, u, uid=100 + u))
    redo = {r.uid: r for r in eng.run_to_completion()}
    ref_eng = RAGServeEngine(pipe, params, cfg, config=ServingConfig(
        slots=2, cache_len=CACHE_LEN, paged_kv=paged))
    for u in range(3):
        ref_eng.submit(_req(g, u, uid=100 + u))
    ref = {r.uid: r for r in ref_eng.run_to_completion()}
    for uid in ref:
        assert redo[uid].done and redo[uid].out_tokens == ref[uid].out_tokens
    _assert_clean(eng)


def test_recovery_after_run_to_completion_exhaustion(stack):
    """The PR-motivating bug: a run_to_completion RuntimeError used to leave
    the engine unrecoverable.  abort() reconciles; drain() never raises."""
    g, pipe, cfg, params = stack
    eng = RAGServeEngine(pipe, params, cfg, config=ServingConfig(
        slots=2, cache_len=CACHE_LEN, prefetch=True))
    for u in range(4):
        eng.submit(_req(g, u, uid=u, max_new=20))
    with pytest.raises(RuntimeError, match="still pending"):
        eng.run_to_completion(max_steps=2)
    leftovers = eng.abort(reason="exhausted")
    assert leftovers and all(r.failed or r.shed for r in leftovers)
    _assert_clean(eng)
    eng.submit(_req(g, 0, uid=50))
    done = eng.run_to_completion()
    assert len(done) == 1 and done[0].done
    _assert_clean(eng)

    # drain() folds the same recovery into one call
    eng2 = RAGServeEngine(pipe, params, cfg,
                          config=ServingConfig(slots=2, cache_len=CACHE_LEN))
    for u in range(4):
        eng2.submit(_req(g, u, uid=u, max_new=20))
    out = eng2.drain(max_steps=2)
    assert len(out) == 4 and any(r.failed or r.shed for r in out)
    _assert_clean(eng2)


def test_mid_flight_fault_then_fresh_workload(stack):
    """Regression (satellite): after a contained mid-flight fault the SAME
    engine must complete a fresh workload with clean outputs."""
    g, pipe, cfg, params = stack
    faulty = FaultyRetrieval(pipe, seed=5, fault_rate=1.0,
                             fault_types=("force",), fails_per_row=1)
    eng = RAGServeEngine(faulty, params, cfg, config=ServingConfig(
        slots=2, cache_len=CACHE_LEN, prefetch=True,
        max_retries=0))  # first fault goes straight to ladder
    eng.submit(_req(g, 0, uid=0))
    first = eng.run_to_completion()[0]
    assert first.done and first.degraded
    _assert_clean(eng)
    # the row healed (fails_per_row=1); the same engine now serves it fully
    eng.submit(_req(g, 0, uid=1))
    second = eng.run_to_completion()[0]
    assert second.done and not second.degraded
    assert second.retrieved_nodes.size > 0
    _assert_clean(eng)


def test_abort_mid_launch_continuous_wave(stack):
    """abort() invoked while continuous-admission waves sit between launch
    and collect: every layer reconciles (no leaked slots, waves, or
    in-flight cache keys) and the engine serves a fresh workload after."""
    g, pipe, cfg, params = stack
    clock = [0.0]
    sleep = lambda s: clock.__setitem__(0, clock[0] + s)  # noqa: E731
    feat0 = np.asarray(g.node_feat[0], np.float32)

    def cost(row):  # row 0 lands instantly; every other row never does
        return 0.0 if np.allclose(row, feat0) else np.inf

    delayed = DelayedRetrieval(pipe, cost_s=0.0, cost_fn=cost,
                               now_fn=lambda: clock[0], sleep_fn=sleep)
    eng = RAGServeEngine(delayed, params, cfg, config=ServingConfig(
        slots=SLOTS, cache_len=CACHE_LEN, prefetch=True,
        admission="continuous"), now_fn=lambda: clock[0], sleep_fn=sleep)
    for u in range(3):
        eng.submit(_req(g, u, uid=u))
    eng.step()
    # uid 0's wave collected + admitted (arena non-idle); uids 1-2 are
    # launched-but-uncollected, their keys registered in flight
    assert int(eng.engine.live.sum()) == 1
    assert eng.prefetcher.in_flight == 2
    assert eng.cache.inflight_count == 2
    out = {r.uid: r for r in eng.abort(reason="mid-launch abort")}
    assert set(out) == {0, 1, 2}  # exactly one terminal per request
    assert all(r.failed or r.shed for r in out.values())
    _assert_clean(eng)
    # the same engine serves a fresh (instant-retrieval) workload cleanly
    eng.submit(_req(g, 0, uid=9))
    done = eng.run_to_completion()
    assert len(done) == 1 and done[0].done
    _assert_clean(eng)


def test_cache_stale_counters(stack):
    """peek_stale is observable at the cache tier: stale_hits counts
    resident (even TTL-expired) fallbacks, stale_misses counts empty-handed
    lookups — neither touches the hit/miss counters or recency."""
    g, *_ = stack
    now = [0.0]
    cache = RetrievalCache(capacity=4, ttl=10.0, now_fn=lambda: now[0])
    emb = np.asarray(g.node_feat[0])
    assert cache.peek_stale(emb) is None
    s = cache.stats()
    assert s["stale_misses"] == 1 and s["stale_hits"] == 0
    entry = CachedRetrieval(
        nodes=np.arange(4, dtype=np.int32), mask=np.ones(4, bool),
        dist=np.zeros(4, np.int32), seeds=np.arange(2, dtype=np.int32),
    )
    cache.put(emb, entry)
    assert cache.peek_stale(emb) is entry
    now[0] = 100.0  # TTL-expired: invisible to get, served by peek_stale
    assert cache.get(emb) is None
    assert cache.peek_stale(emb) is entry
    s = cache.stats()
    assert s["stale_hits"] == 2 and s["stale_misses"] == 1
    assert s["hits"] == 0 and s["misses"] == 1  # peeks counted separately


# -------------------------------------------------------------- chaos soak ----
@pytest.mark.slow
@pytest.mark.parametrize("prefetch", [False, True])
@pytest.mark.parametrize("admission", ["wave", "continuous"])
@pytest.mark.parametrize("paged", [False, True])
def test_chaos_soak_matrix(stack, prefetch, admission, paged):
    """Seeded chaos across the admission/decode matrix: all fault types at a
    25% rate over a repeat-heavy stream.  Invariants: step() never raises,
    every request reaches exactly one terminal state, nothing leaks in any
    layer, counters account for every submitted request, and the fault-free
    subset is bitwise identical to a no-fault run."""
    g, pipe, cfg, params = stack
    n = 14
    q_ids = [u % 7 for u in range(n)]  # repeats: cache hits + dedup + stale

    def run(src, **kw):
        eng = RAGServeEngine(src, params, cfg, config=ServingConfig(
            slots=SLOTS, cache_len=CACHE_LEN, prefetch=prefetch,
            admission=admission, paged_kv=paged, max_retries=1,
            retrieval_timeout_s=0.05, **kw))
        for u, qi in enumerate(q_ids):
            eng.submit(_req(g, qi, uid=u, max_new=4))
        done = {r.uid: r for r in eng.drain()}
        _assert_clean(eng)
        return eng, done

    _, clean = run(pipe)
    faulty = FaultyRetrieval(pipe, seed=23, fault_rate=0.25)
    bad_q = {qi for qi in set(q_ids)
             if faulty.fault_of(np.asarray(g.node_feat[qi])) is not None}
    assert bad_q and len(bad_q) < 7
    eng, done = run(faulty)

    assert set(done) == set(range(n))  # every request terminal, exactly once
    s = eng.stats()
    n_done = sum(r.done and not r.failed for r in done.values())
    assert n_done + s["failed"] + s["shed"] == n  # accounting closes
    assert n_done > 0
    for u, qi in enumerate(q_ids):
        r = done[u]
        assert r.done or r.failed or r.shed
        if qi not in bad_q and r.done and not r.degraded and not r.stale:
            assert r.out_tokens == clean[u].out_tokens
            np.testing.assert_array_equal(r.retrieved_nodes,
                                          clean[u].retrieved_nodes)
    # fault-free requests are never collateral damage of faulty wave-mates
    for u, qi in enumerate(q_ids):
        if qi not in bad_q:
            assert done[u].done and not done[u].failed
            assert not done[u].degraded and not done[u].stale


@pytest.mark.slow
@pytest.mark.parametrize("admission", ["wave", "continuous"])
def test_chaos_soak_with_mutations(stack, admission):
    """Mutation cell of the chaos soak: seeded retrieval faults AND seeded
    graph mutations interleave with serving steps.  Invariants: step() and
    apply_mutations() never raise, every request reaches exactly one
    terminal state, no layer leaks, the cache epoch tracks the store, and
    the post-soak compacted store is bitwise identical to a from-scratch
    rebuild of its merged corpus."""
    from repro.core import MutableGraphStore, MutationBatch
    from repro.graph import CSRGraph

    g, _, cfg, params = stack
    store = MutableGraphStore.build(g, index_kind="brute")
    pipe = store.make_pipeline(
        tokenizer=GraphTokenizer(Vocab.build(g.node_text), max_len=64,
                                 node_budget=6),
        config=PipelineConfig(strategy="bfs", k_seeds=3, max_hops=2,
                              max_nodes=16, filter_budget=8),
    )
    faulty = FaultyRetrieval(pipe, seed=23, fault_rate=0.25)
    eng = RAGServeEngine(faulty, params, cfg, config=ServingConfig(
        slots=SLOTS, cache_len=CACHE_LEN, prefetch=True, admission=admission,
        max_retries=1, retrieval_timeout_s=0.05, compact_every=6))
    n = 14
    for u in range(n):
        eng.submit(_req(g, u % 7, uid=u, max_new=4))

    rng = np.random.default_rng(29)
    done, steps = {}, 0
    while not eng._drained() and steps < 400:
        for r in eng.step():
            assert r.uid not in done  # exactly one terminal per request
            done[r.uid] = r
        steps += 1
        if rng.random() < 0.5:  # ~10% write mix relative to decode steps
            n_nodes = store.n_nodes
            alive = np.flatnonzero(np.asarray(store.alive)[:n_nodes])
            u, v = int(rng.choice(alive)), int(rng.choice(alive))
            roll = rng.random()
            if roll < 0.45:
                batch = MutationBatch(add_edges=np.array([[u, v]]))
            elif roll < 0.9:
                batch = MutationBatch(del_edges=np.array([[u, v]]))
            else:
                batch = MutationBatch(
                    add_node_feat=rng.normal(
                        size=(1, g.node_feat.shape[1])).astype(np.float32),
                    add_node_text=[f"chaos {n_nodes}"],
                    add_edges=np.array([[n_nodes, u]]),
                )
            eng.apply_mutations(batch)

    assert set(done) == set(range(n))
    s = eng.stats()
    n_done = sum(r.done and not r.failed for r in done.values())
    assert n_done + s["failed"] + s["shed"] == n
    assert n_done > 0
    assert store.epoch >= 1 and s["mutation_batches"] == store.batches_applied
    assert eng.cache.graph_epoch == store.epoch
    _assert_clean(eng)

    # the soaked store still compacts to rebuild-equivalent state
    store.compact()
    src, dst = store.delta.live_edge_list()
    g2 = CSRGraph.from_edges(
        src, dst, store.n_nodes,
        node_feat=store.h_feat[:store.n_nodes].copy(),
        node_text=list(store.node_text[:store.n_nodes]))
    ref = MutableGraphStore.build(g2, index_kind="brute", alive=store.alive,
                                  active=True)
    np.testing.assert_array_equal(np.asarray(store.graph.nbr),
                                  np.asarray(ref.graph.nbr))
    np.testing.assert_array_equal(np.asarray(store.node_emb),
                                  np.asarray(ref.node_emb))
