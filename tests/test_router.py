"""Multi-replica serving: health-aware routing, circuit breaking, crash
failover with bitwise parity, front-door shedding, the shared retrieval
tier's cross-replica single flight, and the replica-level chaos soak."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import BruteIndex, GraphTokenizer, PipelineConfig, \
    RGLPipeline, Vocab
from repro.graph import csr_to_ell, generators
from repro.models.transformer import TransformerConfig, model as tm
from repro.serving import (
    DelayedRetrieval, FaultyReplica, FaultyRetrieval, RAGRequest,
    RAGServeEngine, ReplicaFault, ReplicaRouter, RetrievalCache, ServingConfig,
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


def _engine(pipe, params, cfg, **kw):
    """A replica: engine arguments (shared cache, clocks) pass through,
    every other keyword is a ServingConfig field."""
    engine_kw = {k: kw.pop(k) for k in ("retrieval_cache", "now_fn",
                                         "sleep_fn") if k in kw}
    kw.setdefault("slots", SLOTS)
    kw.setdefault("cache_len", CACHE_LEN)
    kw.setdefault("max_pending", 0)
    kw.setdefault("max_retries", 1)
    kw.setdefault("retrieval_timeout_s", 1.0)
    return RAGServeEngine(pipe, params, cfg, config=ServingConfig(**kw),
                          **engine_kw)


def _fleet(pipe, params, cfg, n, cache=None, **kw):
    cache = cache if cache is not None else RetrievalCache(capacity=256)
    return [_engine(pipe, params, cfg, retrieval_cache=cache, **kw)
            for _ in range(n)], cache


def _reference(pipe, params, cfg, reqs):
    """Single clean engine, own cache: the parity oracle."""
    eng = _engine(pipe, params, cfg)
    for r in reqs:
        eng.submit(r)
    return {r.uid: r for r in eng.run_to_completion()}


def _assert_fleet_clean(router, cache):
    """Zero leaked state in any layer of any replica after the fleet
    settles — including crashed (aborted) replicas."""
    assert cache.inflight_count == 0
    assert not router.pending and not router._terminal
    for st in router.replicas:
        eng = st.engine
        if isinstance(eng, FaultyReplica):
            eng = eng.engine  # unwrap to the RAGServeEngine
        assert not st.assigned
        assert eng.prefetcher.in_flight == 0
        assert not eng._inflight and not eng._terminal
        assert not eng.engine.queue and not eng.engine.live.any()
        inner = eng.engine
        if inner.paged_kv:
            # prefill pins (prefix sharing) may hold blocks by design
            assert inner._free_host == \
                inner.pool_blocks - inner.kv_pinned_blocks
            assert int(inner._ntab.sum()) == 0


# ------------------------------------------------------------- validation ----
def test_router_and_faulty_replica_validation(stack):
    g, pipe, cfg, params = stack
    eng = _engine(pipe, params, cfg)
    with pytest.raises(ValueError, match="at least one replica"):
        ReplicaRouter([])
    with pytest.raises(ValueError, match="shed_policy"):
        ReplicaRouter([eng], shed_policy="drop-newest")
    with pytest.raises(ValueError, match="max_pending"):
        ReplicaRouter([eng], max_pending=-1)
    with pytest.raises(ValueError, match="trip_threshold"):
        ReplicaRouter([eng], trip_threshold=0)
    with pytest.raises(ValueError, match="mode"):
        FaultyReplica(eng, mode="gremlin")
    with pytest.raises(ValueError, match="heal_step"):
        FaultyReplica(eng, mode="flap", crash_step=3, heal_step=2)
    with pytest.raises(ValueError, match="heal_step"):
        FaultyReplica(eng, mode="crash", heal_step=5)
    # malformed requests are refused at the router's front door
    router = ReplicaRouter([eng])
    bad = np.asarray(g.node_feat[0]).copy()
    bad[0] = np.nan
    with pytest.raises(ValueError, match="NaN"):
        router.submit(RAGRequest(uid=0, query_emb=bad, query_text="q"))
    assert not router.pending


def test_faulty_replica_modes(stack):
    g, pipe, cfg, params = stack
    eng = _engine(pipe, params, cfg)
    crash = FaultyReplica(eng, mode="crash", crash_step=1)
    assert crash.slots == SLOTS  # delegation
    crash.step()  # step 0: healthy
    with pytest.raises(ReplicaFault, match="crash fault at replica step 1"):
        crash.step()
    with pytest.raises(ReplicaFault):
        crash.step()  # crash is permanent
    assert crash.steps == 3 and crash.faults_injected == 2

    flap = FaultyReplica(eng, mode="flap", crash_step=0, heal_step=2)
    for _ in range(2):
        with pytest.raises(ReplicaFault):
            flap.step()
    flap.step()  # healed
    assert flap.faults_injected == 2

    clock = [0.0]
    grey = FaultyReplica(eng, mode="grey", slow_s=0.5,
                         sleep_fn=lambda s: clock.__setitem__(0, clock[0] + s))
    grey.step()
    assert clock[0] == 0.5 and grey.faults_injected == 0


# ------------------------------------------------------- routing & parity ----
def test_load_balanced_routing_matches_single_replica_bitwise(stack):
    """A healthy 3-replica fleet spreads load and produces outputs bitwise
    identical to one clean engine serving the same stream."""
    g, pipe, cfg, params = stack
    n = 9
    ref = _reference(pipe, params, cfg, [_req(g, u % 6, uid=u)
                                         for u in range(n)])
    replicas, cache = _fleet(pipe, params, cfg, 3)
    router = ReplicaRouter(replicas)
    for u in range(n):
        router.submit(_req(g, u % 6, uid=u))
    done = {r.uid: r for r in router.run_to_completion()}
    assert set(done) == set(range(n))
    for u in range(n):
        assert done[u].done and not done[u].failed
        assert done[u].out_tokens == ref[u].out_tokens
        np.testing.assert_array_equal(done[u].retrieved_nodes,
                                      ref[u].retrieved_nodes)
    s = router.stats()
    assert s["duplicate_deliveries"] == 0 and s["failovers"] == 0
    # least-loaded + round-robin: every replica served some of the stream
    assert all(r["dispatched"] > 0 for r in s["per_replica"])
    _assert_fleet_clean(router, cache)


def test_crash_failover_redispatches_bitwise(stack):
    """One replica crashes mid-run: its in-flight requests are re-dispatched
    onto survivors and complete bitwise identical to a clean single-replica
    run — replica failure is survived, not surfaced."""
    g, pipe, cfg, params = stack
    n = 9
    # max_new long enough that the crashed replica still holds in-flight
    # work at crash_step even when spec decode commits multiple tokens/step
    ref = _reference(pipe, params, cfg, [_req(g, u % 6, uid=u, max_new=12)
                                         for u in range(n)])
    replicas, cache = _fleet(pipe, params, cfg, 3)
    replicas[1] = FaultyReplica(replicas[1], mode="crash", crash_step=2)
    router = ReplicaRouter(replicas, cooldown_steps=50)
    for u in range(n):
        router.submit(_req(g, u % 6, uid=u, max_new=12))
    done = {r.uid: r for r in router.run_to_completion()}
    assert set(done) == set(range(n))  # exactly-once, fleet-wide
    for u in range(n):
        assert done[u].done and not done[u].failed, done[u].error
        assert done[u].out_tokens == ref[u].out_tokens
        np.testing.assert_array_equal(done[u].retrieved_nodes,
                                      ref[u].retrieved_nodes)
    s = router.stats()
    assert s["failovers"] == 1 and s["redispatched"] > 0
    assert s["stranded"] == 0 and s["duplicate_deliveries"] == 0
    assert s["per_replica"][1]["circuit"] == "crashed"
    assert s["per_replica"][1]["crashes"] == 1
    _assert_fleet_clean(router, cache)


def test_naive_router_strands_crashed_replicas_requests(stack):
    """failover=False is the baseline the tentpole beats: the crashed
    replica's requests are delivered failed instead of re-dispatched."""
    g, pipe, cfg, params = stack
    n = 9
    replicas, cache = _fleet(pipe, params, cfg, 3)
    replicas[1] = FaultyReplica(replicas[1], mode="crash", crash_step=2)
    router = ReplicaRouter(replicas, failover=False, cooldown_steps=50)
    for u in range(n):
        router.submit(_req(g, u % 6, uid=u, max_new=12))
    done = {r.uid: r for r in router.run_to_completion()}
    assert set(done) == set(range(n))  # still exactly-once
    stranded = [r for r in done.values() if r.failed]
    served = [r for r in done.values() if r.done]
    assert stranded and len(stranded) == router.stats()["stranded"]
    assert all("crashed" in r.error for r in stranded)
    assert len(served) + len(stranded) == n
    assert router.stats()["redispatched"] == 0
    _assert_fleet_clean(router, cache)


def test_flapping_replica_heals_and_rejoins_through_half_open(stack):
    """A flapping replica crashes, is probed back to life, serves a clean
    half-open probe, and re-closes its circuit into full rotation."""
    g, pipe, cfg, params = stack
    replicas, cache = _fleet(pipe, params, cfg, 2)
    replicas[1] = FaultyReplica(replicas[1], mode="flap", crash_step=1,
                                heal_step=4)
    router = ReplicaRouter(replicas, cooldown_steps=2)
    for u in range(6):
        router.submit(_req(g, u % 4, uid=u))
    done = {r.uid: r for r in router.run_to_completion()}
    assert set(done) == set(range(6))
    assert all(r.done for r in done.values())
    assert router.stats()["failovers"] == 1

    # second workload: the healed replica must be back in rotation
    for u in range(10, 18):
        router.submit(_req(g, u % 4, uid=u))
    done2 = {r.uid: r for r in router.run_to_completion()}
    assert set(done2) == set(range(10, 18))
    assert all(r.done for r in done2.values())
    s = router.stats()
    assert s["per_replica"][1]["circuit"] == "closed"  # healed + probe passed
    assert s["per_replica"][1]["delivered"] > 0
    assert s["duplicate_deliveries"] == 0
    _assert_fleet_clean(router, cache)


def test_grey_replica_trips_circuit_and_traffic_routes_around(stack):
    """A degraded-but-alive replica (fault counters climbing) trips its
    breaker; later traffic goes to healthy replicas only."""
    g, pipe, cfg, params = stack
    cache = RetrievalCache(capacity=256)
    healthy = _engine(pipe, params, cfg, retrieval_cache=cache)
    sick_pipe = FaultyRetrieval(pipe, seed=0, fault_rate=1.0,
                                fault_types=("dispatch",))
    sick = _engine(sick_pipe, params, cfg, retrieval_cache=cache,
                   max_retries=0, degraded_mode=True)
    grey = FaultyReplica(sick, mode="grey", slow_s=0.0)
    router = ReplicaRouter([healthy, grey], trip_threshold=2,
                           cooldown_steps=500)  # stays open once tripped
    for u in range(4):
        router.submit(_req(g, u, uid=u))
    done = {r.uid: r for r in router.run_to_completion()}
    assert len(done) == 4 and all(r.done for r in done.values())
    s = router.stats()
    assert s["per_replica"][1]["circuit"] == "open"
    assert s["per_replica"][1]["trips"] == 1
    first_wave_on_grey = s["per_replica"][1]["dispatched"]
    assert first_wave_on_grey > 0  # it did take traffic before tripping

    # post-trip traffic bypasses the grey replica entirely
    for u in range(10, 16):
        router.submit(_req(g, u % 6, uid=u))
    done2 = {r.uid: r for r in router.run_to_completion()}
    assert all(r.done and not r.degraded for r in done2.values())
    s2 = router.stats()
    assert s2["per_replica"][1]["dispatched"] == first_wave_on_grey
    assert s2["per_replica"][1]["circuit"] == "open"
    _assert_fleet_clean(router, cache)


# --------------------------------------------------------- front-door shed ----
def test_front_door_shed_reject_and_evict_oldest(stack):
    g, pipe, cfg, params = stack
    replicas, cache = _fleet(pipe, params, cfg, 1)
    router = ReplicaRouter(replicas, max_pending=2, shed_policy="reject")
    assert router.submit(_req(g, 0, uid=0))
    assert router.submit(_req(g, 1, uid=1))
    assert not router.submit(_req(g, 2, uid=2))  # full -> shed on arrival
    done = {r.uid: r for r in router.run_to_completion()}
    assert done[0].done and done[1].done
    assert done[2].shed and "reject" in done[2].error
    assert router.stats()["front_door_shed"] == 1
    _assert_fleet_clean(router, cache)

    replicas2, cache2 = _fleet(pipe, params, cfg, 1)
    router2 = ReplicaRouter(replicas2, max_pending=2,
                            shed_policy="evict-oldest")
    for u in range(3):
        router2.submit(_req(g, u, uid=u))
    done2 = {r.uid: r for r in router2.run_to_completion()}
    assert done2[0].shed and "evict-oldest" in done2[0].error
    assert done2[1].done and done2[2].done
    _assert_fleet_clean(router2, cache2)


def test_router_deadline_pinned_across_failover(stack):
    """A failover re-dispatch must not restart the request's deadline
    budget: the absolute deadline pinned at the front door stands, and an
    already-expired orphan is shed, not re-served."""
    g, pipe, cfg, params = stack
    clock = [0.0]
    replicas, cache = _fleet(pipe, params, cfg, 2,
                             now_fn=lambda: clock[0])
    replicas[1] = FaultyReplica(replicas[1], mode="crash", crash_step=1)
    router = ReplicaRouter(replicas, cooldown_steps=50,
                           now_fn=lambda: clock[0])
    router.submit(_req(g, 0, uid=0, max_new=12, deadline_s=5.0))
    router.submit(_req(g, 1, uid=1, max_new=12, deadline_s=5.0))
    assert all(r.deadline_at == 5.0 for r in router.pending)
    done = {}
    done.update({r.uid: r for r in router.step()})  # dispatch; replica1 dies
    clock[0] = 6.0  # past both deadlines; survivors must not extend them
    for r in router.drain():
        done[r.uid] = r
    assert set(done) == {0, 1}
    # whichever requests were still un-served at expiry went shed — none
    # were re-served on a restarted budget
    for r in done.values():
        assert r.done or (r.shed and "deadline" in r.error)
        if r.shed:
            assert r.deadline_at == 5.0  # budget was never restarted
    assert any(r.shed for r in done.values())
    _assert_fleet_clean(router, cache)


# ----------------------------------------------- shared retrieval tier -------
def test_shared_cache_single_flight_across_replicas(stack):
    """The same query submitted to two different replicas dispatches ONE
    retrieval fleet-wide: the second replica defers to the first's in-flight
    wave through the shared cache's registry and resolves as a hit."""
    g, pipe, cfg, params = stack
    clock = [0.0]
    delayed = DelayedRetrieval(
        pipe, cost_s=0.01,
        now_fn=lambda: clock[0],
        sleep_fn=lambda s: clock.__setitem__(0, clock[0] + s),
    )
    cache = RetrievalCache(capacity=256)
    replicas = [
        _engine(delayed, params, cfg, retrieval_cache=cache, prefetch=True,
                admission="wave", now_fn=lambda: clock[0],
                sleep_fn=lambda s: clock.__setitem__(0, clock[0] + s))
        for _ in range(2)
    ]
    router = ReplicaRouter(replicas, now_fn=lambda: clock[0])
    # warm-up: one unique request per replica so both arenas are busy and
    # neither takes the idle-arena blocking-collect shortcut
    router.submit(_req(g, 1, uid=10))
    router.submit(_req(g, 2, uid=11))
    router.step()
    assert delayed.dispatches == 2
    # the contended query, one copy to each replica
    router.submit(_req(g, 0, uid=0))
    router.submit(_req(g, 0, uid=1))
    done = {r.uid: r for r in router.run_to_completion()}
    assert set(done) == {0, 1, 10, 11}
    assert all(r.done for r in done.values())
    assert delayed.dispatches == 3  # qi=0 dispatched ONCE for the fleet
    assert done[0].out_tokens == done[1].out_tokens
    # exactly one copy was the dispatcher; the other resolved as a hit
    assert sorted([done[0].cache_hit, done[1].cache_hit]) == [False, True]
    assert cache.stats()["hits"] >= 1
    _assert_fleet_clean(router, cache)


# ------------------------------------------------------------- chaos soak ----
def test_replica_chaos_soak_small(stack):
    """Tier-1 replica chaos: crash + flap in one 3-replica fleet over a
    repeat-heavy stream.  Exactly one terminal per request fleet-wide, zero
    leaks anywhere, and — retrieval being clean and failover on — every
    request completes bitwise identical to a clean single-replica run."""
    g, pipe, cfg, params = stack
    n = 15
    ref = _reference(pipe, params, cfg, [_req(g, u % 5, uid=u)
                                         for u in range(n)])
    replicas, cache = _fleet(pipe, params, cfg, 3)
    replicas[1] = FaultyReplica(replicas[1], mode="crash", crash_step=3)
    replicas[2] = FaultyReplica(replicas[2], mode="flap", crash_step=2,
                                heal_step=6)
    router = ReplicaRouter(replicas, cooldown_steps=2)
    for u in range(n):
        router.submit(_req(g, u % 5, uid=u))
    done = {r.uid: r for r in router.drain()}
    assert set(done) == set(range(n))
    s = router.stats()
    assert s["duplicate_deliveries"] == 0
    assert s["failovers"] >= 2  # both faulty replicas crashed at least once
    for u in range(n):
        assert done[u].done and not done[u].failed, done[u].error
        assert done[u].out_tokens == ref[u].out_tokens
        np.testing.assert_array_equal(done[u].retrieved_nodes,
                                      ref[u].retrieved_nodes)
    _assert_fleet_clean(router, cache)


@pytest.mark.slow
@pytest.mark.parametrize("paged", [False, True])
def test_replica_chaos_soak_with_retrieval_faults(stack, paged):
    """Full-depth chaos: replica crashes + flaps ON TOP of a 25% seeded
    retrieval fault schedule, shared cache, failover on.  Invariants: the
    router never raises, every request reaches exactly one terminal state,
    accounting closes, nothing leaks, and the fault-free subset (clean
    query, served un-degraded) is bitwise identical to a no-fault run."""
    g, pipe, cfg, params = stack
    n = 24
    q_ids = [u % 8 for u in range(n)]
    ref = _reference(pipe, params, cfg,
                     [_req(g, qi, uid=u) for u, qi in enumerate(q_ids)])
    faulty = FaultyRetrieval(pipe, seed=23, fault_rate=0.25)
    bad_q = {qi for qi in set(q_ids)
             if faulty.fault_of(np.asarray(g.node_feat[qi])) is not None}
    assert bad_q and len(bad_q) < 8
    cache = RetrievalCache(capacity=256)
    replicas = [_engine(faulty, params, cfg, retrieval_cache=cache,
                        paged_kv=paged, retrieval_timeout_s=0.05)
                for _ in range(3)]
    replicas[1] = FaultyReplica(replicas[1], mode="crash", crash_step=4)
    replicas[2] = FaultyReplica(replicas[2], mode="flap", crash_step=3,
                                heal_step=8)
    router = ReplicaRouter(replicas, cooldown_steps=2)
    for u, qi in enumerate(q_ids):
        router.submit(_req(g, qi, uid=u))
    done = {r.uid: r for r in router.drain()}

    assert set(done) == set(range(n))  # exactly-once, fleet-wide
    s = router.stats()
    assert s["duplicate_deliveries"] == 0
    n_done = sum(r.done and not r.failed for r in done.values())
    n_failed = sum(bool(r.failed) for r in done.values())
    n_shed = sum(bool(r.shed) for r in done.values())
    assert n_done + n_failed + n_shed == n  # accounting closes
    assert n_done > 0
    for u, qi in enumerate(q_ids):
        r = done[u]
        if qi not in bad_q and r.done and not r.degraded and not r.stale:
            assert r.out_tokens == ref[u].out_tokens
            np.testing.assert_array_equal(r.retrieved_nodes,
                                          ref[u].retrieved_nodes)
    _assert_fleet_clean(router, cache)
