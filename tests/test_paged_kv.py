"""Paged KV arena: bitwise parity vs the contiguous arena across the whole
decode/admission schedule matrix, block-allocator oracles (alloc/free/reuse,
exhaustion), KV-exhaustion truncation flags, and per-request (continuous)
admission semantics."""
import itertools
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.transformer import TransformerConfig, model as tm
from repro.serving import Request, ServeEngine, ServingConfig
from repro.serving.engine import _auto_block_size

CFG = TransformerConfig(
    name="paged-t", n_layers=2, d_model=32, n_heads=2, n_kv_heads=2,
    d_head=16, d_ff=64, vocab=64, dtype="float32",
)
PARAMS = tm.init_params(jax.random.PRNGKey(0), CFG)


def _mixed_requests(seed=3):
    """Random + repetitive prompts, mixed generation lengths — staggered
    slot turnover so retirement (block free) interleaves with admission
    (block alloc), incl. a max_new=1 admission-time finish."""
    rng = np.random.default_rng(seed)
    reqs = []
    for u, mn in enumerate([5, 12, 1, 30, 8, 12, 25]):
        if u % 2:
            pat = rng.integers(1, 64, size=int(rng.integers(2, 4)))
            p = np.tile(pat, 6)[: int(rng.integers(4, 10))]
        else:
            p = rng.integers(1, 64, size=int(rng.integers(3, 10)))
        reqs.append(Request(uid=u, prompt_ids=p.astype(np.int32),
                            max_new_tokens=mn))
    return reqs


def _run(reqs, **kw):
    eng = ServeEngine(PARAMS, CFG, slots=3, cache_len=48, **kw)
    for r in reqs:
        eng.submit(r)
    done = {r.uid: r for r in eng.run_to_completion()}
    return eng, done


# ------------------------------------------------------------------ parity ----
@pytest.mark.parametrize("spec", [False, True])
def test_paged_parity_both_decode_modes(spec):
    """paged_kv=on emits bitwise-identical out_tokens (and truncation flags)
    to the contiguous arena, in one-token and speculative decode."""
    ref_eng, ref = _run(_mixed_requests(), paged_kv=False,
                        spec_decode=spec, draft_window=4)
    pag_eng, pag = _run(_mixed_requests(), paged_kv=True,
                        spec_decode=spec, draft_window=4)
    assert set(ref) == set(pag) == set(range(7))
    for u in ref:
        assert pag[u].out_tokens == ref[u].out_tokens, f"uid {u}"
        assert pag[u].truncated == ref[u].truncated, f"uid {u}"
    # identical schedule: same dispatch count, same committed tokens
    assert pag_eng.decode_steps == ref_eng.decode_steps
    assert pag_eng.decode_tokens == ref_eng.decode_tokens
    assert pag_eng.truncations == ref_eng.truncations
    ds = pag_eng.decode_stats()
    assert ds["paged_kv"] and ds["block_size"] == 16
    # full-size pool (3 slots x 3 blocks): never gates, fully drains
    assert ds["pool_blocks"] == 9
    assert ds["pool_free_blocks"] == 9


def test_paged_parity_with_sliding_window_attention():
    cfg = TransformerConfig(
        name="paged-sw", n_layers=2, d_model=32, n_heads=2, n_kv_heads=2,
        d_head=16, d_ff=64, vocab=64, dtype="float32", sliding_window=16,
    )
    params = tm.init_params(jax.random.PRNGKey(1), cfg)
    outs = {}
    for paged in (False, True):
        eng = ServeEngine(params, cfg, slots=2, cache_len=48,
                          paged_kv=paged, spec_decode=True, draft_window=4)
        r2 = np.random.default_rng(0)
        for u in range(4):
            eng.submit(Request(uid=u,
                               prompt_ids=r2.integers(1, 64, 8).astype(np.int32),
                               max_new_tokens=30))
        outs[paged] = {r.uid: r.out_tokens for r in eng.run_to_completion()}
    assert outs[True] == outs[False]


def test_paged_parity_with_quantized_kv_cache():
    cfg = TransformerConfig(
        name="paged-q", n_layers=2, d_model=32, n_heads=2, n_kv_heads=2,
        d_head=16, d_ff=64, vocab=64, dtype="float32", kv_quant=True,
    )
    params = tm.init_params(jax.random.PRNGKey(2), cfg)
    outs = {}
    for paged in (False, True):
        eng = ServeEngine(params, cfg, slots=2, cache_len=48,
                          paged_kv=paged)
        r2 = np.random.default_rng(5)
        for u in range(3):
            eng.submit(Request(uid=u,
                               prompt_ids=r2.integers(1, 64, 6).astype(np.int32),
                               max_new_tokens=20))
        outs[paged] = {r.uid: r.out_tokens for r in eng.run_to_completion()}
    assert outs[True] == outs[False]


def test_paged_matches_offline_greedy():
    """Paged decode == offline greedy generation (the reference oracle that
    does not go through any serving-engine code path)."""
    from repro.models.transformer.generate import generate_tokens

    prompt = np.asarray([5, 9, 3, 22, 41], np.int32)
    eng = ServeEngine(PARAMS, CFG, slots=2, cache_len=32, paged_kv=True,
                      kv_block_size=8)
    eng.submit(Request(uid=0, prompt_ids=prompt, max_new_tokens=8))
    done = eng.run_to_completion()
    offline = generate_tokens(
        PARAMS, jnp.asarray(prompt)[None], jnp.asarray([len(prompt)]),
        jax.random.PRNGKey(0), CFG, max_new=8, cache_len=32, temperature=0.0,
    )
    assert done[0].out_tokens[:8] == np.asarray(offline[0]).tolist()


# ------------------------------------------------------- allocator oracles ----
def test_alloc_blocks_pops_distinct_and_masks_dead_slots():
    pool = 6
    table = jnp.full((3, 3), -1, jnp.int32)
    free = jnp.arange(pool, dtype=jnp.int32)
    n_free = jnp.asarray(pool, jnp.int32)
    target = jnp.asarray([2, 3, 1], jnp.int32)
    live = jnp.asarray([True, True, False])
    ref = jnp.zeros((pool,), jnp.int32)
    t2, nf2, ref2 = tm.alloc_blocks(table, free, n_free, ref, target, live, 3)
    t2 = np.asarray(t2)
    assert int(nf2) == pool - 5  # 2 + 3, dead slot allocates nothing
    # every popped block carries exactly one hold (its slot's table entry)
    assert int(np.asarray(ref2).sum()) == 5
    assert set(np.where(np.asarray(ref2) == 1)[0]) == \
        {b for row in t2[:2] for b in row if b >= 0}
    assert (t2[2] == -1).all()
    got = [b for row in t2[:2] for b in row if b >= 0]
    assert len(got) == 5 and len(set(got)) == 5  # distinct blocks
    assert set(got) <= set(range(pool))
    # table prefix is filled left-to-right, no holes
    assert (t2[0][:2] >= 0).all() and t2[0][2] == -1
    assert (t2[1] >= 0).all()


def test_alloc_is_incremental_against_existing_table():
    """target counts TOTAL blocks: a slot already holding n gets target-n
    new ones appended after its existing entries."""
    pool = 4
    table = jnp.asarray([[7, -1, -1]], jnp.int32)  # one block held already
    free = jnp.arange(pool, dtype=jnp.int32)
    n_free = jnp.asarray(pool, jnp.int32)
    t2, nf2, _ = tm.alloc_blocks(table, free, n_free,
                                 jnp.zeros((pool,), jnp.int32),
                                 jnp.asarray([3], jnp.int32),
                                 jnp.asarray([True]), 3)
    t2 = np.asarray(t2)
    assert int(nf2) == pool - 2
    assert t2[0][0] == 7  # existing entry untouched
    assert (t2[0][1:] >= 0).all()


def test_free_then_realloc_reuses_blocks():
    """free_slot_blocks pushes a slot's blocks back; the next alloc pops
    exactly those (LIFO stack → zero fragmentation growth on churn)."""
    cache = tm.init_paged_cache(CFG, 2, 32, 16, 4)
    t2, nf2, ref2 = tm.alloc_blocks(cache.table, cache.free, cache.n_free,
                                    cache.ref,
                                    jnp.asarray([2, 0], jnp.int32),
                                    jnp.asarray([True, False]), 2)
    import dataclasses
    held = set(np.asarray(t2)[0].tolist())
    cache = dataclasses.replace(cache, table=t2, n_free=nf2, ref=ref2)
    cache = tm.free_slot_blocks(cache, jnp.asarray([True, False]))
    assert int(cache.n_free) == 4
    assert (np.asarray(cache.ref) == 0).all()  # zero holders everywhere
    assert (np.asarray(cache.table)[0] == -1).all()
    assert (np.asarray(cache.pos)[0] == -1).all()
    assert int(np.asarray(cache.cursor)[0]) == 0
    t3, nf3, _ = tm.alloc_blocks(cache.table, cache.free, cache.n_free,
                                 cache.ref,
                                 jnp.asarray([0, 2], jnp.int32),
                                 jnp.asarray([False, True]), 2)
    assert set(np.asarray(t3)[1].tolist()) == held  # same blocks, new slot


def test_block_reuse_through_engine_churn():
    """Back-to-back request batches through an engine with a minimal pool:
    every batch drains, the free count returns to full, and the high-water
    mark never exceeds the pool (host mirror == device allocator)."""
    eng = ServeEngine(PARAMS, CFG, slots=2, cache_len=32, paged_kv=True,
                      kv_block_size=8, kv_pool_blocks=8)
    rng = np.random.default_rng(9)
    for batch in range(3):
        for u in range(4):
            eng.submit(Request(
                uid=batch * 10 + u,
                prompt_ids=rng.integers(1, 64, size=7).astype(np.int32),
                max_new_tokens=10))
        done = eng.run_to_completion()
        assert len(done) == 4
        assert eng._free_host == 8
        assert (eng._ntab == 0).all()
        assert int(np.asarray(eng.cache.n_free)) == 8
    assert eng.pool_high_water <= 8
    assert eng.truncations == 0


# --------------------------------------------------- exhaustion/truncation ----
@pytest.mark.parametrize("spec", [False, True])
def test_pool_exhaustion_truncates_and_recovers(spec):
    """An undersized pool retires requests early with truncated=True instead
    of wedging or corrupting: everything completes, flags and counters
    agree, and the pool is whole again afterwards."""
    eng = ServeEngine(PARAMS, CFG, slots=4, cache_len=32, paged_kv=True,
                      kv_block_size=16, kv_pool_blocks=5, spec_decode=spec,
                      draft_window=4)
    rng = np.random.default_rng(1)
    for u in range(8):
        eng.submit(Request(uid=u,
                           prompt_ids=rng.integers(1, 64, 12).astype(np.int32),
                           max_new_tokens=25))
    done = eng.run_to_completion()
    assert len(done) == 8
    truncated = [r for r in done if r.truncated]
    assert truncated  # 4 live slots x 2 blocks > 5: pressure is guaranteed
    for r in truncated:
        assert len(r.out_tokens) < r.max_new_tokens
    assert eng.truncations == len(truncated)
    assert eng.decode_stats()["truncations"] == len(truncated)
    assert eng.decode_stats()["pool_high_water_blocks"] <= 5
    assert eng._free_host == 5 and (eng._ntab == 0).all()


def test_contiguous_arena_exhaustion_sets_truncated():
    """The pre-existing silent-truncation path (cursor >= cache_len on the
    contiguous arena) now reports itself."""
    eng = ServeEngine(PARAMS, CFG, slots=1, cache_len=16, paged_kv=False)
    prompt = np.arange(1, 11, dtype=np.int32)  # 10 + 1 + room for 5 more
    eng.submit(Request(uid=0, prompt_ids=prompt, max_new_tokens=50))
    done = eng.run_to_completion()
    assert done[0].truncated
    # 1 admission token + one per decode step until cursor hits cache_len
    assert len(done[0].out_tokens) == 16 - 10 + 1
    assert eng.truncations == 1
    assert eng.decode_stats()["truncations"] == 1
    # a request that ends by its own budget is NOT truncated
    eng.submit(Request(uid=1, prompt_ids=prompt, max_new_tokens=3))
    done = eng.run_to_completion()
    assert not done[0].truncated and eng.truncations == 1


# ----------------------------------------------------------- configuration ----
def test_env_toggle_and_validation(rag_stack, monkeypatch):
    """RGL_PAGED_KV / RGL_KV_BLOCK resolve in ServingConfig (field beats
    env); the decode engine a RAGServeEngine builds follows the resolved
    config and validates the block geometry."""
    from repro.serving import RAGServeEngine

    _, pipe, cfg, params = rag_stack

    def make(**kw):
        return RAGServeEngine(pipe, params, cfg, config=ServingConfig(
            slots=1, cache_len=96, **kw)).engine

    monkeypatch.delenv("RGL_PAGED_KV", raising=False)
    monkeypatch.delenv("RGL_KV_BLOCK", raising=False)
    assert not ServingConfig.from_env().paged_kv
    assert not make().paged_kv
    monkeypatch.setenv("RGL_PAGED_KV", "1")
    assert ServingConfig.from_env().paged_kv
    eng = make()
    assert eng.paged_kv and eng.block_size == 16
    assert not ServingConfig.resolve(None, paged_kv=False).paged_kv
    assert not make(paged_kv=False).paged_kv  # explicit beats env
    monkeypatch.setenv("RGL_KV_BLOCK", "8")
    assert ServingConfig.from_env().kv_block_size == 8
    assert make().block_size == 8
    with pytest.raises(ValueError, match="divide"):
        make(kv_block_size=7)  # 96 % 7 != 0
    with pytest.raises(ValueError, match="pool_blocks"):
        make(kv_block_size=8, kv_pool_blocks=3)  # < one full-length request


def test_env_reaches_engines_only_through_serving_config(rag_stack,
                                                          monkeypatch):
    """RGL_* options are read by ServingConfig alone: a directly built
    ServeEngine keeps its defaults (contiguous, one-token) under
    RGL_PAGED_KV=1 / RGL_SPEC_DECODE=1, while a RAGServeEngine resolving
    its ServingConfig in the same environment is paged and speculative."""
    from repro.serving import RAGServeEngine

    _, pipe, cfg, params = rag_stack
    monkeypatch.setenv("RGL_PAGED_KV", "1")
    monkeypatch.setenv("RGL_SPEC_DECODE", "1")
    direct = ServeEngine(PARAMS, CFG, slots=1, cache_len=32)
    assert not direct.paged_kv and not direct.spec_decode
    rag = RAGServeEngine(pipe, params, cfg,
                         config=ServingConfig(slots=1, cache_len=96))
    assert rag.config.paged_kv and rag.config.spec_decode
    assert rag.engine.paged_kv and rag.engine.spec_decode


def test_auto_block_size_divides_any_cache_len():
    assert _auto_block_size(512) == 16
    assert _auto_block_size(48) == 16
    assert _auto_block_size(24) == 12
    assert _auto_block_size(13) == 13  # <= preferred, divides itself
    assert _auto_block_size(34) == 2   # 2 x 17: largest divisor <= 16
    for n in (13, 24, 34, 48, 100, 512):
        bs = _auto_block_size(n)
        assert 1 <= bs <= 16 and n % bs == 0


# ------------------------------------------------- fused RAG engine matrix ----
@pytest.fixture(scope="module")
def rag_stack():
    from repro.core import BruteIndex, GraphTokenizer, PipelineConfig, \
        RGLPipeline, Vocab
    from repro.graph import csr_to_ell, generators

    g = generators.citation_graph(100, avg_deg=6, seed=11)
    ell = csr_to_ell(g)
    emb = jnp.asarray(g.node_feat)
    vocab = Vocab.build(g.node_text)
    tok = GraphTokenizer(vocab, max_len=48, node_budget=6)
    pipe = RGLPipeline(
        graph=ell, index=BruteIndex.build(emb), node_emb=emb, tokenizer=tok,
        node_text=g.node_text,
        config=PipelineConfig(strategy="bfs", k_seeds=3, max_hops=2,
                              max_nodes=12, filter_budget=6),
    )
    cfg = TransformerConfig(
        name="paged-rag-t", n_layers=2, d_model=32, n_heads=2, n_kv_heads=2,
        d_head=16, d_ff=64, vocab=vocab.size, dtype="float32",
    )
    params = tm.init_params(jax.random.PRNGKey(0), cfg)
    return g, pipe, cfg, params


def _rag_run(rag_stack, **kw):
    from repro.serving import RAGRequest, RAGServeEngine

    g, pipe, cfg, params = rag_stack
    eng = RAGServeEngine(pipe, params, cfg,
                         config=ServingConfig(slots=2, cache_len=96, **kw))
    q_ids = [0, 1, 2, 0, 3, 1]
    for u, qi in enumerate(q_ids):
        eng.submit(RAGRequest(uid=u, query_emb=np.asarray(g.node_feat[qi]),
                              query_text=g.node_text[qi],
                              max_new_tokens=4 + 2 * (u % 3)))
    done = {r.uid: r for r in eng.run_to_completion()}
    assert len(done) == 6
    return eng, done


def test_rag_schedule_matrix_bitwise_identical(rag_stack):
    """paged x prefetch x spec_decode (the tier-1 CI axes): per-request
    out_tokens, retrievals, prompts, and cache accounting all match the
    contiguous sync one-token reference."""
    ref_eng, ref = _rag_run(rag_stack, paged_kv=False, prefetch=False,
                            spec_decode=False)
    cells = [c for c in itertools.product((False, True), repeat=3)
             if c != (False, False, False)]
    for paged, prefetch, spec in cells:
        eng, done = _rag_run(rag_stack, paged_kv=paged, prefetch=prefetch,
                             spec_decode=spec, draft_window=4)
        for u in ref:
            assert done[u].out_tokens == ref[u].out_tokens, \
                (paged, prefetch, spec, u)
            assert done[u].truncated == ref[u].truncated
            np.testing.assert_array_equal(done[u].retrieved_nodes,
                                          ref[u].retrieved_nodes)
            np.testing.assert_array_equal(done[u].prompt_ids,
                                          ref[u].prompt_ids)
        assert eng.cache_hits == ref_eng.cache_hits, (paged, prefetch, spec)
        assert eng.cache_misses == ref_eng.cache_misses
        s = eng.stats()
        assert s["paged_kv"] == paged
        assert s["emitted_tokens"] == ref_eng.stats()["emitted_tokens"]


def test_continuous_admission_bitwise_identical(rag_stack):
    """Per-request (continuous) admission — sync and prefetched, contiguous
    and paged — produces the same per-request outputs as wave admission
    (greedy decode is schedule-invariant per request)."""
    _, ref = _rag_run(rag_stack, paged_kv=False, prefetch=False,
                      spec_decode=False, admission="wave")
    for paged, prefetch in itertools.product((False, True), repeat=2):
        eng, done = _rag_run(rag_stack, paged_kv=paged, prefetch=prefetch,
                             admission="continuous")
        for u in ref:
            assert done[u].out_tokens == ref[u].out_tokens, (paged, prefetch)
            np.testing.assert_array_equal(done[u].retrieved_nodes,
                                          ref[u].retrieved_nodes)
        assert eng.stats()["admission"] == "continuous"


def test_continuous_admission_dodges_slow_retrieval_row(rag_stack):
    """One expensive retrieval row: wave admission holds its wave-mates
    behind it; continuous admission admits the fast requests immediately
    and the slow request finishes last."""
    from repro.serving import RAGRequest, RAGServeEngine
    from repro.serving.simulate import DelayedRetrieval

    g, pipe, cfg, params = rag_stack
    slow_emb = np.asarray(g.node_feat[0])

    def cost_fn(row):
        return 0.2 if np.array_equal(row, slow_emb) else 0.0

    def run(admission):
        delayed = DelayedRetrieval(pipe, cost_s=0.0, cost_fn=cost_fn)
        eng = RAGServeEngine(delayed, params, cfg, config=ServingConfig(
            slots=2, cache_len=96, prefetch=True, admission=admission,
            cache_capacity=0))
        for u in range(5):
            eng.submit(RAGRequest(uid=u, query_emb=np.asarray(g.node_feat[u]),
                                  query_text=g.node_text[u],
                                  max_new_tokens=6))
        t0 = time.perf_counter()
        order = [r.uid for r in eng.run_to_completion()]
        return order, time.perf_counter() - t0

    run("wave")  # absorb any remaining jit compiles before timing
    o_wave, t_wave = run("wave")
    o_cont, t_cont = run("continuous")
    assert sorted(o_cont) == sorted(o_wave) == list(range(5))
    # continuous: the slow request (uid 0, launched first) finishes after
    # every fast wave-mate instead of gating them at admission
    assert o_cont.index(0) > max(o_cont.index(u) for u in (1, 2, 3, 4))
    # and the whole batch clears sooner than the wave schedule
    assert t_cont < t_wave


def test_rag_pool_exhaustion_propagates_truncated(rag_stack):
    """RAGRequest.truncated mirrors the inner engine's flag under an
    undersized paged pool, and the count lands in stats()."""
    from repro.serving import RAGRequest, RAGServeEngine

    g, pipe, cfg, params = rag_stack
    eng = RAGServeEngine(pipe, params, cfg, config=ServingConfig(
        slots=2, cache_len=96, paged_kv=True, kv_block_size=16,
        kv_pool_blocks=6, cache_capacity=0))
    for u in range(4):
        eng.submit(RAGRequest(uid=u, query_emb=np.asarray(g.node_feat[u]),
                              query_text=g.node_text[u],
                              max_new_tokens=64))
    done = eng.run_to_completion()
    assert len(done) == 4
    assert any(r.truncated for r in done)
    for r in done:
        if r.truncated:
            assert len(r.out_tokens) < r.max_new_tokens
    assert eng.stats()["truncations"] == sum(r.truncated for r in done)
